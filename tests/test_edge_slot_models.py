"""The stacks that convert per-edge values into the dense layout
(`ops/segment.edge_gather`), with and without the inverse slot map on the
batch: the energy+force train step gives the same loss and gradients, and
with `edge_slot` neither the lowered train step nor the serving engine's
forward holds a scatter that comes from `edge_gather`. (The transposes of
the node -> slot gathers are true sums and still scatter.)"""
import copy
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chip_smoke import smoke_config
from examples.LennardJones.lj_data import generate_lj_dataset
from hydragnn_tpu.config import build_model_config, update_config
from hydragnn_tpu.graphs.batch import collate, with_neighbor_format
from hydragnn_tpu.models.create import create_model, init_params
from hydragnn_tpu.serving.engine import InferenceEngine
from hydragnn_tpu.train.optimizer import select_optimizer
from hydragnn_tpu.train.train_step import (TrainState, make_loss_fn,
                                           make_train_step)

SIZES = {"hidden_dim": 8, "cutoff": 2.0, "num_conv_layers": 2,
         "num_epoch": 1, "batch_size": 4, "learning_rate": 1e-3}


@pytest.fixture(scope="module")
def samples():
    return generate_lj_dataset(num_configs=6, atoms_per_dim=2, cutoff=2.0)


def _setup(model_type, samples):
    config = copy.deepcopy(smoke_config(SIZES))
    arch = config["NeuralNetwork"]["Architecture"]
    arch.update(model_type=model_type, num_gaussians=8, num_filters=8)
    config = update_config(config, samples)
    mcfg = build_model_config(config)
    model = create_model(mcfg)
    batch = with_neighbor_format(collate(samples[:4], np_out=True))
    assert batch.edge_slot is not None and not batch.nbr_mask.all()
    variables = init_params(model, batch)
    return config, mcfg, model, variables, batch


def _scatters_of_edge_gather(lowered):
    """Location names of the lowered program's scatter operations that were
    traced under the `edge_gather` scope (an op's name ends in the
    primitive: `.../edge_gather/scatter-add`)."""
    names = re.findall(r'^#loc\d+ = loc\("(jit\([^"]*)"',
                       lowered.as_text(debug_info=True), re.M)
    scatters = [n for n in names if "scatter" in n.rsplit("/", 1)[-1]]
    return [n for n in scatters if "edge_gather" in n]


@pytest.mark.parametrize("model_type", ["PNAPlus", "SchNet", "PNAEq"])
def test_ef_step_is_the_same_and_edge_gather_never_scatters(model_type,
                                                            samples):
    config, mcfg, model, variables, batch = _setup(model_type, samples)
    plain = batch.replace(edge_slot=None)
    # in float64: the two programs sum in different orders, which alone
    # moves a float32 bias gradient by 1e-5 of the largest
    with jax.enable_x64():
        wide = lambda tree: jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float64 if np.issubdtype(
                np.asarray(a).dtype, np.floating) else None), tree)
        loss_fn = make_loss_fn(model, mcfg, "mae", compute_grad_energy=True)
        value_and_grad = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
        params = wide(variables["params"])
        stats = wide(variables.get("batch_stats", {}))  # PNAEq has none
        (loss, _), grads = value_and_grad(params, stats, wide(batch))
        (loss0, _), grads0 = value_and_grad(params, stats, wide(plain))
        assert loss.dtype == jnp.float64
        assert np.isfinite(float(loss)) and float(loss) > 0
        assert float(loss) == pytest.approx(float(loss0), rel=1e-9)
        flat, flat0 = (jax.tree_util.tree_leaves(g) for g in (grads, grads0))
        top = max(float(jnp.max(jnp.abs(g))) for g in flat0)
        assert top > 1e-6
        for g, g0 in zip(flat, flat0):
            assert float(jnp.max(jnp.abs(g - g0))) <= 1e-9 * top

    tx = select_optimizer(config["NeuralNetwork"]["Training"])
    step = make_train_step(model, mcfg, tx, loss_name="mae",
                           compute_grad_energy=True, donate=False)
    state = TrainState.create(
        jax.tree_util.tree_map(jnp.asarray, variables), tx)
    assert _scatters_of_edge_gather(step.lower(state, batch)) == []
    # the control: jax's own transpose of the same gather scatters
    assert _scatters_of_edge_gather(step.lower(state, plain))

    engine = InferenceEngine(model, variables, mcfg,
                             reference_samples=samples, max_batch_size=4,
                             neighbor_format=True, ef_forward=True)
    try:
        proto = engine._collate_bucket([engine._proto], engine.buckets[-1])
        assert proto.edge_slot is not None
        forward = engine._jit_forward.lower(variables, proto)
        assert _scatters_of_edge_gather(forward) == []
        assert _scatters_of_edge_gather(engine._jit_forward.lower(
            variables, proto.replace(edge_slot=None)))
    finally:
        engine.shutdown()
