"""The stacks that convert per-edge values into the dense layout
(`ops/segment.edge_gather`), with and without the inverse slot map on the
batch: the energy+force train step gives the same loss and gradients, and
with `edge_slot` neither the lowered train step nor the serving engine's
forward holds a scatter that comes from `edge_gather`. (The transposes of
the node -> slot gathers are true sums and still scatter.)

And the stacks whose per-edge input is a function of distance (PNAPlus,
SchNet): with the tables on the batch `conv_args` makes it in slot order,
once a step, and no conv converts a layout: the one `edge_gather` left is
the 3-wide one of the shifts, under `geometry`."""
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


# the stacks that make their per-edge input in slot order; "SchNetEq" is
# SchNet with the coordinate update of its `equivariant` branch
SLOT_ORDER_STACKS = ["PNAPlus", "SchNet", "SchNetEq"]


def _setup(model_type, samples):
    config = copy.deepcopy(smoke_config(SIZES))
    arch = config["NeuralNetwork"]["Architecture"]
    equivariant = model_type == "SchNetEq"
    arch.update(model_type="SchNet" if equivariant else model_type,
                num_gaussians=8, num_filters=8, equivariance=equivariant)
    config = update_config(config, samples)
    mcfg = build_model_config(config)
    model = create_model(mcfg)
    batch = with_neighbor_format(collate(samples[:4], np_out=True))
    assert batch.edge_slot is not None and not batch.nbr_mask.all()
    variables = init_params(model, batch)
    return config, mcfg, model, variables, batch


def _op_names(lowered):
    """Location names of the lowered program's operations: the scope path,
    then the primitive (`.../conv_0/edge_gather/gather`)."""
    return re.findall(r'^#loc\d+ = loc\("(jit\([^"]*)"',
                      lowered.as_text(debug_info=True), re.M)


def _scatters_of_edge_gather(lowered):
    """The scatter operations that were traced under the `edge_gather`
    scope (an op's name ends in the primitive:
    `.../edge_gather/scatter-add`)."""
    scatters = [n for n in _op_names(lowered)
                if "scatter" in n.rsplit("/", 1)[-1]]
    return [n for n in scatters if "edge_gather" in n]


def _edge_gathers(lowered):
    """(those under a `conv_<i>` component, those under `geometry`) of the
    operations traced under `edge_gather`, wrappers (`jvp(...)`,
    `transpose(...)`) stripped from each component."""
    inside_conv, inside_geometry = [], []
    for name in _op_names(lowered):
        parts = [re.sub(r"^(?:\w+\()*|\)*$", "", p)
                 for p in name.split("/")]
        if "edge_gather" not in parts:
            continue
        if any(p.startswith("conv_") for p in parts):
            inside_conv.append(name)
        if "geometry" in parts:
            inside_geometry.append(name)
    return inside_conv, inside_geometry


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
    # the control: jax's own transpose of the same gather scatters, where a
    # conv still converts a per-edge value that is differentiated (PNAEq's
    # vectors); PNAPlus and SchNet gather only the shifts, an input
    converts = bool(_edge_gathers(step.lower(state, batch))[0])
    assert converts == (model_type == "PNAEq")
    assert bool(_scatters_of_edge_gather(step.lower(state, plain))) \
        == converts

    engine = InferenceEngine(model, variables, mcfg,
                             reference_samples=samples, max_batch_size=4,
                             neighbor_format=True, ef_forward=True)
    try:
        proto = engine._collate_bucket([engine._proto], engine.buckets[-1])
        assert proto.edge_slot is not None
        forward = engine._jit_forward.lower(variables, proto)
        assert _scatters_of_edge_gather(forward) == []
        assert bool(_scatters_of_edge_gather(engine._jit_forward.lower(
            variables, proto.replace(edge_slot=None)))) == converts
    finally:
        engine.shutdown()


# ------------------------------------ per-edge inputs in slot order, once

def _edge_list(batch):
    """The same batch without the neighbour tables."""
    return batch.replace(nbr=None, nbr_mask=None, nbr_edge=None,
                         edge_slot=None)


@pytest.mark.parametrize("model_type", SLOT_ORDER_STACKS)
def test_slot_order_conv_args_hold_the_bits_of_the_edge_order_ones(
        model_type, samples):
    """What `conv_args` makes per slot is, in every real slot, the bits of
    what it makes per edge (float32, periodic shifts included); a padding
    slot holds what a length of 1 gives, finite."""
    _, _, model, _, batch = _setup(model_type, samples)
    assert batch.edge_shifts is not None and np.any(batch.edge_shifts)
    in_slots = jax.jit(model.conv_args)(batch)
    in_edges = jax.jit(model.conv_args)(_edge_list(batch))
    key = "rbf" if model_type == "PNAPlus" else "edge_length"
    slot, edge = np.asarray(in_slots[key]), np.asarray(in_edges[key])
    n, k = batch.nbr.shape
    assert slot.shape[:2] == (n, k) and edge.shape[0] == batch.num_edges
    assert slot.dtype == edge.dtype == np.float32
    real = np.asarray(batch.nbr_mask)
    assert np.array_equal(slot[real], edge[np.asarray(batch.nbr_edge)][real])
    assert np.isfinite(slot).all()
    if key == "edge_length":
        assert np.all(slot[~real] == 1.0)


@pytest.mark.parametrize("model_type", SLOT_ORDER_STACKS)
def test_energies_forces_and_gradients_with_and_without_the_tables(
        model_type, samples):
    """The energy+force loss, its parameter gradients, the energies and
    the forces of one batch on the two layouts. In float64, as
    tests/test_layout_parity.py records why (float32's sqrt(var + eps) at
    var ~ 0 amplifies the last bit of a sum a hundredfold), at its
    tolerance; energies and forces in float32 too, loosely."""
    from hydragnn_tpu.train.loss import energy_forces_from_node_head
    _, mcfg, model, variables, batch = _setup(model_type, samples)
    edges = _edge_list(batch)
    loss_fn = make_loss_fn(model, mcfg, "mae", compute_grad_energy=True)
    value_and_grad = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))

    def apply_fn(v, b, train):
        return model.apply(v, b, train=train), None

    ef = jax.jit(lambda v, b: energy_forces_from_node_head(
        apply_fn, v, b)[:2])

    def wide(tree):
        return jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float64 if np.issubdtype(
                np.asarray(a).dtype, np.floating) else None), tree)

    with jax.enable_x64():
        params = wide(variables["params"])
        stats = wide(variables.get("batch_stats", {}))
        (loss_d, _), grads_d = value_and_grad(params, stats, wide(batch))
        (loss_e, _), grads_e = value_and_grad(params, stats, wide(edges))
        assert loss_d.dtype == jnp.float64 and float(loss_e) > 0
        assert float(loss_d) == pytest.approx(float(loss_e), rel=1e-9)
        flat_e = jax.tree_util.tree_flatten_with_path(grads_e)[0]
        top = max(float(jnp.max(jnp.abs(g))) for _, g in flat_e)
        assert top > 1e-6
        for (path, g_e), g_d in zip(flat_e,
                                    jax.tree_util.tree_leaves(grads_d)):
            assert float(jnp.max(jnp.abs(g_d - g_e))) <= 1e-9 * top, \
                jax.tree_util.keystr(path)
        wide_vars = {"params": params, "batch_stats": stats}
        for got, want in zip(ef(wide_vars, wide(batch)),
                             ef(wide_vars, wide(edges))):
            assert float(jnp.max(jnp.abs(want))) > 1e-6
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-9, atol=1e-12)
    for got, want in zip(ef(variables, batch), ef(variables, edges)):
        assert got.dtype == jnp.float32
        scale = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(got - want))) <= 1e-4 * scale


@pytest.mark.parametrize("program", ["train_step", "ef_forward"])
@pytest.mark.parametrize("model_type", SLOT_ORDER_STACKS)
def test_no_conv_converts_a_layout_and_geometry_gathers_the_shifts(
        model_type, program, samples):
    """The lowered programs hold no operation named `edge_gather` under a
    `conv_<i>` component and at least one under `geometry` (the 3-wide
    gather of `edge_shifts` in `slot_vectors`): what the benchmark's
    `step_layout_gather_share` / `forward_layout_gather_share` read."""
    config, mcfg, model, variables, batch = _setup(model_type, samples)
    if program == "train_step":
        tx = select_optimizer(config["NeuralNetwork"]["Training"])
        step = make_train_step(model, mcfg, tx, loss_name="mae",
                               compute_grad_energy=True, donate=False)
        state = TrainState.create(
            jax.tree_util.tree_map(jnp.asarray, variables), tx)
        lowered = step.lower(state, batch)
    else:
        engine = InferenceEngine(model, variables, mcfg,
                                 reference_samples=samples, max_batch_size=4,
                                 neighbor_format=True, ef_forward=True)
        try:
            proto = engine._collate_bucket([engine._proto],
                                           engine.buckets[-1])
            lowered = engine._jit_forward.lower(variables, proto)
        finally:
            engine.shutdown()
    inside_conv, inside_geometry = _edge_gathers(lowered)
    if model_type != "SchNetEq":
        # (the equivariant conv moves the positions, so each layer takes
        # its own vectors from `slot_vectors`: the same 3-wide gather)
        assert inside_conv == []
    assert inside_geometry
    assert any(n.rsplit("/", 1)[-1] == "gather" for n in inside_geometry)


@pytest.mark.parametrize("conv", ["PNAConv", "CFConv", "CFConv-equivariant"])
def test_a_conv_handed_edge_order_inputs_on_a_batch_with_tables(conv,
                                                                samples):
    """The pipeline trainer's case: `cargs` in edge order, tables on the
    batch. The conv reads the layout from the rank of its input and takes
    the `nbr_edge` gather as before: the values of the edge list (summed in
    another order) and of the slot-order path (the same products in the
    same slots; the filter network ran on other rows)."""
    from hydragnn_tpu.models.convs import PNAConv
    from hydragnn_tpu.models.schnet import CFConv
    from hydragnn_tpu.ops.basis import bessel_basis
    from hydragnn_tpu.ops.geometry import edge_lengths
    batch = with_neighbor_format(collate(samples[:4], np_out=True))
    edges = _edge_list(batch)
    in_slots, in_edges = edge_lengths(batch), edge_lengths(edges)
    assert in_slots.ndim == 2 and in_edges.ndim == 1
    if conv == "PNAConv":
        module = PNAConv(out_dim=8, deg_hist=[0, 1, 4, 8, 16, 8], rbf=True)
        key, encode = "rbf", lambda d: bessel_basis(d, 2.0, 6)
    else:
        module = CFConv(out_dim=8, num_filters=8, num_gaussians=8,
                        cutoff=2.0, equivariant=conv.endswith("equivariant"))
        key, encode = "edge_length", lambda d: d
    x = jnp.asarray(np.random.RandomState(0).normal(
        size=(batch.num_nodes, 5)), jnp.float32)
    pos = jnp.asarray(batch.pos)
    cargs_slots, cargs_edges = ({key: encode(d)}
                                for d in (in_slots, in_edges))
    params = module.init(jax.random.PRNGKey(0), x, pos, batch, cargs_slots)
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(
            module.init(jax.random.PRNGKey(0), x, pos, edges, cargs_edges))
    apply = jax.jit(module.apply)
    mixed = apply(params, x, pos, batch, cargs_edges)
    on_edge_list = apply(params, x, pos, edges, cargs_edges)
    on_slots = apply(params, x, pos, batch, cargs_slots)
    real = np.asarray(batch.node_mask)
    for got, listed, slotted in zip(mixed, on_edge_list, on_slots):
        got, listed, slotted = (np.asarray(a)[real]
                                for a in (got, listed, slotted))
        assert np.abs(listed).max() > 1e-3
        np.testing.assert_allclose(got, listed, rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(got, slotted, rtol=2e-5, atol=2e-6)
    # edge order converts the layout inside the conv; slot order converts
    # nothing (the equivariant branch gathers the shifts for its vectors)
    assert any("edge_gather" in n for n in _op_names(
        apply.lower(params, x, pos, batch, cargs_edges)))
    if not conv.endswith("equivariant"):
        assert not any("edge_gather" in n for n in _op_names(
            apply.lower(params, x, pos, batch, cargs_slots)))


@pytest.mark.parametrize("model_type", ["PNAPlus", "SchNet"])
def test_the_batched_engine_equals_forward_single_bit_for_bit(model_type,
                                                              samples):
    """What the `predict` cell checks, on the CPU: an answer from a batch
    of several structures is the bits of the same structure alone on the
    same bucket."""
    _, mcfg, model, variables, _ = _setup(model_type, samples)
    engine = InferenceEngine(model, variables, mcfg,
                             reference_samples=samples, max_batch_size=4,
                             neighbor_format=True, ef_forward=True)
    try:
        futures = [engine.submit(s) for s in samples]
        answers = [f.result(timeout=300) for f in futures]
        assert engine.stats()["batches"] < len(samples)
        for sample, future, answer in zip(samples, futures, answers):
            assert answer[1].shape == (sample.num_nodes, 3)
            assert np.abs(answer[1]).max() > 0
            alone = engine.forward_single(sample, bucket=future.bucket)
            assert all(np.array_equal(a, b) for a, b in zip(answer, alone))
    finally:
        engine.shutdown()


@pytest.mark.parametrize("layout,train,recomputes", [
    ("tables", False, True), ("tables", True, False),
    ("edge_list", False, False), ("tables-conv_checkpointing", False, False)])
def test_filters_are_recomputed_where_the_program_is_differentiated_once(
        layout, train, recomputes, samples):
    """Slot order runs `filter_nn` on N x K rows; in evaluation and serving
    (`train` False) its hidden activations are rematerialised, in the train
    step (differentiated twice) and on the edge list they are kept; under
    `conv_checkpointing` the whole conv is, and nothing inside it. Same
    parameters, same values either way."""
    _, mcfg, model, variables, batch = _setup("SchNet", samples)
    if layout == "edge_list":
        batch = _edge_list(batch)
    if layout.endswith("conv_checkpointing"):
        import dataclasses
        model = create_model(dataclasses.replace(mcfg,
                                                 conv_checkpointing=True))

    def energy(pos):
        outs, _ = model.apply(variables, batch.replace(pos=pos), train=train,
                              mutable=["batch_stats"])
        return jnp.sum(jnp.where(batch.node_mask, outs[0][0][:, 0], 0.0))

    jaxpr = str(jax.make_jaxpr(jax.grad(energy))(jnp.asarray(batch.pos)))
    inside = re.findall(r"\b(?:remat|checkpoint)\w*\[", jaxpr)
    if layout.endswith("conv_checkpointing"):
        assert len(inside) == SIZES["num_conv_layers"]   # one a conv, whole
    else:
        assert bool(inside) == recomputes
    reference, _ = create_model(mcfg).apply(
        variables, _edge_list(batch), train=train, mutable=["batch_stats"])
    outs, _ = model.apply(variables, batch, train=train,
                          mutable=["batch_stats"])
    real = np.asarray(batch.node_mask)
    np.testing.assert_allclose(np.asarray(outs[0][0])[real],
                               np.asarray(reference[0][0])[real],
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("model_type,order", [("PNAPlus", "slot"),
                                              ("SchNet", "slot"),
                                              ("PNAEq", "edge")])
def test_run_training_logs_the_order_of_the_per_edge_inputs(
        model_type, order, samples, tmp_path, monkeypatch):
    """The line that names the batch layout also says in which order the
    stack's `conv_args` come: read off their shapes, not off a name."""
    import glob

    import hydragnn_tpu
    monkeypatch.chdir(tmp_path)
    config = copy.deepcopy(smoke_config(SIZES))
    config["NeuralNetwork"]["Architecture"].update(
        model_type=model_type, num_gaussians=8, num_filters=8)
    config["NeuralNetwork"]["Training"]["Checkpoint"] = False
    hydragnn_tpu.run_training(
        config, datasets=(samples[:4], samples[4:5], samples[5:6]))
    layout = [line.rstrip("\n").split(" ", 3)[-1]
              for path in glob.glob("logs/*/train.log")
              for line in open(path) if " layout: " in line]
    assert len(layout) == 1
    assert "neighbor_format=True" in layout[0]
    assert layout[0].endswith(f"per-edge inputs made in {order} order")
