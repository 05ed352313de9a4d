"""Model-zoo construction/forward tests (the shape/compile smoke layer;
accuracy thresholds live in test_training.py, mirroring the reference's
tests/test_graphs.py split)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hydragnn_tpu.models import create_model, init_params
from hydragnn_tpu.config import build_model_config

from tests.deterministic_data import deterministic_graph_dataset
from tests.utils import prepare

INVARIANT_MODELS = ["GIN", "SAGE", "GAT", "MFC", "CGCNN", "PNA", "PNAPlus",
                    "SchNet", "EGNN"]
ALL_MODELS = INVARIANT_MODELS + ["PAINN", "PNAEq", "DimeNet", "MACE"]


def _prepare_any(model_type, samples, **kw):
    arch = {}
    if model_type == "MACE":
        arch = dict(max_ell=2, node_max_ell=1, correlation=[2])
    arch.update(kw)
    cfg, mcfg, batch = prepare(model_type, samples, **arch)
    if model_type == "DimeNet":
        import dataclasses
        import numpy as np
        from hydragnn_tpu.graphs.triplets import add_triplets, triplet_budget
        batch = jax.tree_util.tree_map(lambda a: np.asarray(a), batch)
        batch = add_triplets(batch, triplet_budget(samples[:8], 8))
    return cfg, mcfg, batch


@pytest.fixture(scope="module")
def samples():
    return deterministic_graph_dataset(num_configs=12, heads=("graph", "node"))


@pytest.mark.parametrize("model_type", ALL_MODELS)
def test_forward_shapes_singlehead(model_type, samples):
    cfg, mcfg, batch = _prepare_any(model_type, samples)
    model = create_model(mcfg)
    variables = init_params(model, batch)
    (outputs, outputs_var) = model.apply(variables, batch, train=False)
    assert outputs_var is None
    assert len(outputs) == 1
    assert outputs[0].shape == (batch.num_graphs, 1)
    assert np.all(np.isfinite(np.asarray(outputs[0])))


@pytest.mark.parametrize("model_type", ["GIN", "PNA", "SchNet", "EGNN",
                                        "PAINN", "PNAEq", "MACE"])
def test_forward_multihead(model_type, samples):
    cfg, mcfg, batch = _prepare_any(model_type, samples,
                                    heads=("graph", "node"))
    model = create_model(mcfg)
    variables = init_params(model, batch)
    outputs, _ = model.apply(variables, batch, train=False)
    assert outputs[0].shape == (batch.num_graphs, 1)
    assert outputs[1].shape == (batch.num_nodes, 1)


@pytest.mark.parametrize("model_type", ["GIN", "PNA"])
def test_jit_and_grad(model_type, samples):
    cfg, mcfg, batch = prepare(model_type, samples)
    model = create_model(mcfg)
    variables = init_params(model, batch)

    @jax.jit
    def loss(params):
        out, _ = model.apply({"params": params,
                              "batch_stats": variables["batch_stats"]},
                             batch, train=False)
        return jnp.sum(out[0] ** 2)

    g = jax.grad(loss)(variables["params"])
    flat = jax.tree_util.tree_leaves(g)
    assert all(np.all(np.isfinite(np.asarray(x))) for x in flat)
    assert any(float(jnp.max(jnp.abs(x))) > 0 for x in flat)


def test_padding_invariance(samples):
    """Outputs on real graphs must not depend on the padding amount —
    the core correctness property of the static-shape design."""
    from hydragnn_tpu.graphs import collate
    cfg, mcfg, _ = prepare("GIN", samples)
    model = create_model(mcfg)
    b1 = collate(samples[:4], n_node=80, n_edge=1024, n_graph=5)
    b2 = collate(samples[:4], n_node=160, n_edge=2048, n_graph=9)
    variables = init_params(model, b1)
    o1, _ = model.apply(variables, b1, train=False)
    o2, _ = model.apply(variables, b2, train=False)
    np.testing.assert_allclose(np.asarray(o1[0][:4]), np.asarray(o2[0][:4]),
                               rtol=2e-4, atol=1e-5)


def test_gaussian_nll_var_output(samples):
    cfg, mcfg, batch = prepare("GIN", samples)
    import dataclasses
    mcfg = dataclasses.replace(mcfg, var_output=1)
    model = create_model(mcfg)
    variables = init_params(model, batch)
    outputs, outputs_var = model.apply(variables, batch, train=False)
    assert outputs[0].shape == (batch.num_graphs, 1)
    assert outputs_var[0].shape == (batch.num_graphs, 1)
    assert np.all(np.asarray(outputs_var[0]) >= 0)


# the two vector-channel stacks compile for ~25 s each on CPU: slow lane
# since PR 21 (tier-1 budget); GIN keeps the conv-head path in tier-1
@pytest.mark.parametrize("model_type", [
    "GIN",
    pytest.param("PAINN", marks=pytest.mark.slow),
    pytest.param("PNAEq", marks=pytest.mark.slow)])
def test_conv_node_head(model_type, samples):
    """Node head of type 'conv' (reference: Base.py:262-290; for the
    vector-channel stacks the head convs thread the encoder's final v,
    reference: PAINNStack.py:139-145)."""
    cfg, mcfg, batch = prepare(model_type, samples, heads=("node",))
    import dataclasses
    head = dataclasses.replace(mcfg.heads[0], node_arch="conv")
    mcfg = dataclasses.replace(mcfg, heads=(head,))
    model = create_model(mcfg)
    variables = init_params(model, batch)
    outputs, _ = model.apply(variables, batch, train=False)
    assert outputs[0].shape == (batch.num_nodes, 1)
    assert np.all(np.isfinite(np.asarray(outputs[0])))
    if model_type == "GIN":
        # the grad-flow check below is for the vector-channel threading;
        # GIN's head conv can be legitimately relu-dead at init on this
        # unnormalized fixture (its 1-wide MLP saturates negative)
        return
    # gradients flow through the threaded vector channel (train=True: the
    # masked batchnorm recenters on batch stats, so the head's final
    # activation isn't uniformly relu-dead at init)
    def loss(params):
        out_and_var, _ = model.apply(
            {"params": params,
             "batch_stats": variables.get("batch_stats", {})},
            batch, train=True, mutable=["batch_stats"])
        out, _ = out_and_var
        return jnp.sum(out[0] ** 2)
    g = jax.grad(loss)(variables["params"])
    flat = jax.tree_util.tree_leaves(g)
    assert all(np.all(np.isfinite(np.asarray(x))) for x in flat)
    assert any(float(jnp.max(jnp.abs(x))) > 0 for x in flat)


def test_mace_lmax4(samples):
    """MACE above the old lmax=3 cap: the general-l spherical harmonics +
    sympy CG path builds and produces finite outputs at max_ell=4
    (reference: e3nn machinery is arbitrary-l, mace_utils/tools/cg.py:94)."""
    cfg, mcfg, batch = prepare("MACE", samples, max_ell=4, node_max_ell=2,
                               correlation=[2])
    model = create_model(mcfg)
    variables = init_params(model, batch)
    outputs, _ = model.apply(variables, batch, train=False)
    assert outputs[0].shape == (batch.num_graphs, 1)
    assert np.all(np.isfinite(np.asarray(outputs[0])))


def test_mlp_per_node_head():
    samples = deterministic_graph_dataset(num_configs=8, heads=("node",))
    # fix graph size: filter to the modal size
    sizes = [s.num_nodes for s in samples]
    modal = max(set(sizes), key=sizes.count)
    fixed = [s for s in samples if s.num_nodes == modal]
    cfg, mcfg, batch = prepare("GIN", fixed, heads=("node",))
    import dataclasses
    head = dataclasses.replace(mcfg.heads[0], node_arch="mlp_per_node")
    mcfg = dataclasses.replace(mcfg, heads=(head,), num_nodes=modal)
    model = create_model(mcfg)
    variables = init_params(model, batch)
    outputs, _ = model.apply(variables, batch, train=False)
    assert outputs[0].shape == (batch.num_nodes, 1)
