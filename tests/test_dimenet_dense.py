"""DimeNet++'s pair space derived on the device from the dense neighbour
table (models/dimenet.py): equal to the host-built triplet list, exact zeros
from padding, finite at collinear pairs, and through every normal path
(budget packing, data-parallel SPMD, the serving engine) with no batch
transform and no fallback. CPU, the benchmark configuration's tiny preset."""
import copy
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.config import build_model_config, update_config
from hydragnn_tpu.graphs.batch import collate, with_neighbor_format
from hydragnn_tpu.graphs.triplets import (TripletTransform, count_triplets,
                                          maybe_triplet_transform,
                                          sample_triplets)
from hydragnn_tpu.models.create import create_model, init_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_doc():
    from benchmark import system
    with open(os.path.join(REPO, "benchmark", "configs",
                           "dimenetpp-s2ef.json")) as f:
        return system.apply_tiny(json.load(f))


@pytest.fixture(scope="module")
def structures():
    """Periodic slab cells of the benchmark's generator: 14 small ones and
    one of the law's largest, 225 atoms."""
    from benchmark.data import s2ef_like
    params = {**tiny_doc()["data"]["params"], "max_atoms": 12,
              "size_median": 8}
    small = s2ef_like.generate(14, 5, params)
    big = [s for s in s2ef_like.generate(
        40, 11, {**params, "max_atoms": 225, "size_median": 150})
        if s.num_nodes == 225][:1]
    assert big, "the generator's size law reaches its maximum"
    return small + big


@pytest.fixture(scope="module")
def setup(structures):
    config = update_config(copy.deepcopy(tiny_doc()["hydragnn"]),
                           structures)
    mcfg = build_model_config(config)
    model = create_model(mcfg)
    # the equivalence batch: two small cells and the 225-atom one
    members = structures[:2] + structures[-1:]
    n = 64 * (sum(s.num_nodes for s in members) // 64 + 1)
    e = 64 * (sum(s.num_edges for s in members) // 64 + 1)
    plain = collate(members, n_node=n, n_edge=e, n_graph=len(members) + 1,
                    np_out=True)
    listed = TripletTransform(members, len(members))(plain, members)
    dense = with_neighbor_format(plain)
    variables = init_params(model, dense)
    return config, mcfg, model, variables, listed, dense


def energies_and_forces(model, variables, batch):
    @jax.jit
    def run(variables, batch):
        def total(pos):
            out, _ = model.apply(variables, batch.replace(pos=pos),
                                 train=False)
            graph_e = jax.ops.segment_sum(
                jnp.where(batch.node_mask, out[0][:, 0], 0.0),
                batch.node_graph, batch.num_graphs)
            return jnp.sum(graph_e), graph_e
        return jax.value_and_grad(total, has_aux=True)(batch.pos)
    (_, graph_e), grad = run(variables, batch)
    return np.asarray(graph_e), -np.asarray(grad)


def close(got, want, tol=1e-5):
    scale = max(float(np.abs(want).max()), 1e-30)
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()) / scale \
        < tol


def test_dense_pairs_equal_the_host_list(setup):
    """Energies, forces and the first train step's loss on periodic cells;
    K follows the 225-atom member, so the small cells' slots are mostly
    empty."""
    config, mcfg, model, variables, listed, dense = setup
    assert dense.nbr.shape[1] >= 48 and listed.triplet_mask.sum() > 1e5
    assert dense.nbr_mask[:16].mean() < 0.5
    e_list, f_list = energies_and_forces(model, variables, listed)
    e_dense, f_dense = energies_and_forces(model, variables, dense)
    assert np.abs(f_list).max() > 1e-4
    assert close(e_dense, e_list) and close(f_dense, f_list)
    # with both attached the derived space is the one used
    both = dense.replace(idx_kj=listed.idx_kj, idx_ji=listed.idx_ji,
                         triplet_mask=listed.triplet_mask)
    e_both, _ = energies_and_forces(model, variables, both)
    assert np.array_equal(e_both, e_dense)

    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.train_step import TrainState, make_train_step
    tcfg = config["NeuralNetwork"]["Training"]
    tx = select_optimizer(tcfg)
    step = make_train_step(model, mcfg, tx, loss_name="mae",
                           compute_grad_energy=True, donate=False)
    losses = []
    for batch in (listed, dense):
        state = TrainState.create(jax.tree_util.tree_map(jnp.asarray,
                                                         variables), tx)
        new_state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        assert all(np.isfinite(np.asarray(leaf)).all() for leaf in
                   jax.tree_util.tree_leaves(new_state.params))
    assert losses[0] == pytest.approx(losses[1], rel=1e-5)


def test_pair_count_is_the_lists_own(structures):
    for s in structures[:4] + structures[-1:]:
        kj, _ = sample_triplets(np.asarray(s.senders),
                                np.asarray(s.receivers))
        assert count_triplets(s.senders, s.receivers) == len(kj)
    assert count_triplets(np.zeros(0, int), np.zeros(0, int)) == 0


def weight_gradient(model, variables, batch):
    """d(sum of squared forces + energies)/d(weights): the force loss's
    double backward through the pair space."""
    def loss(params, batch):
        def total(pos):
            out, _ = model.apply({**variables, "params": params},
                                 batch.replace(pos=pos), train=False)
            return jnp.sum(jnp.where(batch.node_mask, out[0][:, 0], 0.0))
        energy, grad = jax.value_and_grad(total)(batch.pos)
        return energy ** 2 + jnp.sum(grad ** 2)
    return jax.jit(jax.grad(loss))(variables["params"], batch)


def test_padding_adds_exactly_nothing(setup, structures):
    """The same three structures alone and inside a batch whose slots are
    90% padding (more node slots, a wider K): energies, forces and the
    weights' gradient are finite and unchanged."""
    _, _, model, variables, _, _ = setup
    few = structures[:3]
    n = 64 * (sum(s.num_nodes for s in few) // 64 + 1)
    e = 64 * (sum(s.num_edges for s in few) // 64 + 1)
    tight = with_neighbor_format(collate(few, n_node=n, n_edge=e, n_graph=4,
                                         np_out=True))
    loose = with_neighbor_format(
        collate(few, n_node=4 * n, n_edge=2 * e, n_graph=9, np_out=True),
        k=2 * tight.nbr.shape[1])
    real = tight.nbr_mask.sum()
    assert real == loose.nbr_mask.sum()
    assert 1.0 - real / loose.nbr_mask.size > 0.9
    e_t, f_t = energies_and_forces(model, variables, tight)
    e_l, f_l = energies_and_forces(model, variables, loose)
    assert np.isfinite(f_l).all()
    assert close(e_l[:3], e_t[:3]) and close(f_l[:n], f_t)
    assert not f_l[n:].any(), "padding atoms feel no force"
    g_t = weight_gradient(model, variables, tight)
    g_l = weight_gradient(model, variables, loose)
    for a, b in zip(jax.tree_util.tree_leaves(g_l),
                    jax.tree_util.tree_leaves(g_t)):
        assert np.isfinite(np.asarray(a)).all()
        # (the sums over a wider K run in another order: float32)
        assert close(a, b, 1e-4)


def test_collinear_neighbours_have_finite_gradients(setup):
    """Three atoms on a line (angle pi at the middle one, 0 at the ends):
    arctan2(|a x b|, a.b) has no gradient there, the cosine has."""
    from hydragnn_tpu.graphs.batch import GraphSample
    _, _, model, variables, _, _ = setup
    pos = np.array([[0.0, 0.0, 0.0], [1.5, 0.0, 0.0], [3.25, 0.0, 0.0],
                    [1.0, 2.0, 0.5]], np.float32)
    send, recv = np.array([(i, j) for i in range(4) for j in range(4)
                           if i != j]).T
    sample = GraphSample(x=np.ones((4, 1), np.float32), pos=pos,
                         senders=send, receivers=recv,
                         edge_shifts=np.zeros((12, 3), np.float32),
                         energy=np.zeros((1,), np.float32),
                         forces=np.zeros((4, 3), np.float32))
    batch = with_neighbor_format(collate([sample], n_node=8, n_edge=16,
                                         n_graph=2, np_out=True))
    _, forces = energies_and_forces(model, variables, batch)
    assert np.isfinite(forces).all() and np.abs(forces[:4]).max() > 0
    grads = weight_gradient(model, variables, batch)
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree_util.tree_leaves(grads))
    # and it is the limit of the nearly collinear case
    nudged = batch.replace(pos=batch.pos + np.array(
        [[0, 0, 0], [0, 1e-3, 0]] + [[0, 0, 0]] * 6, np.float32))
    _, near = energies_and_forces(model, variables, nudged)
    assert close(near[:4], forces[:4], 2e-2)


def test_the_transform_is_built_only_without_the_table(structures):
    assert maybe_triplet_transform("DimeNet", structures, 4, True) is None
    assert maybe_triplet_transform("PNAPlus", structures, 4, False) is None
    assert isinstance(maybe_triplet_transform("DimeNet", structures, 4,
                                              False), TripletTransform)


@pytest.fixture(scope="module")
def trained(structures, tmp_path_factory):
    """`run_training` with budget packing ON, DimeNet, no transform."""
    import hydragnn_tpu
    os.chdir(tmp_path_factory.mktemp("run"))
    config = copy.deepcopy(tiny_doc()["hydragnn"])
    config["NeuralNetwork"]["Training"].update(
        num_epoch=2, batch_size=4, batch_packing=True)
    sets = (structures[:10], structures[10:12], structures[12:14])
    try:
        state, history, model, config = hydragnn_tpu.run_training(
            config, datasets=sets)
        # the run's own log file (`setup_log` clears other handlers):
        # "<date> <time> <level> <message>"
        import glob
        messages = [line.rstrip("\n").split(" ", 3)[-1]
                    for path in glob.glob("logs/*/train.log")
                    for line in open(path)]
    finally:
        os.chdir(REPO)
    assert any(m.startswith("epoch 0:") for m in messages)
    return state, history, model, config, sets, messages


def test_run_training_packs_dimenet(trained, capsys):
    state, history, _, config, _, messages = trained
    assert len(history["train_loss"]) == 2
    assert all(np.isfinite(history["train_loss"]))
    assert config["NeuralNetwork"]["Training"]["batch_packing"] is True
    # packed: padding of the node slots is what the budget leaves, and no
    # line says DimeNet fell back
    assert history["padding_frac_nodes"][0] < 0.6
    assert not any("falling back" in m for m in messages)
    # the stack says it derives a pair space; the loader counts its padding
    layout = [m for m in messages if m.startswith("layout: ")]
    assert len(layout) == 1 and "pad_pair_share=0." in layout[0]
    assert "per-edge inputs made in slot order" in layout[0]


def test_run_prediction_serves_dimenet_through_the_engine(trained, caplog):
    """The engine answers, bit for bit what `forward_single` gives on the
    bucket a batch ran on (what the `predict` cell checks)."""
    import hydragnn_tpu
    from hydragnn_tpu.serving.engine import InferenceEngine
    state, _, model, config, sets, _ = trained
    with caplog.at_level(logging.WARNING, logger="hydragnn_tpu"):
        trues, preds = hydragnn_tpu.run_prediction(
            config, datasets=sets, state=state, model=model, serve=True)
    assert not any("falling back" in r.getMessage() for r in caplog.records)
    assert np.isfinite(preds[0]).all() and len(preds[0]) == sum(
        s.num_nodes for s in sets[2])
    mcfg = build_model_config(config)
    variables = {"params": state.params, "batch_stats": state.batch_stats}
    from hydragnn_tpu.datasets.async_loader import neighbor_budget
    everything = [s for part in sets for s in part]
    engine = InferenceEngine(
        model, variables, mcfg, reference_samples=everything,
        max_batch_size=4, neighbor_format=True,
        neighbor_k=neighbor_budget(everything), ef_forward=True)
    try:
        futures = [engine.submit(s) for s in sets[2]]
        answers = [f.result(timeout=300) for f in futures]
        for sample, future, answer in zip(sets[2], futures, answers):
            alone = engine.forward_single(sample, bucket=future.bucket)
            assert all(np.array_equal(a, b) for a, b in
                       zip(jax.tree_util.tree_leaves(answer),
                           jax.tree_util.tree_leaves(alone)))
    finally:
        engine.shutdown()


def test_data_parallel_step_over_virtual_devices(setup, structures):
    """`make_spmd_train_step` over two shards, batches from a loader with
    no transform: what a 2-process run executes on each process."""
    from hydragnn_tpu.datasets.loader import GraphDataLoader
    from hydragnn_tpu.parallel.mesh import make_mesh, shard_batch
    from hydragnn_tpu.parallel.spmd import make_spmd_train_step
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.train_step import TrainState
    config, mcfg, model, variables, _, _ = setup
    loader = GraphDataLoader(structures[:8], batch_size=4, num_shards=2,
                             neighbor_format=True)
    assert loader.batch_transform is None and loader.neighbor_k
    stats = loader.padding_stats(pair_space=True)
    assert 0.5 < stats["pad_pair_share"] < 1.0
    assert "pad_pair_share" not in loader.padding_stats()
    mesh = make_mesh((("data", 2),), devices=jax.devices()[:2])
    tx = select_optimizer(config["NeuralNetwork"]["Training"])
    step = make_spmd_train_step(model, mcfg, tx, mesh, loss_name="mae",
                                compute_grad_energy=True)
    state = TrainState.create(jax.tree_util.tree_map(jnp.asarray, variables),
                              tx)
    state, metrics = step(state, shard_batch(next(iter(loader)), mesh))
    assert np.isfinite(float(metrics["loss"]))
