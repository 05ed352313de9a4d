"""Each configuration's plain reference against the system, at a small
width on the CPU: energies, forces, and the train step's first loss, with
the same seeded weights. And the data generator the references are fed
from."""
import json
import os

import numpy as np
import pytest

from benchmark import cells, system
from benchmark.data import s2ef_like
from benchmark.jobs import checks
from benchmark.reference import common

from bench_testlib import NO_BATCHNORM, config_doc

CONFIGS = [c["name"] for c in cells.load_benchmark()["configs"]]
# float32 on the CPU: the two sides differ by summation order only
TIGHT = 2e-5


def tiny_doc(name):
    return system.apply_tiny(config_doc(name))


# beside the benchmark's configurations, with a BatchNorm or without, the
# tests' throw-away stack that has none
@pytest.fixture(scope="module", params=CONFIGS + [NO_BATCHNORM])
def composed(request, tmp_path_factory):
    doc = tiny_doc(request.param)
    pools = system.load_pools(doc, str(tmp_path_factory.mktemp("pools")))
    config = system.complete_config(doc, pools, 8)
    comp = system.Training(config, pools, num_shards=1)
    return doc, config, comp, comp.initial_state(seed=3)


def test_reference_matches_the_system_forward_forces_and_first_loss(composed):
    import jax
    doc, config, comp, state = composed
    chk = system.check_structures(comp.loaders[2].dataset, 8)
    batch = comp.collate(chk)
    variables = {"params": state.params, "batch_stats": state.batch_stats}

    ref_e, ref_f, struct = system.reference_energy_forces(
        doc, config, variables, chk, train=False)
    _, (energy, forces) = comp.eval_step(state, comp.place(batch))
    got_e, got_f = checks.unpad_ef(energy, forces, chk)
    assert got_e.shape == ref_e.shape == (8,)
    assert got_f.shape == ref_f.shape == (sum(s.num_nodes for s in chk), 3)
    assert np.abs(ref_f).max() > 1e-3, "the stack reads positions"
    assert system.relative_error(got_e, ref_e) < TIGHT
    assert system.relative_error(got_f, ref_f) < TIGHT

    e, f, s = system.reference_energy_forces(doc, config, variables, chk,
                                             train=True)
    want = sum(common.mae_losses(e, f, s))
    copied = jax.tree_util.tree_map(np.array, state)
    _, metrics = comp.train_step(copied, comp.place(batch))
    assert float(metrics["loss"]) == pytest.approx(want, rel=TIGHT)
    if jax.tree_util.tree_leaves(state.batch_stats):
        # train-mode statistics are the batch's own: another number than
        # eval
        assert system.relative_error(e, ref_e) > 1e-3
    else:
        # no BatchNorm, so nothing tells the two modes apart
        assert system.relative_error(e, ref_e) < TIGHT
        assert state.batch_stats == {}


def test_the_tolerance_of_correct_would_catch_bfloat16_compute(composed):
    """On the CPU float32 agrees with the reference to 1e-5 while the same
    stack computing in bfloat16 is percents away: the chip's tolerance
    (jobs/checks.REL_TOL) has to sit between its own two readings, which
    ``calibrate tolerance`` takes."""
    doc, config, comp, state = composed
    chk = system.check_structures(comp.loaders[2].dataset, 8)
    batch = comp.collate(chk)
    variables = {"params": state.params, "batch_stats": state.batch_stats}
    ref_e, ref_f, _ = system.reference_energy_forces(
        doc, config, variables, chk, train=False)
    low = json.loads(json.dumps(doc))
    low["hydragnn"]["NeuralNetwork"]["Architecture"]["dtype"] = "bfloat16"
    pools = tuple(l.dataset for l in comp.loaders)
    comp16 = system.Training(system.complete_config(low, pools, 8), pools, 1)
    _, (energy, forces) = comp16.eval_step(state, comp16.place(batch))
    _, got_f = checks.unpad_ef(energy, forces, chk)
    assert system.relative_error(got_f, ref_f) > 100 * TIGHT


def test_sharded_losses_compose_as_the_data_parallel_eval_step_does():
    struct = {"node_graph": np.array([0, 0, 1, 2, 2, 2]),
              "energy": np.array([1.0, 2.0, 3.0]),
              "forces": np.zeros((6, 3))}
    ref_e = np.array([2.0, 2.0, 0.0])
    ref_f = np.concatenate([np.full((2, 3), 1.0), np.full((1, 3), 4.0),
                            np.full((3, 3), 2.0)])
    got = checks.compose(checks.shard_terms(ref_e, ref_f, struct,
                                            [[0, 2], [1]]))
    # shard 0: graphs 0 and 2 (|dE| 1, 3; 5 atoms of |F| 1, 1, 2, 2, 2);
    # shard 1: graph 1 (|dE| 0; 1 atom of 4); weights 2/3 and 1/3
    assert got["energy_loss"] == pytest.approx(2 / 3 * 2.0 + 1 / 3 * 0.0)
    assert got["force_loss"] == pytest.approx(2 / 3 * 1.6 + 1 / 3 * 4.0)


PARAMS = tiny_doc(CONFIGS[0])["data"]["params"]


def test_generator_is_seeded_and_shaped_like_its_parameters():
    a = s2ef_like.generate(24, 5, PARAMS)
    b = s2ef_like.generate(24, 5, PARAMS)
    c = s2ef_like.generate(24, 6, PARAMS)
    assert all(np.array_equal(x.pos, y.pos) and np.array_equal(
        x.senders, y.senders) for x, y in zip(a, b))
    assert not np.array_equal(a[0].pos, c[0].pos)
    for s in a:
        assert PARAMS["min_atoms"] <= s.num_nodes <= PARAMS["max_atoms"]
        deg = np.bincount(s.receivers, minlength=s.num_nodes)
        assert deg.max() <= PARAMS["max_neighbours"] and deg.min() >= 1
        vec = s.pos[s.senders] + s.edge_shifts - s.pos[s.receivers]
        d = np.linalg.norm(vec, axis=1)
        assert d.max() <= PARAMS["cutoff"] + 1e-4 and d.min() > 0.5
        assert np.isfinite(s.forces).all() and s.forces.shape == s.pos.shape
    sizes = s2ef_like.sample_sizes(np.random.RandomState(0), 4000, {
        **PARAMS, "size_median": 60, "max_atoms": 225})
    assert 70 < sizes.mean() < 82 and sizes.max() == 225, "mean near 75"


def test_force_labels_are_the_gradient_of_the_energy_labels():
    rng = np.random.RandomState(2)
    z, pos, cell, sigma = s2ef_like.make_structure(rng, 14, PARAMS)
    _, forces = s2ef_like.lj_labels(pos, cell, sigma, PARAMS["cutoff"])
    h = 1e-5
    for atom, axis in ((0, 0), (5, 2), (13, 1)):
        plus, minus = pos.copy(), pos.copy()
        plus[atom, axis] += h
        minus[atom, axis] -= h
        slope = (s2ef_like.lj_labels(plus, cell, sigma, PARAMS["cutoff"])[0]
                 - s2ef_like.lj_labels(minus, cell, sigma,
                                       PARAMS["cutoff"])[0]) / (2 * h)
        # pairs crossing the cutoff inside +-h leave a small step
        assert forces[atom, axis] == pytest.approx(-slope, rel=2e-3,
                                                   abs=2e-3)


def test_pools_are_cached_by_generator_seed_and_parameters(tmp_path):
    doc = tiny_doc(CONFIGS[0])
    first = system.load_pools(doc, str(tmp_path))
    files = sorted(os.listdir(tmp_path))
    assert len(files) == 3
    stamp = [os.path.getmtime(tmp_path / f) for f in files]
    again = system.load_pools(doc, str(tmp_path))
    assert [os.path.getmtime(tmp_path / f) for f in files] == stamp
    assert all(np.array_equal(x.pos, y.pos) and np.array_equal(
        x.forces, y.forces) for x, y in zip(first[0], again[0]))
    doc["data"]["pool_seed"] += 1
    system.load_pools(doc, str(tmp_path))
    assert len(os.listdir(tmp_path)) == 6
    # --seed orders the pool and never changes it
    assert sorted(system.seeded_order(10, 1)) == list(range(10))
    assert list(system.seeded_order(10, 1)) != list(system.seeded_order(10, 2))
