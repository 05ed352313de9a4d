"""A configuration is described by its source, not by what the harness
happens to index (PR 33): a stack WITHOUT BatchNorm goes all the way
through, beside one with it, as two cases of every test here: the seeded
weights, the composition `run_training` makes, the plain reference in both
modes, the train cell's comparisons, and a `predict` rehearsal of a
throw-away cell in a temporary root. The weights of every configuration
of the benchmark, and of the one without BatchNorm, are bit for bit what
the harness made before it learnt this; and that configuration joins the
benchmark by new files and entries alone, through every check that holds
of each configuration. Tiny preset, CPU."""
import json

import jax
import numpy as np
import pytest

from benchmark import cells, system
from benchmark.jobs import train

from bench_testlib import (NO_BATCHNORM, REPO, add_cell,
                           check_configuration_file, check_weights,
                           config_doc, rehearse, throwaway_root)

WITH_BATCHNORM = "dimenetpp-s2ef"
KINDS = [WITH_BATCHNORM, NO_BATCHNORM]
CONFIGS = [c["name"] for c in cells.load_benchmark()["configs"]]


def leaves(tree):
    return jax.tree_util.tree_leaves(tree)


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return str(tmp_path_factory.mktemp("pools"))


def composed(name, cache):
    doc = system.apply_tiny(config_doc(name))
    pools = system.load_pools(doc, cache)
    config = system.complete_config(doc, pools, 4)
    return doc, config, system.Training(config, pools, num_shards=1)


@pytest.mark.parametrize("name", KINDS)
def test_a_stack_goes_through_with_batchnorm_and_without(name, cache):
    doc, config, comp = composed(name, cache)
    chk = system.check_structures(comp.loaders[2].dataset, 4)
    variables = system.init_variables(comp.model, chk, seed=5)
    assert set(variables) == {"params", "batch_stats"}
    has_batchnorm = name == WITH_BATCHNORM
    assert bool(leaves(variables["batch_stats"])) is has_batchnorm
    if has_batchnorm:
        # calibrated: no longer flax's initial mean 0, variance 1
        stats = variables["batch_stats"]["head_0_norm_0"]
        assert np.abs(np.asarray(stats["mean"])).max() > 1e-3
    else:
        assert variables["batch_stats"] == {}
    state = comp.initial_state(5)
    assert all(np.array_equal(a, b) for a, b in zip(
        leaves(state.params), leaves(variables["params"])))
    assert bool(leaves(state.batch_stats)) is has_batchnorm

    # the plain reference in both modes, with the system's weights
    plain = {"params": state.params, "batch_stats": state.batch_stats}
    e_eval, f_eval, _ = system.reference_energy_forces(
        doc, config, plain, chk, train=False)
    e_train, _, _ = system.reference_energy_forces(
        doc, config, plain, chk, train=True)
    assert np.isfinite(e_eval).all() and np.abs(f_eval).max() > 1e-3
    assert (system.relative_error(e_train, e_eval) > 1e-4) is has_batchnorm

    # the train cell's comparisons: warm-up, judge, the reading of
    # `loss_fell`
    against = train.Checks(comp, doc, config)
    against.as_run(state, warm=False)
    judged = against.judge()
    assert all(judged.ok.values()), judged.numbers
    assert {"train_step_energy_loss_at_highest", "eval_step_as_run_forces"
            } <= set(judged.numbers)
    # one step moves the weights, and the statistics where there are any
    stepped = against.stepped
    assert not np.array_equal(leaves(stepped.params)[0],
                              leaves(state.params)[0])
    assert bool(leaves(stepped.batch_stats)) is has_batchnorm
    batch = next(iter(comp.loaders[0]))
    assert 0 < train.loss_of(comp, stepped, [batch]) < np.inf


@pytest.mark.parametrize("name", KINDS)
def test_a_throw_away_predict_cell_of_either_kind_rehearses(
        name, tmp_path, monkeypatch, capsys):
    """New files and new entries in a temporary root, as
    test_bench_cells.py adds its own: the configuration (for the stack
    without BatchNorm, a file of its own), a cell of it under the
    `predict` mix, and the end-to-end rehearsal through `make_engine` and
    the serving job's comparisons (in this process, which holds the
    throw-away reference)."""
    root, bench, cell = throwaway_root(tmp_path, "throwaway",
                                       config_doc(name))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert cells.problems(str(root)) == []
    line, _ = rehearse(monkeypatch, capsys, cell, 3, root=str(root))
    assert line["correct"] is True and line["metrics"] == {}
    assert {"engine_at_highest_energy", "engine_at_highest_forces",
            "engine_as_run_energy", "batched_equals_single_bitwise"
            } <= set(line["checks"])
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("name", CONFIGS + [NO_BATCHNORM])
def test_weights_are_bit_for_bit_what_they_were(name, cache, tmp_path):
    """`check_weights`: with a `batch_stats` collection the 64 train-mode
    passes, without one `model.init`'s parameters, bit for bit at two
    seeds. Each configuration of the benchmark as its entry stands; the
    one without BatchNorm as a new file and entry in a temporary root."""
    if name in CONFIGS:
        root, bench = REPO, cells.load_benchmark()
        entry = next(c for c in bench["configs"] if c["name"] == name)
    else:
        root, bench, _ = throwaway_root(tmp_path, "throwaway",
                                        config_doc(name))
        root, entry = str(root), bench["configs"][-1]
    check_weights(root, entry, cache)


def test_a_configuration_without_batchnorm_joins_by_files_and_entries(
        tmp_path, cache):
    """The configuration without BatchNorm, cut in depth (`reduced`: two
    blocks of three), added as a later PR adds one: its file, its entry, a
    `predict` cell listed by the metrics of `schnet-s2ef.predict` and a
    `train-g4` cell listed by those of `dimenetpp-s2ef.train`, in a
    temporary root. Every name resolves and every check that holds of each
    configuration (`bench_testlib`: the file, the weights) holds there, as
    the tests over the real BENCHMARK.json hold it. (The rehearsal of such
    a cell is `test_a_throw_away_predict_cell_of_either_kind_rehearses`.)
    The weights of the configurations copied from BENCHMARK.json are
    `test_weights_are_bit_for_bit_what_they_were`'s own cases."""
    root, bench, predict = throwaway_root(tmp_path, "throwaway",
                                          config_doc(NO_BATCHNORM))
    train = add_cell(bench, "throwaway", "train-g4", "dimenetpp-s2ef.train")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    root = str(root)
    assert cells.problems(root) == []
    assert bench["configs"][-1]["reduced"] == ["num_conv_layers"]
    for config in bench["configs"]:
        check_configuration_file(bench, root, config)
    check_weights(root, bench["configs"][-1], cache)
    for cell, e2e in ((predict, "infer_graphs_per_s"),
                      (train, "train_graphs_per_s")):
        loaded = cells.load_cell(cell, root)
        assert {m["name"] for m in loaded.end_to_end} == {e2e, "setup_s"}
        assert loaded.per_layer and all(m["moves"] in (e2e, "setup_s")
                                        for m in loaded.per_layer)
