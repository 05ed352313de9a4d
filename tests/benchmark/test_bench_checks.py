"""The comparison behind `correct` (benchmark/jobs/checks.py), held to its
own rule at the tiny preset on the CPU: over many seeds every at-highest
reading of the sound program stays under a third of its tolerance, on one
device and on four; every shard of a data-parallel check gets its full
share of structures in the loader's padded shape; each negative control
fails at every seed tried; the scalars made of forces are printed and not
judged at highest; a run whose step is broken underneath comes out
`"correct": false`; and `calibrate tolerance --seeds` writes the
distribution it promises. One compile per module-scoped fixture."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import cells, run, system
from benchmark.jobs import checks, train

from bench_testlib import REPO, kept_sweeps

SEEDS = [int(s) for s in np.random.RandomState(25).randint(
    1, 2 ** 31 - 1, size=15)] + [2071849904]
CONTROL_SEEDS = SEEDS[-8:]
# what is judged at highest on one device and on four, and what is printed
JUDGED = {1: {"train_step_energy_loss_at_highest",
              "eval_step_at_highest_energy", "eval_step_at_highest_forces"},
          4: {"train_step_energy_loss_at_highest",
              "eval_step_at_highest_energy_loss"}}
RECORDED = {1: {"train_step_loss_at_highest",
                "train_step_force_loss_at_highest"},
            4: {"train_step_loss_at_highest",
                "train_step_force_loss_at_highest",
                "eval_step_at_highest_force_loss"}}
PER_SHARD = system.CHECK_STRUCTURES


def tiny_doc(dtype=None):
    """The PNAPlus configuration at its tiny preset, with a test pool large
    enough to give four shards their eight structures each."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "pnaplus-s2ef.json")) as f:
        doc = system.apply_tiny(json.load(f))
    doc["data"]["test_structures"] = 4 * PER_SHARD
    if dtype:
        doc["hydragnn"]["NeuralNetwork"]["Architecture"]["dtype"] = dtype
    return doc


@pytest.fixture(scope="module")
def pools(tmp_path_factory):
    return system.load_pools(tiny_doc(), str(tmp_path_factory.mktemp("p")))


def composed(pools, shards, dtype=None):
    doc = tiny_doc()
    config = system.complete_config(doc, pools, PER_SHARD * shards)
    comp = system.Training(
        system.complete_config(tiny_doc(dtype), pools, PER_SHARD * shards)
        if dtype else config, pools, num_shards=shards)
    # the reference is always the float32 one
    return comp, train.Checks(comp, doc, config)


@pytest.fixture(scope="module", params=[1, 4], ids=["1dev", "4dev"])
def sound(request, pools):
    return composed(pools, request.param)


@pytest.fixture(scope="module", params=[1, 4], ids=["1dev", "4dev"])
def bfloat16(request, pools):
    return composed(pools, request.param, "bfloat16")


def test_every_shard_gets_its_structures_in_the_loaders_padded_shape(sound):
    comp, chk = sound
    n = comp.num_shards
    loader = comp.loaders[0]
    assert chk.per_shard == PER_SHARD and len(chk.chk) == PER_SHARD * n
    assert len({id(s) for s in chk.chk}) == len(chk.chk), "no structure twice"
    assert sorted(g for members in chk.shards for g in members) == list(
        range(len(chk.chk)))
    median = np.median([s.num_nodes for s in chk.chk])
    for members in chk.shards:
        sizes = [chk.chk[g].num_nodes for g in members]
        assert len(members) == PER_SHARD
        assert min(sizes) < median < max(sizes), "small and large in each"
    lead = () if n == 1 else (n,)
    assert chk.placed.x.shape[:len(lead) + 1] == lead + (loader.n_node,)
    assert chk.placed.senders.shape == lead + (loader.n_edge,)
    assert chk.placed.graph_mask.shape == lead + (loader.n_graph,)
    real = np.asarray(chk.placed.graph_mask).reshape(n, -1).sum(axis=1)
    assert list(real) == [PER_SHARD] * n
    # a train batch of the window has the same shape: the warm-up compiles
    # what the window runs
    batch = comp.place(next(iter(loader)))
    assert batch.x.shape == chk.placed.x.shape
    assert batch.senders.shape == chk.placed.senders.shape


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_program_reads_under_a_third_of_every_tolerance(sound, seed):
    comp, chk = sound
    chk.as_run(comp.initial_state(seed), warm=False)
    judged = chk.judge()
    assert all(judged.ok.values()), judged.numbers
    highest = {k: v for k, v in judged.numbers.items() if "at_highest" in k}
    assert set(highest) == JUDGED[comp.num_shards]
    for name, (value, limit) in highest.items():
        assert value <= limit / 3, (name, value, limit)
        assert limit == checks.HIGHEST_TOL[judged.keys[name]]
    # the scalars made of forces are printed at highest and not judged
    # there: they have a tail (seed 353376731 reads 1.2e-4 on four devices,
    # forty times the median seed: a near-tie rounded the other way)
    recorded = {k: v for k, v in judged.recorded.items() if "at_highest" in k}
    assert set(recorded) == RECORDED[comp.num_shards]
    assert max(recorded.values()) < 1e-2
    # every judged number is there beside its limit; as run the total (and
    # a data-parallel eval step's losses) are still judged
    assert set(judged.numbers) == set(judged.ok)
    assert judged.numbers["train_step_loss_as_run"][1] == 0.05
    if comp.num_shards > 1:
        assert judged.numbers["eval_step_as_run_force_loss"][1] == 0.05


@pytest.mark.parametrize("seed", CONTROL_SEEDS)
def test_a_change_of_the_mathematics_fails(sound, seed):
    """The reference with one edge in a hundred masked out, in the
    program's place: some at-highest number is three times over its
    tolerance or more."""
    comp, chk = sound
    chk.as_run(comp.initial_state(seed), warm=False)
    judged = chk.control("edge_mask")
    assert set(judged.numbers) == JUDGED[comp.num_shards]
    assert not all(judged.ok.values())
    assert max(v / limit for v, limit in judged.numbers.values()) >= 3


@pytest.mark.parametrize("seed", CONTROL_SEEDS[-4:])
def test_bfloat16_compute_fails(bfloat16, seed):
    """The program itself with Architecture.dtype bfloat16, traced at
    highest matmul precision, against the float32 reference."""
    comp, chk = bfloat16
    chk.as_run(comp.initial_state(seed), warm=False)
    judged = chk.judge()
    highest = {k: v for k, v in judged.numbers.items() if "at_highest" in k}
    assert not all(judged.ok[k] for k in highest)
    assert max(v / limit for v, limit in highest.values()) >= 3


def test_compared_prints_value_reference_tolerance_and_shard(capsys):
    lines = []
    out = checks.Compared(lines.append)
    tol = {"loss": 1e-3}
    assert out.close("a_loss", 1.01, 1.0, tol, "loss", "mean of 4 shards x "
                     "8 structures") is False
    # a number the table has no entry for is printed and not judged
    assert out.close("a_force_loss", 2.0, 1.0, tol, "force_loss",
                     "one chip") is True
    assert out.recorded == {"a_force_loss": 1.0}
    assert "recorded, not judged" in lines[1] and "system 2.0" in lines[1]
    assert out.keys == {"a_loss": "loss"}
    assert out.flag("nothing_failed", True) is True
    out.arrays("eval", np.ones(3), np.ones((5, 3)), np.ones(3),
               np.ones((5, 3)), {"energy": 1e-4, "forces": 2e-2}, "one chip")
    assert out.record("not_a_number", float("nan"), 1.0) is False
    assert "system 1.010000, reference 1.000000" in lines[0]
    assert "tolerance 1e-03" in lines[0] and "mean of 4 shards" in lines[0]
    assert out.ok == {"a_loss": False, "nothing_failed": True,
                      "eval_energy": True, "eval_forces": True,
                      "not_a_number": False}
    assert out.numbers["a_loss"] == [pytest.approx(0.01), 1e-3]
    out.report()
    err = capsys.readouterr().err.strip().splitlines()
    assert err[0].startswith("compared a_loss: 0.01") and "FAILED" in err[0]
    assert len(err) == 5


def test_shard_terms_and_their_composition():
    struct = {"node_graph": np.array([0, 0, 1, 2, 2, 2]),
              "energy": np.array([1.0, 2.0, 3.0]),
              "forces": np.zeros((6, 3))}
    ref_e = np.array([2.0, 2.0, 0.0])
    ref_f = np.concatenate([np.full((2, 3), 1.0), np.full((1, 3), 4.0),
                            np.full((3, 3), 2.0)])
    terms = checks.shard_terms(ref_e, ref_f, struct, [[0, 2], [1]])
    assert terms[0] == {"energy_loss": 2.0, "force_loss": 1.6, "graphs": 2}
    assert terms[1] == {"energy_loss": 0.0, "force_loss": 4.0, "graphs": 1}
    assert checks.compose(terms)["force_loss"] == pytest.approx(
        2 / 3 * 1.6 + 1 / 3 * 4.0)
    lines = []
    checks.describe_shards(terms, lines.append, "train mode,")
    assert len(lines) == 2 and "shard 1 of 2 (1 structures)" in lines[1]


def test_drop_edges_leaves_one_edge_in_a_hundred_out(pools):
    sample = max(pools[2], key=lambda s: len(s.senders))
    less = system.drop_edges(sample)
    assert len(less.senders) == len(sample.senders) - len(
        sample.senders) // 100
    assert less.num_nodes == sample.num_nodes
    assert len(less.receivers) == len(less.edge_shifts) == len(less.senders)


def test_the_tolerances_of_the_loose_reading_are_what_pr22_set():
    assert checks.AS_RUN_TOL == {"energy": 0.2, "forces": 1.0, "loss": 0.05}
    # at highest no scalar made of forces has a limit (jobs/checks.py)
    assert set(checks.HIGHEST_TOL) == {"energy", "forces",
                                       "train_energy_loss", "energy_loss"}


FAULTS = ["half_of_the_batch_left_out", "losses_altered"]


@pytest.mark.parametrize("workload", ["pnaplus-s2ef.train",
                                      "pnaplus-s2ef.train-dp4"])
@pytest.mark.parametrize("fault", FAULTS)
def test_a_run_with_its_step_broken_underneath_is_not_correct(
        monkeypatch, capsys, fault, workload):
    """The rest of a run of a train cell, on one device and on four
    (rehearsal preset, this process, no look for a chip beyond the CPU
    gate), with the train step broken under the job: `correct` comes out
    false, through an at-highest number, and the number is on the result
    line beside its limit."""
    import jax.numpy as jnp
    built = system.Training.__init__

    def broken(self, *args, **kwargs):
        built(self, *args, **kwargs)
        step = self.train_step

        def train_step(state, batch):
            if fault == "half_of_the_batch_left_out":
                keep = jnp.cumsum(batch.graph_mask.astype(jnp.int32),
                                  axis=-1) % 2
                batch = batch.replace(
                    graph_mask=batch.graph_mask * keep.astype(
                        batch.graph_mask.dtype))
            state, metrics = step(state, batch)
            if fault == "losses_altered":
                metrics = {k: v * 1.001 if k.endswith("loss") else v
                           for k, v in metrics.items()}
            return state, metrics
        self.train_step = train_step
    monkeypatch.setattr(system.Training, "__init__", broken)
    monkeypatch.setattr(system, "enable_compile_cache", lambda: None)
    assert run.main(["--workload", workload, "--seed", "11",
                     "--seconds", "0.5", "--trace", "0"]) == 0
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert list(line)[-1] == "compared"
    value, limit = line["compared"]["train_step_energy_loss_at_highest"]
    assert value > 3 * limit
    assert line["checks"]["train_step_energy_loss_at_highest"] is False
    assert "train_step_loss_at_highest" not in line["compared"]
    last = captured.err.strip().splitlines()[-len(line["compared"]):]
    assert all(l.startswith("compared ") for l in last)
    assert any("train_step_energy_loss_at_highest" in l and "FAILED" in l
               for l in last)


def test_a_served_answer_altered_where_it_is_produced_is_not_correct(
        monkeypatch, capsys):
    """The rest of a `predict` run with every engine's answers moved by a
    thousandth of the energy: the at-highest energies fail, ten times over
    their tolerance."""
    made = system.make_engine

    def altered(*args, **kwargs):
        engine = made(*args, **kwargs)
        predict = engine.predict

        def moved(samples, **kw):
            return [((energy * 1.001,) + tuple(rest), *more)
                    for (energy, *rest), *more in predict(samples, **kw)]
        engine.predict = moved
        return engine
    monkeypatch.setattr(system, "make_engine", altered)
    monkeypatch.setattr(system, "enable_compile_cache", lambda: None)
    assert run.main(["--workload", "schnet-s2ef.predict", "--seed", "12",
                     "--seconds", "0.5", "--trace", "0"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    value, limit = line["compared"]["engine_at_highest_energy"]
    assert value > 3 * limit
    assert line["checks"]["engine_at_highest_forces"] is True


def test_calibrate_tolerance_sweeps_seeds_and_writes_the_distribution(
        tmp_path):
    out = tmp_path / "tolerance.json"
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTEST_CURRENT_TEST")}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.calibrate", "tolerance",
         "--workload", "pnaplus-s2ef.train", "--seeds", "3", "--also", "7",
         "--control-seeds", "1", "--out", str(out)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    doc = json.loads(out.read_text())
    assert doc["workload"] == "pnaplus-s2ef.train" and doc["devices"] == 1
    names = JUDGED[1]
    assert set(doc["at_highest"]) == names | RECORDED[1]
    for name, dist in doc["at_highest"].items():
        assert {"seeds", "min", "median", "p90", "max", "seed_of_max"} <= set(
            dist)
        assert dist["seeds"] == 3 and dist["min"] <= dist["median"] <= dist[
            "p90"] <= dist["max"]
        assert (dist["judged_by"] in checks.HIGHEST_TOL) == (name in names)
        assert (dist["judged_by"] is None) == (name in RECORDED[1])
    assert set(doc["controls_at_highest"]) == set(checks.CONTROLS)
    assert doc["controls_at_highest"]["bfloat16"][
        "eval_step_at_highest_forces"]["seeds"] == 1
    verdict = doc["verdict"]
    assert verdict["holds"] is True
    assert set(verdict["limits"]) == names
    assert set(verdict["controls"]) == set(checks.CONTROLS)
    assert all(v["room_above_sound"] >= 3 for v in verdict["limits"].values())
    assert all(v["room_below_controls"] > 1
               for v in verdict["limits"].values())
    assert all(v["room_below_control"] >= 3
               for v in verdict["controls"].values())
    assert cells.load_cell("pnaplus-s2ef.train").traffic["job"] == "train"


def test_the_kept_sweeps_hold_the_tolerances_as_they_stand(capsys):
    """The sweeps kept in every `benchmark/calibration*.json` (TPU v5
    lite; PR 25's in calibration.json, a later cell's in the file its PR
    adds) judged by the present HIGHEST_TOL: every limit 3 x above the
    widest sound reading of every sweep kept and below every control that
    separates from the sound program, every control 3 x over the limit of
    some number; every cell of the benchmark has a sweep in one of the
    files. A PR that moves a tolerance, or adds a cell, without a sweep
    beside it fails here."""
    from benchmark import calibrate
    verdicts, kept = {}, {}
    for path, sweeps in kept_sweeps("tolerance").items():
        kept.update(sweeps)
        assert calibrate.main(["verdict", path]) == 0
        verdicts.update(json.loads(capsys.readouterr().out))
    assert set(verdicts) == {w["name"] for w in
                             cells.load_benchmark()["workloads"]}
    for cell, verdict in verdicts.items():
        assert verdict["holds"] is True, (cell, verdict)
        assert set(verdict["controls"]) == set(checks.CONTROLS)
        for name, held in verdict["limits"].items():
            assert held["room_above_sound"] >= 3, (cell, name)
            # no limit sits above a control that separates
            assert all(reading > held["limit"]
                       for reading in held["held_against"].values())
    assert {dist["judged_by"] for sweep in kept.values()
            for dist in sweep["at_highest"].values()
            if dist["judged_by"]} == set(checks.HIGHEST_TOL)
