"""`loss_fell` (benchmark/jobs/train.py, PR 33): the loss of the trainer's
first batches, read by the train step itself with the fresh weights, with
the state the window's last step returned and with the states of the four
steps after it: the least of the five over the first. At the tiny preset
on the CPU: the sound step passes, the two controls that leave the
weights where they were read exactly 1 and fail, what the check adds lies
outside the window, and `calibrate loss_fell` writes what it promises;
the sweeps kept from the chip hold the limit as it stands."""
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import calibrate, cells, run, system
from benchmark.jobs import checks, train

from bench_testlib import REPO, kept_sweeps, rehearse

TRAIN_CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]
               if cells.load_cell(w["name"]).traffic["job"] == "train"]


@pytest.mark.parametrize("workload,seed", [
    ("pnaplus-s2ef.train", 1), ("pnaplus-s2ef.train", 11),
    ("schnet-s2ef.train", 1), ("pnaplus-s2ef.train-dp4", 1),
    ("dimenetpp-s2ef.train", 1)])
def test_the_sound_step_passes_at_the_seeds_the_rehearsals_use(
        monkeypatch, capsys, workload, seed):
    line, captured = rehearse(monkeypatch, capsys, workload, seed)
    value, limit = line["compared"]["loss_fell"]
    assert limit == checks.LOSS_FELL and 0 < value <= limit
    assert line["checks"]["loss_fell"] is True and line["correct"] is True
    # as many of the trainer's first batches as hold 32 structures (at the
    # rehearsal's 4 a step and 16 on four devices: 8 and 2), or as the
    # window had taken before it closed; five states read them
    said = re.search(r"loss_fell: the trainer's first (\d+) batch\(es\) "
                     r"read [\d.]+ with the fresh weights; with the state "
                     r"the window's last step returned and the 4 after it "
                     r"((?:[\d.]+, ){4}[\d.]+): least ratio ([\d.]+)",
                     captured.out)
    most = 2 if workload.endswith("dp4") else 8
    assert min(3, most) <= int(said.group(1)) <= most
    after = [float(x) for x in said.group(2).split(", ")]
    assert float(said.group(3)) == pytest.approx(value, abs=1e-4)
    assert min(after) < max(after)
    # the training loss of the window's ends is still in the log
    assert "(first tenth) ->" in captured.out


@pytest.mark.parametrize("workload", ["pnaplus-s2ef.train",
                                      "pnaplus-s2ef.train-dp4",
                                      "dimenetpp-s2ef.train"])
@pytest.mark.parametrize("fault", calibrate.LOSS_FELL_FAULTS)
def test_a_step_that_leaves_the_weights_where_they_were_is_not_correct(
        monkeypatch, capsys, fault, workload):
    """A step that returns its state unchanged, and the optimizer at
    learning rate 0: every comparison with the reference passes (the
    first step's losses and the eval step are what they should be), the
    TRAINING loss of the window's ends may fall by the draw of the
    batches, and `loss_fell` reads 1: `correct` is false through it
    alone."""
    cell = cells.load_cell(workload)
    monkeypatch.setattr(cells, "load_cell", lambda *a, **k: cell)
    with calibrate.planted(fault, cell):
        line, captured = rehearse(monkeypatch, capsys, workload, 11)
    assert line["compared"]["loss_fell"] == [1.0, checks.LOSS_FELL]
    assert [k for k, ok in line["checks"].items() if not ok] == ["loss_fell"]
    assert line["correct"] is False
    assert "compared loss_fell: 1 (limit" in captured.err
    assert "FAILED" in captured.err.strip().splitlines()[-2]
    # nothing stays planted
    assert "training" not in cell.traffic
    assert system.Training.__init__.__name__ == "__init__"


def test_what_the_check_adds_lies_outside_the_window(monkeypatch, capsys):
    """Set-up does what it did; the readings come past the window's
    close and before the comparisons: the state that closed the window and
    the four after it as the trainer goes on, then the fresh weights. The
    window itself counts the steps and graphs it counted (three traced
    steps of four graphs) and compiles nothing."""
    seen = []
    opened, closed = run.Context.open_window, run.Context.close_window
    monkeypatch.setattr(run.Context, "open_window", lambda ctx: (
        seen.append("open"), opened(ctx))[1])
    monkeypatch.setattr(run.Context, "close_window", lambda ctx: (
        seen.append("close"), closed(ctx))[1])
    for owner, name in ((train, "loss_of"), (train.Checks, "as_run"),
                        (train.Checks, "judge")):
        inner = getattr(owner, name)
        monkeypatch.setattr(
            owner, name, lambda *a, _inner=inner, _name=name, **k:
            (seen.append(_name), _inner(*a, **k))[1])
    line, captured = rehearse(monkeypatch, capsys, "pnaplus-s2ef.train", 1,
                              trace=1)
    assert seen == (["as_run", "open", "close"]
                    + ["loss_of"] * (train.LATE_STEPS + 2) + ["judge"])
    assert line["attempted"] == 3 and "3 steps in" in captured.out
    assert ": 12 graphs," in captured.out
    assert line["checks"]["zero_compiles_in_window"] is True
    assert line["correct"] is True


def test_calibrate_loss_fell_sweeps_seeds_and_writes_the_distribution(
        tmp_path):
    out = tmp_path / "loss_fell.json"
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTEST_CURRENT_TEST")}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.calibrate", "loss_fell",
         "--workload", "schnet-s2ef.train", "--seeds", "2", "--also", "1",
         "--seconds", "2.0", "--probe-at", "6", "--out", str(out)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    doc = json.loads(out.read_text())
    assert doc["workload"] == "schnet-s2ef.train" and doc["fault"] is None
    assert doc["limit"] == checks.LOSS_FELL and doc["trace_steps"] == 3
    assert doc["probe_at"] == [3, 6] and doc["late_steps"] == 4
    assert doc["batches"] == 8
    assert [r["seed"] for r in doc["rows"]][0] == 1 and len(doc["rows"]) == 2
    # a count is read once the trainer has fed all 8 batches (2 warm-up
    # steps + 6: not after the rehearsal's 3 traced steps) and where the
    # window reached the four steps after it
    assert min(doc["steps"]) >= 10
    assert set(doc["distribution"]) == {
        "ratio", "ratio_one_state", "ratio_at_6_steps",
        "ratio_one_state_at_6_steps"}
    for dist in doc["distribution"].values():
        assert dist["seeds"] == 2 and 0 < dist["min"] <= dist["max"]
    for row in doc["rows"]:
        # the least of five states is at most the first of them
        assert row["ratio"] <= row["ratio_one_state"]
        assert row["ratio_at_6_steps"] <= row["ratio_one_state_at_6_steps"]
    assert doc["other_checks_failed"] == {}
    assert doc["every_run_as_it_should_be"] is (
        doc["distribution"]["ratio"]["max"] <= checks.LOSS_FELL)


def test_the_kept_loss_fell_sweeps_hold_the_limit_as_it_stands(capsys):
    """Every `benchmark/calibration*.json` that keeps `loss_fell` sweeps
    (TPU v5 lite; a later PR adds a file beside PR 33's, as it does for the
    tolerances) judged by the present `LOSS_FELL`: every train cell of the
    benchmark has a sweep in one of them, read from the traced steps on;
    the limit lies above the widest sound reading and below the narrowest
    of both controls, with the rule's room above the sound program (the
    controls read exactly 1, with no spread). A file that holds fewer than
    a dozen seeds of a cell names its count and says why. A PR that moves
    the limit, or adds a train cell, without a sweep beside it fails
    here."""
    verdicts = {}
    for path, kept in kept_sweeps("loss_fell").items():
        assert calibrate.main(["verdict", path]) == 0
        judged = json.loads(capsys.readouterr().out)
        assert set(judged) == set(kept) and not set(judged) & set(verdicts)
        verdicts.update(judged)
        for cell, block in kept.items():
            assert ("seeds_floor" in block) is ("seeds_floor_why" in block)
            assert 3 <= block.get("seeds_floor", 3) < calibrate.SEEDS_FLOOR
    assert set(verdicts) == set(TRAIN_CELLS)
    for cell, verdict in verdicts.items():
        assert verdict["holds"] is True, (cell, verdict)
        assert verdict["limit"] == checks.LOSS_FELL
        assert verdict["seeds"] >= verdict["seeds_floor"], cell
        assert set(verdict["controls"]) == set(calibrate.LOSS_FELL_FAULTS)
        assert set(verdict["controls"].values()) == {1.0}
        assert 3 * verdict["sound_max"] <= verdict["limit"] < 1.0
