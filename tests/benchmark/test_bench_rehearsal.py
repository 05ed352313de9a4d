"""The benchmark's command, end to end, at each cell's tiny preset on the
CPU: every job runs through the program's real entry points, checks its
outputs against the plain reference, and prints the contract's line with
an EMPTY `metrics` — a CPU run never reports a number under a metric's
name. `train-dp4` runs on four virtual CPU devices."""
import json
import os
import shutil

import pytest

from benchmark import cells, run

from bench_testlib import DEVICE_KEYS, LINE_KEYS, REPO, run_cell

CASES = [(w["name"], w["chips"], 0) for w in
         cells.load_benchmark()["workloads"]]
# the traced path once for each kind of window: steps, and requests
CASES += [("pnaplus-s2ef.train", 1, 1), ("schnet-s2ef.serve-open", 1, 1)]


@pytest.mark.parametrize("workload,chips,trace", CASES,
                         ids=[f"{w}-trace{t}" for w, _, t in CASES])
def test_tiny_preset_runs_end_to_end_and_reports_no_metric(workload, chips,
                                                           trace):
    rc, out, err = run_cell(workload, trace=trace, devices=chips)
    assert rc == 0, err[-3000:]
    line = json.loads(out[-1])
    assert LINE_KEYS <= set(line) <= LINE_KEYS | {"checks", "breakdown",
                                                  "compared"}
    # every number compared, beside its limit, under the last key
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == set(line["checks"])
    assert all(len(pair) == 2 for pair in line["compared"].values())
    assert err.strip().splitlines()[-1].startswith("compared ")
    assert ("breakdown" in line) == bool(trace)
    assert DEVICE_KEYS <= set(line["device"])
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert line["metrics"] == {}, "no CPU number under a metric's name"
    assert line["attempted"] > 0 and line["failed"] == 0
    failed = [k for k, ok in line["checks"].items() if not ok]
    assert line["correct"] is True and not failed, failed
    assert line["checks"]["zero_compiles_in_window"] is True
    # held to the plain reference both at highest precision and as run
    assert any("at_highest" in k for k in line["checks"])
    assert any("as_run" in k for k in line["checks"])
    assert any("] layout: neighbor_format=" in l
               for l in out), "the layout the cell ran is printed"


def test_without_the_program_the_command_fails_and_prints_no_result(
        tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths` there is no system to measure."""
    bench = cells.load_benchmark()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for path in bench["paths"]:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    rc, out, err = run_cell("schnet-s2ef.predict", cwd=str(tmp_path),
                            extra_env={"PYTHONPATH": ""})
    assert rc != 0
    assert not any(l.startswith("{") for l in out)
    assert "hydragnn_tpu" in err


@pytest.mark.parametrize("backend,env,devices,ok", [
    ("tpu", None, 1, True), ("tpu", None, 4, True), ("cpu", "cpu", 4, False),
    ("cpu", None, 1, None), ("cpu", "cpu,tpu", 1, None),
    ("gpu", None, 1, None), ("tpu", None, 0, None)])
def test_gate(monkeypatch, backend, env, devices, ok):
    """A chip run needs the TPU and enough chips; the CPU is a rehearsal
    only when JAX_PLATFORMS=cpu is set by name; anything else exits."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    if env is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", env)
    chips = 4 if devices == 4 else 1
    if ok is None:
        with pytest.raises(SystemExit) as exc:
            run.gate(chips)
        assert exc.value.code not in (0, None)
    else:
        assert run.gate(chips) is ok
