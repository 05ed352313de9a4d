"""Helpers of the benchmark's tests (no test lives here)."""
import glob
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def config_doc(name):
    """A configuration file of the benchmark as it stands; `<name>/mlp-head`
    is the throw-away configuration WITHOUT BatchNorm of the tests: the
    same stack read out through its MLP node head (DIMEStack has identity
    feature layers, so `model.init` then makes no `batch_stats`), with its
    plain reference beside the tests (dimenetpp_mlp_head.py), handed to
    the harness of THIS process under the package it looks references up
    in."""
    name, _, variant = name.partition("/")
    with open(os.path.join(REPO, "benchmark", "configs",
                           f"{name}.json")) as f:
        doc = json.load(f)
    if variant:
        assert variant == "mlp-head", variant
        import dimenetpp_mlp_head
        sys.modules["benchmark.reference.dimenetpp_mlp_head"] = (
            dimenetpp_mlp_head)
        doc["reference"] = "tests/benchmark/dimenetpp_mlp_head.py"
        doc["hydragnn"]["NeuralNetwork"]["Architecture"]["output_heads"][
            "node"]["type"] = "mlp"
    return doc


NO_BATCHNORM = "dimenetpp-s2ef/mlp-head"


def throwaway_root(tmp_path, config, doc, traffic="predict"):
    """A copy of the data files under `tmp_path` with one more configuration
    (`doc`, as `<config>.json`) and BENCHMARK.json's entries with that
    configuration and a cell of it under `traffic`, listed by every metric
    that lists `schnet-s2ef.predict`: new files and new entries only,
    nothing that was there is edited. Returns (root, entries, cell); the
    caller adds what else it needs and writes `root / "BENCHMARK.json"`."""
    root = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(REPO, "benchmark", sub),
                        root / "benchmark" / sub)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (root / "benchmark" / "configs" / f"{config}.json").write_text(
        json.dumps(doc))
    bench["configs"].append({
        "name": config, "source": "https://example.org/paper",
        "file": f"benchmark/configs/{config}.json", "reduced": [],
        "why": "throw-away"})
    cell = f"{config}.{traffic}"
    bench["workloads"].append({
        "name": cell, "config": config, "traffic": traffic, "chips": 1,
        "why": "throw-away"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "schnet-s2ef.predict" in metric.get("workloads", []):
            metric["workloads"].append(cell)
    return root, bench, cell


def kept_sweeps(kind):
    """{path: the sweeps of `kind` ("tolerance", "loss_fell") it keeps, by
    cell} of every `benchmark/calibration*.json` that keeps any: a later PR
    adds a file beside those that are there, and edits none."""
    kept = {}
    for path in sorted(glob.glob(os.path.join(REPO, "benchmark",
                                              "calibration*.json"))):
        with open(path) as f:
            sweeps = json.load(f).get(kind, {}).get("cells")
        if sweeps:
            kept[path] = sweeps
    return kept


def rehearse(monkeypatch, capsys, workload, seed, trace=0, seconds=0.5,
             root=None):
    """The rest of a run in THIS process (rehearsal preset, no look for a
    chip beyond the CPU gate, no compile cache): (its result line, what it
    printed)."""
    from benchmark import run, system
    monkeypatch.setattr(system, "enable_compile_cache", lambda: None)
    args = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    assert run.main(args + (["--root", root] if root else [])) == 0
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1]), captured


def run_cell(workload, seed=1, seconds=1.5, trace=0, devices=1, root=None,
             cwd=REPO, extra_env=None, timeout=420):
    """One rehearsal run of the benchmark's command in a child process on
    the CPU; returns (returncode, stdout lines)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTEST_CURRENT_TEST")}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.update(extra_env or {})
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    command = [sys.executable if command[0].startswith("python") else
               command[0]] + command[1:]
    args = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    if root is not None:
        args += ["--root", root]
    done = subprocess.run(command + args, cwd=cwd, env=env, timeout=timeout,
                          capture_output=True, text=True)
    return done.returncode, done.stdout.strip().splitlines(), done.stderr


def xspace_text(planes):
    """An XSpace text proto from {plane: {line: [(name, start_ns, dur_ns)]}}
    — what `ProfileData.from_text_proto` reads."""
    out = []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        names = {}
        body = []
        for lid, (line, events) in enumerate(lines.items(), 1):
            evs = []
            for name, start, dur in events:
                mid = names.setdefault(name, len(names) + 1)
                evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                           f"{int(start * 1000)} duration_ps: "
                           f"{int(dur * 1000)} }}")
            body.append(f'lines {{ id: {lid} name: {json.dumps(line)} '
                        f'timestamp_ns: 0 {" ".join(evs)} }}')
        meta = [f'event_metadata {{ key: {i} value {{ id: {i} name: '
                f'{json.dumps(n)} }} }}' for n, i in names.items()]
        out.append(f'planes {{ id: {pid} name: {json.dumps(plane)} '
                   f'{" ".join(body)} {" ".join(meta)} }}')
    return "\n".join(out)
