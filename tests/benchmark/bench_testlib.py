"""Helpers of the benchmark's tests (no test lives here)."""
import glob
import json
import os
import re
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
        arch = doc["hydragnn"]["NeuralNetwork"]["Architecture"]
        arch["output_heads"]["node"]["type"] = "mlp"
        # two DimeNet++ blocks where the source has three: the conv-type
        # head's block is gone with it
        doc["reduced"] = [f"num_conv_layers: {arch['num_conv_layers']} of "
                          f"{doc['assumed']['num_blocks']}"]
    return doc


NO_BATCHNORM = "dimenetpp-s2ef/mlp-head"


def throwaway_root(tmp_path, config, doc, traffic="predict",
                   like="schnet-s2ef.predict"):
    """A copy of the data files under `tmp_path` with one more configuration
    (`doc`, as `<config>.json`) and BENCHMARK.json's entries with that
    configuration and a cell of it under `traffic` (`add_cell`, listed by
    the metrics that list the cell `like`): new files and new entries only,
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
    arch = doc["hydragnn"]["NeuralNetwork"]["Architecture"]
    bench["configs"].append({
        "name": config, "source": "https://example.org/paper",
        "file": f"benchmark/configs/{config}.json",
        "reduced": depth_cuts(doc["reduced"], arch), "why": "throw-away"})
    return root, bench, add_cell(bench, config, traffic, like)


def add_cell(bench, config, traffic, like):
    """Appends to `bench` the cell `<config>.<traffic>` on one chip and adds
    its name to every metric's `workloads` list that names the cell
    `like`; returns the new cell's name."""
    cell = f"{config}.{traffic}"
    bench["workloads"].append({
        "name": cell, "config": config, "traffic": traffic, "chips": 1,
        "why": "throw-away"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if like in metric.get("workloads", []):
            metric["workloads"].append(cell)
    return cell


# What holds of EVERY configuration is checked by a function of one
# `configs` entry and the root its file lies under (with BENCHMARK.json's
# entries where it reads them), never by a test that indexes the real file
# alone: the tests over the real BENCHMARK.json call it for each of its
# configurations, and
# test_bench_batchnorm_optional.py's
# `test_a_configuration_without_batchnorm_joins_by_files_and_entries` calls
# it over a throw-away root with one more, as a later PR would add it. A
# check that only the real file ever reaches can pin what every
# configuration is (a BatchNorm was one) where no test sees it.

DEPTH_KEYS = ("num_conv_layers",)
CUT = re.compile(r"^([A-Za-z0-9_]+): ([0-9]+) of ([0-9]+)$")


def depth_cuts(reduced, arch):
    """The Architecture keys that a configuration file's `reduced` cuts,
    in its order. Each entry reads `"<Architecture key>: <held> of
    <published>"`: a cut in depth (the model-configs guide, section 4), of
    a key in `DEPTH_KEYS`, held as the file's Architecture holds it and
    under the published count. A width is never cut."""
    keys = []
    for cut in reduced:
        match = CUT.match(cut)
        assert match, f"{cut!r} does not read '<key>: <held> of <published>'"
        key, held, published = match.group(1), *map(int, match.groups()[1:])
        assert key in DEPTH_KEYS, (
            f"{key}: only a cut in depth ({', '.join(DEPTH_KEYS)}), never "
            "a width")
        assert arch[key] == held and 0 < held < published, cut
        keys.append(key)
    assert len(set(keys)) == len(keys), reduced
    return keys


def read_config(root, config):
    with open(os.path.join(root, config["file"])) as f:
        return json.load(f)


def check_configuration_file(bench, root, config):
    """A `configs` entry of `bench` and the file it names under `root`: the
    entry's keys, the file's sections, `reduced` (`depth_cuts`: the entry
    lists the keys the file cuts), the plain reference (code, so this
    package's, wherever the data lies) and no batch layout."""
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert any(config["file"].startswith(p + "/") for p in bench["paths"])
    doc = read_config(root, config)
    for key in ("source", "reduced", "assumed", "departures", "reference",
                "hydragnn", "data", "tiny"):
        assert key in doc, key
    arch = doc["hydragnn"]["NeuralNetwork"]["Architecture"]
    assert depth_cuts(doc["reduced"], arch) == config["reduced"]
    assert os.path.exists(os.path.join(REPO, doc["reference"]))
    assert "neighbor_format" not in arch, "a cell takes the default layout"


def check_weights(root, config, cache):
    """The seeded weights of a configuration (its file under `root`, tiny
    preset, pools under `cache`) are bit for bit what `model.init` makes,
    as `system.initialiser` did before BatchNorm became optional, written
    out: where the model has a `batch_stats` collection, the same key and
    the same 64 train-mode passes over the check structures, in one jitted
    call; where it has none, the parameters and an empty collection. At
    two seeds, through `system.init_variables` and the composition's
    `initial_state`."""
    import jax
    import numpy as np
    from benchmark import system
    from hydragnn_tpu.graphs.batch import collate, with_neighbor_format
    doc = system.apply_tiny(read_config(root, config))
    pools = system.load_pools(doc, cache)
    comp = system.Training(system.complete_config(doc, pools, 4), pools,
                           num_shards=1)
    model = comp.model
    chk = system.check_structures(comp.loaders[2].dataset)
    n = 64 * (sum(s.num_nodes for s in chk) // 64 + 1)
    e = 64 * (sum(s.num_edges for s in chk) // 64 + 1)
    batch = with_neighbor_format(collate(
        list(chk), n_node=n, n_edge=e, n_graph=len(chk) + 1, np_out=True))

    @jax.jit
    def as_it_was(key):
        variables = model.init(key, batch, train=False)
        if "batch_stats" not in variables:
            return {"params": variables["params"], "batch_stats": {}}

        def one_pass(_, stats):
            _, mutated = model.apply(
                {"params": variables["params"], "batch_stats": stats},
                batch, train=True, mutable=["batch_stats"])
            return mutated["batch_stats"]
        stats = jax.lax.fori_loop(0, 64, one_pass, variables["batch_stats"])
        return {"params": variables["params"], "batch_stats": stats}

    leaves = jax.tree_util.tree_leaves
    for seed in (3, 2071849904):
        was = as_it_was(jax.random.PRNGKey(seed))
        now = system.init_variables(model, chk, seed)
        assert jax.tree_util.tree_structure(was) \
            == jax.tree_util.tree_structure(now)
        assert all(np.array_equal(a, b)
                   for a, b in zip(leaves(was), leaves(now)))
        state = comp.initial_state(seed)
        assert all(np.array_equal(a, b) for a, b in zip(
            leaves(was["batch_stats"]), leaves(state.batch_stats)))


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
