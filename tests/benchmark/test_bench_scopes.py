"""`benchmark/readers/scopes.py`: the reader on hand-made traces (written
here byte by byte in the protobuf wire format, read back by JAX's own
reader too); every scope of the vocabulary in the lowered programs the
cells run; and the new metric entries on a run that has nothing for them.
"""
import functools
import json
import os
import types

import pytest

from benchmark import cells, run
from benchmark.readers import scopes, spans
from benchmark.trace import reduce as tr

from bench_testlib import REPO


# ------------------------------------------- an .xplane.pb, byte by byte --

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(field, value):
    return _varint(field << 3) + _varint(value)


def _bytes(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


TF_OP, CATEGORY = 7, 9          # stat metadata ids, any numbers


def plane(name, lines, by_reference=False):
    """An XPlane: {line: [(event name, op name or None, start_ns,
    dur_ns)]}. `by_reference`: op names as `ref_value` into the stat
    names instead of `str_value` (the profiler uses both forms)."""
    stat_names = {TF_OP: "tf_op", CATEGORY: "hlo_category"}
    metadata, body = {}, b""
    for lid, (line, events) in enumerate(lines.items(), 1):
        evs = b""
        for event, op_name, start, dur in events:
            mid = metadata.setdefault((event, op_name), len(metadata) + 1)
            evs += _bytes(4, _int(1, mid) + _int(2, int(start * 1000))
                          + _int(3, int(dur * 1000)))
        body += _bytes(3, _int(1, lid) + _bytes(2, line) + evs)
    meta = b""
    for (event, op_name), mid in metadata.items():
        stats = _bytes(5, _int(1, CATEGORY) + _bytes(5, "loop fusion"))
        if op_name is not None and by_reference:
            ref = 100 + mid
            stat_names[ref] = op_name
            stats += _bytes(5, _int(1, TF_OP) + _int(7, ref))
        elif op_name is not None:
            stats += _bytes(5, _int(1, TF_OP) + _bytes(5, op_name))
        meta += _bytes(4, _int(1, mid) + _bytes(
            2, _int(1, mid) + _bytes(2, event) + stats))
    for sid, text in stat_names.items():
        meta += _bytes(5, _int(1, sid) + _bytes(
            2, _int(1, sid) + _bytes(2, text)))
    return _bytes(1, _bytes(2, name) + body + meta)


@pytest.fixture()
def write_trace(tmp_path, monkeypatch):
    """Writes planes as the one trace under a `.bench_trace/` of its own,
    which the reader is pointed at; returns the file's path."""
    monkeypatch.setattr(scopes, "TRACE_ROOT", str(tmp_path))

    def write(planes):
        out = tmp_path / "cell" / "plugins" / "profile" / "t"
        out.mkdir(parents=True)
        path = out / "host.xplane.pb"
        path.write_bytes(b"".join(planes))
        return str(path)
    return write


STEP = "jit(step_body)/"
OPS = [  # one chip, window 0..1000 ns; 900 ns busy
    ("%fusion.1", STEP + "jvp(ef_forces)/jvp(PNAPlusStack)/"
     "PNAPlusStack.encode/conv_0/edge_gather/gather:", 0, 200),
    ("%fusion.2", STEP + "transpose(jvp(ef_forces))/transpose(jvp("
     "PNAPlusStack))/PNAPlusStack.encode/conv_0/edge_gather/gather:",
     200, 100),
    ("%fusion.3", STEP + "jvp(ef_forces)/jvp(PNAPlusStack)/"
     "PNAPlusStack.encode/conv_1/neighbor_gather/gather:", 300, 100),
    ("%fusion.4", STEP + "jvp(ef_forces)/jvp(PNAPlusStack)/"
     "PNAPlusStack.encode/conv_1/pre_i/dot_general:", 400, 100),
    ("%fusion.5", STEP + "jvp(ef_forces)/jvp(PNAPlusStack)/heads/"
     "PNAPlusStack.decode/head_0/dense_0/dot_general:", 500, 100),
    ("%fusion.6", STEP + "jvp(ef_forces)/jvp(PNAPlusStack)/heads/"
     "PNAPlusStack.decode/aggregate/scatter-add:", 600, 50),
    ("%fusion.7", STEP + "optimizer/mul:", 650, 50),
    ("%copy-done.8", None, 700, 100),
    ("%fusion.9", STEP + "checkpoint/vmap(jvp(conv_2))/aggregate/"
     "reduce_sum:", 800, 100),
]
WINDOW = ("/host:CPU", {"python": [(tr.WINDOW_EVENT, None, 0, 1000)]})


def readings(path):
    """The part of `Readings` the reader looks at, from the file itself."""
    planes = tr.load_xplane(path)
    reduced = (tr.reduce_planes(planes) if tr.device_planes(planes)
               else None)
    return types.SimpleNamespace(reduced=reduced)


@pytest.fixture()
def step_trace(write_trace):
    return write_trace([
        plane("/device:TPU:0", {tr.OPS_LINE: OPS}), plane(*WINDOW)])


def test_the_file_written_here_is_one_jax_reads_too(step_trace):
    """The encoder above and the decoder under test agree with the real
    schema: JAX's own reader sees the same events."""
    planes = tr.load_xplane(step_trace)
    device = tr.device_planes(planes)[0]
    assert [(e.name, e.start_ns, e.dur_ns) for e in
            device.lines[tr.OPS_LINE]] == [
        (name, float(a), float(d)) for name, _, a, d in OPS]
    (ops,) = scopes.read_device_ops(step_trace)
    assert [(o[3], o[0], o[1] - o[0]) for o in ops] == [
        (name, float(a), float(d)) for name, _, a, d in OPS]
    assert ops[0][4] == "loop fusion"
    assert ops[7][2] == ()              # no op name at all


@pytest.mark.parametrize("args,expected", [
    # forward and transpose of one scope count together: 300 of 900
    (dict(scopes=["edge_gather"]), 100 * 300 / 900),
    # a pattern; conv_2 sits under checkpoint/vmap(jvp()) wrappers
    (dict(scopes=["conv_*"]), 100 * 600 / 900),
    (dict(scopes=["conv_1"]), 100 * 200 / 900),
    # module names: pre_i in a conv and dense_0 in the heads
    (dict(scopes=["pre_i", "dense_*"]), 100 * 200 / 900),
    # `within`: the heads' pooling is `aggregate` too, but not the conv's
    (dict(scopes=["aggregate"]), 100 * 150 / 900),
    (dict(scopes=["aggregate"], within="conv_*"), 100 * 100 / 900),
    # the complement: the copy without an op name, and nothing else
    (dict(scopes=list(scopes.VOCABULARY), unscoped=True), 100 * 100 / 900),
    (dict(scopes=["ef_forces"], requires="optimizer"), 100 * 650 / 900),
    (dict(scopes=["grad_allreduce"]), 0.0),
], ids=["forward_and_transpose", "pattern_under_wrappers", "one_conv",
        "module_names", "aggregate_anywhere", "aggregate_within_conv",
        "unscoped", "requires_met", "absent_scope_reads_zero"])
def test_share_of_busy_time(step_trace, args, expected):
    got = scopes.share(readings(step_trace), **args)
    assert got == pytest.approx(expected)


def test_metric_files_on_the_hand_made_step(step_trace):
    """The train cell's own metric files: the conv's inner shares cannot
    sum to more than the conv's, and everything adds up to the whole."""
    cell = cells.load_cell("pnaplus-s2ef.train")
    r = readings(step_trace)
    got = {m["name"]: scopes.share(r, **m["file"]["args"])
           for m in cell.per_layer
           if m["file"]["reader"] == "scopes.share"}
    assert set(got) == {
        "step_conv_share", "step_layout_gather_share",
        "step_neighbor_gather_share", "step_aggregate_share",
        "step_dense_share", "step_unscoped_share"}
    assert all(0.0 <= v <= 100.0 for v in got.values()), got
    inner = (got["step_layout_gather_share"] + got["step_aggregate_share"]
             + got["step_neighbor_gather_share"])
    assert inner <= got["step_conv_share"] + 1e-9
    assert got["step_unscoped_share"] == pytest.approx(100 * 100 / 900)


PARENT_OPS = [  # the parent's program: flax module names, no vocabulary
    ("%fusion.1", STEP + "jvp(PNAPlusStack)/PNAPlusStack.encode/conv_0/"
     "pre_i/dot_general:", 0, 500),
    ("%fusion.2", STEP + "mul:", 500, 400),
]


@pytest.mark.parametrize("case", ["no_device_plane", "no_requires",
                                  "parent_module_names", "no_trace"])
def test_none_where_there_is_nothing_to_read(write_trace, case):
    if case == "no_device_plane":   # a CPU rehearsal's trace
        path = write_trace([plane(*WINDOW)])
        assert scopes.read_device_ops(path) == []
        r, args = readings(path), dict(scopes=["conv_*"])
    elif case == "no_trace":         # nothing under .bench_trace/
        r = types.SimpleNamespace(reduced=types.SimpleNamespace(
            window_ns=(0.0, 1000.0)))
        assert scopes.share(r, scopes=["conv_*"]) is None
        return
    else:
        path = write_trace([
            plane("/device:TPU:0", {tr.OPS_LINE: PARENT_OPS}),
            plane(*WINDOW)])
        r = readings(path)
        if case == "no_requires":
            args = dict(scopes=["conv_*"], requires="optimizer")
        else:
            # every scope metric of every cell is silent on it, though
            # `conv_0` and `pre_i` ARE in the trace
            assert scopes.share(r, scopes=["conv_*"]) \
                == pytest.approx(100 * 500 / 900)
            for metric in cells.load_benchmark()["per_layer"]:
                spec = json.load(open(os.path.join(
                    REPO, "benchmark", "metrics",
                    metric["name"] + ".json")))
                if spec["reader"] == "scopes.share":
                    assert spec["args"]["requires"] in ("optimizer",
                                                        "ef_forces")
                    assert scopes.share(r, **spec["args"]) is None
            return
    assert scopes.share(r, **args) is None


def test_never_over_100_and_summed_over_chips(write_trace):
    """Overlapping operations (an outer `while` with its body) are a union,
    not a sum; chips are summed, not averaged share by share; operations
    outside the window do not count; names by reference read the same."""
    conv = STEP + "jvp(SCFStack)/SCFStack.encode/conv_0/lin1/dot_general:"
    chip0 = [("%while.1", conv, 0, 800), ("%fusion.2", conv, 100, 300),
             ("%fusion.3", conv, 300, 300), ("%fusion.4", conv, 2000, 500)]
    chip1 = [("%fusion.5", STEP + "ef_forces/loss/mul:", 0, 200)]
    path = write_trace([
        plane("/device:TPU:1", {tr.OPS_LINE: chip1}, by_reference=True),
        plane("/device:TPU:0", {tr.OPS_LINE: chip0}, by_reference=True),
        plane(*WINDOW)])
    r = readings(path)
    assert r.reduced.busy_s == pytest.approx((800 + 200) / 2 * 1e-9)
    assert scopes.share(r, scopes=["conv_*"]) == pytest.approx(80.0)
    assert scopes.share(r, scopes=["conv_*", "loss"]) \
        == pytest.approx(100.0)
    assert scopes.share(r, scopes=["loss"], requires="ef_forces") \
        == pytest.approx(20.0)
    table = scopes.table(scopes.read_device_ops(path),
                         r.reduced.window_ns)
    assert table["chips"] == 2
    assert dict(map(tuple, table["by_conv"])) == pytest.approx(
        {"conv_0": (800 + 300 + 300) / 2 * 1e-9})


@pytest.mark.parametrize("op_name,expected", [
    ("jit(step_body)/transpose(jvp(ef_forces))/jvp(PNAPlusStack)/"
     "PNAPlusStack.encode/conv_3/edge_gather/gather:",
     ("step_body", "ef_forces", "PNAPlusStack", "PNAPlusStack.encode",
      "conv_3", "edge_gather", "gather")),
    ("jit(step_body)/shard_map/grad_allreduce/psum_invariant:",
     ("step_body", "shard_map", "grad_allreduce", "psum_invariant")),
    ("jit(head_forward)/ef_forces/vmap(jvp(SCFStack))/checkpoint/conv_0/"
     "aggregate/jit(_where)/select_n:",
     ("head_forward", "ef_forces", "SCFStack", "checkpoint", "conv_0",
      "aggregate", "_where", "select_n")),
    ("optimizer/add", ("optimizer", "add")),
    ("", ()),
])
def test_components(op_name, expected):
    assert scopes.components(op_name) == expected


# ---------------------------- the vocabulary in the programs the cells run --

def _tiny(config):
    from benchmark import system
    with open(os.path.join(REPO, "benchmark", "configs",
                           config + ".json")) as f:
        return system.apply_tiny(json.load(f))


@functools.lru_cache(maxsize=None)
def lowered_text(program, cache_dir):
    """The StableHLO, WITH debug info, of one program at the tiny preset."""
    import jax
    from jax.sharding import SingleDeviceSharding
    from benchmark import system
    if program == "schnet_ef_forward":
        doc = _tiny("schnet-s2ef")
        pools = system.load_pools(doc, cache_dir)
        traffic = cells.load_cell("schnet-s2ef.predict").traffic
        traffic = dict(traffic, **traffic.get("tiny", {}))
        lowered, _ = system.lower_largest_bucket(
            doc, pools, traffic, int(traffic["serving"]["max_batch_size"]),
            SingleDeviceSharding(jax.devices()[0]))
    else:
        doc = _tiny("pnaplus-s2ef")
        if program == "pnaplus_train_step_remat":
            arch = doc["hydragnn"]["NeuralNetwork"]["Architecture"]
            arch["conv_checkpointing"] = True
        chips = 4 if program == "pnaplus_spmd_step" else 1
        pools = system.load_pools(doc, cache_dir)
        lowered, _ = system.lower_train_step(doc, pools, 2, chips,
                                             jax.devices()[:chips])
    return lowered.as_text(debug_info=True)


@pytest.fixture(scope="module")
def pool_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("pools"))


MODEL = ["conv_0", "conv_1", "neighbor_gather", "edge_gather", "aggregate",
         "geometry", "heads", "ef_forces"]
STEP_SCOPES = MODEL + ["loss", "optimizer"]
PROGRAMS = ([("pnaplus_train_step", s) for s in STEP_SCOPES]
            + [("schnet_ef_forward", s) for s in MODEL]
            + [("pnaplus_spmd_step", s)
               for s in STEP_SCOPES + ["grad_allreduce"]]
            + [("pnaplus_train_step_remat", s)
               for s in ("conv_0", "edge_gather")])


@pytest.mark.parametrize("program,scope", PROGRAMS,
                         ids=[f"{p}-{s}" for p, s in PROGRAMS])
def test_every_scope_of_the_vocabulary_is_in_the_lowered_program(
        program, scope, pool_dir):
    """One case a scope and program: the tiny PNAPlus train step, the tiny
    SchNet energy+force forward, the 4-device SPMD step, and the step
    under `conv_checkpointing` (the remat path keeps the conv's name)."""
    import re
    text = lowered_text(program, pool_dir)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    paths = {scopes.components(n) for n in names if "/" in n}
    assert any(scope in path for path in paths), (
        f"{scope} not in the op names of {program}")


# ------------------------ the new entries where there is nothing to read --

def test_problems_empty_and_new_entries_resolve():
    assert cells.problems() == []
    names = [m["name"] for m in cells.load_benchmark()["per_layer"]]
    # appended after what was there at PR 26 and never reordered: one
    # contiguous block in PR 26's order, wherever later PRs' entries
    # leave it (a later PR appends; this test does not pin the end)
    at = names.index(PR26_ORDER[0])
    assert at >= 17, "after the seventeen entries of PR 22"
    assert names[at:at + len(PR26_ORDER)] == PR26_ORDER
    assert len(set(names)) == len(names)


PR26_ORDER = [
    "step_conv_share", "step_layout_gather_share",
    "step_neighbor_gather_share", "step_aggregate_share",
    "step_dense_share", "step_unscoped_share", "h2d_ms",
    "forward_conv_share", "forward_layout_gather_share",
    "forward_dense_share", "serve_collate_ms", "serve_fetch_ms",
    "serve_coalesce_wait_ms", "serve_request_ms",
    "serve_dispatcher_idle_share"]
NEW_METRICS = set(PR26_ORDER)


@pytest.mark.parametrize("workload", [
    w["name"] for w in cells.load_benchmark()["workloads"]])
def test_new_metrics_absent_on_a_run_without_them(workload, tmp_path,
                                                  monkeypatch):
    """What the harness does with a traced run of the PARENT's program, or
    of a CPU rehearsal: no device plane, none of the new spans. Every new
    metric is left out of the line and nothing raises; the old ones that
    have something to read are still there."""
    from benchmark.readers import Readings
    monkeypatch.setattr(scopes, "TRACE_ROOT", str(tmp_path))
    cell = cells.load_cell(workload)
    old_spans = [("dataload_wait", 0.1, 0.2), ("h2d_parent", 0.2, 0.3),
                 ("serve.queue_wait", 0.1, 0.2), ("serve.forward", 0.2, 0.4),
                 ("serve.graph_build", 0.0, 0.1)]
    r = Readings(arch={}, chips=cell.chips, reduced=None, window=(0.0, 1.0),
                 spans=old_spans, counters={"setup_programs": 17},
                 work={}, device_kind="cpu")
    got = run.per_layer_metrics(cell, r)
    assert not set(got) & NEW_METRICS
    assert "setup_programs" in got
    # and with the new spans recorded, the span metrics of the cell read
    r.spans = old_spans + [
        ("h2d", 0.0, 0.01), ("serve.collate", 0.0, 0.002),
        ("serve.fetch", 0.0, 0.004), ("serve.coalesce_wait", 0.0, 0.005),
        ("serve.request", 0.0, 0.03), ("serve.await_request", 0.0, 0.4)]
    got = run.per_layer_metrics(cell, r)
    expected = {m["name"] for m in cell.per_layer
                if m["name"] in NEW_METRICS
                and m["file"]["reader"].startswith("spans.")}
    assert set(got) & NEW_METRICS == expected and expected
    if "serve_dispatcher_idle_share" in expected:
        assert got["serve_dispatcher_idle_share"]["value"] \
            == pytest.approx(40.0)
    assert spans.percentile_ms(r, "no.such.span") is None


# -------------------- the traced rehearsal of the cells not yet rehearsed --

@pytest.mark.parametrize("workload,chips,host_events", [
    ("schnet-s2ef.predict", 1,
     {"serve.collate", "serve.dispatch", "serve.fetch", "serve.unpad",
      "hydragnn.clock"}),
    ("pnaplus-s2ef.train-dp4", 4, {"train_step", "h2d", "device_wait",
                                   "hydragnn.clock"}),
], ids=["predict", "train-dp4"])
def test_traced_rehearsal_under_the_new_entries(workload, chips,
                                                host_events):
    """`--trace 1` of the two cells `test_bench_rehearsal.py` runs untraced
    only (it traces `train` and `serve-open`), under the new entries: a
    result line, no metric, nothing raised — and the program's spans are
    on the /host:CPU plane of the trace the run left, with the clock
    mark, as they will be beside the device's operations on the chip."""
    from bench_testlib import run_cell
    rc, out, err = run_cell(workload, seed=2071849904, seconds=0.5,
                            trace=1, devices=chips)
    assert rc == 0, err[-3000:]
    line = json.loads(out[-1])
    # every check holds, `loss_fell` too since PR 33 reads it on the
    # trainer's first batches (0.59 here after the three traced steps of the
    # tiny preset; up to PR 32 it compared the training loss of other
    # structures and was let through)
    failed = {k for k, ok in line["checks"].items() if not ok}
    assert not failed and line["correct"] is True, line["compared"]
    assert line["metrics"] == {}
    assert line["breakdown"] == {"device_ops": [], "idle_gaps": []}
    path = tr.find_xplane(os.path.join(REPO, ".bench_trace", workload))
    assert scopes.read_device_ops(path) == []
    names = {e.name for p in tr.load_xplane(path) if p.name == tr.HOST_PLANE
             for events in p.lines.values() for e in events}
    assert host_events <= names, host_events - names
