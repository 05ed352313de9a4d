"""Unified telemetry layer (hydragnn_tpu/telemetry/, docs/observability.md).

Contract under test:
* registry type discipline + Prometheus exposition format,
* JSONL determinism: two identical runs -> identical epoch events modulo
  timestamps and the `timing` payload,
* a 2-epoch train run produces a schema-valid Chrome trace-event file
  covering the step-timeline span taxonomy,
* /metrics + /healthz scrape round-trip against a live engine,
* disabled-by-default telemetry keeps the per-batch producers at
  near-zero cost (the hot-path overhead guard),
* latency_percentiles / jit_cache_total edge-case hardening,
* the per-epoch MFU gauge math and knob resolution precedence.
"""
import functools
import json
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from hydragnn_tpu import telemetry
from hydragnn_tpu.telemetry import spans as tspans
from hydragnn_tpu.telemetry.mfu import achieved_and_mfu, peak_flops
from hydragnn_tpu.telemetry.registry import MetricsRegistry, MetricTypeError
from hydragnn_tpu.utils.profiling import (HostStallMonitor, Tracer,
                                          jit_cache_total,
                                          latency_percentiles)

from tests.deterministic_data import deterministic_graph_dataset
from tests.utils import make_config


# ----------------------------------------------------------------- registry

def test_registry_type_discipline():
    r = MetricsRegistry()
    r.counter_inc("requests_total", 2)
    with pytest.raises(MetricTypeError):
        r.gauge_set("requests_total", 1.0)
    with pytest.raises(ValueError):
        r.counter_inc("requests_total", -1)
    r.counter_inc("requests_total", 3)
    snap = r.snapshot()
    assert snap["requests_total"]["values"][()] == 5.0


def test_registry_prometheus_format():
    r = MetricsRegistry()
    r.counter_inc("req_total", 4, help="requests", route="/metrics")
    r.gauge_set("depth", 7)
    r.histogram_observe("lat_s", 0.03, buckets=(0.01, 0.1))
    text = r.to_prometheus()
    lines = [ln for ln in text.splitlines() if ln]
    # every sample line is `name{labels} value` with a parseable float
    for ln in lines:
        if ln.startswith("#"):
            continue
        name_part, value = ln.rsplit(" ", 1)
        float(value)
        assert name_part.startswith("hydragnn_")
    assert 'hydragnn_req_total{route="/metrics"} 4.0' in lines
    assert "# TYPE hydragnn_req_total counter" in lines
    assert "# HELP hydragnn_req_total requests" in lines
    # histogram: cumulative buckets + _sum/_count triple
    assert 'hydragnn_lat_s_bucket{le="+Inf"} 1' in lines
    assert "hydragnn_lat_s_count 1" in lines


def test_registry_prometheus_escapes_label_values():
    """Dynamic label values (exception text, paths) must never produce a
    line the scraper rejects — Prometheus drops the WHOLE page on one
    malformed line."""
    r = MetricsRegistry()
    r.counter_inc("errors_total", 1, help="line1\nline2",
                  reason='boom "quoted" \\ trailing\nnewline')
    text = r.to_prometheus()
    line = [ln for ln in text.splitlines()
            if ln.startswith("hydragnn_errors_total{")][0]
    assert '\\"quoted\\"' in line
    assert "\\\\ trailing" in line
    assert "\\n" in line and "\n" not in line
    help_line = [ln for ln in text.splitlines()
                 if ln.startswith("# HELP")][0]
    assert help_line == "# HELP hydragnn_errors_total line1\\nline2"


def test_registry_jsonl_roundtrip(tmp_path):
    r = MetricsRegistry()
    r.log_event("epoch", "epoch_0", data={"loss": 1.5}, timing={"s": 0.1})
    path = tmp_path / "t.jsonl"
    assert r.write_jsonl(str(path)) == 1
    evt = json.loads(path.read_text().splitlines()[0])
    assert evt["kind"] == "epoch" and evt["data"]["loss"] == 1.5
    assert "ts" in evt and "timing" in evt


# ------------------------------------------------- profiling edge hardening

def test_latency_percentiles_empty_has_full_key_set():
    out = latency_percentiles([])
    assert out == {"p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0,
                   "mean_ms": 0.0, "count": 0}


def test_latency_percentiles_values_and_generators():
    # generator input must work (consumers pass lazily-built iterables)
    out = latency_percentiles(x for x in (0.001, 0.002, 0.1))
    assert out["count"] == 3
    assert out["p99_ms"] >= out["p95_ms"] >= out["p50_ms"] > 0.0
    assert out["mean_ms"] == pytest.approx(
        np.mean([1.0, 2.0, 100.0]), rel=1e-6)
    single = latency_percentiles([0.05])
    assert single["count"] == 1
    assert single["p50_ms"] == pytest.approx(50.0)


def test_jit_cache_total_edge_cases():
    class RaisingProbe:
        def _cache_size(self):
            raise RuntimeError("introspection moved")

    class NoneProbe:
        def _cache_size(self):
            return None

    class NotCallable:
        _cache_size = 42

    # nothing measurable -> None (distinct from "zero compiles")
    assert jit_cache_total() is None
    assert jit_cache_total(None, object(), RaisingProbe(), NoneProbe(),
                           NotCallable()) is None
    jitted = jax.jit(lambda x: x + 1)
    jitted(1.0)
    total = jit_cache_total(jitted, None, RaisingProbe())
    assert isinstance(total, int) and total >= 1


def test_profiler_shim_removed():
    """The PR 7 deprecation shim aged out: `utils.profiling.Profiler`
    is GONE (pinned, so it cannot quietly come back), the
    `device_profile` entry point survives, and the merged facility —
    `telemetry.EpochDeviceTrace` — carries the whole former surface."""
    from hydragnn_tpu.utils import profiling
    assert not hasattr(profiling, "Profiler")
    assert profiling.device_profile is tspans.device_trace
    p = telemetry.EpochDeviceTrace("/tmp/x", enable=False)
    p.setup({"enable": 0, "target_epoch": 3})
    assert p.target_epoch == 3 and p.enable is False
    with p:  # disabled: enter/exit are no-ops
        pass


# ------------------------------------------------------------------ spans

def test_span_recorder_chrome_schema():
    rec = tspans.SpanRecorder()
    prev = tspans.install_recorder(rec)
    try:
        with tspans.span("region", cat="test", detail=1):
            time.sleep(0.001)
        t0 = tspans.now()
        time.sleep(0.001)
        tspans.record("explicit", t0, tspans.now() - t0, cat="test")
    finally:
        tspans.install_recorder(prev)
    trace = rec.chrome_trace()
    _validate_chrome_trace(trace, expect={"region", "explicit"})


def _validate_chrome_trace(trace, expect=()):
    assert isinstance(trace["traceEvents"], list) and trace["traceEvents"]
    names = set()
    for evt in trace["traceEvents"]:
        assert {"name", "ph", "pid", "tid"} <= set(evt), evt
        assert isinstance(evt["name"], str)
        if evt["ph"] == "X":
            assert isinstance(evt["ts"], float) and np.isfinite(evt["ts"])
            assert evt["dur"] >= 0.0
            assert isinstance(evt["cat"], str)
        names.add(evt["name"])
    missing = set(expect) - names
    assert not missing, f"spans missing from trace: {missing}"


def test_span_recorder_bounded_with_visible_drop():
    """The recorder is memory-bounded: past max_events new spans are
    dropped and COUNTED, and the exported trace carries the drop count
    as an instant event — truncation is never silent."""
    rec = tspans.SpanRecorder(max_events=8)
    for i in range(20):
        rec.add(f"s{i}", 0.0, 0.001)
    assert len(rec.events) == 8
    assert rec.dropped == 20 - (8 - 1)  # metadata event takes one slot
    trace = rec.chrome_trace()
    drop_evts = [e for e in trace["traceEvents"]
                 if e.get("args", {}).get("dropped")]
    assert drop_evts and drop_evts[0]["args"]["dropped"] == rec.dropped


def _disabled_producers():
    """name -> one call of each per-batch / per-request producer, as the
    trainer and the engine make it."""
    from hydragnn_tpu.serving.engine import InferenceEngine
    from hydragnn_tpu.train import trainer
    tr = Tracer()
    stall = HostStallMonitor(tracer=tr)
    stall.step = 7
    place = trainer._traced_place(lambda batch: batch)

    def span_with_args():
        with tspans.span("serve.collate", "serving", batch=3,
                         parent="serve.batch"):
            pass

    def timed_step():
        with tr.timer("train_step", step=stall.step), stall.step_timer():
            pass

    return {
        "record": lambda: tspans.record("x", 0.0, 0.0, req=1),
        "span": span_with_args,
        "h2d_placement": lambda: place(None),
        "train_step_timer": timed_step,
        "request_end": lambda: InferenceEngine._span_request(
            1, 0.0, error="QueueFullError"),
    }


# per call, with no recorder installed: microseconds that would not
# register against a multi-millisecond training step or request. The step
# timer enters a jax StepTraceAnnotation and two context managers: a
# generous absolute budget
DISABLED_BUDGET_S = {"record": 5e-6, "span": 5e-6, "h2d_placement": 5e-6,
                     "request_end": 5e-6, "train_step_timer": 100e-6}


@pytest.mark.parametrize("producer", sorted(DISABLED_BUDGET_S))
def test_disabled_producers_are_near_free(producer):
    """The hot-path overhead contract: with no recorder installed, the
    per-batch and per-request producer calls (spans.record, spans.span
    with its arguments, the trainer's wrapped placement, the tracer timer
    + stall step_timer round a step) cost next to nothing."""
    assert tspans.current_recorder() is None
    call = _disabled_producers()[producer]
    n = 20_000
    call()
    t0 = time.perf_counter()
    for _ in range(n):
        call()
    per_call = (time.perf_counter() - t0) / n
    assert per_call < DISABLED_BUDGET_S[producer], \
        f"disabled {producer} at {per_call * 1e6:.2f}us"
    assert tspans.span("x") is tspans.span("y"), "one shared no-op"


# ------------------------------------------- spans on the profiler's clock

def _host_events(xplane_path):
    """{name: [(start_ns, dur_ns, stats)]} of the /host:CPU plane."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.duration_ns, dict(ev.stats)))
    return out


def test_spans_land_on_the_profilers_clock(tmp_path):
    """Under a device-trace capture every `spans.span()` is an event of
    /host:CPU on the profiler's clock, and the `hydragnn.clock` mark maps
    the recorder's perf_counter twin onto it to within 200 us — also for
    spans of another thread, and for a `record()`ed span that exists on
    perf_counter alone."""
    import glob
    import threading
    rec = tspans.SpanRecorder("test")
    with tspans.device_trace(str(tmp_path)):      # marks the clock
        previous = tspans.install_recorder(rec)   # and again
        try:
            def work(tag):
                for i in range(3):
                    with tspans.span(f"probe.{tag}", "test", i=i):
                        time.sleep(0.002)
            other = threading.Thread(target=work, args=("thread",))
            other.start()
            work("main")
            other.join()
            t0 = tspans.now()
            with jax.profiler.TraceAnnotation("probe.recorded_twin"):
                time.sleep(0.001)
            tspans.record("probe.recorded", t0, tspans.now() - t0)
        finally:
            tspans.install_recorder(previous)
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    host = _host_events(path)
    marks = host[tspans.CLOCK_EVENT]
    assert len(marks) == 2
    start_ns, _, stats = marks[0]
    offset_ns = start_ns - stats["perf_counter_ns"]

    def on_profiler_clock(event):
        return (rec._t0 + event["ts"] * 1e-6) * 1e9 + offset_ns
    twins = [e for e in rec.chrome_trace()["traceEvents"]
             if e.get("ph") == "X"]
    assert sorted(e["name"] for e in twins) == sorted(
        ["probe.main", "probe.thread"] * 3 + ["probe.recorded"])
    for event in twins:
        name = ("probe.recorded_twin" if event["name"] == "probe.recorded"
                else event["name"])
        starts = [s for s, _, st in host[name]
                  if st.get("i", event.get("args", {}).get("i"))
                  == event.get("args", {}).get("i")]
        assert len(starts) == 1, (name, host[name])
        assert abs(starts[0] - on_profiler_clock(event)) < 200e3, (
            name, starts[0] - on_profiler_clock(event))
    # the second mark agrees with the first on the offset
    assert abs((marks[1][0] - marks[1][2]["perf_counter_ns"])
               - offset_ns) < 200e3


def test_span_ending_after_its_recorder_left_is_dropped():
    rec = tspans.SpanRecorder("test")
    previous = tspans.install_recorder(rec)
    try:
        with tspans.span("kept"):
            pass
        late = tspans.span("serve.await_request", "serving")
        late.__enter__()
    finally:
        tspans.install_recorder(previous)
    late.__exit__(None, None, None)
    names = [e["name"] for e in rec.events if e.get("ph") == "X"]
    assert names == ["kept"]


# ------------------------------------- the span table of the documentation

@functools.lru_cache(maxsize=None)
def _documented_spans():
    import os
    import re
    doc = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "observability.md")).read()
    section = doc[doc.index("## Span taxonomy"):
                  doc.index("**Device-side names.**")]
    return sorted(set(re.findall(r"^\| `([a-z0-9_.<>]+)` \|", section,
                                 re.MULTILINE)))


@functools.lru_cache(maxsize=None)
def _call_site_spans():
    """Every span name the package can record: the literal first argument
    of span / record / rec.add / add_time / timer, and the pass names
    handed to `_eval_epoch`; `{...}` of an f-string reads `<id>`."""
    import os
    import re
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "hydragnn_tpu")
    call = re.compile(
        r"(?:\bspan|\brecord|rec\.add|add_time|\.timer)\(\s*f?\"([^\"]+)\"")
    passes = re.compile(r"_eval_epoch\([^\"]*\"(\w+)\"")
    names = set()
    for folder, _, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                text = open(os.path.join(folder, name)).read()
                names.update(call.findall(text) + passes.findall(text))
    return sorted(re.sub(r"\{[^}]*\}", "<id>", n) for n in names)


@pytest.mark.parametrize("name", sorted(set(_documented_spans())
                                        | set(_call_site_spans())))
def test_span_table_matches_call_sites(name):
    """docs/observability.md lists every span a call site can record, and
    nothing that no call site records."""
    assert name in _call_site_spans(), f"{name}: documented, no call site"
    assert name in _documented_spans(), f"{name}: recorded, not documented"


@functools.lru_cache(maxsize=None)
def _scopes():
    """(documented, called): the device-side scope table of
    docs/observability.md and the literal `jax.named_scope("...")` names
    of the package."""
    import os
    import re
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    doc = open(os.path.join(repo, "docs", "observability.md")).read()
    section = doc[doc.index("**Device-side names.**"):
                  doc.index("## JSONL event log")]
    documented = set(re.findall(r"^\| `([a-z_]+)` \|", section,
                                re.MULTILINE))
    called = set()
    for folder, _, files in os.walk(os.path.join(repo, "hydragnn_tpu")):
        for name in files:
            if name.endswith(".py"):
                called.update(re.findall(
                    r"named_scope\(\s*\"([a-z_]+)\"\)",
                    open(os.path.join(folder, name)).read()))
    return documented, called


@pytest.mark.parametrize("name", sorted(set().union(*_scopes())))
def test_device_scope_table_matches_call_sites(name):
    documented, called = _scopes()
    assert name in called, f"{name}: documented, no call site"
    assert name in documented, f"{name}: a scope, not documented"


def test_train_spans_carry_the_step(telemetry_run):
    """The step-level spans of the train pass carry `step`, the global
    optimizer-step number, counted on over the epochs."""
    trace = json.loads((telemetry_run["dir"] / "trace.json").read_text())
    evts = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    steps = [e["args"]["step"] for e in evts if e["name"] == "train_step"]
    assert steps == list(range(len(steps))) and len(steps) >= 2
    for name in ("dataload_wait", "step_dispatch"):
        got = [e["args"]["step"] for e in evts if e["name"] == name]
        assert got and set(got) <= set(range(len(steps) + 1)), name
    # the prefetch thread's placements are ahead of the step: no `step`
    assert all("step" not in e.get("args", {}) for e in evts
               if e["name"] == "h2d")
    waits = [e for e in evts if e["name"] == "device_wait"]
    assert sorted(e["args"]["step"] for e in waits if "args" in e) == steps
    # each step's dispatch span sits inside its train_step span
    by_step = {e["args"]["step"]: e for e in evts
               if e["name"] == "train_step"}
    for e in evts:
        if e["name"] == "step_dispatch":
            outer = by_step[e["args"]["step"]]
            assert outer["ts"] <= e["ts"] + 1.0
            assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1.0


# ------------------------------------------------------------ mfu helpers

def test_peak_flops_halves_f32():
    bf16 = peak_flops("TPU v5e", "bfloat16")
    f32 = peak_flops("TPU v5e", "float32")
    assert f32 == pytest.approx(bf16 / 2)
    # the kind string the v5e reports about itself
    assert peak_flops("TPU v5 lite", "bfloat16") == bf16
    assert peak_flops("TPU v5e", "bfloat16", peak_override=1e12) == 1e12


def test_peak_flops_unknown_kind_raises():
    # an unknown device is an error that names the kind, never a silent
    # v5e default; an explicit override still names the peak itself
    with pytest.raises(ValueError, match="no such chip"):
        peak_flops("no such chip")
    assert peak_flops("no such chip", peak_override=2e12) == 2e12


def test_achieved_and_mfu_gates():
    achieved, mfu = achieved_and_mfu(1e9, 10, 2.0, "cpu", "cpu")
    assert achieved == pytest.approx(5e9)
    assert mfu is None  # no invented CPU peak
    achieved, mfu = achieved_and_mfu(1e9, 10, 2.0, "tpu", "TPU v5e",
                                     "bfloat16")
    assert mfu == pytest.approx(5e9 / peak_flops("TPU v5e", "bfloat16"))
    assert achieved_and_mfu(None, 10, 2.0, "tpu", "TPU v5e") == (None, None)
    assert achieved_and_mfu(1e9, 0, 2.0, "tpu", "TPU v5e") == (None, None)
    assert achieved_and_mfu(1e9, 10, 0.0, "tpu", "TPU v5e") == (None, None)


# ----------------------------------------------------------- knob resolution

def test_resolve_telemetry_precedence(monkeypatch):
    from hydragnn_tpu.utils.envflags import resolve_telemetry
    for var in ("HYDRAGNN_TELEMETRY", "HYDRAGNN_TELEMETRY_DIR",
                "HYDRAGNN_DEVICE_TRACE", "HYDRAGNN_DEVICE_TRACE_EPOCH"):
        monkeypatch.delenv(var, raising=False)
    cfg = resolve_telemetry({})
    assert cfg.enabled is False and cfg.device_trace is False
    # config block enables; env overrides both ways; strict parsing on
    # typos (warn + keep default)
    block = {"Telemetry": {"enabled": True, "dir": "/tmp/t",
                           "device_trace_epoch": 2}}
    cfg = resolve_telemetry(block)
    assert cfg.enabled and cfg.out_dir == "/tmp/t"
    assert cfg.device_trace_epoch == 2
    monkeypatch.setenv("HYDRAGNN_TELEMETRY", "0")
    assert resolve_telemetry(block).enabled is False
    monkeypatch.setenv("HYDRAGNN_TELEMETRY", "ture")  # typo
    assert resolve_telemetry(block).enabled is True  # falls back to block
    assert resolve_telemetry({}).enabled is False
    monkeypatch.setenv("HYDRAGNN_TELEMETRY_DIR", "/tmp/env")
    assert resolve_telemetry(block).out_dir == "/tmp/env"
    monkeypatch.setenv("HYDRAGNN_DEVICE_TRACE_EPOCH", "nope")
    assert resolve_telemetry(block).device_trace_epoch == 2


# ------------------------------------- 2-epoch train run (tier-1 acceptance)

def _run_tiny_training(tel_dir):
    from hydragnn_tpu.preprocess.load_data import split_dataset
    from hydragnn_tpu.run_training import run_training
    samples = deterministic_graph_dataset(num_configs=32)
    splits = split_dataset(samples, 0.7)
    cfg = make_config("GIN")
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 2
    cfg["NeuralNetwork"]["Training"]["EarlyStopping"] = False
    cfg["NeuralNetwork"]["Training"]["Telemetry"] = {
        "enabled": True, "dir": str(tel_dir)}
    state, history, model, completed = run_training(cfg, datasets=splits,
                                                    num_shards=1)
    return history


@pytest.fixture(scope="module")
def telemetry_run(tmp_path_factory):
    """One telemetry-enabled 2-epoch train run — powers the Chrome-trace
    schema, MFU-history, and Prometheus-artifact tests (tier-1). The
    JSONL determinism test runs a SECOND identical training and lives in
    the slow lane (CI robust shard + nightly) to keep the tier-1
    wall-clock down."""
    d = tmp_path_factory.mktemp("tel_a")
    history = _run_tiny_training(d)
    # the session must uninstall itself: later runs (and the other
    # tests in this module) start from the disabled state
    assert tspans.current_recorder() is None
    return {"dir": d, "history": history}


def test_train_run_emits_schema_valid_chrome_trace(telemetry_run):
    d = telemetry_run["dir"]
    trace = json.loads((d / "trace.json").read_text())
    _validate_chrome_trace(trace, expect={
        "dataload_wait", "h2d", "step_dispatch", "device_wait",
        "train_step", "train_epoch", "validate", "test",
        "loader.collate"})
    # spans nest sanely: per-epoch region at least as long as any step
    evts = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    epoch_dur = max(e["dur"] for e in evts if e["name"] == "train_epoch")
    step_dur = max(e["dur"] for e in evts if e["name"] == "train_step")
    assert epoch_dur >= step_dur


def test_train_run_history_has_mfu_numerator(telemetry_run):
    history = telemetry_run["history"]
    achieved = history.get("achieved_flops_per_s")
    assert achieved and len(achieved) == 2
    assert all(a > 0 for a in achieved)
    # CPU backend: no invented peak, so no mfu series
    assert "mfu" not in history


@pytest.mark.slow
def test_jsonl_determinism_modulo_timestamps(telemetry_run,
                                             tmp_path_factory):
    """Two identical runs -> identical epoch-event streams once `ts` and
    the wall-clock `timing` payload are stripped (losses, counts, lr,
    padding are bitwise-deterministic). Slow lane: the second training
    is pure adjudication cost — CI's robust shard and the nightly
    telemetry job run it; tier-1 keeps the single-run schema tests."""
    dir_b = tmp_path_factory.mktemp("tel_b")
    _run_tiny_training(dir_b)
    assert tspans.current_recorder() is None

    def epochs(d):
        lines = [json.loads(ln) for ln in
                 (d / "telemetry.jsonl").read_text().splitlines()]
        assert [ln["kind"] for ln in lines] == ["run", "epoch", "epoch",
                                                "run"]
        for ln in lines:
            assert "ts" in ln
        return [{"kind": e["kind"], "name": e["name"], "data": e["data"]}
                for e in lines if e["kind"] == "epoch"]

    a = epochs(telemetry_run["dir"])
    b = epochs(dir_b)
    assert len(a) == 2
    assert a == b
    # and the deterministic payload carries the metric catalog
    for key in ("train_loss", "val_loss", "test_loss", "lr", "epoch",
                "nonfinite_steps", "batches"):
        assert key in a[0]["data"], key


def test_registry_restored_after_session(tmp_path):
    from hydragnn_tpu.telemetry import (TelemetryConfig, get_registry,
                                        start_session)
    before = get_registry()
    # a cold-path counter reported BEFORE the session (the preproc cache
    # probes during dataset build) must be visible in the run's exports
    before.counter_inc("presession_probe_total", 3)
    session = start_session(TelemetryConfig(enabled=True,
                                            out_dir=str(tmp_path)),
                            str(tmp_path))
    assert get_registry() is session.registry
    assert tspans.current_recorder() is session.recorder
    snap = session.registry.snapshot()
    assert snap["presession_probe_total"]["values"][()] == 3.0
    paths = session.finalize()
    assert get_registry() is before
    assert tspans.current_recorder() is None
    assert (tmp_path / "telemetry.jsonl").exists()
    assert paths["chrome_trace"].endswith("trace.json")
    # the registry's final state is an artifact, not write-only memory
    prom = (tmp_path / "metrics.prom").read_text()
    assert "hydragnn_presession_probe_total 3.0" in prom
    assert session.finalize() == {}  # idempotent


def test_train_run_writes_prometheus_artifact(telemetry_run):
    prom = (telemetry_run["dir"] / "metrics.prom").read_text()
    for name in ("hydragnn_train_loss", "hydragnn_val_loss",
                 "hydragnn_train_input_bound_frac",
                 "hydragnn_train_achieved_flops_per_s",
                 "hydragnn_train_nonfinite_steps_total"):
        assert name in prom, f"{name} missing from metrics.prom"


# --------------------------------------------------- live-engine /metrics

@pytest.fixture(scope="module")
def live_engine():
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.graphs.batch import collate
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.serving.engine import InferenceEngine
    samples = deterministic_graph_dataset(num_configs=16)
    cfg = make_config("GIN")
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    model = create_model(mcfg)
    variables = init_params(model, collate(samples[:4]))
    eng = InferenceEngine(model, variables, mcfg,
                          reference_samples=samples, max_batch_size=4,
                          max_wait_ms=5.0)
    eng.warmup()
    yield eng, samples
    eng.shutdown()


def test_metrics_endpoint_scrape_roundtrip(live_engine):
    engine, samples = live_engine
    server = engine.start_metrics_server(port=0)
    assert server.port > 0
    # starting twice returns the same server, no double bind
    assert engine.start_metrics_server(port=0) is server
    engine.predict(samples[:6])
    with urllib.request.urlopen(server.url + "/healthz", timeout=10) as r:
        assert r.status == 200
        health = json.loads(r.read().decode())
    assert health["state"] == "closed" and health["dispatcher_alive"]
    with urllib.request.urlopen(server.url + "/metrics", timeout=10) as r:
        assert r.status == 200
        assert "text/plain" in r.headers["Content-Type"]
        text = r.read().decode()
    metrics = {}
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        name_part, value = ln.rsplit(" ", 1)
        metrics[name_part] = float(value)  # every sample line parses
    assert metrics["hydragnn_serving_requests_total"] >= 6
    assert metrics["hydragnn_serving_dispatcher_alive"] == 1.0
    assert metrics['hydragnn_serving_breaker_state{state="closed"}'] == 1.0
    assert metrics['hydragnn_serving_breaker_state{state="open"}'] == 0.0
    assert 'hydragnn_serving_latency_ms{quantile="p99"}' in metrics
    # unknown path -> 404, not a server death
    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(server.url + "/nope", timeout=10)
    with urllib.request.urlopen(server.url + "/healthz", timeout=10) as r:
        assert r.status == 200


def test_metrics_endpoint_stops_with_engine(live_engine):
    """shutdown() must tear the HTTP server down with the dispatcher,
    and a post-shutdown healthz reports 503. LAST test in this module:
    it shuts the shared engine down (the fixture teardown's shutdown is
    idempotent), trading a fresh compile for suite wall-clock."""
    from hydragnn_tpu.telemetry.http import serve_engine_metrics
    engine, _ = live_engine
    server = engine.start_metrics_server(port=0)
    url = server.url
    engine.shutdown()
    with pytest.raises(Exception):
        urllib.request.urlopen(url + "/healthz", timeout=2)
    # the handler-level contract: a shut-down engine is a 503 for probes
    probe = serve_engine_metrics(engine, port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(probe.url + "/healthz", timeout=10)
        assert err.value.code == 503
    finally:
        probe.stop()
