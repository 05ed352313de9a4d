"""Region tracer + aggregate timers.

reference: hydragnn/utils/profiling_and_tracing/tracer.py:14-167 (Tracer
facade with GPTL/Score-P backends, @profile decorator, timer contextmanager).
The reference's time_utils.py (class-level timer dicts) has no port: nothing
called it, and a region's aggregate lives in `Tracer.times` / `counts`.

TPU mapping: `jax.profiler.TraceAnnotation` replaces Score-P regions;
`jax.block_until_ready` replaces cudasync for accurate walls
(reference: tracer.py:107-112). GPTL-style per-rank text summaries are
written by `print_timers`.
"""
from __future__ import annotations

import contextlib
import functools
import os
import time
from typing import Any, Callable, Dict, Optional

import jax

from ..telemetry import spans as _spans


class Tracer:
    """Hierarchical region timer with optional device sync + jax profiler
    annotations.

    Telemetry integration (docs/observability.md): every closed region
    also lands as a span in the process SpanRecorder when a
    TelemetrySession is active — the Tracer is the ONE host timing
    facility, and the Chrome trace is just another export of it. With no
    recorder installed the extra cost is one global read per stop."""

    def __init__(self, sync: bool = False, use_jax_annotations: bool = True):
        self.sync = sync
        self.use_jax_annotations = use_jax_annotations
        self.times: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self._starts: Dict[str, float] = {}
        self.enabled = True

    def enable(self):
        self.enabled = True

    def disable(self):
        self.enabled = False

    def reset(self):
        self.times.clear()
        self.counts.clear()
        self._starts.clear()

    def start(self, name: str):
        if not self.enabled:
            return
        self._starts[name] = time.perf_counter()

    def stop(self, name: str, result: Any = None):
        if not self.enabled or name not in self._starts:
            return
        if self.sync and result is not None:
            jax.block_until_ready(result)
        t0 = self._starts.pop(name)
        self.add_time(name, time.perf_counter() - t0, t_start=t0)

    def add_time(self, name: str, dt: float,
                 t_start: Optional[float] = None, **args):
        """Accumulate a measured region (external timers — the stall
        monitor — report through here so aggregates and spans cannot
        drift). `t_start` is the perf_counter start for span placement;
        None means "ends now". `args` go on the span (the trainer's
        `step`)."""
        self.times[name] = self.times.get(name, 0.0) + dt
        self.counts[name] = self.counts.get(name, 0) + 1
        if t_start is None:
            t_start = time.perf_counter() - dt
        _spans.record(name, t_start, dt, cat="tracer", **args)

    @contextlib.contextmanager
    def timer(self, name: str, step: Optional[int] = None):
        """reference: tracer.py:157-167 `tr.timer` contextmanager. With a
        `step` the region is a ``jax.profiler.StepTraceAnnotation``, so
        XProf groups the device work under it by step number, and the
        span carries `step`."""
        if not self.enabled:
            yield
            return
        if not self.use_jax_annotations:
            ctx = contextlib.nullcontext()
        elif step is None:
            ctx = jax.profiler.TraceAnnotation(name)
        else:
            ctx = jax.profiler.StepTraceAnnotation(name, step_num=step)
        args = {} if step is None else {"step": step}
        with ctx:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.add_time(name, time.perf_counter() - t0, t_start=t0,
                              **args)

    def profile(self, name: Optional[str] = None):
        """reference: tracer.py:145-155 `@tr.profile` decorator."""
        def deco(fn: Callable):
            label = name or fn.__qualname__
            @functools.wraps(fn)
            def wrapped(*a, **kw):
                with self.timer(label):
                    return fn(*a, **kw)
            return wrapped
        return deco

    def print_timers(self, path: Optional[str] = None):
        """GPTL-style per-rank summary (reference: time_utils.py:95-138;
        gp_timing.p{rank} artifacts)."""
        lines = [f"{'region':<30}{'count':>8}{'total_s':>12}{'avg_ms':>12}"]
        for name, tot in sorted(self.times.items()):
            c = self.counts[name]
            lines.append(f"{name:<30}{c:>8}{tot:>12.4f}{tot / c * 1e3:>12.3f}")
        text = "\n".join(lines)
        if path:
            rank = jax.process_index()
            with open(os.path.join(path, f"gp_timing.p{rank}"), "w") as f:
                f.write(text + "\n")
        return text


class HostStallMonitor:
    """Per-epoch accounting of host time blocked on the input pipeline vs
    time spent dispatching/executing steps.

    ``wrap(stream)`` times every ``next()`` on the batch stream (collation,
    cache lookups, host->device staging); ``step_timer()`` wraps the step
    call. ``input_bound_frac`` is wait / (wait + step): the fraction of the
    host's epoch spent on the input. The trainer keeps one step owed on the
    device while it waits, so this is host time under a running step, not
    device idle time: the device idles only when the owed step finishes
    first (the trainer's `host_bound_steps`). bench.py emits it as
    `input_bound_frac`; the trainer logs it per epoch and accumulates
    tracer regions `dataload_wait` / `step_dispatch`."""

    def __init__(self, tracer: Optional[Tracer] = None):
        self.tracer = tracer
        # the optimizer step the trainer is at (it sets this): the
        # `dataload_wait` before step k and its `step_dispatch` carry k
        self.step: Optional[int] = None
        self.reset()

    def span_args(self) -> Dict[str, int]:
        """What the step-level spans carry: `step`, once the trainer has
        set it."""
        return {} if self.step is None else {"step": self.step}

    def reset(self):
        self.wait_s = 0.0
        self.step_s = 0.0
        self.batches = 0

    def wrap(self, stream):
        it = iter(stream)
        while True:
            t0 = time.perf_counter()
            try:
                batch = next(it)
            except StopIteration:
                return
            finally:
                dt = time.perf_counter() - t0
                self.wait_s += dt
                if self.tracer is not None:
                    self.tracer.add_time("dataload_wait", dt, t_start=t0,
                                         **self.span_args())
            self.batches += 1
            yield batch

    @contextlib.contextmanager
    def step_timer(self):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self.step_s += dt
            if self.tracer is not None:
                self.tracer.add_time("step_dispatch", dt, t_start=t0,
                                     **self.span_args())

    def input_bound_frac(self) -> float:
        total = self.wait_s + self.step_s
        return self.wait_s / total if total > 0 else 0.0


def latency_percentiles(latencies_s, percentiles=(50, 95, 99)) -> Dict[str, float]:
    """Tail-latency summary: {"p50_ms", "p95_ms", "p99_ms", "mean_ms",
    "count"} from per-request latencies in SECONDS. The one percentile
    formatter shared by the serving engine (serving/engine.stats),
    BENCH_SERVE, and the /metrics exposition so the reported fields
    cannot drift between them.

    Edge-case contract (PR 7): the FULL key set is always present —
    empty input yields zeroed quantiles with ``count == 0`` instead of
    the former ``{}``, so telemetry consumers (Prometheus exposition,
    dashboards keyed on p99) never special-case a just-started or
    just-reset engine. `count` disambiguates "no traffic yet" from
    "genuinely sub-millisecond"."""
    import numpy as np
    lat = np.asarray(list(latencies_s), np.float64)
    out: Dict[str, float] = {f"p{int(q)}_ms": 0.0 for q in percentiles}
    out["mean_ms"] = 0.0
    out["count"] = 0
    if lat.size == 0:
        return out
    for q in percentiles:
        out[f"p{int(q)}_ms"] = float(np.percentile(lat, q) * 1e3)
    out["mean_ms"] = float(lat.mean() * 1e3)
    out["count"] = int(lat.size)
    return out


class CompileWatch:
    """Process-wide count of XLA backend compiles and the seconds spent
    in them, read off jax's own monitoring events — every program any
    jitted callable or AOT ``.lower().compile()`` builds while the
    watch is open, whoever owns the callable (``jit_cache_size`` below
    needs the callable in hand). ``count`` includes programs served
    from the persistent compilation cache (their ``seconds`` are the
    retrieval time); ``cache_hits`` says how many those were. The
    accelerator smoke (chip_smoke.py) reports cold and warm compile
    seconds from it and asserts no compile lands after warm-up.

        with CompileWatch() as watch:
            ...
            before = watch.count
            serve()
            assert watch.count == before
    """

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def _on_duration(self, event: str, duration: float, **_):
        if event == self._COMPILE:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event: str, **_):
        if event == self._CACHE_HIT:
            self.cache_hits += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        return False


def jit_cache_size(fn) -> Optional[int]:
    """Number of compiled programs a jitted callable currently holds
    (PjitFunction `_cache_size`, confirmed to count on jax 0.9.0); None
    when `fn` is not a jitted function. The trainer/bench
    report this as the recompile counter — budget-packed batching must
    keep it at ONE program per step function (docs/packing.md).

    Edge-case contract (PR 7): any probe misbehavior — a `_cache_size`
    attribute that is not callable, raises, or returns something
    non-integer (None included) — degrades to None, never an exception:
    this runs inside the per-epoch telemetry path and an introspection
    API drift must not kill training."""
    if fn is None:
        return None
    probe = getattr(fn, "_cache_size", None)
    if not callable(probe):
        return None
    try:
        return int(probe())
    except Exception:
        return None


def jit_cache_total(*fns) -> Optional[int]:
    """Sum of `jit_cache_size` over the given callables; None when none
    of them expose a cache (so callers can distinguish 'zero compiles'
    from 'not measurable'). Accepts any mix of None / non-jitted /
    probe-raising entries — they are simply skipped (the same hardening
    contract as `jit_cache_size`); an empty call returns None."""
    total, seen = 0, False
    for fn in fns:
        n = jit_cache_size(fn)
        if n is not None:
            total += n
            seen = True
    return total if seen else None


_GLOBAL = Tracer()


def initialize(sync: bool = False):
    global _GLOBAL
    _GLOBAL = Tracer(sync=sync)
    return _GLOBAL


def get() -> Tracer:
    return _GLOBAL


def start(name: str):
    _GLOBAL.start(name)


def stop(name: str, result: Any = None):
    _GLOBAL.stop(name, result)


def enable():
    _GLOBAL.enable()


def disable():
    _GLOBAL.disable()


def reset():
    _GLOBAL.reset()


def print_timers(path: Optional[str] = None):
    return _GLOBAL.print_timers(path)


# device-side trace brackets live in telemetry/spans.py now — ONE timing
# facility; this name remains as the historical entry point. The
# epoch-targeted `Profiler` shim that used to live beside it is GONE
# (deprecated in PR 7, removed after aging out) — use
# `hydragnn_tpu.telemetry.EpochDeviceTrace`.
device_profile = _spans.device_trace
