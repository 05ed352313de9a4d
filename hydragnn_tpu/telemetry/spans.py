"""Span tracing: Chrome trace-event JSON for the training-step and serving
request timelines, plus the opt-in jax.profiler device-trace bracket.

The registry (telemetry/registry.py) answers "how much, how often"; spans
answer "WHEN, on which thread, overlapping what". One recorder per run
collects complete events (`ph: "X"`) with microsecond timestamps and the
recording thread's id, so the exported file shows the host pipeline the
way GNNPipe/DistGNN-style overlap analysis needs it: fetch/collate spans
on the loader worker threads, H2D/dispatch/device-wait spans on the
trainer thread, queue-wait/forward/unpad spans on the serving dispatcher
— all on one shared clock.

Export is standard Chrome trace-event JSON (`{"traceEvents": [...]}`,
https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU):
load it in Perfetto (ui.perfetto.dev), chrome://tracing, or anything that
speaks the format. The opt-in ``device_trace`` bracket additionally
captures a jax.profiler trace (XLA HLO + device timelines, TensorBoard/
XProf-viewable) around a region — host spans tell you WHERE to point it.

Two clocks, one link. A `span()` (the context-manager form) is entered
as a ``jax.profiler.TraceAnnotation`` as well, so whenever a device trace
is being captured it lands on ``/host:CPU`` of the ``.xplane.pb`` on the
profiler's own clock, next to the device's operations. A `record()`ed span
(one whose start predates the call site) exists on ``perf_counter`` only;
`mark_clock()` — called when a recorder is installed and when a device
trace bracket opens — writes one ``hydragnn.clock`` annotation whose
``perf_counter_ns`` stat is the span clock read as the annotation starts,
so the trace file itself carries the offset between the two clocks:
``profiler_ns = perf_counter_ns - clock.perf_counter_ns + clock.start_ns``.

Disabled-by-default contract: when no recorder is installed, the
module-level ``record``/``span`` helpers are a single global read + None
check — the per-batch call sites in the trainer/loader/engine stay at
nanoseconds of overhead (tests/test_telemetry.py pins a per-call budget).
The per-call sites MUST use these helpers rather than holding a recorder
reference, so enabling/disabling a session flips every producer at once.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

# trace-event timestamps are microseconds on one shared clock;
# perf_counter is monotonic and high-resolution, which is exactly what
# overlap analysis needs (absolute wall time goes in the JSONL instead)
_CLOCK = time.perf_counter


# default retained-event cap: at ~200 bytes/event this bounds a
# recorder at roughly 200 MB — generous for any run worth tracing in
# one file, and a hard stop against a multi-day run OOMing the host
# (the trace is only written at finalize, so unbounded growth would
# lose the whole artifact with the process)
DEFAULT_MAX_EVENTS = 1_000_000


class SpanRecorder:
    """Collects Chrome trace events in memory; thread-safe appends.

    Bounded: after `max_events` spans the recorder DROPS new events and
    counts them (`dropped`); the exported trace carries the drop count
    as an instant event so truncation is visible, never silent (the
    no-silent-caps rule). Long campaigns that need full timelines should
    bracket the interesting window with a session rather than record
    days of steady state."""

    def __init__(self, process_name: str = "hydragnn",
                 max_events: int = DEFAULT_MAX_EVENTS):
        self._lock = threading.Lock()
        self.events: List[Dict[str, Any]] = []
        self.max_events = int(max_events)
        self.dropped = 0
        self.pid = os.getpid()
        self._t0 = _CLOCK()
        # process metadata event so Perfetto names the track
        self.events.append({
            "name": "process_name", "ph": "M", "pid": self.pid, "tid": 0,
            "args": {"name": process_name},
        })

    def _append(self, evt: Dict[str, Any]) -> None:
        with self._lock:
            if len(self.events) >= self.max_events:
                self.dropped += 1
                return
            self.events.append(evt)

    def add(self, name: str, t_start: float, dur_s: float,
            cat: str = "host", args: Optional[Dict[str, Any]] = None
            ) -> None:
        """One complete event; `t_start` is a _CLOCK() reading."""
        evt: Dict[str, Any] = {
            "name": name, "cat": cat, "ph": "X",
            "ts": (t_start - self._t0) * 1e6,
            "dur": max(dur_s, 0.0) * 1e6,
            "pid": self.pid, "tid": threading.get_ident(),
        }
        if args:
            evt["args"] = dict(args)
        self._append(evt)

    def instant(self, name: str, cat: str = "host",
                args: Optional[Dict[str, Any]] = None) -> None:
        evt: Dict[str, Any] = {
            "name": name, "cat": cat, "ph": "i", "s": "t",
            "ts": (_CLOCK() - self._t0) * 1e6,
            "pid": self.pid, "tid": threading.get_ident(),
        }
        if args:
            evt["args"] = dict(args)
        self._append(evt)

    def chrome_trace(self) -> Dict[str, Any]:
        with self._lock:
            events = list(self.events)
            dropped = self.dropped
        if dropped:
            events.append({
                "name": f"spans_dropped_at_cap: {dropped}",
                "ph": "i", "s": "g",
                "ts": (_CLOCK() - self._t0) * 1e6,
                "pid": self.pid, "tid": 0,
                "args": {"dropped": dropped,
                         "max_events": self.max_events},
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write(self, path: str) -> int:
        """Write the Chrome trace JSON; returns the event count."""
        trace = self.chrome_trace()
        with open(path, "w") as f:
            json.dump(trace, f)
        return len(trace["traceEvents"])


# ------------------------------------------------------------------ global --

_RECORDER: Optional[SpanRecorder] = None


def install_recorder(rec: Optional[SpanRecorder]) -> Optional[SpanRecorder]:
    """Install the process span recorder (None = disable); returns the
    previous one. Installing one marks the clock (`mark_clock`)."""
    global _RECORDER
    prev = _RECORDER
    _RECORDER = rec
    if rec is not None:
        mark_clock()
    return prev


def current_recorder() -> Optional[SpanRecorder]:
    return _RECORDER


def enabled() -> bool:
    return _RECORDER is not None


def record(name: str, t_start: float, dur_s: float, cat: str = "host",
           **args) -> None:
    """Record a completed span from explicit timings. The disabled path is
    one global read + None check — safe at per-batch frequency."""
    rec = _RECORDER
    if rec is not None:
        rec.add(name, t_start, dur_s, cat, args or None)


def _annotation(name: str, **kwargs):
    # jax is imported on first use: importing this module stays cheap, and
    # outside a profiler session an annotation costs one flag check
    import jax
    return jax.profiler.TraceAnnotation(name, **kwargs)


CLOCK_EVENT = "hydragnn.clock"


def mark_clock() -> None:
    """One `hydragnn.clock` annotation on the profiler's timeline (when a
    trace is being captured; nothing otherwise) carrying the span clock's
    reading at its start — module docstring, "Two clocks"."""
    with _annotation(CLOCK_EVENT, perf_counter_ns=time.perf_counter_ns()):
        pass


class _Span:
    """`span()` with a recorder installed: a TraceAnnotation on the
    profiler's clock and a complete event in the recorder. A span that
    ends after its recorder was taken out is dropped: a recorder holds what
    ended while it was installed (an idle dispatcher's wait that outlives
    a traced window would otherwise count minutes against it)."""
    __slots__ = ("rec", "name", "cat", "args", "t0", "ann")

    def __init__(self, rec, name, cat, args):
        self.rec, self.name, self.cat, self.args = rec, name, cat, args

    def __enter__(self):
        self.ann = _annotation(self.name, **self.args)
        self.ann.__enter__()
        self.t0 = _CLOCK()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = _CLOCK() - self.t0
        self.ann.__exit__(exc_type, exc, tb)
        if _RECORDER is self.rec:
            self.rec.add(self.name, self.t0, dur, self.cat,
                         self.args or None)
        return False


_NO_SPAN = contextlib.nullcontext()


def span(name: str, cat: str = "host", **args):
    """Context-manager span around a host region; with no recorder
    installed, one global read + None check and a shared no-op."""
    rec = _RECORDER
    if rec is None:
        return _NO_SPAN
    return _Span(rec, name, cat, args)


def now() -> float:
    """The span clock — pair with `record` for spans whose start predates
    the call site (e.g. serving queue-wait measured from submit time)."""
    return _CLOCK()


# ------------------------------------------------------- device-side traces --


@contextlib.contextmanager
def device_trace(log_dir: str):
    """Opt-in jax.profiler capture bracket (XLA HLO + device timelines,
    TensorBoard/XProf-viewable) — the device-side companion to the host
    spans. Heavyweight: holds trace buffers for the whole region, so it is
    never enabled by default (HYDRAGNN_DEVICE_TRACE, resolved by
    utils/envflags.resolve_telemetry)."""
    import jax
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    mark_clock()
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class EpochDeviceTrace:
    """Epoch-targeted device-trace capture — the ONE timing facility for
    "profile epoch K of this run" (docs/observability.md). Entered around
    each epoch by the trainer; captures a jax.profiler trace of exactly
    the target epoch under <prefix>/profile/.

    Replaces the former utils/profiling.Profiler (the reference's
    torch.profiler wrapper, profile.py:9-70), which duplicated the
    device_profile bracket with its own half-wired state; that name
    remains as a deprecation shim over this class."""

    def __init__(self, prefix: str = "", enable: bool = False,
                 target_epoch: int = 0):
        self.prefix = prefix
        self.enable = enable
        self.target_epoch = target_epoch
        self.current_epoch = -1
        self.done = False
        self._active = False

    def setup(self, config) -> None:
        """reference: Profiler.setup (profile.py:32-42) — the `Profile`
        config section with `enable` 0/1 and `target_epoch`."""
        self.enable = int(config.get("enable", 0)) == 1
        self.target_epoch = int(config.get("target_epoch", 0))

    def set_current_epoch(self, current_epoch: int) -> None:
        self.current_epoch = current_epoch

    def __enter__(self):
        if self.enable and not self.done \
                and self.current_epoch == self.target_epoch:
            import jax
            out = os.path.join(self.prefix or ".", "profile")
            os.makedirs(out, exist_ok=True)
            jax.profiler.start_trace(out)
            mark_clock()
            self._active = True
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._active:
            import jax
            jax.profiler.stop_trace()
            self._active = False
            self.done = True
        return False
