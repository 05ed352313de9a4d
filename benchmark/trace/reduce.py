"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle
time, device time per program execution, time by operation, collective
time not hidden behind compute, and the idle gaps
with what the host was doing in each.

Nothing here needs more than JAX (``jax.profiler.ProfileData``) and
numpy. A trace is read into plain ``Plane``/``Event`` records first, so the
arithmetic can be checked on hand-made intervals as well as on a recorded
trace (tests/benchmark/test_bench_trace_reduce.py).

Reading of a TPU trace, as recorded on a v5e with jax 0.9.0: one plane per
chip named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per
executed HLO operation, named by the whole HLO instruction, and
``XLA Modules`` one per executed program, `jit_<name>(<fingerprint>)`; host
threads are lines of ``/host:CPU``; all planes share one clock. No event
carries an HLO category, so time by category (matmul, gather) cannot be
read yet (PERF.md section 7).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_EVENT = "bench.window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Plane:
    name: str
    lines: Dict[str, List[Event]]


def read_planes(profile) -> List[Plane]:
    """``jax.profiler.ProfileData`` -> plain records. Lines of one name
    (host threads can share one) are concatenated."""
    planes = []
    for plane in profile.planes:
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append(Event(ev.name, float(ev.start_ns),
                                    float(ev.duration_ns)))
        planes.append(Plane(plane.name, lines))
    return planes


def load_xplane(path: str) -> List[Plane]:
    from jax.profiler import ProfileData
    return read_planes(ProfileData.from_file(path))


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` the profiler wrote under `trace_dir`."""
    import glob
    import os
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


# ------------------------------------------------------------ intervals --

def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return float(sum(b - a for a, b in intervals))


def subtract(intervals: Sequence[Interval], holes: Sequence[Interval]
             ) -> List[Interval]:
    """Parts of merged `intervals` not covered by merged `holes`."""
    out = []
    holes = list(holes)
    for a, b in intervals:
        at = a
        for ha, hb in holes:
            if hb <= at or ha >= b:
                continue
            if ha > at:
                out.append((at, ha))
            at = max(at, hb)
            if at >= b:
                break
        if at < b:
            out.append((at, b))
    return out


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    return subtract([window], busy)


# --------------------------------------------------------------- planes --

def device_planes(planes: Sequence[Plane]) -> List[Plane]:
    found = [(int(DEVICE_PLANE.match(p.name).group(1)), p) for p in planes
             if DEVICE_PLANE.match(p.name) and p.lines.get(OPS_LINE)]
    return [p for _, p in sorted(found, key=lambda t: t[0])]


def spans_of(events: Iterable[Event]) -> List[Interval]:
    return [(e.start_ns, e.end_ns) for e in events]


def host_events(planes: Sequence[Plane], name: str) -> List[Event]:
    out = []
    for plane in planes:
        if plane.name == HOST_PLANE:
            for events in plane.lines.values():
                out.extend(e for e in events if e.name == name)
    return sorted(out, key=lambda e: e.start_ns)


def traced_window(planes: Sequence[Plane]) -> Interval:
    """The window the harness marked on the host (`bench.window`), on the
    profiler's clock; without the mark, first to last device event."""
    marks = host_events(planes, WINDOW_EVENT)
    if marks:
        return marks[0].start_ns, marks[0].end_ns
    ops = [e for p in device_planes(planes) for e in p.lines[OPS_LINE]]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return min(e.start_ns for e in ops), max(e.end_ns for e in ops)


def is_collective(event: Event) -> bool:
    return event.name.lower().lstrip("%").startswith(COLLECTIVES)


# -------------------------------------------------------------- reduced --

@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over the chips
    busy_s_per_device: List[float]
    op_seconds: Dict[str, float]        # by op name, mean over the chips
    collective_s: float                 # mean over the chips
    collective_exposed_s: float         # ... with no compute on that chip
    program_runs: Dict[str, List[float]]  # program -> busy seconds per run
    # idle time of chip 0, split by whether a program was running on it:
    # inside a program the device waits for itself (between its own
    # operations); between programs it waits for the host
    idle_in_programs_s: float
    idle_gaps: List[Interval]           # between programs; profiler ns
    window_ns: Interval

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce_planes(planes: Sequence[Plane]) -> Reduced:
    devices = device_planes(planes)
    if not devices:
        raise ValueError("the trace holds no TPU device plane with "
                         f"an {OPS_LINE!r} line")
    window = traced_window(planes)
    busy_per, coll_per, exposed_per = [], [], []
    op_seconds: Dict[str, float] = {}
    program_runs: Dict[str, List[float]] = {}
    first_gaps: List[Interval] = []
    idle_in_programs = 0.0
    for idx, plane in enumerate(devices):
        ops = [e for e in plane.lines[OPS_LINE]
               if e.end_ns > window[0] and e.start_ns < window[1]]
        busy = merge(clip(spans_of(ops), window))
        busy_per.append(length(busy) * 1e-9)
        coll = merge(clip(spans_of(e for e in ops if is_collective(e)),
                          window))
        compute = merge(clip(spans_of(e for e in ops
                                      if not is_collective(e)), window))
        coll_per.append(length(coll) * 1e-9)
        exposed_per.append(length(subtract(coll, compute)) * 1e-9)
        for e in ops:
            dur = length(clip([(e.start_ns, e.end_ns)], window)) * 1e-9
            name = short_name(e.name)
            op_seconds[name] = op_seconds.get(name, 0.0) + dur
        if idx == 0:
            runs = plane.lines.get(MODULES_LINE, [])
            running = merge(clip(spans_of(runs), window))
            first_gaps = gaps(merge(list(busy) + running), window)
            idle_in_programs = length(subtract(running, busy)) * 1e-9
            for run in runs:
                if run.start_ns < window[0] or run.end_ns > window[1]:
                    continue
                program_runs.setdefault(program_name(run.name), []).append(
                    length(clip(busy, (run.start_ns, run.end_ns))) * 1e-9)
    n = float(len(devices))
    return Reduced(
        window_s=(window[1] - window[0]) * 1e-9,
        busy_s=float(np.mean(busy_per)), busy_s_per_device=busy_per,
        op_seconds={k: v / n for k, v in op_seconds.items()},
        collective_s=float(np.mean(coll_per)),
        collective_exposed_s=float(np.mean(exposed_per)),
        program_runs=program_runs, idle_in_programs_s=idle_in_programs,
        idle_gaps=first_gaps, window_ns=window)


def short_name(op_event_name: str) -> str:
    """An operation's event is named by its whole HLO instruction
    (`%fusion.14 = f32[309440,256]{1,0:T(8,128)} fusion(...), kind=...`);
    the result name and shape say which one it is."""
    return op_event_name.split("{", 1)[0].strip()


def program_name(module_event_name: str) -> str:
    """`jit_step_body(1234567)` -> `jit_step_body`: the run id in brackets
    differs from run to run."""
    return re.sub(r"\(\d+\)$", "", module_event_name)


def main_program(reduced: Reduced) -> Optional[str]:
    """The program that held the device longest in the window."""
    if not reduced.program_runs:
        return None
    return max(reduced.program_runs,
               key=lambda k: sum(reduced.program_runs[k]))


def top(seconds: Dict[str, float], count: int = 10) -> List[List]:
    ranked = sorted(seconds.items(), key=lambda kv: -kv[1])[:count]
    return [[name, value] for name, value in ranked]


def attribute_gaps(idle: Sequence[Interval],
                   host_spans: Sequence[Tuple[str, float, float]],
                   count: int = 10) -> List[List]:
    """Idle seconds of the device by what the host was doing: each gap goes
    to the SHORTEST host span that covers its midpoint (the innermost one),
    or to `no_span`. `host_spans` are (name, start_ns, end_ns) on the
    profiler's clock. Returns the `count` largest totals."""
    totals: Dict[str, float] = {}
    ordered = sorted(host_spans, key=lambda s: s[2] - s[1])
    for a, b in idle:
        mid = 0.5 * (a + b)
        label = next((name for name, s, e in ordered if s <= mid <= e),
                     "no_span")
        totals[label] = totals.get(label, 0.0) + (b - a) * 1e-9
    return top(totals, count)
