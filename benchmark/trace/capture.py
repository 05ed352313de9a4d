"""One traced window: the profiler's device trace, the program's host
spans (``telemetry/spans.SpanRecorder``) and the mark that ties the two
clocks together.

The program's spans are read off the host clock (``time.perf_counter``),
the device trace has the profiler's clock. The harness enters a
``TraceAnnotation("bench.window")`` and reads the host clock in the same
breath; the difference of the two readings maps host spans onto the
profiler's clock to within microseconds, which is what gap attribution
needs and all that host-clock spans allow.
"""
from __future__ import annotations

import os
import shutil
import time
from typing import List, Optional, Tuple

from .reduce import WINDOW_EVENT


class TraceSession:
    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self._recorder = None
        self._recorder_t0 = 0.0
        self._previous = None
        self._mark = None

    def open(self) -> None:
        import jax
        from hydragnn_tpu.telemetry import spans
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        jax.profiler.start_trace(self.out_dir)
        self._recorder_t0 = spans.now()
        self._recorder = spans.SpanRecorder("benchmark")
        self._previous = spans.install_recorder(self._recorder)
        self._mark = jax.profiler.TraceAnnotation(WINDOW_EVENT)
        self._mark.__enter__()
        self.t_open = time.perf_counter()

    def close(self) -> None:
        import jax
        from hydragnn_tpu.telemetry import spans
        self.t_close = time.perf_counter()
        self._mark.__exit__(None, None, None)
        spans.install_recorder(self._previous)
        jax.profiler.stop_trace()

    def host_spans(self) -> List[Tuple[str, float, float]]:
        """(name, start, end) of every span the program recorded, on the
        host clock, in seconds."""
        out = []
        for ev in self._recorder.chrome_trace()["traceEvents"]:
            if ev.get("ph") == "X":
                start = self._recorder_t0 + ev["ts"] * 1e-6
                out.append((ev["name"], start, start + ev["dur"] * 1e-6))
        return out
