"""One run of one cell:

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

prints, as the last line of its standard output, one JSON object with
`correct`, `attempted`, `failed`, `metrics` and `device` (with ``--trace 1``
also `breakdown`). ``--trace 0`` reports the cell's end-to-end metrics with
the profiler off; ``--trace 1`` traces a short window of the same job and
reports the cell's per-layer metrics.

One process. It touches JAX itself and starts no child that needs the chip.
Without a TPU it exits non-zero and prints no result — except when
``JAX_PLATFORMS=cpu`` is set by name: then it rehearses the job at the
configuration's `tiny` preset and prints counts with an EMPTY `metrics`
object, so that no CPU number is ever written under a metric's name.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
import sys
import time
from typing import Dict, List, Optional

from . import START, cells, say


@dataclasses.dataclass
class Context:
    """What a job gets, and where it leaves what the harness reads."""
    cell: cells.Cell
    seed: int
    seconds: float
    tiny: bool
    watch: object                       # utils/profiling.CompileWatch
    trace: Optional[object] = None      # trace/capture.TraceSession
    setup_s: Optional[float] = None
    setup_compiles: int = 0
    setup_compile_s: float = 0.0
    setup_cache_hits: int = 0
    window_compiles: Optional[int] = None
    memory_peak_bytes: int = 0          # of the fullest chip, at the close
    _compiles_at_open: int = 0

    def param(self, key: str, default=None):
        """A parameter of the traffic mix; under rehearsal the mix's
        `tiny` block overrides it."""
        traffic = self.cell.traffic
        if self.tiny and key in traffic.get("tiny", {}):
            return traffic["tiny"][key]
        return traffic.get(key, default)

    def window_seconds(self) -> float:
        """How long the measured window lasts: ``--seconds``, or the mix's
        `trace_seconds` when the window is traced (traces are large)."""
        if self.trace is not None:
            return min(self.seconds, float(self.param("trace_seconds", 5)))
        return self.seconds

    def open_window(self) -> None:
        """Set-up ends here: everything up to now is `setup_s`."""
        self.setup_s = time.perf_counter() - START
        self.setup_compiles = self.watch.count
        self.setup_compile_s = self.watch.seconds
        self.setup_cache_hits = self.watch.cache_hits
        if self.trace is not None:
            self.trace.open()
        self._compiles_at_open = self.watch.count

    def close_window(self) -> None:
        """What follows (the comparisons at highest precision load larger
        programs) counts neither as a compile in the window nor towards
        the peak of device memory."""
        import jax
        from .system import memory_peak_bytes
        self.window_compiles = self.watch.count - self._compiles_at_open
        if self.trace is not None:
            self.trace.close()
        self.memory_peak_bytes = max(
            memory_peak_bytes(d) for d in jax.devices()[:self.cell.chips])


def gate(chips: int) -> bool:
    """True for a chip run, False for a CPU rehearsal asked for by name;
    exits otherwise."""
    import jax
    backend = jax.default_backend()
    if backend == "tpu":
        if jax.device_count() < chips:
            sys.exit(f"the cell needs {chips} chips, JAX found "
                     f"{jax.device_count()}")
        return True
    if backend == "cpu" and os.environ.get("JAX_PLATFORMS") == "cpu":
        if jax.device_count() < chips:
            sys.exit(f"the rehearsal needs {chips} CPU devices "
                     "(--xla_force_host_platform_device_count)")
        return False
    sys.exit(f"no TPU: JAX's backend is {backend!r}. Set JAX_PLATFORMS=cpu "
             "by name for a rehearsal that reports no metric.")


def per_layer_metrics(cell: cells.Cell, readings) -> Dict[str, Dict]:
    out = {}
    for metric in cell.per_layer:
        spec = metric["file"]
        reader = cells.resolve_reader(spec["reader"])
        value = reader(readings, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--root", default=cells.ROOT,
                        help="where BENCHMARK.json and the data files are "
                             "(default: this checkout)")
    args = parser.parse_args(argv)
    cell = cells.load_cell(args.workload, args.root)

    from . import system
    real = gate(cell.chips)
    say(f"{cell.name}: JAX is up" + ("" if real else " (CPU rehearsal)"))
    system.enable_compile_cache()  # the first import of the program
    from hydragnn_tpu.utils.profiling import CompileWatch
    say("the program is imported")
    job = importlib.import_module(f"benchmark.jobs.{cell.traffic['job']}")
    with CompileWatch() as watch:
        ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds,
                      tiny=not real, watch=watch)
        if args.trace:
            from .trace.capture import TraceSession
            ctx.trace = TraceSession(os.path.join(
                system.ROOT, ".bench_trace", cell.name))
        result = job.run(ctx)

    compared = result["checks"]         # jobs/checks.Compared
    compared.flag("zero_compiles_in_window", ctx.window_compiles == 0)
    for name, ok in compared.ok.items():
        say(f"check {name}: {'ok' if ok else 'FAILED'}")
    line = {"correct": all(compared.ok.values()),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": {},
            "device": system.device_report(ctx.memory_peak_bytes),
            "checks": compared.ok}
    say(f"set-up took {ctx.setup_s:.1f} s: {ctx.setup_compiles} programs "
        f"built or fetched ({ctx.setup_cache_hits} from the persistent "
        f"cache) in {ctx.setup_compile_s:.1f} s")
    readings = None
    if args.trace:
        from .readers import Readings
        readings = Readings.from_run(ctx, result)
        if readings.reduced is not None:  # always, on the chip
            line["device"]["busy_s"] = readings.reduced.busy_s
            line["device"]["window_s"] = readings.reduced.window_s
        line["breakdown"] = readings.breakdown()
    if real:
        if args.trace:
            line["metrics"] = per_layer_metrics(cell, readings)
        else:
            values = dict(result["end_to_end"], setup_s=ctx.setup_s)
            line["metrics"] = {
                m["name"]: {"value": float(values[m["name"]]),
                            "unit": m["unit"]} for m in cell.end_to_end}
    else:
        say("rehearsal on the CPU: no metric is reported")
    # each number compared beside its limit: the last lines on standard
    # error, and the last key of the result line
    line["compared"] = compared.numbers
    compared.report()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
