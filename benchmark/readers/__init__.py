"""What a traced run leaves for the per-layer metrics to read.

A per-layer metric is ``benchmark/metrics/<name>.json``: a reader
(`module.function` of this package) and its arguments. A reader takes the
`Readings` of the run and returns a number, or None when there is nothing
to read — the harness then leaves the metric out of the line.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional, Tuple

from ..trace import reduce as tr


@dataclasses.dataclass
class Readings:
    arch: Dict                         # completed Architecture of the cell
    chips: int
    reduced: Optional[tr.Reduced]      # None: no device plane (CPU)
    window: Tuple[float, float]        # host clock, seconds
    spans: List[Tuple[str, float, float]]   # host clock, seconds
    counters: Dict[str, float]
    work: Dict[str, float]             # real atoms/edges/graphs/programs
    device_kind: str

    @classmethod
    def from_run(cls, ctx, result) -> "Readings":
        import jax
        path = tr.find_xplane(ctx.trace.out_dir)
        reduced = None
        if path is not None:
            planes = tr.load_xplane(path)
            if tr.device_planes(planes):
                reduced = tr.reduce_planes(planes)
        if reduced is None and not ctx.tiny:
            raise RuntimeError("the traced run left no device operation in "
                               f"{ctx.trace.out_dir}")
        counters = dict(result.get("counters", {}))
        counters.update(setup_compile_s=ctx.setup_compile_s,
                        setup_programs=ctx.setup_compiles,
                        setup_cache_hits=ctx.setup_cache_hits)
        return cls(arch=result["arch"], chips=ctx.cell.chips,
                   reduced=reduced,
                   window=(ctx.trace.t_open, ctx.trace.t_close),
                   spans=ctx.trace.host_spans(), counters=counters,
                   work=dict(result.get("work", {})),
                   device_kind=jax.devices()[0].device_kind)

    def peak(self) -> Dict:
        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "peaks.json")) as f:
            devices = json.load(f)["devices"]
        if self.device_kind not in devices:
            raise KeyError(f"no peaks on record for {self.device_kind!r}: "
                           "add it to benchmark/peaks.json with its source")
        return devices[self.device_kind]

    def spans_on_profiler_clock(self) -> List[Tuple[str, float, float]]:
        offset = self.reduced.window_ns[0] - self.window[0] * 1e9
        return [(name, a * 1e9 + offset, b * 1e9 + offset)
                for name, a, b in self.spans]

    def breakdown(self) -> Dict:
        if self.reduced is None:
            return {"device_ops": [], "idle_gaps": []}
        gaps = tr.attribute_gaps(self.reduced.idle_gaps,
                                 self.spans_on_profiler_clock(), count=9)
        gaps.append(["inside_programs", self.reduced.idle_in_programs_s])
        return {"device_ops": tr.top(self.reduced.op_seconds, 10),
                "idle_gaps": sorted(gaps, key=lambda g: -g[1])}
