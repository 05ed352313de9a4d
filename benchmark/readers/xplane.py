"""Per-layer metrics read from the device trace (``trace/reduce.py``)."""
from __future__ import annotations

import importlib
from typing import Optional

import numpy as np

from ..roofline.common import PASSES, least_seconds
from ..trace import reduce as tr


def program_device_ms(r, program: Optional[str] = None) -> Optional[float]:
    """Median device-busy milliseconds per execution of `program` (a
    substring of its name), or of the program that held the device
    longest: the union of the operations' intervals inside each execution."""
    if r.reduced is None or not r.reduced.program_runs:
        return None
    runs = r.reduced.program_runs
    if program is None:
        name = tr.main_program(r.reduced)
    else:
        name = next((k for k in runs if program in k), None)
    if name is None:
        return None
    return float(np.median(runs[name]) * 1e3)


def collective_exposed_share(r) -> Optional[float]:
    """Percent of device-busy time in which a collective ran and no other
    operation did, mean over the chips."""
    if r.reduced is None or r.chips < 2:
        return None
    return 100.0 * r.reduced.collective_exposed_s / r.reduced.busy_s


def roofline_share(r, conv: str, mode: str) -> Optional[float]:
    """Percent: the least time the chips could take for the window's REAL
    atoms and edges (``roofline/<conv>.forward`` times the passes of
    `mode`) over the time they were busy. Work is split evenly over the
    chips of a data-parallel cell."""
    if r.reduced is None or not r.work.get("atoms"):
        return None
    forward = importlib.import_module(f"benchmark.roofline.{conv}").forward
    flops, hbm = forward(r.arch, r.work["atoms"], r.work["edges"])
    seconds, _ = least_seconds(flops * PASSES[mode] / r.chips,
                               hbm * PASSES[mode] / r.chips, r.peak())
    return 100.0 * seconds / r.reduced.busy_s
