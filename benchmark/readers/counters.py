"""Per-layer metrics that are exact counts: the engine's ``stats()``, the
loader's ``padding_stats()``, ``CompileWatch`` over set-up, and the load
generator's own lateness. The job puts them under `counters`."""
from __future__ import annotations

from typing import Optional


def value(r, key: str, scale: float = 1.0) -> Optional[float]:
    got = r.counters.get(key)
    return None if got is None else float(got) * scale
