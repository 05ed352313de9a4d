"""Per-layer metrics read from the program's own host spans
(``telemetry/spans.SpanRecorder``: `dataload_wait`, `loader.collate`,
`h2d`, `device_wait`, `serve.graph_build`, `serve.queue_wait`,
`serve.forward`, `serve.unpad`)."""
from __future__ import annotations

from typing import Optional

import numpy as np


def _durations(r, span: str) -> np.ndarray:
    return np.array([b - a for name, a, b in r.spans if name == span])


def percentile_ms(r, span: str, q: float = 50.0) -> Optional[float]:
    d = _durations(r, span)
    return float(np.percentile(d, q) * 1e3) if d.size else None


def share_of_window(r, span: str) -> Optional[float]:
    """Percent of the traced window covered by spans of this name (summed:
    meant for spans of one thread, which cannot overlap)."""
    d = _durations(r, span)
    if not d.size:
        return None
    return 100.0 * float(d.sum()) / (r.window[1] - r.window[0])
