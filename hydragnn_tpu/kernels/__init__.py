"""Pallas TPU kernels. All are default OFF behind a user-set flag
(HYDRAGNN_USE_PALLAS, HYDRAGNN_PALLAS_NBR, HYDRAGNN_FUSED_MP); every one
compiles for the v5e and matches its XLA reference there, forward and
backward (chip_smoke.py's kernel section). Whether any WINS its cell end
to end is unmeasured (ROADMAP A9)."""
from __future__ import annotations

import functools
import logging


def interpret_mode() -> bool:
    """Pallas interpret mode everywhere but a real TPU — how tier-1
    exercises the kernels on CPU. THE one switch: on the chip every
    kernel call resolves to compiled."""
    import jax
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=None)
def kernel_not_taken(flag: str, kernel: str, shape: tuple, why: str) -> None:
    """A user turned `flag` on and the conv is about to take the XLA path
    anyway: say which kernel, which shape and why — once per distinct
    (kernel, shape, reason), at the first trace of the step (shapes are
    not known before). Without this the flag is a silent no-op."""
    logging.getLogger("hydragnn_tpu").warning(
        "%s is on but the %s kernel is NOT taken for shape %s: %s — the "
        "XLA path runs instead", flag, kernel, shape, why)
