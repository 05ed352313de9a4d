"""Mixed-precision policy resolution (docs/mixed_precision.md).

ONE place decides the compute dtype for a step/engine, resolved at
CONSTRUCTION time and baked into the compiled program — never read
inside a traced body (tools/check_traced_env_reads.py lints this module
as part of the traced surface, so a direct os.environ read here fails
tier-1).

The policy itself (bf16 compute, f32 parameter master copies, f32 loss
and segment accumulation) lives in train/train_step.py's casting helpers
and ops/segment.py's `_accum_f32`; this module only answers "which
dtype".

Precedence, most specific wins:

1. an explicit per-construction override (the serve-side precision
   override `Serving.precision`/HYDRAGNN_SERVE_PRECISION resolved by
   serving/config.py, or bench.py's BENCH_DTYPE),
2. the HYDRAGNN_PRECISION env knob (STRICT parsing via
   envflags.env_strict_choice — a typo warns and falls through),
3. Architecture.dtype from the model config,
4. float32.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

# accepted spellings -> canonical dtype name. bf16 and f32 are the
# dtypes the policy layer supports end to end (f32 accumulation, serving
# tolerance bound); int8 is the SERVING-ONLY post-training-quantization
# mode (docs/mixed_precision.md "int8") — the serving engine
# handles it via quant/ptq.py and the train-side step factories reject
# it with an actionable error (train_step._resolve_compute_dtype). Other
# valid jnp dtype strings in Architecture.dtype pass through unchanged
# for forward compatibility.
PRECISION_CHOICES = {
    "float32": "float32", "f32": "float32", "fp32": "float32",
    "bfloat16": "bfloat16", "bf16": "bfloat16",
    "int8": "int8", "i8": "int8",
}


def canonical_precision(name) -> Optional[str]:
    """Canonical dtype name for `name`, or None when unrecognized."""
    if name is None:
        return None
    key = str(name).strip().lower()
    if not key:
        return None
    if key in PRECISION_CHOICES:
        return PRECISION_CHOICES[key]
    try:
        return str(jnp.dtype(key).name)
    except TypeError:
        return None


def canonical_or_f32(name, what: str = "Architecture.dtype") -> str:
    """Canonical dtype name, or warn-and-float32 for an unrecognized
    value — THE config-side fallback, shared by `resolve_precision` and
    `config.build_model_config` so the policy cannot fork."""
    if name is None:
        return "float32"
    canon = canonical_precision(name)
    if canon is None:
        import logging
        logging.getLogger("hydragnn_tpu").warning(
            "%s %r is not a recognized precision; using float32",
            what, name)
        return "float32"
    if canon == "int8":
        # int8 is post-training quantization, a serving-side mode: a
        # TRAIN-side config asking for it would cast the float params to
        # int8 and destroy them. Warn-and-f32 here (the config-side
        # fallback); the serve-side override path accepts int8.
        import logging
        logging.getLogger("hydragnn_tpu").warning(
            "%s 'int8' is serving-only (post-training quantization, "
            "docs/mixed_precision.md) — the train-side policy "
            "uses float32; serve with Serving.precision='int8' / "
            "HYDRAGNN_SERVE_PRECISION=int8 instead", what)
        return "float32"
    return canon


def resolve_precision(cfg_dtype=None, override=None) -> str:
    """The compute-dtype name a step/engine factory should bake in.

    `override` is the construction-site argument (serve-side precision,
    BENCH_DTYPE); `cfg_dtype` is Architecture.dtype. An unrecognized
    override value warns and falls through to the next precedence level
    rather than taking effect."""
    name = canonical_precision(override)
    if override is not None and name is None:
        import logging
        logging.getLogger("hydragnn_tpu").warning(
            "compute dtype override %r is not a recognized precision "
            "(%s); falling through", override,
            sorted(set(PRECISION_CHOICES)))
    if name is not None:
        return name
    from ..utils.envflags import env_strict_choice
    name = env_strict_choice("HYDRAGNN_PRECISION", PRECISION_CHOICES, None)
    if name is not None:
        return name
    return canonical_or_f32(cfg_dtype)
