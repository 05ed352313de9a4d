"""Symmetric per-channel int8 matmuls for the serving tier
(docs/mixed_precision.md "int8").

The quantization math, per in-scope ``nn.Dense`` (kernel ``w`` of shape
[in, out], calibrated per-input-channel activation scales ``s_x``):

* activations quantize against the CALIBRATED scales —
  ``x_q = clip(round(x / s_x), -127, 127) : int8``;
* the activation scales fold into the weight ROWS before weight
  quantization — ``w_fold[i, o] = w[i, o] * s_x[i]`` — so the
  contraction needs no per-channel rescale on the int8 side;
* weights quantize per OUTPUT channel against their own absmax —
  ``s_w[o] = max_i |w_fold[i, o]| / 127``,
  ``w_q = clip(round(w_fold / s_w), -127, 127) : int8``;
* the matmul runs int8 x int8 with EXACT int32 accumulation
  (``lax.dot_general(..., preferred_element_type=int32)``), then one
  f32 dequantization multiply + the f32 bias:
  ``y = (x_q @ w_q) : int32 -> f32 * s_w + b``.

Accumulation is exact (<= 255 * 127 * 127 per partial fits int32 for
every model-zoo width), so the int8-vs-fp32 error is pure input/weight
rounding — the provenance of the engine's documented
``SERVE_INT8_RTOL/ATOL = 2^-3`` bound (serving/engine.py).

Weights are quantized IN TRACE from the runtime variables: the compiled
program takes the f32 params as an argument and re-derives
``(w_q, s_w)`` on device, so ``swap_variables`` hot-swaps re-quantize
with zero recompiles. The ACTIVATION scales are trace-time constants —
they are part of the compiled artifact, which is why the engine folds
their digest into the compile-store key (engine._store_key).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .calibrate import CalibrationScales


def int8_dense(x, kernel, bias, s_x):
    """One calibrated int8 Dense: f32 activations/params in, f32 out,
    the contraction in int8 with int32 accumulation (module docstring
    has the math)."""
    if kernel.shape[0] != s_x.shape[0]:
        raise ValueError(
            f"int8_dense: calibration scales cover {s_x.shape[0]} input "
            f"channels but the kernel has {kernel.shape[0]} — the "
            "calibration was taken on a different architecture; "
            "re-calibrate (quant/calibrate.py)")
    x = x.astype(jnp.float32)
    x_q = jnp.clip(jnp.round(x / s_x), -127.0, 127.0).astype(jnp.int8)
    w_fold = kernel.astype(jnp.float32) * s_x[:, None]
    s_w = jnp.max(jnp.abs(w_fold), axis=0) / jnp.float32(127.0)
    s_w = jnp.where(s_w > 0, s_w, jnp.float32(1.0))
    w_q = jnp.clip(jnp.round(w_fold / s_w[None, :]),
                   -127.0, 127.0).astype(jnp.int8)
    acc = jax.lax.dot_general(
        x_q, w_q, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * s_w
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y


def make_quantized_forward(model, mcfg, calibration: CalibrationScales):
    """The int8 serving forward: ``model.apply`` under an interceptor
    that reroutes every CALIBRATED ``nn.Dense.__call__`` through
    ``int8_dense``. Same (variables, batch, train) -> outputs signature
    as ``train_step.make_forward_fn``; uncalibrated layers (heads,
    norms, uncovered convs) run their normal f32 path."""
    from flax import linen as nn

    scales: Dict[str, jnp.ndarray] = {
        key: jnp.asarray(calibration.scales[key], jnp.float32)
        for key in sorted(calibration.scales)}

    def interceptor(next_fun, args, kwargs, context):
        mod = context.module
        if (context.method_name == "__call__"
                and isinstance(mod, nn.Dense)):
            s_x = scales.get("/".join(mod.path))
            if s_x is not None:
                params = mod.variables["params"]
                bias = params["bias"] if mod.use_bias else None
                return int8_dense(args[0], params["kernel"], bias, s_x)
        return next_fun(*args, **kwargs)

    def forward(variables, batch, train=False):
        with nn.intercept_methods(interceptor):
            return model.apply(variables, batch, train=train)

    return forward
