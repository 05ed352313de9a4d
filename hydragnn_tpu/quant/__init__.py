"""Calibrated int8 post-training quantization for the serving tier
(docs/mixed_precision.md "int8").

Three pieces, composed by the serving engine's ``compute_dtype="int8"``
mode (serving/engine.py) and the fleet's tier routing
(serving/fleet.py TierPolicy):

* ``calibrate`` — a deterministic calibration pass collecting per-input-
  channel activation ranges for every conv-stack matmul (same
  calibration set -> bitwise-identical scales, order- and worker-count-
  independent by max-reduce);
* ``make_quantized_forward`` — symmetric per-channel int8 weight +
  activation quantization with exact int32 accumulation and one f32
  dequantization multiply per matmul, weights quantized IN TRACE from
  the runtime variables so ``swap_variables`` hot-swaps re-quantize for
  free;
* ``distill_heads`` — per-head student distillation: the decoder heads
  are fine-tuned against the fp32 teacher's outputs on the calibration
  distribution, shrinking the int8 tier's error head by head.
"""
from .calibrate import (CalibrationScales, calibrate, merge_calibrations,
                        scales_digest)
from .distill import distill_heads
from .ptq import int8_dense, make_quantized_forward

__all__ = [
    "CalibrationScales", "calibrate", "merge_calibrations",
    "scales_digest", "int8_dense", "make_quantized_forward",
    "distill_heads",
]
