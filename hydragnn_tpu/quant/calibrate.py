"""Deterministic per-channel activation calibration for int8 PTQ
(docs/mixed_precision.md "int8").

The calibration pass runs the fp32 model over a calibration set and
records, for every conv-stack ``nn.Dense`` matmul, the per-INPUT-channel
absolute maximum of the activations entering it. Scales are symmetric
(``amax / 127``) so quantization needs no zero points and the int8
matmul stays a pure int8 x int8 -> int32 contraction (quant/ptq.py).

Determinism is a CONTRACT, not a best effort (the tier-1 test pins it):

* identical calibration set -> bitwise-identical scale tensors and
  digest. Per-sample ranges are accumulated by ``np.maximum`` — a
  commutative, associative, idempotent reduction — so the result is
  independent of sample order AND of how the set is sharded across
  workers (``merge_calibrations`` is the shard-merge; a 1-worker and an
  N-worker calibration of the same set are bitwise equal).
* every sample is collated ALONE into one fixed padding shape that is a
  pure function of the calibration set, and PADDING rows are EXCLUDED
  from the absmax (node-aligned activations mask by ``node_mask``,
  edge-aligned by ``edge_mask``). Padding rows carry garbage by
  contract — masked out downstream — and that garbage can be enormous
  (PNA's attenuation scaler alone turns a zero-degree padding row into
  ~1e3–1e4 activations); folding it into the scales would quantize
  every REAL row to zero. Masking also makes the scales independent of
  HOW MUCH padding the calibration shape happened to carry.
* iteration over the recorded layer keys is always ``sorted`` — this
  module sits in hydralint's nondeterministic-order scope.

The pass reports through the PR 7 telemetry probes: a
``quant.calibrate`` span plus ``quant.calibrations_total`` /
``quant.calibration_samples_total`` counters and a
``quant.calibrated_layers`` gauge.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..graphs.batch import GraphSample, collate
from ..telemetry import spans as _spans
from ..telemetry.registry import get_registry


def encoder_conv_path(path: Sequence[str], num_conv_layers: int) -> bool:
    """True when a module `path` (root-relative name tuple) sits inside
    the ENCODER conv stack: top-level ``conv_<i>`` with i <
    num_conv_layers. Conv-type node heads reuse the ``conv_`` prefix at
    indices ``num_conv_layers + 100 * head + layer`` (models/base.py)
    and are deliberately OUT of scope — heads stay f32 (they are the
    distillation target, quant/distill.py)."""
    if not path:
        return False
    name = str(path[0])
    if not name.startswith("conv_"):
        return False
    try:
        idx = int(name[len("conv_"):])
    except ValueError:
        return False
    return idx < int(num_conv_layers)


def encoder_param_key(key: str, num_conv_layers: int) -> bool:
    """True for top-level param-tree keys owned by the encoder: the
    in-scope convs plus their ``feature_norm_<i>`` batch norms. The
    complement — heads, ``graph_shared``, head convs/norms — is the
    distillation student's trainable set."""
    if encoder_conv_path((key,), num_conv_layers):
        return True
    return str(key).startswith("feature_norm_")


def scales_digest(scales: Dict[str, np.ndarray]) -> str:
    """sha256 over the sorted (key, f32 bytes) pairs — the identity the
    compile store folds into every int8 program key (two calibrations
    produce colliding executables iff their scales are bitwise equal)."""
    h = hashlib.sha256()
    for key in sorted(scales):
        h.update(key.encode())
        h.update(b"=")
        h.update(np.ascontiguousarray(scales[key], np.float32).tobytes())
        h.update(b";")
    return h.hexdigest()


@dataclasses.dataclass(frozen=True)
class CalibrationScales:
    """The calibration pass's result: per-layer per-input-channel
    symmetric scales (``amax / 127``; silent channels inherit the
    layer's LARGEST channel scale), the raw absmax tensors they came
    from (kept so shard merges compose at the amax level — merging
    SCALES would lose which channels were silent), and the sha256
    digest serving as the compile-store identity."""
    scales: Dict[str, np.ndarray]
    amax: Dict[str, np.ndarray]
    num_samples: int
    digest: str

    @staticmethod
    def from_amax(amax: Dict[str, np.ndarray],
                  num_samples: int) -> "CalibrationScales":
        scales = {}
        for key in sorted(amax):
            a = np.asarray(amax[key], np.float32)
            s = a / np.float32(127.0)
            # a channel that never fired during calibration still needs
            # a finite scale. It must NOT be an arbitrary constant like
            # 1.0: the activation scales fold into the weight ROWS
            # before weight quantization (quant/ptq.py), so a silent
            # channel's sentinel would dominate the per-output-channel
            # weight absmax and crush every CALIBRATED row's weights to
            # zero. The layer's largest channel scale is the neutral
            # choice — the folded row stays the same order of magnitude
            # as the loudest real row, and a channel that does fire at
            # serving time quantizes with the layer's coarsest (still
            # in-family) grid instead of saturating or vanishing.
            layer_max = np.float32(s.max()) if s.size else np.float32(0.0)
            fallback = layer_max if layer_max > 0 else np.float32(1.0)
            scales[key] = np.where(s > 0, s, fallback).astype(np.float32)
        return CalibrationScales(scales=scales,
                                 amax={k: np.asarray(v, np.float32)
                                       for k, v in sorted(amax.items())},
                                 num_samples=int(num_samples),
                                 digest=scales_digest(scales))


def merge_calibrations(parts: Sequence[CalibrationScales]
                       ) -> CalibrationScales:
    """Merge per-shard calibrations into the whole-set result: amax
    tensors max-reduce, sample counts add. Because max is commutative/
    associative, any sharding of the same calibration set merges to the
    bitwise-identical scales a single pass produces (the worker-count
    pin in tests/test_quant.py)."""
    if not parts:
        raise ValueError("merge_calibrations needs at least one part")
    amax: Dict[str, np.ndarray] = {}
    total = 0
    for part in parts:
        total += part.num_samples
        for key in sorted(part.amax):
            a = np.asarray(part.amax[key], np.float32)
            prev = amax.get(key)
            if prev is None:
                amax[key] = a.copy()
            elif prev.shape != a.shape:
                raise ValueError(
                    f"merge_calibrations: layer {key!r} has shape "
                    f"{a.shape} in one shard and {prev.shape} in "
                    "another — shards must calibrate the same "
                    "architecture")
            else:
                amax[key] = np.maximum(prev, a)
    return CalibrationScales.from_amax(amax, total)


def _calibration_shape(samples: Sequence[GraphSample]) -> tuple:
    """The fixed per-sample collation shape — a pure function of the
    calibration set (max node/edge counts rounded up to a multiple of
    8, plus the mandatory padding slot), so the padded rows every
    forward sees are reproducible."""
    max_n = max(int(s.num_nodes) for s in samples)
    max_e = max(int(s.num_edges) for s in samples)
    rup = lambda v: -(-int(v + 1) // 8) * 8
    n_node, n_edge = rup(max_n), rup(max_e)
    if n_edge == n_node:
        # keep the node and edge axes distinguishable by LENGTH: the
        # calibration interceptor tells node-aligned from edge-aligned
        # activations by their leading dimension (to apply the right
        # padding mask), so the two paddings must never coincide
        n_edge += 8
    return n_node, n_edge, 2


def calibrate(model, variables, mcfg, samples: Sequence[GraphSample], *,
              num_samples: Optional[int] = None,
              batch_transform=None) -> CalibrationScales:
    """Run the calibration pass: fp32 forwards over the first
    `num_samples` of `samples` (None = all), recording per-input-channel
    absmax for every encoder-conv ``nn.Dense`` input via flax method
    interception. Returns the ``CalibrationScales`` the quantized
    forward and the engine's compile-store key consume."""
    from flax import linen as nn

    subset: List[GraphSample] = list(samples)
    if num_samples is not None:
        subset = subset[:max(int(num_samples), 1)]
    if not subset:
        raise ValueError(
            "calibrate needs at least one calibration sample — int8 "
            "activation scales cannot be invented "
            "(docs/mixed_precision.md)")
    n_node, n_edge, n_graph = _calibration_shape(subset)
    num_conv = int(mcfg.num_conv_layers)
    amax: Dict[str, np.ndarray] = {}
    # the current collated batch's padding masks, refreshed per sample —
    # the interceptor matches an activation's leading dim against the
    # (deliberately distinct) node/edge padding lengths to drop padding
    # rows from the absmax. A tensor aligned with neither axis (e.g. the
    # [N, K, F] dense-neighbor message layout) keeps all rows.
    masks: Dict[int, np.ndarray] = {}

    def interceptor(next_fun, args, kwargs, context):
        mod = context.module
        if (context.method_name == "__call__"
                and isinstance(mod, nn.Dense)
                and encoder_conv_path(mod.path, num_conv)):
            x = np.asarray(args[0], np.float32)
            rows = x.reshape(-1, x.shape[-1])
            mask = masks.get(x.shape[0]) if x.ndim == 2 else None
            if mask is not None:
                rows = rows[mask]
            a = (np.abs(rows).max(axis=0) if rows.size
                 else np.zeros((x.shape[-1],), np.float32))
            key = "/".join(mod.path)
            prev = amax.get(key)
            amax[key] = a if prev is None else np.maximum(prev, a)
        return next_fun(*args, **kwargs)

    t0 = _spans.now()
    for sample in subset:
        batch = collate([sample], n_node=n_node, n_edge=n_edge,
                        n_graph=n_graph, np_out=True)
        batch = batch.replace(y_graph=None, y_node=None, energy=None,
                              forces=None)
        if batch_transform is not None:
            batch = batch_transform(batch)
        masks.clear()
        node_mask = np.asarray(batch.node_mask, bool)
        masks[node_mask.shape[0]] = node_mask
        if batch.edge_mask is not None:
            edge_mask = np.asarray(batch.edge_mask, bool)
            masks[edge_mask.shape[0]] = edge_mask
        # EAGER apply (no jit): the interceptor needs concrete arrays to
        # record host-side, and eager per-sample forwards keep the pass
        # free of trace-time constants
        with nn.intercept_methods(interceptor):
            model.apply(variables, batch, train=False)
    if not amax:
        raise ValueError(
            "calibration recorded no conv-stack Dense activations — "
            f"model {type(model).__name__} exposes no encoder "
            "``conv_<i>`` matmuls to quantize "
            "(docs/mixed_precision.md \"int8\")")
    result = CalibrationScales.from_amax(amax, len(subset))
    dur = _spans.now() - t0
    rec = _spans.current_recorder()
    if rec is not None:
        rec.add("quant.calibrate", t0, dur, "quant",
                {"samples": len(subset), "layers": len(result.scales),
                 "digest": result.digest[:12]})
    reg = get_registry()
    reg.counter_inc("quant.calibrations_total",
                    help="int8 calibration passes completed")
    reg.counter_inc("quant.calibration_samples_total",
                    float(len(subset)),
                    help="samples consumed by int8 calibration passes")
    reg.gauge_set("quant.calibrated_layers", float(len(result.scales)),
                  help="conv-stack Dense layers covered by the most "
                       "recent int8 calibration")
    return result
