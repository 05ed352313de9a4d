"""Fused gather -> edge-compute -> scatter Pallas kernels for the
edge-list message-passing hot path (ROADMAP item 5, DGL's kernel
argument in PAPERS.md).

Every conv family's segment branch materializes a full [E, F] edge
tensor through HBM on the gather -> edge-op -> scatter chain
(models/convs.py, models/schnet.py): XLA fuses the elementwise edge op
into the scatter, but the gathered operands still round-trip HBM at
edge cardinality (E ~ 30N for radius graphs). These kernels keep the
whole chain in VMEM per tile:

* ``fused_filter_scatter`` — SchNet's continuous-filter aggregation
  ``out[n] = sum_{e: recv[e]=n} h[send[e]] * w[e]`` (models/schnet.py
  CFConv; reference: SCFStack.py:143-223). Per (node-block x edge-tile)
  grid step the gather is a one-hot x h MXU matmul, the filter multiply
  happens in-register, and the scatter is a second one-hot matmul into
  an f32 VMEM accumulator — the [E, F] message tensor never exists in
  HBM.
* ``fused_pna_edge_aggregate`` — PNA's multi-aggregator over
  ``h_e = proj_i[recv] + proj_j[send]`` (models/convs.py PNAConv;
  reference: PNAStack.py:41-66). One kernel produces all five
  statistics (mean/min/max/std/degree): sum, sum-of-squares and count
  ride MXU one-hot matmuls; min/max ride chunked VPU masked reductions.
  The edge-list sibling of kernels/nbr_pallas.py (which covers the
  dense neighbor layout).

Numerical contract (pinned by tests/test_kernels.py, interpret mode):

* Forward sums accumulate in f32 scratch and are cast to the data dtype
  at the final tile — mirroring ops/segment.py's mixed-precision policy
  (reduced-precision segment sums accumulate f32). Summation ORDER
  differs from XLA's sequential scatter-add (the MXU contracts a whole
  tile at once), so random-float forwards agree to the last ulp, and
  are BITWISE-equal whenever every partial sum is exactly representable
  (integer-valued data — the bit-level indexing/masking contract the
  parity suite pins across fp32/bf16 and ragged/padded segment ids).
  Min/max/count and all gather steps are rounding-free, hence bitwise
  for any input.
* Backward is BITWISE-equal to the unfused path by construction: the
  custom VJP recomputes gradients through the ops/segment.py
  formulation (remat-style — the same trade kernels/nbr_pallas.py
  makes: the fused forward's HBM saving is what the backward trades
  back in FLOPs).

Whether the +2*E*N*F one-hot-matmul FLOPs beat the removed HBM traffic
is an ON-CHIP question (the r3 scatter kernel lost end-to-end despite a
microbench win — ops/segment.py decision record), so the kernels are

  * default OFF; HYDRAGNN_FUSED_MP=1 enables them (STRICT parsing via
    utils/envflags.env_strict_flag — a typo warns and stays off, the
    HYDRAGNN_PALLAS_NBR lesson), resolved ONCE at step construction
    (resolve_fused_mp_flag(refresh=True) in train_step factories),
  * interpret-mode off the TPU (kernels.interpret_mode) so tier-1
    exercises them end to end,
  * bounded by the whole node array fitting VMEM (the one-hot gather
    reads all of h/proj_j per tile): larger inputs fall back to the
    XLA path via ``fused_mp_enabled``.

BENCH_KERNELS (bench.py) adjudicates fused-vs-unfused and fp32-vs-bf16
graphs/s; docs/kernels_mixed_precision.md is the design record.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# edges per grid step x output nodes per block. VMEM at f32, F=128:
# one-hot gather TILE_E x N (bounded by VMEM_BYTES_LIMIT below), data
# tiles TILE_E x F, accumulators 5 x TILE_N x F — comfortably under the
# ~16 MB/core budget.
TILE_E = 256
TILE_N = 128
# node arrays bigger than this stay on the XLA path: the kernels hold
# the whole h / proj_j in VMEM for the one-hot gather (same bound and
# rationale as kernels/nbr_pallas.py)
VMEM_BYTES_LIMIT = 4 * 1024 * 1024


def _pad_axis0(x, size, fill=0):
    pad = size - x.shape[0]
    if pad <= 0:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill)


def _masked_ids(senders, receivers, edge_mask, e_pad):
    """Fold the edge mask into the ids: masked/padded edges get recv -1
    (matches no node block — they contribute nothing to any statistic,
    exactly like the unfused where(mask, ., 0)/neutral fills) and send 0
    (any valid gather row; the result is discarded)."""
    send = jnp.where(edge_mask, senders.astype(jnp.int32), 0)
    recv = jnp.where(edge_mask, receivers.astype(jnp.int32), -1)
    send = _pad_axis0(send, e_pad, 0).reshape(1, e_pad)
    recv = _pad_axis0(recv, e_pad, -1).reshape(1, e_pad)
    return send, recv


def _gather_rows(ids, table32, dtype):
    """table[ids] as a one-hot x table MXU matmul — rounding-free (one
    1.0 against zeros per row), so bitwise-equal to a real gather."""
    n_all = table32.shape[0]
    iota = lax.broadcasted_iota(jnp.int32, (ids.shape[0], n_all), 1)
    onehot = (ids[:, None] == iota).astype(jnp.float32)
    out = _dot(onehot, table32, (((1,), (0,)), ((), ())))
    return out.astype(dtype)


def _dot(a, b, dims):
    """f32 MXU matmul at HIGHEST precision. Every matmul in this module
    has a one-hot operand and exists to MOVE f32 values (gather) or ADD
    them (scatter); at the default precision the MXU rounds the values
    to bf16 first and the result is no longer the unfused path's
    (kernels/segment_pallas.py has the on-chip numbers)."""
    return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# SchNet continuous-filter aggregation
# --------------------------------------------------------------------------

def _filter_kernel(send_ref, recv_ref, h_ref, w_ref, out_ref, acc_ref):
    n_blk = pl.program_id(0)
    e_idx = pl.program_id(1)
    e_last = pl.num_programs(1) - 1

    @pl.when(e_idx == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    dtype = h_ref.dtype
    send = send_ref[0, :]                               # [TILE_E]
    recv = recv_ref[0, :]
    gath = _gather_rows(send, h_ref[...].astype(jnp.float32), dtype)
    # filter multiply in the data dtype — mirrors the unfused
    # h[send] * w bit for bit, then f32 for the accumulation
    msgs = (gath * w_ref[...]).astype(jnp.float32)      # [TILE_E, F]
    local = recv - n_blk * TILE_N
    cols = lax.broadcasted_iota(jnp.int32, (TILE_E, TILE_N), 1)
    onehot = (local[:, None] == cols).astype(jnp.float32)
    acc_ref[:] += _dot(onehot, msgs, (((0,), (0,)), ((), ())))

    @pl.when(e_idx == e_last)
    def _():
        out_ref[:] = acc_ref[:].astype(out_ref.dtype)


def _filter_call(h, w, senders, receivers, edge_mask, num_nodes, interpret):
    # mirror the unfused path's dtype promotion (h[send] * w): mixed
    # operands — e.g. a bf16 model with an f32 radial filter, the SchNet
    # mixed-precision case — promote before the multiply; the upcast is
    # exact, so bitwise parity is preserved
    dtype = jnp.promote_types(h.dtype, w.dtype)
    h = h.astype(dtype)
    w = w.astype(dtype)
    e, f = w.shape
    e_pad = pl.cdiv(e, TILE_E) * TILE_E
    n_pad = pl.cdiv(num_nodes, TILE_N) * TILE_N
    send, recv = _masked_ids(senders, receivers, edge_mask, e_pad)
    w_p = _pad_axis0(w, e_pad)

    grid = (n_pad // TILE_N, e_pad // TILE_E)
    out = pl.pallas_call(
        _filter_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, TILE_E), lambda n, e_: (0, e_)),
            pl.BlockSpec((1, TILE_E), lambda n, e_: (0, e_)),
            pl.BlockSpec(h.shape, lambda n, e_: (0, 0)),      # whole h
            pl.BlockSpec((TILE_E, f), lambda n, e_: (e_, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_N, f), lambda n, e_: (n, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, f), h.dtype),
        scratch_shapes=[pltpu.VMEM((TILE_N, f), jnp.float32)],
        interpret=interpret,
    )(send, recv, h, w_p)
    return out[:num_nodes]


def _filter_reference(h, w, senders, receivers, edge_mask, num_nodes):
    from ..ops import segment as seg
    return seg.segment_sum(h[senders] * w, receivers, num_nodes, edge_mask)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def fused_filter_scatter(h, w, senders, receivers, edge_mask,
                         num_nodes: int, interpret: bool = False):
    """sum_{e: recv[e]=n} h[send[e], :] * w[e, :] -> [num_nodes, F]
    without materializing the [E, F] message tensor — semantics identical
    to ops/segment.segment_sum(h[senders] * w, receivers, ...)."""
    return _filter_call(h, w, senders, receivers, edge_mask, num_nodes,
                        interpret)


def _filter_fwd(h, w, senders, receivers, edge_mask, num_nodes, interpret):
    out = _filter_call(h, w, senders, receivers, edge_mask, num_nodes,
                       interpret)
    return out, (h, w, senders, receivers, edge_mask)


def _filter_bwd(num_nodes, interpret, res, g):
    # remat-style backward through the unfused XLA formulation — bitwise
    # gradient parity with the default path by construction
    h, w, senders, receivers, edge_mask = res
    _, vjp = jax.vjp(
        lambda hh, ww: _filter_reference(hh, ww, senders, receivers,
                                         edge_mask, num_nodes), h, w)
    dh, dw = vjp(g)
    return dh, dw, None, None, None


fused_filter_scatter.defvjp(_filter_fwd, _filter_bwd)


# --------------------------------------------------------------------------
# PNA multi-aggregator over proj_i[recv] + proj_j[send]
# --------------------------------------------------------------------------

def _pna_kernel(send_ref, recv_ref, pi_ref, pj_ref,
                s_out, sq_out, cnt_out, mn_out, mx_out,
                s_ref, sq_ref, cnt_ref, amn_ref, amx_ref):
    n_blk = pl.program_id(0)
    e_idx = pl.program_id(1)
    e_last = pl.num_programs(1) - 1
    dtype = pi_ref.dtype
    big = jnp.asarray(jnp.finfo(dtype).max, dtype)

    @pl.when(e_idx == 0)
    def _():
        s_ref[:] = jnp.zeros_like(s_ref)
        sq_ref[:] = jnp.zeros_like(sq_ref)
        cnt_ref[:] = jnp.zeros_like(cnt_ref)
        amn_ref[:] = jnp.full_like(amn_ref, big)
        amx_ref[:] = jnp.full_like(amx_ref, -big)

    # ONE membership mask, TRANSPOSED — nodes on sublanes, edges on lanes
    # — so that every op below is a 2-D op in its natural layout: plain
    # and transposed-LHS MXU matmuls for the sums and the proj_i gather,
    # a lane reduction for the count, and for min/max, per edge, a
    # [TILE_N, 1] column broadcast against a [1, F] row (the pattern
    # kernels/nbr_pallas.py uses per neighbour slot). The 3-D
    # [chunk, TILE_N, F] select this replaces needed a bool
    # [C, TILE_N] -> [C, TILE_N, 1] shape cast that Mosaic refuses
    # (v5e, PR 21: "infer-vector-layout: unsupported shape cast").
    rows = lax.broadcasted_iota(jnp.int32, (TILE_N, TILE_E), 0)
    onblk_t = rows == (recv_ref[...] - n_blk * TILE_N)  # [TILE_N, TILE_E]
    oh_t = onblk_t.astype(jnp.float32)

    # both gathers are rounding-free one-hot matmuls; the edge message is
    # formed in the data dtype exactly like the unfused
    # proj_i[recv] + proj_j[send]
    pj_g = _gather_rows(send_ref[0, :], pj_ref[...].astype(jnp.float32),
                        dtype)
    pi_g = _dot(oh_t, pi_ref[...].astype(jnp.float32),
                (((0,), (0,)), ((), ()))).astype(dtype)
    h_e = pi_g + pj_g                                   # [TILE_E, F]

    h32 = h_e.astype(jnp.float32)
    sq32 = (h_e * h_e).astype(jnp.float32)  # square in dtype (mirrors
    # pna_aggregate's packed data*data), accumulate f32
    s_ref[:] += _dot(oh_t, h32, (((1,), (0,)), ((), ())))
    sq_ref[:] += _dot(oh_t, sq32, (((1,), (0,)), ((), ())))
    cnt_ref[:] += jnp.sum(oh_t, axis=1, keepdims=True)  # exact integers
    amn, amx = amn_ref[:], amx_ref[:]
    for c in range(TILE_E):                 # static: unrolled
        sel = onblk_t[:, c:c + 1]                       # [TILE_N, 1]
        hc = h_e[c:c + 1, :]                            # [1, F]
        amn = jnp.minimum(amn, jnp.where(sel, hc, big))
        amx = jnp.maximum(amx, jnp.where(sel, hc, -big))
    amn_ref[:] = amn
    amx_ref[:] = amx

    # the mean/std epilogue stays OUTSIDE the kernel (in _pna_call): the
    # kernel's one XLA computation would let the backend contract
    # sq/cnt - mean*mean into an FMA, breaking last-ulp parity with the
    # unfused path's separately-dispatched ops
    @pl.when(e_idx == e_last)
    def _():
        s_out[:] = s_ref[:]
        sq_out[:] = sq_ref[:]
        cnt_out[:] = cnt_ref[:]
        mn_out[:] = amn_ref[:]
        mx_out[:] = amx_ref[:]


def _pna_call(proj_i, proj_j, senders, receivers, edge_mask, num_nodes,
              interpret):
    # mirror the unfused proj_i[recv] + proj_j[send] dtype promotion
    dt = jnp.promote_types(proj_i.dtype, proj_j.dtype)
    proj_i = proj_i.astype(dt)
    proj_j = proj_j.astype(dt)
    e = senders.shape[0]
    f = proj_i.shape[1]
    e_pad = pl.cdiv(e, TILE_E) * TILE_E
    n_pad = pl.cdiv(num_nodes, TILE_N) * TILE_N
    send, recv = _masked_ids(senders, receivers, edge_mask, e_pad)
    pi_p = _pad_axis0(proj_i, n_pad)

    grid = (n_pad // TILE_N, e_pad // TILE_E)
    node_spec = pl.BlockSpec((TILE_N, f), lambda n, e_: (n, 0))
    dtype = proj_i.dtype
    out_shape = [jax.ShapeDtypeStruct((n_pad, f), jnp.float32),  # sum
                 jax.ShapeDtypeStruct((n_pad, f), jnp.float32),  # sum sq
                 jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),  # count
                 jax.ShapeDtypeStruct((n_pad, f), dtype),        # min
                 jax.ShapeDtypeStruct((n_pad, f), dtype)]        # max
    outs = pl.pallas_call(
        _pna_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, TILE_E), lambda n, e_: (0, e_)),
            pl.BlockSpec((1, TILE_E), lambda n, e_: (0, e_)),
            node_spec,                                       # proj_i block
            pl.BlockSpec(proj_j.shape, lambda n, e_: (0, 0)),  # whole proj_j
        ],
        out_specs=[node_spec, node_spec,
                   pl.BlockSpec((TILE_N, 1), lambda n, e_: (n, 0)),
                   node_spec, node_spec],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((TILE_N, f), jnp.float32),
                        pltpu.VMEM((TILE_N, f), jnp.float32),
                        pltpu.VMEM((TILE_N, 1), jnp.float32),
                        pltpu.VMEM((TILE_N, f), dtype),
                        pltpu.VMEM((TILE_N, f), dtype)],
        interpret=interpret,
    )(send, recv, pi_p, proj_j)
    n = num_nodes
    s, sq, cnt = (o[:n] for o in outs[:3])
    amn, amx = outs[3][:n], outs[4][:n]
    # cast the f32 accumulators back to the data dtype (the unfused
    # path's segment_sum cast-back policy) and clamp empty segments'
    # extrema to 0 (segment_min/max's neutral clamp) — the custom-VJP
    # boundary hands back exactly what the unfused accumulator
    # computation produces; the mean/std epilogue lives OUTSIDE the
    # boundary in the shared ops/segment.pna_stats_epilogue
    s, sq, cnt = s.astype(dtype), sq.astype(dtype), cnt.astype(dtype)
    has = cnt > 0
    mn = jnp.where(has, amn, 0.0)
    mx = jnp.where(has, amx, 0.0)
    return s, sq, cnt, mn, mx


def _pna_accums_reference(proj_i, proj_j, senders, receivers, edge_mask,
                          num_nodes):
    """The unfused accumulator computation — mirrors
    ops/segment.pna_aggregate up to (but excluding) the shared
    epilogue; the fused backward differentiates through this."""
    from ..ops import segment as seg
    data = proj_i[receivers] + proj_j[senders]
    f = data.shape[-1]
    ones = jnp.ones(data.shape[:-1] + (1,), data.dtype)
    packed = jnp.concatenate([data, data * data, ones], axis=-1)
    ps = seg.segment_sum(packed, receivers, num_nodes, edge_mask)
    s, sq, cnt = ps[..., :f], ps[..., f:2 * f], ps[..., 2 * f:]
    mn = seg.segment_min(data, receivers, num_nodes, edge_mask)
    mx = seg.segment_max(data, receivers, num_nodes, edge_mask)
    return s, sq, cnt, mn, mx


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fused_pna_accums(proj_i, proj_j, senders, receivers, edge_mask,
                      num_nodes: int, interpret: bool = False):
    return _pna_call(proj_i, proj_j, senders, receivers, edge_mask,
                     num_nodes, interpret)


def _pna_fwd(proj_i, proj_j, senders, receivers, edge_mask, num_nodes,
             interpret):
    out = _pna_call(proj_i, proj_j, senders, receivers, edge_mask,
                    num_nodes, interpret)
    return out, (proj_i, proj_j, senders, receivers, edge_mask)


def _pna_bwd(num_nodes, interpret, res, cots):
    # remat-style backward through the unfused XLA formulation — bitwise
    # gradient parity with the default path by construction
    proj_i, proj_j, senders, receivers, edge_mask = res
    _, vjp = jax.vjp(
        lambda pi, pj: _pna_accums_reference(pi, pj, senders, receivers,
                                             edge_mask, num_nodes),
        proj_i, proj_j)
    dpi, dpj = vjp(cots)
    return dpi, dpj, None, None, None


_fused_pna_accums.defvjp(_pna_fwd, _pna_bwd)


def fused_pna_edge_aggregate(proj_i, proj_j, senders, receivers, edge_mask,
                             num_nodes: int, eps: float = 1e-5,
                             interpret: bool = False):
    """(mean, min, max, std, degree) of proj_i[recv] + proj_j[send] over
    in-edges, without materializing the [E, F] edge tensor — semantics
    identical to ops/segment.pna_aggregate on that sum (the epilogue IS
    pna_stats_epilogue, shared with the unfused path)."""
    from ..ops.segment import pna_stats_epilogue
    s, sq, cnt, mn, mx = _fused_pna_accums(
        proj_i, proj_j, senders, receivers, edge_mask, num_nodes,
        interpret)
    return pna_stats_epilogue(s, sq, cnt, mn, mx, eps)


# --------------------------------------------------------------------------
# flag gating — HYDRAGNN_FUSED_MP, resolved ONCE at step construction
# (the kernels/nbr_pallas.py pattern; tools/check_traced_env_reads.py
# keeps direct env reads out of this module)
# --------------------------------------------------------------------------

_RESOLVED_FLAG = None


def resolve_fused_mp_flag(refresh: bool = False) -> bool:
    """Resolve HYDRAGNN_FUSED_MP to a pinned boolean. Only explicit
    truthy values ('1'/'true'/'on') enable the kernels; a typo warns and
    leaves them off (envflags.env_strict_flag). Step constructors call
    this with refresh=True so the decision is made at step-construction
    time, never at trace time."""
    global _RESOLVED_FLAG
    if _RESOLVED_FLAG is None or refresh:
        from ..utils.envflags import env_strict_flag
        _RESOLVED_FLAG = env_strict_flag("HYDRAGNN_FUSED_MP", False)
    return _RESOLVED_FLAG


def fused_mp_enabled(node_array_shape, dtype, edge_terms: bool = False,
                     has_edge_mask: bool = True) -> bool:
    """Flag on AND the kernels apply: no per-edge encoder terms
    (`edge_terms`), a masked edge list, and the per-tile VMEM residents
    fit the budget — the whole node array (h / proj_j, read per tile by
    the one-hot gather) AND the [TILE_E, N] f32 one-hot itself: the
    one-hot's footprint is TILE_E * N * 4 bytes regardless of F, so a
    narrow-F/bf16 shape can pass the node-array bound alone while the
    gather operand blows VMEM on real TPU (interpret mode would never
    catch it). A flag that is on while the kernel is not taken is logged
    (kernels.kernel_not_taken), never silent."""
    if not resolve_fused_mp_flag():
        return False
    from . import kernel_not_taken
    n = node_array_shape[0]
    node_bytes = n * node_array_shape[1] * jnp.dtype(dtype).itemsize
    n_pad = pl.cdiv(n, TILE_N) * TILE_N
    onehot_bytes = TILE_E * n_pad * 4
    why = None
    if edge_terms:
        why = "the conv adds per-edge encoder terms (edge_dim/rbf)"
    elif not has_edge_mask:
        why = "the batch carries no edge mask"
    elif node_bytes > VMEM_BYTES_LIMIT:
        why = (f"the node array is {node_bytes} bytes, over the "
               f"{VMEM_BYTES_LIMIT}-byte whole-array VMEM bound")
    elif onehot_bytes > VMEM_BYTES_LIMIT:
        why = (f"the [TILE_E, N] one-hot is {onehot_bytes} bytes, over the "
               f"{VMEM_BYTES_LIMIT}-byte VMEM bound")
    if why is not None:
        kernel_not_taken("HYDRAGNN_FUSED_MP", "fused_mp_pallas",
                         tuple(node_array_shape), why)
        return False
    return True
