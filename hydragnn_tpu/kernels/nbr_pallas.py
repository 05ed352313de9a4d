"""Fused neighbor-gather -> PNA-statistics Pallas kernel (r4 verdict
Next #2).

docs/MFU_ANALYSIS.md attributes the CI-shape step's 4x above-roofline
residual most plausibly to the materialized dense-neighbor tensor: the
XLA lowering of

    h = proj_i[:, None, :] + proj_j[nbr]          # [N, K, F] in HBM
    mean, mn, mx, sd, deg = neighbor_aggregate(h, nbr_mask)

round-trips ~K x the node features through HBM (reference analogue of
the message materialization: hydragnn/models/EGCLStack.py:225-236 /
Base.py:303-347). This kernel never materializes [N, K, F]: per node
tile it reconstructs each neighbor slot with a one-hot x proj_j matmul
(the gather becomes MXU work instead of dynamic-slice chains) and keeps
the five PNA statistics as running accumulators in VMEM.

Trade: +2*K*N^2*F matmul FLOPs per layer in exchange for removing the
[N, K, F] HBM traffic. Whether that wins is an ON-CHIP question
(the r3 scatter kernel lost end-to-end despite a microbench win —
ops/segment.py decision record), so:

  * default OFF; HYDRAGNN_PALLAS_NBR=1 enables it,
  * bench.py exposes it for the up-window A/B (BENCH_NBR_PALLAS),
  * applicability is bounded by proj_j fitting VMEM (the one-hot
    contraction reads all of it per tile): callers fall back to the XLA
    path above ~4 MB, and the backward recomputes through the XLA
    formulation (remat-style — the fused forward's memory saving is
    what the backward trades back in FLOPs).

Equivalence against ops/segment.neighbor_aggregate is asserted in
tests/test_kernels.py (interpret mode on CPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

# proj_j bigger than this stays on the XLA path: the kernel holds the
# whole projection in VMEM for the one-hot contraction (v5e: 16 MB/core)
VMEM_BYTES_LIMIT = 4 * 1024 * 1024


def _kernel(pi_ref, pj_ref, nbr_ref, mask_ref,
            mean_ref, mn_ref, mx_ref, sd_ref, deg_ref, *, eps: float):
    pi = pi_ref[...]                       # [TN, F]
    pj = pj_ref[...]                       # [N, F]
    idx = nbr_ref[...]                     # [TN, K] int32
    msk = mask_ref[...]                    # [TN, K] bool
    tn, f = pi.shape
    n = pj.shape[0]
    k = idx.shape[1]
    dtype = pi.dtype
    big = jnp.asarray(jnp.finfo(dtype).max, dtype)

    iota_n = lax.broadcasted_iota(jnp.int32, (1, n), 1)  # [1, N]
    acc_s = jnp.zeros((tn, f), dtype)
    acc_sq = jnp.zeros((tn, f), dtype)
    acc_mn = jnp.full((tn, f), big, dtype)
    acc_mx = jnp.full((tn, f), -big, dtype)
    for kk in range(k):                    # K is small and static: unroll
        onehot = (idx[:, kk:kk + 1] == iota_n).astype(dtype)   # [TN, N]
        # HIGHEST: the default MXU precision rounds f32 proj_j to bf16,
        # and the "gather" would return other values than proj_j[nbr]
        # (kernels/segment_pallas.py has the on-chip numbers)
        gath = jax.lax.dot_general(
            onehot, pj, (((1,), (0,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32).astype(dtype)
        hk = gath + pi                                          # [TN, F]
        mk = msk[:, kk:kk + 1].astype(dtype)                    # [TN, 1]
        acc_s = acc_s + hk * mk
        acc_sq = acc_sq + hk * hk * mk
        on = msk[:, kk:kk + 1]
        acc_mn = jnp.minimum(acc_mn, jnp.where(on, hk, big))
        acc_mx = jnp.maximum(acc_mx, jnp.where(on, hk, -big))

    cnt = jnp.sum(msk.astype(dtype), axis=1, keepdims=True)     # [TN, 1]
    cnt_safe = jnp.maximum(cnt, 1.0)
    mean = acc_s / cnt_safe
    var = jnp.maximum(acc_sq / cnt_safe - mean * mean, 0.0)
    has = cnt > 0
    mean_ref[...] = mean
    sd_ref[...] = jnp.sqrt(var + eps)
    mn_ref[...] = jnp.where(has, acc_mn, 0.0)
    mx_ref[...] = jnp.where(has, acc_mx, 0.0)
    deg_ref[...] = cnt


def _reference(proj_i, proj_j, nbr, nbr_mask, eps):
    from ..ops.segment import neighbor_aggregate
    h = proj_i[:, None, :] + proj_j[nbr]
    return neighbor_aggregate(h, nbr_mask, eps=eps)


def _fused_call(proj_i, proj_j, nbr, nbr_mask, block_n, interpret, eps):
    n_in, f = proj_i.shape
    k = nbr.shape[1]
    block_n = min(block_n, n_in)
    # pad the tiled axis up to a block multiple (bench batches pad nodes
    # to N+8, not a block multiple): padded rows carry mask=False and
    # index 0, and their output rows are sliced off below — degenerating
    # to one whole-array tile would blow the per-k one-hot out of VMEM
    n = -(-n_in // block_n) * block_n
    if n != n_in:
        pad = n - n_in
        proj_i = jnp.pad(proj_i, ((0, pad), (0, 0)))
        nbr = jnp.pad(nbr, ((0, pad), (0, 0)))
        nbr_mask = jnp.pad(nbr_mask, ((0, pad), (0, 0)))
    grid = (n // block_n,)
    out_shape = [jax.ShapeDtypeStruct((n, f), proj_i.dtype)
                 for _ in range(4)] + \
        [jax.ShapeDtypeStruct((n, 1), proj_i.dtype)]
    node_spec = pl.BlockSpec((block_n, f), lambda i: (i, 0))
    outs = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=grid,
        in_specs=[node_spec,
                  pl.BlockSpec(proj_j.shape,
                               lambda i: (0, 0)),   # whole proj_j
                  pl.BlockSpec((block_n, k), lambda i: (i, 0)),
                  pl.BlockSpec((block_n, k), lambda i: (i, 0))],
        out_specs=[node_spec, node_spec, node_spec, node_spec,
                   pl.BlockSpec((block_n, 1), lambda i: (i, 0))],
        out_shape=out_shape,
        interpret=interpret,
    )(proj_i, proj_j, nbr, nbr_mask)
    mean, mn, mx, sd, deg = outs
    return (mean[:n_in], mn[:n_in], mx[:n_in], sd[:n_in],
            deg[:n_in, 0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def fused_neighbor_aggregate(proj_i, proj_j, nbr, nbr_mask,
                             block_n=128, interpret=False, eps=1e-5):
    """(mean, min, max, std, degree) of proj_i[:,None,:] + proj_j[nbr]
    without materializing [N, K, F] — semantics identical to
    ops/segment.neighbor_aggregate on that sum."""
    return _fused_call(proj_i, proj_j, nbr, nbr_mask, block_n, interpret,
                       eps)


def _fwd(proj_i, proj_j, nbr, nbr_mask, block_n, interpret, eps):
    out = _fused_call(proj_i, proj_j, nbr, nbr_mask, block_n, interpret,
                      eps)
    return out, (proj_i, proj_j, nbr, nbr_mask)


def _bwd(block_n, interpret, eps, res, cots):
    # remat-style backward: re-derive the gradients through the XLA
    # formulation (materializes [N, K, F] for the backward only — the
    # same trade jax.checkpoint makes)
    proj_i, proj_j, nbr, nbr_mask = res
    _, vjp = jax.vjp(lambda pi, pj: _reference(pi, pj, nbr, nbr_mask, eps),
                     proj_i, proj_j)
    dpi, dpj = vjp(cots)
    return dpi, dpj, None, None


fused_neighbor_aggregate.defvjp(_fwd, _bwd)


# HYDRAGNN_PALLAS_NBR, resolved ONCE (at step construction via
# resolve_nbr_pallas_flag(refresh=True), or lazily on first trace) and
# frozen thereafter. The old trace-time os.environ read meant a toggle
# after the step compiled silently did nothing, and any unrecognized
# value (a typo) enabled the kernel (r5 advisor, convs.py:218).
_RESOLVED_FLAG = None


def resolve_nbr_pallas_flag(refresh: bool = False) -> bool:
    """Resolve HYDRAGNN_PALLAS_NBR to a pinned boolean. Only explicit
    truthy values ('1'/'true'/'on') enable the kernel. Step constructors
    call this with refresh=True so the decision is made at
    step-construction time, not at trace time."""
    global _RESOLVED_FLAG
    if _RESOLVED_FLAG is None or refresh:
        from ..utils.envflags import env_strict_flag
        _RESOLVED_FLAG = env_strict_flag("HYDRAGNN_PALLAS_NBR", False)
    return _RESOLVED_FLAG


def nbr_pallas_enabled(proj_j_shape, dtype, edge_terms: bool = False) -> bool:
    """Flag on AND the kernel applies: no per-edge encoder terms in the
    message (`edge_terms` — the kernel only forms proj_i + proj_j[nbr])
    and proj_j fits the VMEM bound. A flag that is on while the kernel is
    not taken is logged (kernels.kernel_not_taken), never silent."""
    if not resolve_nbr_pallas_flag():
        return False
    from . import kernel_not_taken
    why = None
    nbytes = (proj_j_shape[0] * proj_j_shape[1]
              * jnp.dtype(dtype).itemsize)
    if edge_terms:
        why = "the conv adds per-edge encoder terms (edge_dim/rbf)"
    elif nbytes > VMEM_BYTES_LIMIT:
        why = (f"proj_j is {nbytes} bytes, over the {VMEM_BYTES_LIMIT}-byte "
               "whole-array VMEM bound")
    if why is not None:
        kernel_not_taken("HYDRAGNN_PALLAS_NBR", "nbr_pallas",
                         tuple(proj_j_shape), why)
        return False
    return True
