"""Masked segment ops — the TPU replacement for torch_scatter.

The reference uses torch_scatter's scatter_add/scatter_mean
(reference: hydragnn/models/Base.py:18,375; EGCLStack.py:239-245;
utils/model/model.py:214-221). On TPU these lower to XLA scatter/gather which
fuse well; padding entries are handled by masks rather than dynamic shapes.

All functions take `num_segments` statically so XLA sees fixed shapes.

Trace vocabulary (PERF.md section 3; metadata only, the lowered program is
the same): the reductions here run under `jax.named_scope("aggregate")`,
node -> neighbour gathers under "neighbor_gather" (`neighbor_gather`), the
edge -> dense-slot layout conversion `ev[batch.nbr_edge]` under
"edge_gather" (`edge_gather`: within `geometry` the 3-wide gather of the
shifts, within a `conv_<i>` a conversion that conv makes itself), the
slot -> pair row gather of directional
message passing under "pair_gather" (`row_gather`). Convs call the gather
helpers instead of indexing, so a reduction of a device trace finds the same
names after a refactor.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

def _aggregate(fn):
    """Run `fn` under the "aggregate" scope. A fresh context manager a
    call: a shared `jax.named_scope` object keeps ONE saved name stack, so
    nesting it (`pna_aggregate` -> `segment_sum`) would restore the wrong
    one."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope("aggregate"):
            return fn(*args, **kwargs)
    return scoped


def neighbor_gather(x, index):
    """`x[index]` for a node -> neighbour gather (`index` is `batch.nbr`,
    `batch.senders` or `batch.receivers`), named for the trace."""
    with jax.named_scope("neighbor_gather"):
        return x[index]


def edge_gather(edge_values, batch):
    """Per-edge values [E, ...] into the dense neighbour layout [N, K, ...]
    (`edge_values[batch.nbr_edge]`), named for the trace. A row gather
    costs 12-20 ns a row on the v5e whatever its width or index pattern
    (PERF.md section 6), so what CAN be made in slot order is: lengths and
    every basis of them come from `ops/geometry.slot_vectors`, whose 3-wide
    gather of `edge_shifts` is the one call a step that PNAPlus, SchNet and
    DimeNet make. What still converts here: a true per-edge feature
    (`edge_attr`), a conv's own per-edge product (`edge_aggregate_*`), and
    edge-order inputs handed to a conv on a batch with tables.

    A batch that carries `edge_slot`, the inverse of the table
    (graphs/batch.build_neighbor_tables), gets the same values under a
    hand-written derivative: every real edge sits in exactly one slot, so
    the transpose of this gather is a gather too (`_slot_to_edge`), and the
    transpose of that one is this one again. No backward pass of any order
    then scatters; jax's own transpose is a scatter-add of N x K updates,
    serial on the chip, because XLA cannot know the index is injective.
    A batch without it (hand-built, an old pickle) indexes plainly."""
    if batch.edge_slot is None:
        with jax.named_scope("edge_gather"):
            return edge_values[batch.nbr_edge]
    return _edge_to_slot(edge_values, batch.nbr_edge, batch.nbr_mask,
                         batch.edge_slot, batch.edge_mask)


@jax.custom_vjp
def _edge_to_slot(edge_values, nbr_edge, nbr_mask, edge_slot, edge_mask):
    """`edge_values[nbr_edge]`, bit for bit in every slot, without reading
    one row N x K times: every padding slot of the table names edge E - 1
    (graphs/batch.build_neighbor_tables), and a gather whose indices all
    name one row runs at the pace of a scatter on the TPU. So a padding
    slot s gathers a row of its own, s mod E, and then takes by a select
    what the LAST slot read, which keeps the table's index: a batch carries
    `edge_slot` only where that slot is a padding slot
    (graphs/batch.build_neighbor_tables holds the rule). (Not
    `edge_values[E - 1]`: a second reader of `edge_values` made the
    compiler keep two layouts of it, 0.45 GiB more in the PNAPlus step.)"""
    with jax.named_scope("edge_gather"):
        n, k = nbr_edge.shape
        slot = (jax.lax.broadcasted_iota(jnp.int32, (n, k), 0) * k
                + jax.lax.broadcasted_iota(jnp.int32, (n, k), 1))
        keep = nbr_mask | (slot == n * k - 1)
        rows = edge_values[jnp.where(keep, nbr_edge,
                                     slot % edge_values.shape[0])]
        return jnp.where(_bcast(nbr_mask, rows), rows, rows[-1, -1])


@jax.custom_vjp
def _slot_to_edge(d_slot, nbr_edge, nbr_mask, edge_slot, edge_mask):
    """The transpose of `_edge_to_slot`, [N, K, ...] -> [E, ...], exact for
    any `d_slot`: a real edge reads its one slot, a padding edge reads
    nothing, and edge E - 1, at which every padding slot points, also takes
    the sum over those slots (one masked reduction, one row written). What
    a padding edge gathers is masked, so it gathers a slot of its own (e mod
    N x K) and not the table's 0: one row read by every padding edge costs
    a quarter more here too (PERF.md section 6, PR 29)."""
    with jax.named_scope("edge_gather"):
        # (node, k) indices, NOT a flat index into d_slot.reshape(N * K,
        # ...): XLA moves that reshape up through the elementwise producers
        # of d_slot, whose [N, F] -> [N, K, F] broadcasts then no longer
        # fuse (the PNAPlus step compiled to 15.1 GiB against 7.0: PR 29)
        n, k = nbr_edge.shape
        slot = jnp.where(edge_mask, edge_slot,
                         jnp.arange(edge_slot.shape[0]) % (n * k))
        d_edge = d_slot[slot // k, slot % k]
        d_edge = jnp.where(_bcast(edge_mask, d_edge), d_edge, 0)
        at_padding = jnp.sum(jnp.where(_bcast(nbr_mask, d_slot), 0, d_slot),
                             axis=(0, 1), dtype=d_slot.dtype)
        last = _bcast(jnp.arange(d_edge.shape[0]) == d_edge.shape[0] - 1,
                      d_edge)
        return jnp.where(last, d_edge + at_padding, d_edge)


def _transposes_of_each_other(f, g):
    """Both are linear in their first argument and save nothing but the
    index tables, which take no cotangent."""
    for fn, transpose in ((f, g), (g, f)):
        fn.defvjp(
            lambda x, *tables, fn=fn: (fn(x, *tables), tables),
            lambda tables, ct, transpose=transpose:
                (transpose(ct, *tables),) + (None,) * len(tables))


_transposes_of_each_other(_edge_to_slot, _slot_to_edge)


def row_gather(slot_values, nbr):
    """`slot_values[nbr]`: for every slot (i, a) of the dense layout the
    whole ROW of slot values of its neighbour j = nbr[i, a], [N, K, ...] ->
    [N, K, K, ...] (one contiguous block per gather index). The pair space
    of directional message passing (models/dimenet.py) is made of these;
    the gather, and its scatter-add transpose, run under "pair_gather"."""
    with jax.named_scope("pair_gather"):
        return slot_values[nbr]


def _accum_f32(data):
    """Mixed-precision accumulation policy
    (docs/mixed_precision.md): reduced-precision segment
    reductions accumulate in f32 and store back reduced — a bf16
    pairwise sum over a 30-neighbor radius-graph segment loses low bits
    at every add otherwise. Returns (upcast data, dtype to cast the
    result back to, or None for the f32/f64 no-op)."""
    if data.dtype in (jnp.bfloat16, jnp.float16):
        return data.astype(jnp.float32), data.dtype
    return data, None


@_aggregate
def segment_sum(data, segment_ids, num_segments, mask=None,
                indices_are_sorted=False):
    """`indices_are_sorted` is the static XLA hint for nondecreasing
    `segment_ids` (the pooling case: collate concatenates graphs in
    order, so `node_graph` is sorted by construction) — it lets the
    scatter lower to a segmented reduction instead of a general
    scatter-add. Only pass True when the ids really are nondecreasing;
    XLA is allowed to return garbage otherwise."""
    if mask is not None:
        data = jnp.where(_bcast(mask, data), data, 0.0)
    data, store_dtype = _accum_f32(data)
    out = jax.ops.segment_sum(data, segment_ids, num_segments,
                              indices_are_sorted=indices_are_sorted)
    return out if store_dtype is None else out.astype(store_dtype)


@_aggregate
def segment_count(segment_ids, num_segments, mask=None,
                  indices_are_sorted=False):
    ones = jnp.ones((segment_ids.shape[0],), jnp.float32)
    if mask is not None:
        ones = jnp.where(mask, ones, 0.0)
    return jax.ops.segment_sum(ones, segment_ids, num_segments,
                               indices_are_sorted=indices_are_sorted)


def segment_mean(data, segment_ids, num_segments, mask=None,
                 indices_are_sorted=False):
    total = segment_sum(data, segment_ids, num_segments, mask,
                        indices_are_sorted=indices_are_sorted)
    count = segment_count(segment_ids, num_segments, mask,
                          indices_are_sorted=indices_are_sorted)
    count = jnp.maximum(count, 1.0)
    return total / count.reshape(count.shape + (1,) * (total.ndim - 1))


@_aggregate
def segment_max(data, segment_ids, num_segments, mask=None, neutral=-1e30):
    if mask is not None:
        data = jnp.where(_bcast(mask, data), data, neutral)
    out = jax.ops.segment_max(data, segment_ids, num_segments)
    # segments with no real entries produce `neutral` (or -inf); clamp to 0
    return jnp.where(out <= neutral, 0.0, out)


@_aggregate
def segment_min(data, segment_ids, num_segments, mask=None, neutral=1e30):
    if mask is not None:
        data = jnp.where(_bcast(mask, data), data, neutral)
    out = jax.ops.segment_min(data, segment_ids, num_segments)
    return jnp.where(out >= neutral, 0.0, out)


def segment_std(data, segment_ids, num_segments, mask=None, eps=1e-5):
    """Per-segment standard deviation (PNA 'std' aggregator,
    reference: torch_geometric PNAConv used at hydragnn/models/PNAStack.py:28-51)."""
    mean = segment_mean(data, segment_ids, num_segments, mask)
    sq_mean = segment_mean(data * data, segment_ids, num_segments, mask)
    var = jnp.maximum(sq_mean - mean * mean, 0.0)
    return jnp.sqrt(var + eps)


def pna_stats_epilogue(s, sq, cnt, mn, mx, eps=1e-5):
    """(mean, min, max, std, degree) from the raw additive accumulators
    and extrema of `pna_aggregate`."""
    cnt_safe = jnp.maximum(cnt, 1.0)
    mean = s / cnt_safe
    var = jnp.maximum(sq / cnt_safe - mean * mean, 0.0)
    std = jnp.sqrt(var + eps)
    return mean, mn, mx, std, cnt[..., 0]


@_aggregate
def pna_aggregate(data, segment_ids, num_segments, mask=None, eps=1e-5):
    """Fused PNA aggregation -> (mean, min, max, std, degree).

    The additive statistics (sum, sum of squares, count) ride ONE scatter
    over a [E, 2F+1] concatenation instead of three separate [E, F]
    scatters — PNA's aggregation is HBM-bound on TPU, so collapsing the
    passes cuts the dominant memory traffic (reference semantics:
    torch_geometric PNAConv aggregators mean/min/max/std used at
    hydragnn/models/PNAStack.py:28-51)."""
    f = data.shape[-1]
    ones = jnp.ones(data.shape[:-1] + (1,), data.dtype)
    packed = jnp.concatenate([data, data * data, ones], axis=-1)
    packed_sum = segment_sum(packed, segment_ids, num_segments, mask)
    s, sq, cnt = (packed_sum[..., :f], packed_sum[..., f:2 * f],
                  packed_sum[..., 2 * f:])
    mn = segment_min(data, segment_ids, num_segments, mask)
    mx = segment_max(data, segment_ids, num_segments, mask)
    return pna_stats_epilogue(s, sq, cnt, mn, mx, eps)


@_aggregate
def neighbor_aggregate(h, nbr_mask, eps=1e-5):
    """PNA statistics over the dense neighbor-list layout
    (graphs.batch.with_neighbor_format): h is [N, K, F] per-slot messages,
    nbr_mask [N, K]. Pure axis reductions — no scatter, no segment ids —
    the layout of choice on TPU for bounded-degree radius graphs.

    Returns (mean, min, max, std, degree), matching `pna_aggregate`.
    """
    m = nbr_mask[:, :, None]
    cnt = jnp.sum(nbr_mask.astype(h.dtype), axis=1)
    cnt_safe = jnp.maximum(cnt, 1.0)[:, None]
    hm = jnp.where(m, h, 0.0)
    s = jnp.sum(hm, axis=1)
    sq = jnp.sum(hm * hm, axis=1)
    mean = s / cnt_safe
    var = jnp.maximum(sq / cnt_safe - mean * mean, 0.0)
    std = jnp.sqrt(var + eps)
    big = jnp.asarray(jnp.finfo(h.dtype).max, h.dtype)
    mn = jnp.min(jnp.where(m, h, big), axis=1)
    mn = jnp.where(cnt[:, None] > 0, mn, 0.0)
    mx = jnp.max(jnp.where(m, h, -big), axis=1)
    mx = jnp.where(cnt[:, None] > 0, mx, 0.0)
    return mean, mn, mx, std, cnt


@_aggregate
def neighbor_sum(h, nbr_mask):
    """Masked sum over the K axis of [N, K, ...] dense-layout messages.
    Reduced-precision inputs accumulate in f32 (the same policy as
    `segment_sum` — the dense layout is the moral equivalent of the
    scatter it replaces)."""
    m = nbr_mask.reshape(nbr_mask.shape + (1,) * (h.ndim - 2))
    masked, store_dtype = _accum_f32(jnp.where(m, h, 0.0))
    out = jnp.sum(masked, axis=1)
    return out if store_dtype is None else out.astype(store_dtype)


def neighbor_mean(h, nbr_mask):
    """Masked mean over the K axis of [N, K, ...] dense-layout messages."""
    cnt = jnp.sum(nbr_mask.astype(h.dtype), axis=1)
    cnt = cnt.reshape(cnt.shape + (1,) * (h.ndim - 2))
    return neighbor_sum(h, nbr_mask) / jnp.maximum(cnt, 1.0)


def edge_aggregate_sum(edge_values, batch):
    """Sum per-edge values into receiver nodes, using the dense
    neighbor-list layout when the batch carries one (gather by nbr_edge +
    masked K-axis reduction — no scatter in the forward pass, and none in
    the backward pass of that gather where the batch carries `edge_slot`)
    and the masked segment scatter otherwise. Drop-in for the edge->node
    aggregation step of any conv."""
    if batch.nbr_edge is not None:
        return neighbor_sum(edge_gather(edge_values, batch), batch.nbr_mask)
    return segment_sum(edge_values, batch.receivers, batch.num_nodes,
                       batch.edge_mask)


def filter_weighted_aggregate(h, w, batch):
    """SchNet CFConv aggregation: sum_{e: recv[e]=n} h[send[e]] * w[e]
    (models/schnet.py; reference: SCFStack.py:143-223 CFConv propagate).

    The layout is read from the filters: `w` in slot order ([N, K, F], made
    from slot-order lengths) meets `h[nbr]` where it stands and a masked
    K-axis reduction follows, no layout converted; `w` in edge order
    ([E, F]) meets `h[senders]`, and the product takes the `nbr_edge`
    gather on a batch with the tables, the masked segment scatter on the
    edge list."""
    if w.ndim == h.ndim + 1:
        return neighbor_sum(neighbor_gather(h, batch.nbr) * w,
                            batch.nbr_mask)
    if batch.nbr_edge is not None:
        return neighbor_sum(
            edge_gather(neighbor_gather(h, batch.senders) * w, batch),
            batch.nbr_mask)
    return segment_sum(neighbor_gather(h, batch.senders) * w,
                       batch.receivers, batch.num_nodes, batch.edge_mask)


def edge_aggregate_mean(edge_values, batch):
    """Mean counterpart of `edge_aggregate_sum`."""
    if batch.nbr_edge is not None:
        return neighbor_mean(edge_gather(edge_values, batch),
                             batch.nbr_mask)
    return segment_mean(edge_values, batch.receivers, batch.num_nodes,
                        batch.edge_mask)


def neighbor_softmax(logits, nbr_mask):
    """Masked softmax over the K axis ([N, K] or [N, K, H] logits) — the
    dense-layout equivalent of `segment_softmax`: attention weights over each
    node's in-edges with padding slots at exactly 0."""
    m = nbr_mask.reshape(nbr_mask.shape + (1,) * (logits.ndim - 2))
    neg = jnp.asarray(jnp.finfo(logits.dtype).min, logits.dtype)
    masked = jnp.where(m, logits, neg)
    mx = jnp.max(masked, axis=1, keepdims=True)
    # select BEFORE exp: on all-masked rows mx is finfo.min, and
    # exp(logits - mx) would overflow to inf — harmless forward, but the
    # where-gradient multiplies inf by a zero cotangent -> NaN
    z = jnp.where(m, logits - jax.lax.stop_gradient(mx), 0.0)
    e = jnp.where(m, jnp.exp(z), 0.0)
    denom = jnp.sum(e, axis=1, keepdims=True)
    return e / jnp.maximum(denom, 1e-16)


def segment_softmax(logits, segment_ids, num_segments, mask=None):
    """Numerically-stable softmax within segments (GAT attention,
    reference: torch_geometric GATConv used at hydragnn/models/GATStack.py:29)."""
    if mask is not None:
        logits = jnp.where(_bcast(mask, logits), logits, -1e30)
    seg_max = jax.ops.segment_max(logits, segment_ids, num_segments)
    seg_max = jnp.where(seg_max <= -1e30, 0.0, seg_max)
    shifted = logits - seg_max[segment_ids]
    exp = jnp.exp(shifted)
    if mask is not None:
        exp = jnp.where(_bcast(mask, exp), exp, 0.0)
    denom = jax.ops.segment_sum(exp, segment_ids, num_segments)
    denom = jnp.maximum(denom, 1e-16)
    return exp / denom[segment_ids]


def global_mean_pool(node_feats, node_graph, num_graphs, node_mask):
    """Masked graph-level mean pooling
    (reference: torch_geometric global_mean_pool at hydragnn/models/Base.py:320-323).

    `node_graph` ids are nondecreasing by construction — collate
    concatenates graphs in order with padding nodes (id G-1) at the tail
    — so the pools pass the static `indices_are_sorted` hint through to
    `jax.ops.segment_*` (tests/test_graph_core.py pins hinted == unhinted)."""
    return segment_mean(node_feats, node_graph, num_graphs, node_mask,
                        indices_are_sorted=True)


def global_sum_pool(node_feats, node_graph, num_graphs, node_mask):
    return segment_sum(node_feats, node_graph, num_graphs, node_mask,
                       indices_are_sorted=True)


def degree(receivers, num_nodes, edge_mask=None):
    """In-degree per node (reference: torch_geometric.utils.degree used by
    hydragnn/utils/model/model.py:141-160 for PNA histograms)."""
    return segment_count(receivers, num_nodes, edge_mask)


def _bcast(mask, data):
    """Broadcast a [K] mask against [K, ...] data."""
    return mask.reshape(mask.shape + (1,) * (data.ndim - mask.ndim))
