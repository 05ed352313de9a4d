"""Graph parallelism: message passing for graphs too large for one chip.

The reference has no analogue — its graphs are small (atoms <= a few
hundred) and scale comes from data parallelism over millions of graphs
(SURVEY.md §2.6, §5.7). On TPU the framework's "long context" axis is graph
SIZE: a single periodic supercell or mesoscale structure can exceed one
chip's HBM. This module is the GNN analogue of sequence/context parallelism:

- **Edge-sharded mode** (`edge_sharded_aggregate`): node features are
  replicated over the ``graph`` mesh axis, the edge set is split evenly
  across devices; each device computes messages for its edge shard and a
  partial segment-sum, then one `psum` over ICI produces the full
  aggregation. Cuts edge memory (the dominant term: E ~ 30x N for radius
  graphs) by the axis size. This is the all-to-all/Ulysses-style layout.

- **Ring mode** (`ring_aggregate`): node features are sharded too —
  device d owns node block d and all edges whose *receiver* lies in block d,
  bucketed by the sender's block. Sender blocks rotate around the ring with
  `ppermute` (one ICI hop per step, D steps); at step k device d holds block
  (d - k) mod D and processes exactly the bucket expecting that block.
  Nothing is ever replicated, and receiver-side aggregation stays local —
  the ring-attention layout with segment-sum in place of softmax-attention.
  Per-edge softmax (GAT-style) still works: all edges of a receiver live on
  its owner, so the normalization is local.

Both modes compute bitwise the same aggregation as the single-device
`ops.segment.segment_sum` (up to float reorder); see
tests/test_graph_parallel.py.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


class RingEdgeBuckets(NamedTuple):
    """Host-built, device-stacked edge partition for ring mode.

    All arrays lead with [D, D, Eb]: device axis, ring-step axis, padded
    per-bucket edge count. ``send_local``/``recv_local`` are block-local
    indices (0..block-1); ``mask`` marks real edges.
    """
    send_local: np.ndarray   # [D, D, Eb] int32 index into the rotating block
    recv_local: np.ndarray   # [D, D, Eb] int32 index into the local block
    edge_id: np.ndarray      # [D, D, Eb] int32 index into the original edge
    mask: np.ndarray         # [D, D, Eb] bool
    block: int               # node block size (padded N / D)


def partition_nodes(num_nodes: int, n_shards: int) -> int:
    """Block size of the contiguous node partition (last block padded)."""
    return -(-num_nodes // n_shards)


def build_ring_buckets(senders: np.ndarray, receivers: np.ndarray,
                       num_nodes: int, n_shards: int,
                       edge_mask: Optional[np.ndarray] = None,
                       pad_multiple: int = 8) -> RingEdgeBuckets:
    """Bucket edges for ring mode: bucket[d, k] holds the edges whose
    receiver is in node block d and whose sender is in block (d - k) mod D —
    the block device d is holding after k ring rotations."""
    senders = np.asarray(senders)
    receivers = np.asarray(receivers)
    block = partition_nodes(num_nodes, n_shards)
    if edge_mask is None:
        edge_mask = np.ones(senders.shape, bool)
    real = np.asarray(edge_mask, bool)
    sb = senders // block
    rb = receivers // block
    step = (rb - sb) % n_shards  # ring step at which the sender block arrives

    buckets = [[None] * n_shards for _ in range(n_shards)]
    eb = 0
    for d in range(n_shards):
        for k in range(n_shards):
            sel = np.nonzero(real & (rb == d) & (step == k))[0]
            buckets[d][k] = sel
            eb = max(eb, len(sel))
    eb = max(pad_multiple, -(-eb // pad_multiple) * pad_multiple)

    shape = (n_shards, n_shards, eb)
    send_local = np.zeros(shape, np.int32)
    recv_local = np.zeros(shape, np.int32)
    edge_id = np.zeros(shape, np.int32)
    mask = np.zeros(shape, bool)
    for d in range(n_shards):
        for k in range(n_shards):
            sel = buckets[d][k]
            n = len(sel)
            send_local[d, k, :n] = senders[sel] % block
            recv_local[d, k, :n] = receivers[sel] % block
            edge_id[d, k, :n] = sel
            mask[d, k, :n] = True
    return RingEdgeBuckets(send_local, recv_local, edge_id, mask, block)


def shard_node_array(arr: jnp.ndarray, n_shards: int) -> jnp.ndarray:
    """[N, ...] -> device-stacked [D, block, ...] with zero padding."""
    block = partition_nodes(arr.shape[0], n_shards)
    pad = block * n_shards - arr.shape[0]
    if pad:
        arr = jnp.concatenate(
            [arr, jnp.zeros((pad,) + arr.shape[1:], arr.dtype)])
    return arr.reshape((n_shards, block) + arr.shape[1:])


def shard_edge_arrays(n_shards: int, *arrays, pad_multiple: int = 8):
    """Split edge arrays evenly into [D, Eb, ...] shards (edge-sharded mode).

    Returns (mask, *shards): mask marks real edges after padding.
    """
    e = arrays[0].shape[0]
    eb = partition_nodes(e, n_shards)
    eb = -(-eb // pad_multiple) * pad_multiple
    pad = eb * n_shards - e
    mask = np.ones((e,), bool)
    out = []
    for a in (mask,) + arrays:
        a = np.asarray(a)
        if pad:
            a = np.concatenate(
                [a, np.zeros((pad,) + a.shape[1:], a.dtype)])
        out.append(a.reshape((n_shards, eb) + a.shape[1:]))
    return tuple(out)


def edge_sharded_aggregate(message_fn: Callable, x: jnp.ndarray,
                           send_shard: jnp.ndarray, recv_shard: jnp.ndarray,
                           mask_shard: jnp.ndarray, num_nodes: int,
                           axis_name: str = "graph",
                           edge_attr_shard: Optional[jnp.ndarray] = None):
    """Inside shard_map: x replicated [N, F]; edges sharded [Eb].

    message_fn(x_i, x_j, edge_attr) -> [Eb, Fm]. Returns the full [N, Fm]
    aggregation on every device (one psum over the graph axis).
    """
    xi = x[recv_shard]
    xj = x[send_shard]
    m = message_fn(xi, xj, edge_attr_shard)
    m = jnp.where(mask_shard[:, None], m, 0.0)
    partial = jax.ops.segment_sum(m, recv_shard, num_nodes)
    return lax.psum(partial, axis_name)


def ring_aggregate(message_fn: Callable, x_block: jnp.ndarray,
                   buckets: RingEdgeBuckets, axis_name: str = "graph",
                   edge_attr_buckets: Optional[jnp.ndarray] = None):
    """Inside shard_map: x sharded [block, F]; edges pre-bucketed by sender
    block (build_ring_buckets). D ring steps, each overlapping one ppermute
    hop with one bucket's message computation. Returns the local [block, Fm]
    aggregation (receiver-partitioned — no final collective needed).
    """
    # ring length == mesh axis size == leading dim of the per-sender-block
    # bucket stack; read it from the static shape (ppermute needs a static
    # permutation)
    d = buckets.send_local.shape[0]
    perm = [(i, (i + 1) % d) for i in range(d)]
    block = x_block.shape[0]

    def step(carry, bucket):
        blk, agg = carry
        if edge_attr_buckets is None:
            send_l, recv_l, mask = bucket
            ea = None
        else:
            send_l, recv_l, mask, ea = bucket
        xj = blk[send_l]
        xi = x_block[recv_l]
        m = message_fn(xi, xj, ea)
        m = jnp.where(mask[:, None], m, 0.0)
        agg = agg + jax.ops.segment_sum(m, recv_l, block)
        blk = lax.ppermute(blk, axis_name, perm)
        return (blk, agg), None

    probe = message_fn(
        x_block[:1], x_block[:1],
        None if edge_attr_buckets is None else edge_attr_buckets[0, :1])
    agg0 = jnp.zeros((block, probe.shape[-1]), probe.dtype)
    # the carry accumulator is device-varying (it sums varying messages);
    # mark the literal zeros as such or scan's carry typecheck rejects it
    if hasattr(lax, "pcast"):
        agg0 = lax.pcast(agg0, (axis_name,), to="varying")
    elif hasattr(lax, "pvary"):
        agg0 = lax.pvary(agg0, (axis_name,))
    if edge_attr_buckets is None:
        xs = (buckets.send_local, buckets.recv_local, buckets.mask)
    else:
        xs = (buckets.send_local, buckets.recv_local, buckets.mask,
              edge_attr_buckets)
    (_, agg), _ = lax.scan(step, (x_block, agg0), xs)
    return agg


def make_ring_layer(mesh: Mesh, message_fn: Callable,
                    update_fn: Optional[Callable] = None,
                    axis_name: str = "graph"):
    """jit-able full layer: (x_sharded [D, block, F], buckets) -> updated
    node features, nodes staying sharded over the ``graph`` axis.

    update_fn(x_block, agg_block) -> new x_block (defaults to returning the
    aggregation — a plain sum-aggregate GNN layer).
    """
    upd = update_fn or (lambda x, agg: agg)

    def per_device(x, send_l, recv_l, mask):
        # sharded leading (device) axes arrive as size-1 dims — drop them
        x, send_l, recv_l, mask = (a[0] for a in (x, send_l, recv_l, mask))
        b = RingEdgeBuckets(send_l, recv_l, None, mask, x.shape[0])
        agg = ring_aggregate(message_fn, x, b, axis_name)
        return upd(x, agg)[None]

    specs = P(axis_name)
    return jax.jit(shard_map(
        per_device, mesh=mesh,
        in_specs=(specs, specs, specs, specs),
        out_specs=specs))


def make_edge_sharded_layer(mesh: Mesh, message_fn: Callable,
                            num_nodes: int,
                            update_fn: Optional[Callable] = None,
                            axis_name: str = "graph"):
    """jit-able full layer for edge-sharded mode: x replicated, edges
    device-stacked [D, Eb]."""
    upd = update_fn or (lambda x, agg: agg)

    def per_device(x, send, recv, mask):
        send, recv, mask = send[0], recv[0], mask[0]
        agg = edge_sharded_aggregate(
            message_fn, x, send, recv, mask, num_nodes, axis_name)
        return upd(x, agg)

    return jax.jit(shard_map(
        per_device, mesh=mesh,
        in_specs=(P(), P(axis_name), P(axis_name), P(axis_name)),
        out_specs=P()))
