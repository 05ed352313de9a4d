"""Static-shape padded graph batching for TPU/XLA.

The reference (HydraGNN) relies on PyG's dynamic `Batch.from_data_list`
(reference: hydragnn/preprocess/load_data.py:160) which produces ragged,
shape-varying batches. XLA compiles one program per shape, so this module
instead provides a jraph-style `GraphBatch` with explicit padding:

* the **last graph slot** is the padding graph,
* the **last node slot** is the padding node,
* padding edges connect the padding node to itself,
* boolean masks mark real vs padding entries.

Bucketing (`BucketSpec`) rounds batch shapes up to a small set of sizes so
recompilation is bounded while padding waste stays low.
"""
from __future__ import annotations

import math
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np
import jax.numpy as jnp
from flax import struct


@struct.dataclass
class GraphBatch:
    """A fixed-shape batch of graphs.

    Shapes: N = padded node count, E = padded edge count, G = padded graph
    count. All arrays are dense; `*_mask` distinguish real entries.

    Label packing mirrors the reference's flat ``data.y`` + ``y_loc`` offset
    table (reference: hydragnn/preprocess/graph_samples_checks_and_updates.py:237-278)
    but with static per-head offsets: ``y_graph`` concatenates all graph-level
    targets per graph, ``y_node`` concatenates all node-level targets per node.
    """

    x: jnp.ndarray            # [N, F] node input features
    pos: jnp.ndarray          # [N, 3] positions
    senders: jnp.ndarray      # [E] int32, edge source node index
    receivers: jnp.ndarray    # [E] int32, edge destination node index
    node_graph: jnp.ndarray   # [N] int32, graph id of each node
    node_mask: jnp.ndarray    # [N] bool
    edge_mask: jnp.ndarray    # [E] bool
    graph_mask: jnp.ndarray   # [G] bool
    y_graph: Optional[jnp.ndarray] = None   # [G, Dg] packed graph targets
    y_node: Optional[jnp.ndarray] = None    # [N, Dn] packed node targets
    edge_attr: Optional[jnp.ndarray] = None  # [E, Fe]
    edge_shifts: Optional[jnp.ndarray] = None  # [E, 3] PBC displacement shifts
    cell: Optional[jnp.ndarray] = None      # [G, 3, 3] lattice (PBC datasets)
    energy: Optional[jnp.ndarray] = None    # [G, 1] reference energies (E-F training)
    forces: Optional[jnp.ndarray] = None    # [N, 3] reference forces
    # triplet indices for directional message passing (DimeNet) — computed on
    # the host by graphs.triplets.add_triplets; indices into the edge arrays
    idx_kj: Optional[jnp.ndarray] = None    # [T] edge index of (k->j)
    idx_ji: Optional[jnp.ndarray] = None    # [T] edge index of (j->i)
    triplet_mask: Optional[jnp.ndarray] = None  # [T] bool
    # fixed-degree neighbor-list layout (with_neighbor_format): aggregation
    # becomes a dense [N, K, F] gather + axis reduction, no scatter in the
    # forward pass — the TPU-native alternative to segment ops for
    # bounded-degree graphs. The backward pass of the edge -> slot gather
    # is a gather too where the batch carries `edge_slot`
    # (ops/segment.edge_gather); the transposes of the node -> slot gathers
    # are true many-to-one sums and still scatter
    nbr: Optional[jnp.ndarray] = None        # [N, K] int32 sender of slot k
    nbr_edge: Optional[jnp.ndarray] = None   # [N, K] int32 edge id of slot k
    nbr_mask: Optional[jnp.ndarray] = None   # [N, K] bool
    # inverse of nbr_edge: the flat slot (receiver * K + rank) of every real
    # edge, 0 for a padding edge (never read: masked by edge_mask). Present
    # ONLY beside tables whose last slot [N - 1, K - 1] is a padding slot
    # (mask False, index E - 1), from which ops/segment.edge_gather reads
    # the padding value: build_neighbor_tables, the one producer, gives
    # None otherwise
    edge_slot: Optional[jnp.ndarray] = None  # [E] int32
    # sampled giant-graph training (preprocess/sampling.py,
    # docs/sampling.md): node slots are one k-hop computation graph laid
    # out [seeds | hop1 | ... | padding]; the loss is taken over seeds
    # only, and slots served from the historical-embedding cache carry
    # stale per-layer states instead of expanding further
    seed_mask: Optional[jnp.ndarray] = None     # [N] bool, loss mask
    node_global: Optional[jnp.ndarray] = None   # [N] int32 global node id
    hist_mask: Optional[jnp.ndarray] = None     # [N] bool, hist-served slot
    refresh_upto: Optional[jnp.ndarray] = None  # [N] int32, deepest hist
    # layer this slot may refresh (-1 = none; loader-deduplicated so at
    # most one slot per global id qualifies — scatter stays deterministic)
    hist_states: Optional[jnp.ndarray] = None   # [L-1, N, H] stale states
    # multi-dataset ("GFM") mixture training (parallel/multidataset.py,
    # docs/gfm.md): which member dataset each graph slot came from.
    # Padding slots carry -1 so they match no head even before the
    # graph/node masks apply. When present, multihead_loss restricts each
    # head's loss mask to its own dataset's graphs (head-masked multi-task
    # step) — the mixture changes the DATA, never the compiled program.
    dataset_id: Optional[jnp.ndarray] = None    # [G] int32, -1 = padding

    @property
    def num_nodes(self) -> int:
        return self.x.shape[0]

    @property
    def num_edges(self) -> int:
        return self.senders.shape[0]

    @property
    def num_graphs(self) -> int:
        return self.graph_mask.shape[0]

    def replace(self, **kw) -> "GraphBatch":  # convenience alias
        return struct.dataclasses.replace(self, **kw)

    def count_real_graphs(self) -> jnp.ndarray:
        return jnp.sum(self.graph_mask.astype(jnp.int32))

    def count_real_nodes(self) -> jnp.ndarray:
        return jnp.sum(self.node_mask.astype(jnp.int32))


class GraphSample:
    """Host-side (numpy) single graph, pre-batching.

    The analogue of a PyG ``Data`` object (torch_geometric.data.Data in the
    reference), but a plain numpy container so the data pipeline never touches
    jax until collation.
    """

    __slots__ = (
        "x", "pos", "senders", "receivers", "edge_attr", "edge_shifts",
        "y_graph", "y_node", "cell", "energy", "forces", "extras",
    )

    def __init__(
        self,
        x: np.ndarray,
        pos: np.ndarray,
        senders: np.ndarray,
        receivers: np.ndarray,
        edge_attr: Optional[np.ndarray] = None,
        edge_shifts: Optional[np.ndarray] = None,
        y_graph: Optional[np.ndarray] = None,
        y_node: Optional[np.ndarray] = None,
        cell: Optional[np.ndarray] = None,
        energy: Optional[np.ndarray] = None,
        forces: Optional[np.ndarray] = None,
        **extras: Any,
    ):
        self.x = np.asarray(x, dtype=np.float32)
        if self.x.ndim == 1:
            self.x = self.x[:, None]
        self.pos = np.asarray(pos, dtype=np.float32)
        self.senders = np.asarray(senders, dtype=np.int32)
        self.receivers = np.asarray(receivers, dtype=np.int32)
        self.edge_attr = None if edge_attr is None else np.asarray(
            edge_attr, dtype=np.float32)
        if self.edge_attr is not None and self.edge_attr.ndim == 1:
            self.edge_attr = self.edge_attr[:, None]
        self.edge_shifts = None if edge_shifts is None else np.asarray(
            edge_shifts, dtype=np.float32)
        self.y_graph = None if y_graph is None else np.atleast_1d(
            np.asarray(y_graph, dtype=np.float32)).reshape(-1)
        self.y_node = None if y_node is None else np.asarray(
            y_node, dtype=np.float32)
        if self.y_node is not None and self.y_node.ndim == 1:
            self.y_node = self.y_node[:, None]
        self.cell = None if cell is None else np.asarray(cell, dtype=np.float32)
        self.energy = None if energy is None else np.atleast_1d(
            np.asarray(energy, dtype=np.float32)).reshape(-1)
        self.forces = None if forces is None else np.asarray(
            forces, dtype=np.float32).reshape(-1, 3)
        self.extras = extras

    @property
    def num_nodes(self) -> int:
        return self.x.shape[0]

    @property
    def num_edges(self) -> int:
        return self.senders.shape[0]


def _round_up(value: int, multiple: int) -> int:
    return int(math.ceil(value / multiple) * multiple)


class BucketSpec:
    """Rounds (n_node, n_edge, n_graph) to a bounded set of shapes.

    Node/edge budgets are rounded up to the next power-of-two-ish bucket
    (1, 1.5, 2, 3, 4, 6, 8, ...) times ``multiple`` so that the number of
    distinct compiled programs stays O(log(max_size)) while padding waste
    stays under ~33%.
    """

    def __init__(self, multiple: int = 64):
        self.multiple = multiple

    def bucket(self, n: int) -> int:
        n = max(n, 1)
        m = self.multiple
        target = _round_up(n, m)
        # power-of-two with half-steps
        p = m
        while p < target:
            if int(p * 1.5) >= target and (p * 3) % 2 == 0:
                return int(p * 1.5)
            p *= 2
        return p

    def shapes(self, n_node: int, n_edge: int, n_graph: int) -> Tuple[int, int, int]:
        return (self.bucket(n_node + 1), self.bucket(n_edge + 1), n_graph + 1)


# optional GraphSample fields whose presence/width the padded buffers take
# from samples[0] — a mixed batch must fail up front, not mid-fill
_COLLATE_OPTIONAL_FIELDS = ("edge_attr", "edge_shifts", "y_graph", "y_node",
                            "cell", "energy", "forces")


def _validate_field_homogeneity(samples: Sequence[GraphSample]) -> None:
    """Every sample must carry the same field schema as samples[0]: the
    fill loop sizes the padded buffers from samples[0] only, so a mixed
    list (e.g. some samples missing edge_attr/forces) would either crash
    mid-fill with an opaque broadcast error or silently drop the field
    for the whole batch. Raise a clear per-field error instead."""
    ref = samples[0]
    for name in _COLLATE_OPTIONAL_FIELDS:
        want = getattr(ref, name) is not None
        for i, s in enumerate(samples):
            if (getattr(s, name) is not None) != want:
                a, b = ("present", "missing") if want else ("missing",
                                                           "present")
                raise ValueError(
                    f"collate: field '{name}' is {a} on sample 0 but {b} "
                    f"on sample {i} — all samples in a batch must share "
                    "one field schema (fill or drop the field "
                    "consistently across the dataset)")
    dims = [("x", lambda s: s.x.shape[1])]
    if ref.edge_attr is not None:
        dims.append(("edge_attr", lambda s: s.edge_attr.shape[1]))
    if ref.y_graph is not None:
        dims.append(("y_graph", lambda s: s.y_graph.shape[0]))
    if ref.y_node is not None:
        dims.append(("y_node", lambda s: s.y_node.shape[1]))
    for name, dim in dims:
        want_d = dim(ref)
        for i, s in enumerate(samples):
            if dim(s) != want_d:
                raise ValueError(
                    f"collate: field '{name}' has width {want_d} on "
                    f"sample 0 but {dim(s)} on sample {i} — all samples "
                    "in a batch must share one feature/label width")


def collate(
    samples: Sequence[GraphSample],
    n_node: Optional[int] = None,
    n_edge: Optional[int] = None,
    n_graph: Optional[int] = None,
    bucket: Optional[BucketSpec] = None,
    np_out: bool = False,
) -> GraphBatch:
    """Concatenate samples and pad to (n_node, n_edge, n_graph).

    At least one padding graph and one padding node are always present
    (jraph ``pad_with_graphs`` convention).
    """
    if not samples:
        raise ValueError("collate: at least one sample is required (the "
                         "loader's empty-shard path pads a proto sample)")
    _validate_field_homogeneity(samples)
    tot_n = sum(s.num_nodes for s in samples)
    tot_e = sum(s.num_edges for s in samples)
    ng = len(samples)
    if bucket is None and (n_node is None or n_edge is None):
        bucket = BucketSpec()
    if n_node is None or n_edge is None or n_graph is None:
        bn, be, bg = bucket.shapes(tot_n, tot_e, ng)
        n_node = n_node or bn
        n_edge = n_edge or be
        n_graph = n_graph or bg
    if tot_n >= n_node or ng >= n_graph or tot_e > n_edge:
        raise ValueError(
            f"batch ({tot_n} nodes, {tot_e} edges, {ng} graphs) does not fit "
            f"padded shape ({n_node}, {n_edge}, {n_graph}); one padding "
            f"node/graph slot is required")

    fdim = samples[0].x.shape[1]
    x = np.zeros((n_node, fdim), np.float32)
    pos = np.zeros((n_node, 3), np.float32)
    senders = np.full((n_edge,), n_node - 1, np.int32)
    receivers = np.full((n_edge,), n_node - 1, np.int32)
    node_graph = np.full((n_node,), n_graph - 1, np.int32)
    node_mask = np.zeros((n_node,), bool)
    edge_mask = np.zeros((n_edge,), bool)
    graph_mask = np.zeros((n_graph,), bool)
    graph_mask[:ng] = True

    has_ea = samples[0].edge_attr is not None
    edge_attr = (np.zeros((n_edge, samples[0].edge_attr.shape[1]), np.float32)
                 if has_ea else None)
    has_shift = samples[0].edge_shifts is not None
    edge_shifts = np.zeros((n_edge, 3), np.float32) if has_shift else None
    has_yg = samples[0].y_graph is not None
    y_graph = (np.zeros((n_graph, samples[0].y_graph.shape[0]), np.float32)
               if has_yg else None)
    has_yn = samples[0].y_node is not None
    y_node = (np.zeros((n_node, samples[0].y_node.shape[1]), np.float32)
              if has_yn else None)
    has_cell = samples[0].cell is not None
    cell = np.zeros((n_graph, 3, 3), np.float32) if has_cell else None
    has_en = samples[0].energy is not None
    energy = np.zeros((n_graph, 1), np.float32) if has_en else None
    has_f = samples[0].forces is not None
    forces = np.zeros((n_node, 3), np.float32) if has_f else None

    no, eo = 0, 0
    for gi, s in enumerate(samples):
        n, e = s.num_nodes, s.num_edges
        x[no:no + n] = s.x
        pos[no:no + n] = s.pos
        senders[eo:eo + e] = s.senders + no
        receivers[eo:eo + e] = s.receivers + no
        node_graph[no:no + n] = gi
        node_mask[no:no + n] = True
        edge_mask[eo:eo + e] = True
        if has_ea:
            edge_attr[eo:eo + e] = s.edge_attr
        if has_shift:
            edge_shifts[eo:eo + e] = s.edge_shifts
        if has_yg:
            y_graph[gi] = s.y_graph
        if has_yn:
            y_node[no:no + n] = s.y_node
        if has_cell:
            cell[gi] = s.cell
        if has_en:
            energy[gi, 0] = s.energy[0]
        if has_f:
            forces[no:no + n] = s.forces
        no += n
        eo += e

    conv = (lambda a: a) if np_out else jnp.asarray
    opt = lambda a: None if a is None else conv(a)
    return GraphBatch(
        x=conv(x), pos=conv(pos), senders=conv(senders),
        receivers=conv(receivers), node_graph=conv(node_graph),
        node_mask=conv(node_mask), edge_mask=conv(edge_mask),
        graph_mask=conv(graph_mask), y_graph=opt(y_graph), y_node=opt(y_node),
        edge_attr=opt(edge_attr), edge_shifts=opt(edge_shifts), cell=opt(cell),
        energy=opt(energy), forces=opt(forces),
    )


def batch_shape_for_dataset(
    samples: Sequence[GraphSample], batch_size: int, bucket: Optional[BucketSpec] = None
) -> Tuple[int, int, int]:
    """Pick a single (n_node, n_edge, n_graph) that fits any `batch_size`
    contiguous window of `samples` — one compiled program per dataset.

    Replaces the reference's variable-graph-size handling
    (hydragnn/preprocess/graph_samples_checks_and_updates.py:25-80) which just
    *detects* variability; under XLA we instead bound it by padding.
    """
    bucket = bucket or BucketSpec()
    max_n = max(s.num_nodes for s in samples)
    max_e = max(s.num_edges for s in samples)
    return (
        bucket.bucket(max_n * batch_size + 1),
        bucket.bucket(max_e * batch_size + 1),
        batch_size + 1,
    )


def build_neighbor_tables(senders: np.ndarray, receivers: np.ndarray,
                          edge_mask: np.ndarray, n_node: int, n_edge: int,
                          k: Optional[int] = None, k_multiple: int = 8):
    """Receiver-major fixed-degree neighbor tables from a padded edge list.

    Returns (nbr [N, K], nbr_edge [N, K], nbr_mask [N, K], edge_slot [E]):
    slot k of node i holds the sender and edge id of i's k-th in-edge.
    Padding slots point at the padding node/edge with mask False. K is the
    max in-degree rounded up to `k_multiple` (or the explicit `k`, which
    must fit). `edge_slot` is the inverse of `nbr_edge`: a real edge has one
    receiver and so sits in exactly one slot, `receiver * K + rank`; a
    padding edge reads 0 (masked by `edge_mask` wherever it is used). It is
    None where the LAST slot is real (a hand-built batch whose last node is
    real and full; collate always leaves a padding node): ops/segment.
    edge_gather takes that slot for a padding slot, and a batch without
    `edge_slot` indexes plainly. This is the one place that rule lives.

    Aggregating over the K axis of a [N, K, F] gather replaces the segment
    scatter of the forward pass — the dense layout the TPU prefers for
    bounded-degree radius graphs (no analogue in the reference: PyG
    scatters, hydragnn/models/Base.py:18). `edge_slot` does the same for
    the backward pass of that gather (ops/segment.edge_gather).
    """
    senders = np.asarray(senders)
    receivers = np.asarray(receivers)
    real = np.asarray(edge_mask, bool)
    deg = np.bincount(receivers[real], minlength=n_node)
    kmax = int(deg.max()) if deg.size else 0
    if k is None:
        k = max(k_multiple, _round_up(max(kmax, 1), k_multiple))
    elif kmax > k:
        raise ValueError(f"max in-degree {kmax} exceeds neighbor budget {k}")

    nbr = np.full((n_node, k), n_node - 1, np.int32)
    nbr_edge = np.full((n_node, k), n_edge - 1, np.int32)
    nbr_mask = np.zeros((n_node, k), bool)
    edge_slot = np.zeros(n_edge, np.int32)
    # vectorized fill: stable-sort real edges by receiver, then the slot of
    # edge e is its rank within its receiver run (arange minus run start)
    eids = np.nonzero(real)[0]
    if eids.size:
        order = np.argsort(receivers[eids], kind="stable")
        e_sorted = eids[order]
        r_sorted = receivers[e_sorted]
        run_start = np.zeros(e_sorted.size, np.int64)
        run_start[1:] = np.cumsum(r_sorted[1:] != r_sorted[:-1])
        first_of_run = np.concatenate(
            ([0], np.nonzero(r_sorted[1:] != r_sorted[:-1])[0] + 1))
        slots = np.arange(e_sorted.size) - first_of_run[run_start]
        nbr[r_sorted, slots] = senders[e_sorted]
        nbr_edge[r_sorted, slots] = e_sorted
        nbr_mask[r_sorted, slots] = True
        edge_slot[e_sorted] = r_sorted * k + slots
    return nbr, nbr_edge, nbr_mask, None if nbr_mask[-1, -1] else edge_slot


def neighbor_budget_for_dataset(samples, k_multiple: int = 8) -> int:
    """Dataset-level neighbor-table width: the max in-degree over all samples
    rounded up to `k_multiple`. Pass the result as `k` to
    `with_neighbor_format` so every batch shares one [N, K] shape — otherwise
    K floats with each batch's max degree and each crossing of a k_multiple
    boundary recompiles the jitted step (the same pinning that
    `batch_shape_for_dataset` does for node/edge counts).

    Thin wrapper over the memoized one-pass dataset scan
    (datasets/async_loader.dataset_invariants) so there is exactly one
    in-degree budget formula — loaders built through either call site
    compile the same [N, K] shape."""
    from ..datasets.async_loader import dataset_invariants
    inv = dataset_invariants(samples, need_degree=True)
    return max(k_multiple, _round_up(max(inv.max_in_degree or 1, 1),
                                     k_multiple))


def with_neighbor_format(batch: GraphBatch, k: Optional[int] = None,
                         k_multiple: int = 8) -> GraphBatch:
    """Attach neighbor tables to a batch (host-side; arrays may be numpy or
    jax). Convs that support the dense layout (PNA family) use it
    automatically when present.

    Default-on (run_training): the r3 CPU sweep measured the dense
    layout ahead of the segment pipeline at every steps-per-call
    setting (41.5/47.6/51.4 vs 39.5/26.7/43.6 g/s at spc 1/4/10,
    BENCH_SWEEP.json) — it removes the forward pass's scatter."""
    nbr, nbr_edge, nbr_mask, edge_slot = build_neighbor_tables(
        np.asarray(batch.senders), np.asarray(batch.receivers),
        np.asarray(batch.edge_mask), batch.num_nodes, batch.num_edges,
        k=k, k_multiple=k_multiple)
    as_jnp = isinstance(batch.x, jnp.ndarray)
    conv = jnp.asarray if as_jnp else (lambda a: a)
    return batch.replace(nbr=conv(nbr), nbr_edge=conv(nbr_edge),
                         nbr_mask=conv(nbr_mask),
                         edge_slot=None if edge_slot is None
                         else conv(edge_slot))
