"""Budget-packed graph batching: plan variable-count batches under one
fixed (n_node, n_edge, n_graph) budget.

The fixed-shape loader (`batch_shape_for_dataset`, graphs/batch.py) pads
every batch to ``max_nodes_per_graph * batch_size`` — on size-skewed
atomistic datasets the majority of node/edge slots (and therefore MXU
FLOPs) are padding. This module instead packs a *variable* number of
graphs into a fixed budget (the graph-centric batching DGL ships for this
workload, arXiv:1909.01315; jraph's ``dynamically_batch`` is the same idea
for jax): the compiled program still sees ONE static shape, but the shape
is sized for the *mean* batch content rather than the worst case, cutting
padding waste from ``~1 - mean/max`` to a target of ~<=15%.

Three pieces, all host-side and deterministic:

* ``choose_budget`` — size a (n_node, n_edge, n_graph) budget from the
  dataset's size histogram so that ``graphs_per_batch`` *average* graphs
  fill a bin, with graph slots generous enough that small-graph runs
  never close a bin early (graph-slot padding is cheap: it only scales
  the tiny [G]-indexed head/pool arrays, not the node/edge compute).
* ``pack_order`` — deterministically pack an epoch's (shuffled) sample
  order into bins by first-fit-decreasing within a bounded lookahead
  window: every sample is placed exactly once, order is approximately
  preserved (a sample is never deferred past one fresh bin), and the
  same (order, sizes, budget) always yields the same plan — the
  multi-process determinism contract (docs/packing.md).
* ``plan_steps`` — group bins into per-step selections for
  ``num_shards`` device shards x ``nproc`` processes, every process
  slicing the SAME global plan so all ranks execute identical step
  counts (no collective divergence); the tail is empty-bin padded or
  dropped, never rank-dependent.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .batch import _round_up

# default bounded lookahead window for first-fit-decreasing: large enough
# to find small "filler" graphs near the stream head, small enough that
# packing stays approximately stream-ordered (and O(n * W) worst case)
DEFAULT_LOOKAHEAD = 128
# sanity cap on real graph slots per bin — far above any sane bin content,
# guards a degenerate min-size-1 dataset from allocating huge [G] arrays
MAX_GRAPH_SLOTS = 4096


@dataclasses.dataclass(frozen=True)
class PackBudget:
    """Per-shard padded budget. Conventions match ``graphs.batch.collate``:
    one padding node and one padding graph slot are always reserved
    (capacities are ``n_node - 1`` nodes, ``n_edge`` edges, ``n_graph - 1``
    graphs), so a loader can pass these shapes straight through."""

    n_node: int
    n_edge: int
    n_graph: int
    lookahead: int = DEFAULT_LOOKAHEAD

    @property
    def cap_nodes(self) -> int:
        return self.n_node - 1

    @property
    def cap_edges(self) -> int:
        return self.n_edge

    @property
    def cap_graphs(self) -> int:
        return self.n_graph - 1


def sample_sizes(samples: Sequence) -> Tuple[np.ndarray, np.ndarray]:
    """ONE pass over the dataset -> (nodes[i], edges[i]) int64 arrays
    (a single pass matters for disk-backed datasets, where each visit
    deserializes the sample)."""
    nodes = np.empty(len(samples), np.int64)
    edges = np.empty(len(samples), np.int64)
    for i, s in enumerate(samples):
        nodes[i] = s.num_nodes
        edges[i] = s.num_edges
    return nodes, edges


def choose_budget(nodes: np.ndarray, edges: np.ndarray,
                  graphs_per_batch: int, multiple: int = 64,
                  lookahead: Optional[int] = None) -> PackBudget:
    """Size the per-shard budget from the dataset size histogram.

    Node/edge capacities target ``graphs_per_batch`` *average* graphs
    (never below one max-size graph — a single graph must always fit),
    rounded up to ``multiple`` for MXU-friendly shapes; the rounding is
    the built-in headroom. Graph slots are sized so a bin full of the
    smallest graphs never closes on the graph axis before the node
    budget is spent.
    """
    nodes = np.asarray(nodes)
    edges = np.asarray(edges)
    if nodes.size == 0:
        raise ValueError("choose_budget: empty dataset")
    g = max(int(graphs_per_batch), 1)
    mean_n = float(nodes.mean())
    mean_e = float(edges.mean())
    max_n = int(nodes.max())
    max_e = int(edges.max())
    min_n = max(int(nodes.min()), 1)
    cap_n = max(int(math.ceil(mean_n * g)), max_n)
    cap_e = max(int(math.ceil(mean_e * g)), max_e, 1)
    n_node = _round_up(cap_n + 1, multiple)
    n_edge = _round_up(cap_e, multiple)
    slots = min(int(math.ceil((n_node - 1) / min_n)), MAX_GRAPH_SLOTS)
    return PackBudget(n_node=n_node, n_edge=n_edge,
                      n_graph=max(slots, g) + 1,
                      lookahead=int(lookahead or DEFAULT_LOOKAHEAD))


def check_fits(nodes: np.ndarray, edges: np.ndarray,
               budget: PackBudget, indices=None) -> None:
    """Raise with a clear message if any single graph overflows the
    budget (the budget-overflow fallback contract: fail loudly up front,
    not mid-epoch inside collate). `indices` maps positions in
    `nodes`/`edges` back to dataset indices so the error names the
    actual offending sample, not its position in a shuffled order."""
    over_n = np.nonzero(np.asarray(nodes) > budget.cap_nodes)[0]
    over_e = np.nonzero(np.asarray(edges) > budget.cap_edges)[0]
    if over_n.size or over_e.size:
        i = int(over_n[0] if over_n.size else over_e[0])
        ds_i = int(np.asarray(indices)[i]) if indices is not None else i
        raise ValueError(
            f"budget-packed batching: sample {ds_i} "
            f"({int(np.asarray(nodes)[i])} nodes, "
            f"{int(np.asarray(edges)[i])} edges) does not fit the pack "
            f"budget (capacity {budget.cap_nodes} nodes / "
            f"{budget.cap_edges} edges per bin, from n_node="
            f"{budget.n_node}, n_edge={budget.n_edge}) — raise the "
            "budget (larger batch_size or explicit pack budget) or "
            "filter oversized graphs from the dataset")


def pack_order(order: Sequence[int], nodes: np.ndarray, edges: np.ndarray,
               budget: PackBudget) -> List[Tuple[int, ...]]:
    """Pack the epoch order into bins; returns tuples of dataset indices.

    First-fit-decreasing within a bounded lookahead window: keep the next
    ``budget.lookahead`` stream samples sorted by descending node count
    (ties broken by stream position — the determinism tiebreak), place
    the largest one that fits the open bin, refill the window, and close
    the bin when nothing in the window fits. Every sample lands in
    exactly one bin; a fresh bin always fits the largest waiting sample
    (``check_fits``), so no sample is deferred more than one bin.
    """
    order = [int(i) for i in order]
    nodes = np.asarray(nodes)
    edges = np.asarray(edges)
    check_fits(nodes[order] if order else nodes[:0],
               edges[order] if order else edges[:0], budget,
               indices=order)

    # window entries sorted ascending by (-n_nodes, stream_pos): index 0 is
    # the largest/earliest sample — first-fit scans from there
    import bisect
    keys: List[Tuple[int, int]] = []
    vals: List[int] = []          # dataset index, parallel to keys
    stream = iter(enumerate(order))
    exhausted = False

    def refill():
        nonlocal exhausted
        while not exhausted and len(keys) < budget.lookahead:
            try:
                pos, idx = next(stream)
            except StopIteration:
                exhausted = True
                return
            k = (-int(nodes[idx]), pos)
            at = bisect.bisect_left(keys, k)
            keys.insert(at, k)
            vals.insert(at, idx)

    refill()
    bins: List[Tuple[int, ...]] = []
    cur: List[int] = []
    rem_n, rem_e, rem_g = budget.cap_nodes, budget.cap_edges, \
        budget.cap_graphs
    while keys:
        placed = False
        if rem_g > 0:
            for i in range(len(keys)):
                idx = vals[i]
                if nodes[idx] <= rem_n and edges[idx] <= rem_e:
                    keys.pop(i)
                    vals.pop(i)
                    cur.append(idx)
                    rem_n -= int(nodes[idx])
                    rem_e -= int(edges[idx])
                    rem_g -= 1
                    refill()
                    placed = True
                    break
        if not placed:
            bins.append(tuple(cur))
            cur = []
            rem_n, rem_e, rem_g = budget.cap_nodes, budget.cap_edges, \
                budget.cap_graphs
    if cur:
        bins.append(tuple(cur))
    return bins


def plan_steps(bins: Sequence[Tuple[int, ...]], num_shards: int,
               nproc: int = 1, rank: int = 0, drop_last: bool = True
               ) -> List[Tuple[Tuple[int, ...], ...]]:
    """Group bins into this rank's per-step selections.

    One global step consumes ``num_shards * nproc`` consecutive bins;
    rank r takes bins ``[g*B + r*num_shards, g*B + (r+1)*num_shards)``
    of global step g. Every rank slices the SAME global plan, so all
    ranks see identical step counts by construction. The tail is dropped
    (``drop_last``) or padded with empty bins (all-padding shards — the
    loader's proto-sample branch) — but never down to zero steps while
    bins exist, so an epoch can't silently perform no updates.
    """
    bins = list(bins)
    per_step = max(num_shards, 1) * max(nproc, 1)
    nsteps = len(bins) // per_step
    rem = len(bins) - nsteps * per_step
    if rem and (not drop_last or nsteps == 0):
        bins = bins + [()] * (per_step - rem)
        nsteps += 1
    sels = []
    for g in range(nsteps):
        base = g * per_step + rank * num_shards
        sels.append(tuple(bins[base:base + num_shards]))
    return sels


def plan_padding_stats(selections: Sequence, nodes: np.ndarray,
                       edges: np.ndarray, n_node: int, n_edge: int,
                       pairs: np.ndarray = None, neighbor_k: int = None
                       ) -> Dict[str, float]:
    """Measured waste of a plan: fraction of node/edge slots that are
    padding over the epoch (the FLOP-waste proxy the trainer/bench
    report). Works for packed (nested per-shard tuples) and fixed (flat
    tuples) selections. With `pairs` (real edge pairs per sample) and the
    neighbour table's K also `pad_pair_share`: the masked share of the
    [n_node, K, K] pair space a directional stack derives from the table."""
    nodes = np.asarray(nodes)
    edges = np.asarray(edges)
    pairs = None if pairs is None else np.asarray(pairs)
    shards = 0
    real_n = 0
    real_e = 0
    real_p = 0
    graphs = 0
    for sel in selections:
        parts = sel if sel and isinstance(sel[0], tuple) else (sel,)
        for part in parts:
            shards += 1
            if part:
                idx = np.asarray(part, np.int64)
                real_n += int(nodes[idx].sum())
                real_e += int(edges[idx].sum())
                if pairs is not None:
                    real_p += int(pairs[idx].sum())
                graphs += len(part)
    node_slots = shards * n_node
    edge_slots = shards * n_edge
    extra = {}
    if pairs is not None and neighbor_k and node_slots:
        extra["pad_pair_share"] = 1.0 - real_p / (
            node_slots * neighbor_k * neighbor_k)
    return {
        **extra,
        "padding_frac_nodes": (1.0 - real_n / node_slots) if node_slots
        else 0.0,
        "padding_frac_edges": (1.0 - real_e / edge_slots) if edge_slots
        else 0.0,
        "real_graphs": graphs,
        "shards": shards,
    }
