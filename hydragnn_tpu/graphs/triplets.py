"""Host-side triplet enumeration for directional message passing (DimeNet).

The reference builds triplets per batch on the GPU with torch_sparse
SparseTensor (reference: hydragnn/models/DIMEStack.py:181-205 `triplets`).
Under XLA we need static shapes, so on the scatter path triplets are
enumerated on the host at collation time into padded [T] index arrays
(SURVEY.md §7 hard part (c)). With the dense neighbour table (the default
layout) the model derives the same pairs on the device as an [N, K, K]
space (models/dimenet.py) and nothing here runs.

A triplet (k->j->i) is a pair of edges (e1 = k->j, e2 = j->i) with k != i;
`idx_kj`/`idx_ji` index into the batch edge arrays.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

from .batch import GraphBatch


def count_triplets(senders: np.ndarray, receivers: np.ndarray) -> int:
    """Exact number of triplets a single graph yields: the host list's
    budget, and the real share of the [N, K, K] pair space the dense path
    derives on the device (`pad_pair_share`).

    Handles asymmetric edge sets (max_neighbours capping drops one direction
    of a pair) and periodic images (several edges between one pair of
    nodes): pairs = sum_e deg_in(sender(e)), minus the k == i back-tracks,
    which for edge (j->i) are ALL edges (i->j), whatever their image."""
    senders = np.asarray(senders, np.int64)
    receivers = np.asarray(receivers, np.int64)
    if len(senders) == 0:
        return 0
    n = int(max(senders.max(), receivers.max()) + 1)
    deg_in = np.bincount(receivers, minlength=n)   # edges k->j per node j
    pairs = int(deg_in[senders].sum())
    directed, multiplicity = np.unique(senders * n + receivers,
                                       return_counts=True)
    reverse = receivers * n + senders
    at = np.minimum(np.searchsorted(directed, reverse), len(directed) - 1)
    backtracks = int(multiplicity[at][directed[at] == reverse].sum())
    return pairs - backtracks


def triplet_budget(samples: Sequence, graphs_per_batch: int,
                   multiple: int = 128) -> int:
    worst = max(count_triplets(s.senders, s.receivers) for s in samples)
    t = worst * graphs_per_batch + 1
    return int(np.ceil(t / multiple) * multiple)


def add_triplets(batch: GraphBatch, budget: int) -> GraphBatch:
    """Numpy batch -> numpy batch with idx_kj/idx_ji/triplet_mask filled.

    Padding triplets point at the last (padding) edge.
    """
    send = np.asarray(batch.senders)
    recv = np.asarray(batch.receivers)
    emask = np.asarray(batch.edge_mask)
    e = len(send)
    # group real edges by receiver node
    real = np.nonzero(emask)[0]
    order = real[np.argsort(recv[real], kind="stable")]
    sorted_recv = recv[order]
    # for each real edge e2 (j->i), incoming edges of j
    kj_list, ji_list = [], []
    starts = np.searchsorted(sorted_recv, np.arange(len(batch.node_mask)))
    ends = np.searchsorted(sorted_recv, np.arange(len(batch.node_mask)),
                           side="right")
    for e2 in real:
        j, i = send[e2], recv[e2]
        cand = order[starts[j]:ends[j]]       # edges (*->j)
        cand = cand[send[cand] != i]          # exclude back-track k == i
        kj_list.append(cand)
        ji_list.append(np.full(len(cand), e2, np.int64))
    if kj_list:
        kj = np.concatenate(kj_list)
        ji = np.concatenate(ji_list)
    else:
        kj = np.zeros(0, np.int64)
        ji = np.zeros(0, np.int64)
    t = len(kj)
    if t > budget:
        raise ValueError(f"triplet count {t} exceeds budget {budget}")
    idx_kj = np.full(budget, e - 1, np.int32)
    idx_ji = np.full(budget, e - 1, np.int32)
    mask = np.zeros(budget, bool)
    idx_kj[:t] = kj
    idx_ji[:t] = ji
    mask[:t] = True
    import dataclasses
    return dataclasses.replace(batch, idx_kj=idx_kj, idx_ji=idx_ji,
                               triplet_mask=mask)


def sample_triplets(senders: np.ndarray, receivers: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-sample local triplet edge-pair indices (kj, ji). Computed once per
    sample; batches just offset and concatenate these.

    The per-edge Python loop below (10 M iterations over a pool of 4,096
    OC20-sized structures) is paid only on the scatter path: with the dense
    neighbour table the model derives the pairs on the device and
    `maybe_triplet_transform` returns None, so no run builds both."""
    n = int(max(senders.max(initial=-1), receivers.max(initial=-1)) + 1)
    order = np.argsort(receivers, kind="stable")
    sorted_recv = receivers[order]
    starts = np.searchsorted(sorted_recv, np.arange(n))
    ends = np.searchsorted(sorted_recv, np.arange(n), side="right")
    kj_list, ji_list = [], []
    for e2 in range(len(senders)):
        j, i = senders[e2], receivers[e2]
        cand = order[starts[j]:ends[j]]
        cand = cand[senders[cand] != i]
        kj_list.append(cand)
        ji_list.append(np.full(len(cand), e2, np.int64))
    if kj_list:
        return (np.concatenate(kj_list).astype(np.int64),
                np.concatenate(ji_list).astype(np.int64))
    return np.zeros(0, np.int64), np.zeros(0, np.int64)


class TripletTransform:
    """Loader batch_transform for DimeNet: per-sample triplets precomputed
    and cached; per batch only integer offsetting + concatenation remains
    (the per-edge Python loop runs once per sample, not once per batch)."""

    def __init__(self, samples: Sequence, graphs_per_batch: int):
        self.budget = triplet_budget(samples, graphs_per_batch)
        self._cache: dict = {}

    def _lookup(self, s) -> Tuple[np.ndarray, np.ndarray]:
        # content key, not id(s): datasets that materialize fresh GraphSample
        # objects per access would alias reused ids
        send = np.asarray(s.senders)
        recv = np.asarray(s.receivers)
        key = (send.shape[0], hash(send.tobytes()), hash(recv.tobytes()))
        hit = self._cache.get(key)
        if hit is None:
            hit = sample_triplets(send, recv)
            self._cache[key] = hit
        return hit

    def __call__(self, batch: GraphBatch, samples: Optional[Sequence] = None
                 ) -> GraphBatch:
        if samples is None:
            return add_triplets(batch, self.budget)
        e = batch.senders.shape[0]
        kj_parts, ji_parts = [], []
        eo = 0
        for s in samples:
            kj, ji = self._lookup(s)
            kj_parts.append(kj + eo)
            ji_parts.append(ji + eo)
            eo += s.num_edges
        kj = (np.concatenate(kj_parts) if kj_parts
              else np.zeros(0, np.int64))
        ji = (np.concatenate(ji_parts) if ji_parts
              else np.zeros(0, np.int64))
        t = len(kj)
        if t > self.budget:
            raise ValueError(f"triplet count {t} exceeds budget {self.budget}")
        idx_kj = np.full(self.budget, e - 1, np.int32)
        idx_ji = np.full(self.budget, e - 1, np.int32)
        mask = np.zeros(self.budget, bool)
        idx_kj[:t] = kj
        idx_ji[:t] = ji
        mask[:t] = True
        import dataclasses
        return dataclasses.replace(batch, idx_kj=idx_kj, idx_ji=idx_ji,
                                   triplet_mask=mask)


def make_triplet_transform(samples: Sequence, graphs_per_batch: int):
    return TripletTransform(samples, graphs_per_batch)


def maybe_triplet_transform(model_type: str, samples: Sequence,
                            graphs_per_shard: int,
                            neighbor_format: bool = False):
    """One shared helper for run_training/run_prediction wiring, given the
    RESOLVED batch layout: with the dense neighbour table DimeNet derives
    its pair space inside the jitted program from `batch.nbr` (models/
    dimenet.py) and needs no transform; the host list is built only for
    the scatter path (graph sharding, HYDRAGNN_NEIGHBOR_FORMAT=0)."""
    if model_type != "DimeNet" or neighbor_format:
        return None
    return TripletTransform(samples, graphs_per_shard)
