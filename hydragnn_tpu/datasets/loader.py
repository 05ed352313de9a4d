"""Static-shape graph data loader with SPMD sharding.

Replaces the reference's PyG DataLoader + DistributedSampler stack
(reference: hydragnn/preprocess/load_data.py:225-296 `create_dataloaders`,
and the custom thread-pool `HydraDataLoader` :93-203). TPU-first differences:

* every batch has ONE padded shape for the whole run (computed once from
  dataset stats) -> exactly one XLA compilation,
* for an N-device data-parallel mesh the loader emits device-stacked arrays
  [D, ...]: each device's sub-batch is self-contained (local node indices),
  so message passing never crosses shard boundaries and the only collective
  in the train step is the gradient psum — the DDP pattern re-done the
  shard_map way,
* shuffling is a seeded permutation per epoch (`set_epoch`,
  reference: train_validate_test.py:156-158), identical on every host,
* collation runs on background workers by default (datasets/async_loader.py),
  optionally backed by a size-bounded batch cache (HYDRAGNN_BATCH_CACHE_MB),
  so the consumer thread — and therefore the accelerator — does not stall
  on Python array packing; the async stream is bitwise-identical to the
  synchronous one (HYDRAGNN_ASYNC_LOADER=0 restores the synchronous path).

This loader batches whole (small) graphs. Node-level tasks on ONE giant
graph that cannot fit a chip use the sampled pipeline instead
(preprocess/sampling.NeighborSamplingLoader, docs/sampling.md) — same
``set_epoch`` / iteration / background-worker contract, but minibatches
are fixed-shape k-hop subgraphs around seed nodes; ``prefetch_to_device``
below composes with it unchanged.
"""
from __future__ import annotations

import math
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..graphs.batch import BucketSpec, GraphBatch, GraphSample, collate


class GraphDataLoader:
    def __init__(
        self,
        dataset: Sequence[GraphSample],
        batch_size: int,
        shuffle: bool = False,
        seed: int = 0,
        num_shards: int = 1,
        drop_last: Optional[bool] = None,
        n_node_per_shard: Optional[int] = None,
        n_edge_per_shard: Optional[int] = None,
        bucket: Optional[BucketSpec] = None,
        batch_transform=None,
        neighbor_format: bool = False,
        neighbor_k: Optional[int] = None,
        async_workers: Optional[int] = None,
        cache_mb: Optional[int] = None,
        packing: bool = False,
        pack_budget=None,
        pack_lookahead: Optional[int] = None,
        pack_rank: int = 0,
        pack_nproc: int = 1,
    ):
        if batch_size % num_shards != 0 and num_shards != 1:
            raise ValueError(
                f"batch_size {batch_size} must divide evenly over "
                f"{num_shards} shards")
        self.dataset = dataset
        self.batch_size = batch_size
        self.num_shards = num_shards
        self.graphs_per_shard = max(batch_size // num_shards, 1)
        self.shuffle = shuffle
        self.seed = seed
        self.epoch = 0
        self._transform_arity = None
        self.drop_last = shuffle if drop_last is None else drop_last
        self.packing = bool(packing)
        self.pack_rank, self.pack_nproc = int(pack_rank), int(pack_nproc)
        self.pack_budget = None
        self._sizes = None        # lazily-scanned (nodes[], edges[]) arrays
        self._pair_counts = None  # real edge pairs per sample, when asked
        self._plan_cache = {}     # epoch -> (bins, selections)
        if self.packing:
            # budget-packed batching (graphs/packing.py): shapes come from
            # the pack budget — sized for graphs_per_shard AVERAGE graphs,
            # not worst-case — and a variable graph count fills each bin
            import dataclasses as _dc
            from ..graphs.packing import choose_budget
            nodes, edges = self._sample_sizes()
            if pack_budget is None:
                pack_budget = choose_budget(nodes, edges,
                                            self.graphs_per_shard,
                                            lookahead=pack_lookahead)
            elif pack_lookahead:
                pack_budget = _dc.replace(pack_budget,
                                          lookahead=int(pack_lookahead))
            self.pack_budget = pack_budget
            n_node_per_shard = pack_budget.n_node
            n_edge_per_shard = pack_budget.n_edge
        bucket = bucket or BucketSpec(multiple=64)
        if n_node_per_shard is None or n_edge_per_shard is None:
            from .async_loader import dataset_invariants
            inv = dataset_invariants(dataset)
            n_node_per_shard = bucket.bucket(
                inv.max_nodes * self.graphs_per_shard + 1)
            n_edge_per_shard = bucket.bucket(
                inv.max_edges * self.graphs_per_shard + 1)
        self.n_node = n_node_per_shard
        self.n_edge = n_edge_per_shard
        self.n_graph = (self.pack_budget.n_graph if self.packing
                        else self.graphs_per_shard + 1)
        # shape prototype for all-padding (empty-shard) batches, pinned on
        # the constructing thread: _collate_shard_raw may run on a worker
        # thread, and file/socket-backed datasets are not safe to index
        # from there (the iterate_async threadsafe guard)
        self._proto_sample = dataset[0] if len(dataset) else None
        self.batch_transform = batch_transform
        self._cache: Optional[List[GraphBatch]] = None
        # dense neighbor-list layout: K is pinned ONCE from dataset-level
        # max in-degree so every batch shares one [N, K] shape (one compile)
        self.neighbor_k = None
        if neighbor_format:
            from .async_loader import neighbor_budget
            self.neighbor_k = neighbor_k or neighbor_budget(dataset)
        # background collation (datasets/async_loader.py): 0 workers =
        # synchronous; the batch cache reuses collation work whenever the
        # exact index selection repeats (re-iterated epochs, replayed
        # permutations) — padded shapes are static so the reuse is bitwise
        from .async_loader import (BatchCache, resolve_async_workers,
                                   resolve_cache_bytes)
        self.async_workers = resolve_async_workers(async_workers)
        cache_bytes = resolve_cache_bytes(cache_mb)
        self.batch_cache = (BatchCache(cache_bytes) if cache_bytes
                            else None)

    def set_epoch(self, epoch: int):
        """Reseed the epoch's shuffle — the shared loader contract
        (NeighborSamplingLoader.set_epoch honors the same one): the
        epoch's order is a pure function of (seed, epoch), identical on
        every process, so elastic resume replays it exactly."""
        self.epoch = epoch

    def __len__(self):
        if self.packing:
            return len(self._plan()[1])
        n = len(self.dataset)
        if self.drop_last:
            # never drop down to zero batches: a dataset smaller than one
            # batch still yields one padded batch, otherwise an epoch
            # silently performs no updates (loss 0.0 with no error)
            return max(n // self.batch_size, 1 if n else 0)
        return math.ceil(n / self.batch_size)

    def _order(self) -> np.ndarray:
        idx = np.arange(len(self.dataset))
        if self.shuffle:
            rng = np.random.RandomState(self.seed + self.epoch)
            rng.shuffle(idx)
        return idx

    def _sample_sizes(self):
        """(nodes[], edges[]) per dataset index, scanned once and cached —
        the pack planner's input and the padding-stats denominator."""
        if self._sizes is None:
            from ..graphs.packing import sample_sizes
            self._sizes = sample_sizes(self.dataset)
        return self._sizes

    def _plan(self):
        """The epoch's pack plan: (global bins, this rank's selections).

        The plan is computed from the GLOBAL shuffled order over the full
        dataset — identical on every process for a given (seed, epoch) —
        and only then sliced per (pack_rank, pack_nproc), so all ranks
        execute the same step count (docs/packing.md)."""
        key = self.epoch if self.shuffle else -1
        hit = self._plan_cache.get(key)
        if hit is None:
            from ..graphs.packing import pack_order, plan_steps
            nodes, edges = self._sample_sizes()
            bins = pack_order(self._order(), nodes, edges, self.pack_budget)
            sels = plan_steps(bins, self.num_shards, self.pack_nproc,
                              self.pack_rank, drop_last=self.drop_last)
            hit = (bins, sels)
            self._plan_cache = {key: hit}  # keep only the current epoch
        return hit

    def global_plan_fingerprint(self) -> str:
        """sha256 (first 16 hex chars) of the current epoch's GLOBAL pack
        plan — the bin sequence BEFORE per-(rank, shard) slicing, plus
        the budget and the global slicing geometry
        ``num_shards * pack_nproc`` it will be sliced by.

        The world-size-elastic resume contract (docs/fault_tolerance.md)
        rests on every rank of a run, at ANY world size W' with the same
        total shard count, deriving the same global plan: run_training
        logs this value at startup and BENCH_ELASTIC compares it across
        ranks and across a W -> W' restart. Packing-mode loaders only."""
        if not self.packing:
            raise ValueError(
                "global_plan_fingerprint is defined for packing-mode "
                "loaders only: fixed-shape batching slices samples per "
                "process instead of slicing one global plan")
        import hashlib
        bins, _ = self._plan()
        b = self.pack_budget
        payload = repr((tuple(tuple(int(i) for i in bn) for bn in bins),
                        (b.n_node, b.n_edge, b.n_graph),
                        self.num_shards * self.pack_nproc))
        return hashlib.sha256(payload.encode()).hexdigest()[:16]

    def _flat_indices(self, sel) -> List[int]:
        """Flatten a selection to dataset indices (packed selections are
        tuples of per-shard tuples; fixed selections are flat)."""
        if self.packing:
            return [i for shard in sel for i in shard]
        return list(sel)

    def padding_stats(self, pair_space: bool = False):
        """Measured padding waste of the current epoch's plan —
        `padding_frac_nodes` / `padding_frac_edges` over all node/edge
        slots the compiled program will execute (the FLOP-waste proxy
        reported by trainer/bench), plus bookkeeping fields. With
        `pair_space` (and the dense neighbour table) also `pad_pair_share`,
        the masked share of the [N, K, K] pair space that directional
        message passing derives from the table (models/dimenet.py): one
        more scan of every sample's edges, so only who asks pays it.

        Returns None for fixed-mode loaders over non-in-memory datasets:
        the size scan would deserialize every sample from disk/socket
        purely for instrumentation (packing mode already paid that scan
        at plan time, so it always reports)."""
        if (not self.packing and self._sizes is None
                and not isinstance(self.dataset, (list, tuple))):
            return None
        from ..graphs.packing import plan_padding_stats
        nodes, edges = self._sample_sizes()
        sels = self._selections()
        if not self.packing:
            # normalize flat fixed-mode selections to per-shard tuples so
            # the slot denominator counts every shard's padded shape
            g = self.graphs_per_shard
            sels = [tuple(tuple(sel[sh * g:(sh + 1) * g])
                          for sh in range(self.num_shards)) for sel in sels]
        pairs = None
        if pair_space and self.neighbor_k is not None:
            if self._pair_counts is None:
                from ..graphs.triplets import count_triplets
                self._pair_counts = np.array(
                    [count_triplets(s.senders, s.receivers)
                     for s in self.dataset], np.int64)
            pairs = self._pair_counts
        stats = plan_padding_stats(sels, nodes, edges,
                                   self.n_node, self.n_edge, pairs=pairs,
                                   neighbor_k=self.neighbor_k)
        stats["packing"] = "packed" if self.packing else "fixed"
        return stats

    def _collate_shard(self, samples: List[GraphSample]) -> GraphBatch:
        b = self._collate_shard_raw(samples)
        if self.batch_transform is not None:
            b = self._apply_transform(b, samples)
        # after batch_transform: a transform may rewire/prune edges, and the
        # neighbor tables must describe the edge set the model actually sees
        if self.neighbor_k is not None:
            from ..graphs.batch import with_neighbor_format
            b = with_neighbor_format(b, k=self.neighbor_k)
        return b

    def _apply_transform(self, b: GraphBatch, samples) -> GraphBatch:
        if self._transform_arity is None:
            import inspect
            try:
                params = [
                    p for p in inspect.signature(
                        self.batch_transform).parameters.values()
                    if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
                self._transform_arity = min(len(params), 2)
            except (TypeError, ValueError):
                self._transform_arity = 1
        if self._transform_arity >= 2:
            return self.batch_transform(b, samples)
        return self.batch_transform(b)

    def _collate_shard_raw(self, samples: List[GraphSample]) -> GraphBatch:
        if not samples:
            b = collate([self._proto_sample], n_node=self.n_node,
                        n_edge=self.n_edge, n_graph=self.n_graph, np_out=True)
            zero = lambda a: None if a is None else np.zeros_like(a)
            return GraphBatch(
                x=zero(b.x), pos=zero(b.pos),
                senders=np.full_like(b.senders, self.n_node - 1),
                receivers=np.full_like(b.receivers, self.n_node - 1),
                node_graph=np.full_like(b.node_graph, self.n_graph - 1),
                node_mask=np.zeros_like(b.node_mask),
                edge_mask=np.zeros_like(b.edge_mask),
                graph_mask=np.zeros_like(b.graph_mask),
                y_graph=zero(b.y_graph), y_node=zero(b.y_node),
                edge_attr=zero(b.edge_attr), edge_shifts=zero(b.edge_shifts),
                cell=zero(b.cell), energy=zero(b.energy), forces=zero(b.forces))
        return collate(samples, n_node=self.n_node, n_edge=self.n_edge,
                       n_graph=self.n_graph, np_out=True)

    def _selections(self) -> List[Tuple[int, ...]]:
        """The epoch's batch index tuples, in yield order — the unit of
        work for both the synchronous loop and the background workers (and
        the batch-cache key). In packing mode each selection is a tuple of
        per-shard index tuples (still an exact, hashable index key)."""
        if self.packing:
            return self._plan()[1]
        order = self._order()
        return [tuple(int(i) for i in
                      order[ib * self.batch_size:(ib + 1) * self.batch_size])
                for ib in range(len(self))]

    def _build_batch(self, sel: Tuple[int, ...]) -> GraphBatch:
        # sample fetch goes through the bounded-backoff transient-I/O
        # retry (and the loader-fetch fault site) — docs/fault_tolerance.md
        from .async_loader import fetch_samples
        return self._build_batch_from_samples(
            sel, fetch_samples(self.dataset, self._flat_indices(sel)))

    def _postprocess_shard(self, batch: GraphBatch,
                           shard_sel) -> GraphBatch:
        """Subclass hook: per-shard batch enrichment from the shard's
        dataset-index selection, after collation but before stacking.
        The mixture loader (parallel/multidataset.GfmMixtureLoader)
        attaches the per-graph ``dataset_id`` here — selection-derived,
        so the batch cache (keyed by the exact selection) stays
        correct. Runs on worker threads under iterate_async: numpy
        only, no shared mutable state."""
        return batch

    def _build_batch_from_samples(self, sel, samples) -> GraphBatch:
        if self.packing:
            # sel is a tuple of per-shard index tuples; `samples` holds the
            # flattened fetch in the same order
            shards, at = [], 0
            for shard_sel in sel:
                shards.append(self._postprocess_shard(
                    self._collate_shard(samples[at:at + len(shard_sel)]),
                    shard_sel))
                at += len(shard_sel)
            return shards[0] if self.num_shards == 1 else \
                _stack_batches(shards)
        if self.num_shards == 1:
            return self._postprocess_shard(self._collate_shard(samples),
                                           tuple(sel))
        shards = []
        g = self.graphs_per_shard
        for sh in range(self.num_shards):
            shards.append(self._postprocess_shard(
                self._collate_shard(samples[sh * g:(sh + 1) * g]),
                tuple(sel[sh * g:(sh + 1) * g])))
        return _stack_batches(shards)

    def __iter__(self) -> Iterator[GraphBatch]:
        # non-shuffled loaders (val/test) produce identical batches every
        # epoch — collate once and replay (the reference's DataLoader
        # re-collates every epoch because PyG batches are cheap; padded
        # batches are not, and they are static here)
        from ..utils.envflags import env_flag
        if not self.shuffle and env_flag("HYDRAGNN_CACHE_BATCHES", True):
            if self._cache is None:
                self._cache = list(self._iter_batches())
            yield from self._cache
            return
        yield from self._iter_batches()

    def _iter_batches(self) -> Iterator[GraphBatch]:
        # HYDRAGNN_CACHE_BATCHES=0 is the blanket cache opt-out: it disables
        # the whole-epoch replay above AND the selection-keyed BatchCache, so
        # every epoch re-collates from scratch
        from ..utils.envflags import env_flag
        cache = (self.batch_cache
                 if env_flag("HYDRAGNN_CACHE_BATCHES", True) else None)
        if self.async_workers > 0:
            from .async_loader import iterate_async
            yield from iterate_async(self, self._selections(),
                                     self.async_workers, cache)
            return
        yield from self._iter_uncached(cache)

    def _iter_uncached(self, cache: Optional["BatchCache"] = None
                       ) -> Iterator[GraphBatch]:
        """Synchronous reference path (HYDRAGNN_ASYNC_LOADER=0): collate on
        the consumer thread, consulting the same batch cache."""
        for sel in self._selections():
            hit = cache.get(sel) if cache is not None else None
            if hit is None:
                hit = self._build_batch(sel)
                if cache is not None:
                    cache.put(sel, hit)
            yield hit


def prefetch_to_device(iterator, size: int = 2, place_fn=None):
    """Device prefetch: keep `size` placed batches ahead of the consumer
    (the DataLoader worker/pin-memory overlap of the reference's
    HydraDataLoader, preprocess/load_data.py:93-203, expressed as async
    dispatch). A generator on the consumer's thread, not a thread: each
    `next()` places the batch `size` ahead, then yields. That copy overlaps
    device compute only if the consumer has work queued when it calls
    `next()`: the trainer keeps one step owed (dispatches step k+1 before
    fetching step k's metrics), so the copy runs under step k+1; a loop
    that fetches each step's result first leaves the device idle for it.

    `place_fn` customizes placement (e.g. mesh-sharded via
    parallel.mesh.shard_batch); default = jax.device_put to the default
    device."""
    import collections

    import jax
    place = place_fn or (lambda b: jax.tree_util.tree_map(
        lambda a: None if a is None else jax.device_put(a), b))
    queue = collections.deque()
    it = iter(iterator)
    try:
        for _ in range(size):
            queue.append(place(next(it)))
    except StopIteration:
        pass
    while queue:
        yield queue.popleft()
        try:
            queue.append(place(next(it)))
        except StopIteration:
            continue


def _stack_batches(shards: List[GraphBatch]) -> GraphBatch:
    """Stack per-shard batches into [D, ...] arrays for shard_map.

    Heterogeneous multi-dataset mixes may populate the PBC geometry fields
    (edge_shifts, cells) on some shards only — absent shards get zeros,
    which are no-ops in the edge-vector math. Any other field (labels,
    edge_attr, ...) present on some shards but not others is a real
    schema mismatch between member datasets and raises, because
    zero-filling a label would silently train those shards toward 0."""
    import dataclasses
    _ZERO_FILL_OK = ("edge_shifts", "cell")
    def stk(field):
        vals = [getattr(s, field) for s in shards]
        present = [v for v in vals if v is not None]
        if not present:
            return None
        if len(present) < len(vals):
            if field not in _ZERO_FILL_OK:
                raise ValueError(
                    f"member datasets disagree on field '{field}': present "
                    f"on {len(present)}/{len(vals)} shards — all member "
                    "datasets must share one label/feature schema")
            proto = present[0]
            vals = [np.zeros_like(proto) if v is None else v for v in vals]
        return np.stack(vals, axis=0)
    return GraphBatch(**{f.name: stk(f.name)
                         for f in dataclasses.fields(GraphBatch)})
