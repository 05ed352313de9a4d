"""Batched inference serving engine: request micro-batching over a
bucketed compile cache.

The naive serving loop (run_prediction's legacy path, and any per-request
deployment of it) pays one padded forward — and one XLA dispatch — per
request, recompiling whenever a novel shape shows up. Batched execution
over fixed-shape padded graphs is exactly where this framework already
wins at training time (budget-packed batching, graphs/packing.py), so the
serving path reuses the same machinery:

* ``bucket_ladder`` — a small DETERMINISTIC set of padded shapes, one per
  graph-count capacity in {1, 2, 4, ..., max_batch_size}, each sized by
  ``graphs.packing.choose_budget`` over a reference size histogram (node/
  edge capacities target `cap` average-size graphs, never below one
  max-size graph) and rounded to MXU-friendly multiples. Compile count is
  bounded by the ladder length — O(log max_batch_size) programs.
* ``InferenceEngine.submit(sample) -> Future`` — requests enter a queue; a
  background dispatcher coalesces them into one padded batch (greedy, in
  arrival order, while the next request fits the largest bucket's node/
  edge budget) up to ``max_batch_size`` requests or ``max_wait_ms`` after
  the first dequeued request, whichever first. The coalesced batch runs
  one compiled forward on the smallest fitting bucket and each caller's
  future resolves to ITS unpadded slice.
* ``warmup()`` — precompile every bucket up front so no request ever pays
  a compile; after warmup the compile count stays frozen at the ladder
  length (`compile_count`, asserted by tests/bench).

Batched outputs are bitwise-identical to the single-request forward on
the same bucket (tests/test_serving.py): per-node/per-edge ops are
row-independent, and the pooling segment-sums accumulate each graph's
nodes in the same relative order regardless of which slot the graph
occupies.

Multi-device serving (``num_shards > 1``) splits each coalesced batch
into per-shard sub-batches on one bucket shape and runs the SPMD forward
(parallel/spmd.make_spmd_forward) — the same shard_map layout training
uses, with outputs concatenated device-major.

Failure semantics (docs/fault_tolerance.md) — the engine's availability
contract is that EVERY accepted future resolves, with a result or an
error, under any single-batch failure:

* bounded admission queue — ``max_queue`` > 0 makes ``submit`` fast-fail
  with ``QueueFullError`` instead of queueing unboundedly behind a slow
  dispatcher (backpressure the caller can act on);
* per-request deadlines — ``deadline_ms`` (per submit, or the engine
  default) resolves expired requests with ``DeadlineExceededError``; an
  expired request never occupies a batch slot;
* dispatcher supervision — a failed batch resolves only ITS OWN futures
  with the error; a run of ``breaker_threshold`` consecutive batch
  failures trips a circuit breaker to fast-fail (``CircuitOpenError``)
  for ``breaker_reset_s``, then admits one probe batch (half-open) whose
  outcome closes or re-opens the circuit. ``health()`` reports
  state/queue depth/trip count for monitors;
* the ``serving-dispatch`` fault site (utils/faults.py) fires once per
  executed batch, so all of the above is exercised deterministically by
  tier-1 tests and the BENCH_FAULTS chaos mode.

Raw-structure serving (docs/serving.md, ROADMAP item 3): with a
``structure_config`` the engine also accepts raw positions —
``submit_structure(positions, node_features[, cell])`` runs structure →
radius graph → ``build_graph_sample`` → the bucketed forward in one
call, and trajectory clients hold a ``structure_session()`` whose
Verlet-skin incremental NeighborList (graphs/neighborlist.py) makes
step t+1 re-filter step t's candidate cache instead of rebuilding the
cell list. Emitted edges are bitwise the fresh build's (the PR 5 total
order), futures carry ``.rebuilt``/``.graph_build_ms`` breadcrumbs next
to ``.bucket``, and rebuild counts flow into the telemetry registry
(``serve.nbr_rebuilds_total``, the rebuild-fraction gauge, the
``serve.graph_build`` span) plus ``health()``//metrics so a scrape can
tell neighbor-bound from compute-bound serving. ``ef_forward=True``
serves energy+forces from a node-level energy head (forces = -dE/dpos),
closing the MD loop end-to-end (examples/md_loop, BENCH_MD).

Fleet hooks (docs/serving.md "Fleet", serving/fleet.py): the engine is
the fleet's unit of failure isolation — each ``ReplicaRouter`` replica
is one engine with its own breaker and its own compiled programs.
Three engine-level capabilities exist for that layer: an atomic
``swap_variables`` hot-swap (the PR 4 BEST/LATEST checkpoint contract;
``model_version`` is echoed on every resolved future and in
``health()``), a persistent AOT ``compile_store``
(utils/devices.CompileStore) so a replacement replica's ``warmup()``
loads the bucket ladder from disk instead of recompiling
(``compile_store_hits`` vs ``compile_fresh`` report the split), and
``latency_snapshot()`` so the router can compute fleet-aggregate
percentiles from raw per-replica latencies.

Latency and spans (docs/observability.md "Span taxonomy"): a request's
latency runs from its ARRIVAL (entry of ``submit_structure``, before the
graph build, or of ``submit``) to its result being set. With a span
recorder installed every request (``req``) and every executed batch
(``batch``) leaves linked spans: ``serve.request`` over
``serve.graph_build`` and ``serve.queue_wait``; ``serve.batch`` over
``serve.collate``, ``serve.dispatch``, ``serve.fetch`` and
``serve.unpad``; and the dispatcher's own ``serve.await_request`` and
``serve.coalesce_wait``.

The dispatch pipeline (docs/serving.md "Dispatch pipeline"): the call of
a compiled program returns as soon as its work is enqueued, and the
dispatcher keeps that. A dispatched batch's completion (fetch, unpad,
statistics, ``set_result``) is OWED, not done at once: while a batch's
worth of requests is queued (``max_batch_size``: the next batch fills
without the wait for company) the dispatcher coalesces, collates and
dispatches batch n+1 first, then completes batch n, so the device starts
n+1 the moment n ends. At most ``inflight_depth`` batches are owed: 2
where two executions of the largest bucket's program fit the device
memory that is free when that program is compiled, else 1 (the serial
path); 1 too while the breaker is not closed, so a half-open probe is
alone in flight. With a shorter queue a batch is completed as soon as it
is dispatched: the batches formed are the serial path's, always.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, Deque, List, Optional, Sequence, Tuple

import numpy as np

from ..graphs.batch import GraphBatch, GraphSample, collate
from ..graphs.packing import MAX_GRAPH_SLOTS, PackBudget, choose_budget
from ..telemetry import spans as _spans
from ..telemetry.registry import get_registry
from ..utils.faults import fault_point
from .config import Structure

_SHUTDOWN = object()

# Reduced-precision serving parity contract
# (docs/mixed_precision.md). A float32 engine keeps the PR 3
# adjudication: batched outputs are BITWISE-equal to the single-request
# forward on the same bucket. A reduced-precision engine (compute_dtype
# "bfloat16", the serve-side precision override) keeps that same-bucket
# batched-vs-single bitwise guarantee (identical compiled program,
# row-independent math) but relaxes the fp32-reference adjudication to a
# tolerance bound: every output element obeys
#
#     |bf16_out - fp32_out| <= SERVE_REDUCED_ATOL
#                              + SERVE_REDUCED_RTOL * |fp32_out|
#
# on identical buckets. 2^-5 is 8 bf16 ULP at unit scale: bf16's 8-bit
# significand gives a 2^-8 unit roundoff per op, and the error budget
# covers the <= 8 rounding-dominated stages (conv stack + heads) of the
# deepest model-zoo stacks, with f32 segment accumulation keeping the
# reductions themselves exact. Every resolved future carries the bound
# as `.parity` / `.parity_rtol` / `.parity_atol` so clients can see the
# contract they were served under (tests/test_precision.py pins it).
SERVE_REDUCED_RTOL = 2.0 ** -5
SERVE_REDUCED_ATOL = 2.0 ** -5

# int8 serving parity contract (docs/mixed_precision.md
# "int8"). An int8 engine (compute_dtype "int8": calibrated per-channel
# PTQ over the conv-stack matmuls, quant/ptq.py) keeps the same-bucket
# batched-vs-single BITWISE guarantee — identical compiled program,
# row-independent math, exact int32 accumulation — and adjudicates
# against fp32 with
#
#     |int8_out - fp32_out| <= SERVE_INT8_ATOL
#                              + SERVE_INT8_RTOL * |fp32_out|
#
# 2^-3 is the symmetric-127-level budget: one quantized matmul's output
# error is bounded by the input rounding (<= s_x/2 per channel, i.e.
# 2^-8 of the calibrated range) plus the weight rounding (<= s_w/2,
# another 2^-8 relative), amplified through the <= 8
# rounding-dominated stages of the deepest model-zoo conv stacks and
# the nonlinearities between them — 8 stages x ~2^-7 per stage lands
# within 2^-3 at unit scale, with the int32 accumulation contributing
# exactly zero (no swamping term, unlike bf16). Every resolved future
# carries the bound as `.parity`/`.parity_rtol`/`.parity_atol`
# (tests/test_quant.py pins it; BENCH_KERNELS adjudicates it at bench
# scale).
SERVE_INT8_RTOL = 2.0 ** -3
SERVE_INT8_ATOL = 2.0 ** -3


class ServingError(RuntimeError):
    """Base of the engine's failure-semantics errors."""


class QueueFullError(ServingError):
    """submit() fast-fail: the bounded admission queue is at max_queue."""


class DeadlineExceededError(ServingError):
    """The request's deadline expired before a batch could serve it."""


class CircuitOpenError(ServingError):
    """The dispatcher circuit breaker is open (consecutive batch
    failures); requests fast-fail until the probe window."""


def bucket_ladder(nodes, edges, max_batch_size: int, num_buckets: int = 0,
                  multiple: int = 64) -> Tuple[PackBudget, ...]:
    """The engine's deterministic bucket set, smallest first.

    One bucket per graph-count capacity in the geometric ladder
    {1, 2, 4, ..., max_batch_size}; each bucket's node/edge budget comes
    from ``choose_budget`` over the reference (nodes, edges) histogram —
    shapes are a pure function of (histogram, max_batch_size, num_buckets,
    multiple). `num_buckets` > 0 keeps only the largest that many
    capacities (fewer compiled programs, more graph-slot padding on small
    batches). Duplicate shapes (tiny datasets) are deduped."""
    caps: List[int] = []
    g = max(int(max_batch_size), 1)
    while g >= 1:
        caps.append(g)
        g //= 2
    caps = sorted(set(caps))
    if num_buckets and num_buckets > 0:
        caps = caps[-int(num_buckets):]
    ladder: List[PackBudget] = []
    for cap in caps:
        b = choose_budget(nodes, edges, cap, multiple=multiple)
        b = dataclasses.replace(b, n_graph=min(cap, MAX_GRAPH_SLOTS) + 1)
        if not ladder or (b.n_node, b.n_edge) != (ladder[-1].n_node,
                                                  ladder[-1].n_edge):
            ladder.append(b)
        else:  # same shape at a higher capacity: keep the roomier one
            ladder[-1] = b
    return tuple(ladder)


def select_bucket(buckets: Sequence[PackBudget], count: int, tot_n: int,
                  tot_e: int) -> Optional[PackBudget]:
    """Smallest bucket (ladder order) that fits `count` graphs with
    `tot_n` nodes / `tot_e` edges; None when nothing fits. Pure function
    of its arguments — the determinism contract tests pin."""
    for b in buckets:
        if (count <= b.cap_graphs and tot_n <= b.cap_nodes
                and tot_e <= b.cap_edges):
            return b
    return None


class _Request:
    __slots__ = ("sample", "future", "n", "e", "t_submit", "deadline",
                 "req", "t_arrival")

    def __init__(self, sample: GraphSample, future: Future,
                 deadline_ms: Optional[float] = None,
                 req: Optional[int] = None,
                 t_arrival: Optional[float] = None):
        self.sample = sample
        self.future = future
        self.n = sample.num_nodes
        self.e = sample.num_edges
        self.t_submit = time.perf_counter()
        # the request's identifier on its spans, and when it ARRIVED: the
        # entry of `submit_structure` (before the graph build) or of
        # `submit`. Latency (`stats()`, /metrics, `serve.request`) runs
        # from arrival; the deadline and `serve.queue_wait` from t_submit
        self.req = req
        self.t_arrival = self.t_submit if t_arrival is None else t_arrival
        # absolute expiry on the same clock as t_submit; None/0 = none
        self.deadline = (self.t_submit + float(deadline_ms) / 1e3
                         if deadline_ms else None)


class _Dispatched:
    """One batch between its dispatch and its completion: what the
    dispatcher still owes. `outs` are the program's outputs, still on the
    device; `error` is set instead when the dispatch itself failed (the
    batch's futures fail in its turn, so batches resolve in dispatch
    order)."""
    __slots__ = ("batch_id", "shards", "reqs", "bucket", "outs", "version",
                 "overlapped", "rec", "t_disp", "t_fwd", "error")

    def __init__(self, batch_id: int, shards: List[List[_Request]],
                 reqs: List[_Request], overlapped: bool):
        self.batch_id = batch_id
        self.shards = shards
        self.reqs = reqs
        self.overlapped = overlapped
        self.bucket = self.outs = self.version = self.error = None
        self.rec = self.t_disp = self.t_fwd = None

    def ready(self) -> bool:
        """Whether completing this batch would not wait for the device."""
        return self.error is not None or all(
            o.is_ready() for o in self.outs)


def _free_device_bytes(devices) -> Optional[int]:
    """The least memory free on any of `devices`, by
    `device.memory_stats()`; None where the backend keeps no such
    statistics (the CPU: the host's memory is not the engine's to
    ration)."""
    free = None
    for d in devices:
        stats = d.memory_stats()
        if not stats or "bytes_limit" not in stats:
            return None
        left = int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))
        free = left if free is None else min(free, left)
    return free


class InferenceEngine:
    """submit(sample) -> Future resolving to per-head unpadded outputs
    (graph heads: [output_dim]; node heads: [num_nodes, output_dim]).

    Construction needs the model + variables + ModelConfig (head types
    drive the unpadding) and either `reference_samples` (bucket shapes
    and the field schema come from them — typically the training/test
    set) or an explicit `buckets` ladder plus a `proto_sample` for the
    schema. Label fields (y_graph/y_node/energy/forces) are stripped
    before the forward — the compiled signature is label-free, so
    labeled and unlabeled requests share one program.
    """

    def __init__(self, model, variables, mcfg, *,
                 reference_samples: Optional[Sequence[GraphSample]] = None,
                 buckets: Optional[Sequence[PackBudget]] = None,
                 proto_sample: Optional[GraphSample] = None,
                 max_batch_size: int = 32, max_wait_ms: float = 5.0,
                 num_buckets: int = 0, bucket_multiple: int = 64,
                 num_shards: int = 1, neighbor_format: bool = False,
                 neighbor_k: Optional[int] = None,
                 batch_transform: Optional[Callable] = None,
                 compute_dtype: Optional[str] = None,
                 max_queue: int = 0,
                 default_deadline_ms: Optional[float] = None,
                 breaker_threshold: int = 5,
                 breaker_reset_s: float = 30.0,
                 structure_config: Optional[dict] = None,
                 md_skin: float = 0.3,
                 ef_forward: bool = False,
                 compile_store=None,
                 model_version: str = "v0",
                 tier: Optional[str] = None,
                 quant_calibration=None,
                 quant_calib_samples: int = 32):
        import jax
        from ..train.precision import resolve_precision
        from ..train.train_step import make_forward_fn

        self.mcfg = mcfg
        # serve-side precision: the explicit override (Serving.precision /
        # HYDRAGNN_SERVE_PRECISION via serving/config.py) wins over the
        # train-side policy; resolved ONCE here so the parity contract the
        # futures advertise matches the compiled programs
        self.compute_dtype = resolve_precision(
            getattr(mcfg, "dtype", None), compute_dtype)
        compute_dtype = self.compute_dtype
        # three rungs of the precision ladder
        # (docs/mixed_precision.md): fp32 = bitwise parity, bf16
        # = the reduced tolerance bound, int8 = calibrated PTQ
        # (quant/ptq.py) under its own documented bound
        self.quantized = self.compute_dtype == "int8"
        if self.quantized:
            self.parity = "tolerance"
            self.parity_rtol = SERVE_INT8_RTOL
            self.parity_atol = SERVE_INT8_ATOL
        elif self.compute_dtype != "float32":
            self.parity = "tolerance"
            self.parity_rtol = SERVE_REDUCED_RTOL
            self.parity_atol = SERVE_REDUCED_ATOL
        else:
            self.parity = "bitwise"
            self.parity_rtol = 0.0
            self.parity_atol = 0.0
        # the fleet tier this engine serves under (serving/fleet.py
        # TierPolicy): defaults to the compute dtype name, so a mixed
        # int8/fp32 fleet tiers itself without extra wiring; echoed on
        # every resolved future next to `.bucket`/`.model_version`
        self.tier = str(tier) if tier is not None else self.compute_dtype
        self.max_batch_size = max(int(max_batch_size), 1)
        self.max_wait_s = max(float(max_wait_ms), 0.0) / 1e3
        self.num_shards = max(int(num_shards), 1)
        if self.quantized and self.num_shards > 1:
            raise ValueError(
                "int8 serving is single-shard for now — run one int8 "
                "engine per device (a fleet tier of them, "
                "serving/fleet.py) instead of num_shards > 1")
        if self.quantized and ef_forward:
            raise ValueError(
                "ef_forward needs exact gradients (forces = -dE/dpos) "
                "and the int8 round/clip has a zero gradient almost "
                "everywhere — serve EF from the fp32/bf16 tier and keep "
                "int8 for the plain forward tiers")
        # failure-semantics knobs (docs/fault_tolerance.md): 0 disables
        # the bound / deadline / breaker respectively
        self.max_queue = max(int(max_queue), 0)
        self.default_deadline_ms = (float(default_deadline_ms)
                                    if default_deadline_ms else None)
        self.breaker_threshold = max(int(breaker_threshold), 0)
        self.breaker_reset_s = max(float(breaker_reset_s), 0.0)
        # bucket shapes are PER SHARD; the ladder is sized for this many
        # requests per shard so num_shards * cap covers max_batch_size
        self.per_shard_cap = -(-self.max_batch_size // self.num_shards)
        self.batch_transform = batch_transform
        if buckets is None:
            if not reference_samples:
                raise ValueError(
                    "InferenceEngine needs reference_samples (bucket "
                    "shapes + request schema) or an explicit buckets "
                    "ladder with a proto_sample")
            from ..graphs.packing import sample_sizes
            nodes, edges = sample_sizes(reference_samples)
            buckets = bucket_ladder(nodes, edges, self.per_shard_cap,
                                    num_buckets, bucket_multiple)
        self.buckets: Tuple[PackBudget, ...] = tuple(buckets)
        if not self.buckets:
            raise ValueError("InferenceEngine: empty bucket ladder")
        if any(b.n_graph < 2 for b in self.buckets):
            raise ValueError(
                "InferenceEngine: every bucket needs n_graph >= 2 (one "
                "real graph slot + the padding slot, the collate "
                "convention)")
        # per-shard fill limit: an explicit ladder may cap graph slots
        # below the request-count split, and the coalescer must never
        # build a shard that select_bucket cannot place
        self._shard_fill_cap = min(self.per_shard_cap,
                                   self.buckets[-1].cap_graphs)
        self._proto = (proto_sample if proto_sample is not None
                       else reference_samples[0])
        self.neighbor_k = None
        if neighbor_format:
            if neighbor_k is None:
                if not reference_samples:
                    raise ValueError(
                        "neighbor_format=True needs an explicit "
                        "neighbor_k when no reference_samples are given")
                from ..datasets.async_loader import neighbor_budget
                neighbor_k = neighbor_budget(reference_samples)
            self.neighbor_k = int(neighbor_k)

        # raw-structure serving (docs/serving.md): with a structure
        # config the engine accepts raw (positions, node_features[, cell])
        # via submit_structure and builds the radius graph itself —
        # trajectory clients additionally hold a structure_session()
        # whose Verlet-skin NeighborList reuses step t's candidate list
        # at step t+1 (graphs/neighborlist.py)
        self._structure_cfg = structure_config
        self.md_skin = float(md_skin)
        if structure_config is not None:
            s_ds = structure_config["Dataset"]
            s_arch = structure_config["NeuralNetwork"]["Architecture"]
            self._structure_pbc = bool(
                s_arch.get("periodic_boundary_conditions", False))
            self._structure_radius = float(s_arch.get("radius") or 5.0)
            self._structure_max_nb = s_arch.get("max_neighbours")
            self._structure_rot = bool(
                s_ds.get("rotational_invariance", False))

        # EF serving (docs/serving.md): head 0 must be a NODE-level
        # energy head (the energy_force_loss convention, train/loss.py);
        # responses become [energy [1], forces [num_nodes, 3]] with
        # forces = -d(sum of masked graph energies)/d pos. Per-graph
        # independence holds exactly as for the plain forward (each
        # graph's energy only sees its own nodes through the masked
        # segment pooling), so the same-bucket batched-vs-single bitwise
        # contract carries over (tests/test_serving.py).
        self.ef_forward = bool(ef_forward)
        if self.ef_forward:
            if mcfg.heads[0].head_type != "node":
                raise ValueError(
                    "ef_forward=True needs head 0 to be a node-level "
                    "energy head (the energy_force_loss convention); got "
                    f"a {mcfg.heads[0].head_type!r} head")
            if self.num_shards > 1:
                raise ValueError(
                    "ef_forward serving is single-shard for now — run "
                    "one EF engine per device instead of num_shards > 1")
            self._response_heads = ["graph", "node"]
        else:
            self._response_heads = [h.head_type for h in mcfg.heads]

        # the served model state: swapped ATOMICALLY (one reference
        # assignment under the lock) by swap_variables — a batch uses
        # whichever (variables, version) pair it snapshotted, never a
        # torn mix (docs/serving.md "Fleet": hot-swap drain contract)
        self._variables = {"params": variables["params"],  # guarded-by: _lock
                           "batch_stats": variables.get("batch_stats", {})}
        self.model_version = str(model_version)  # guarded-by: _lock
        self.swap_count = 0  # guarded-by: _lock
        self._started_at = time.monotonic()
        self._model = model  # retained for trajectory_farm (the farm
        # builds its own vmapped EF forward from the same model/config)

        # int8 calibration (quant/calibrate.py): explicit scales win
        # (run_prediction calibrates ONCE and shares them across
        # replicas so every replica compiles identical programs);
        # otherwise the engine calibrates itself from the reference
        # samples. The scale digest goes into the compile-store key —
        # the activation scales are trace-time constants inside the
        # compiled artifact (_store_key).
        self.quant_calibration = None
        self._quant_digest = None
        if self.quantized:
            if quant_calibration is None:
                if not reference_samples:
                    raise ValueError(
                        "int8 serving needs calibration: pass "
                        "quant_calibration (quant.calibrate) or "
                        "reference_samples for the engine to calibrate "
                        "from (docs/mixed_precision.md)")
                from ..quant.calibrate import calibrate
                quant_calibration = calibrate(
                    model, self._variables, mcfg, reference_samples,
                    num_samples=quant_calib_samples,
                    batch_transform=self.batch_transform)
            self.quant_calibration = quant_calibration
            self._quant_digest = quant_calibration.digest
        # the devices this engine's programs execute on — what a program
        # reloaded from the compile store must be loaded onto
        # (utils/devices.CompileStore.load). Nothing places an engine
        # yet: a single-shard engine runs on the default device.
        self.devices = jax.devices()[:1]
        if self.num_shards > 1:
            from ..parallel.mesh import make_mesh
            from ..parallel.spmd import make_spmd_forward
            mesh = make_mesh((("data", self.num_shards),))
            self.devices = list(mesh.devices.flat)
            self._jit_forward = make_spmd_forward(model, mesh, mcfg,
                                                  compute_dtype)
        else:
            if self.quantized:
                # the quantized forward is f32-in/f32-out with the
                # conv-stack matmuls rerouted through int8 kernels; it
                # replaces make_forward_fn's cast policy wholesale (an
                # int8 _cast_floats would destroy the params — the
                # train-side guard rejects exactly that)
                from ..quant.ptq import make_quantized_forward
                forward = make_quantized_forward(model, mcfg,
                                                 self.quant_calibration)
            else:
                forward = make_forward_fn(model, mcfg, compute_dtype)

            if self.ef_forward:
                from ..train.loss import energy_forces_from_node_head

                def head_forward(variables, batch):
                    # the eval forward mutates nothing; adapt to the
                    # energy_force_loss apply contract so the served
                    # quantity IS the trained quantity (one shared core)
                    def apply_fn(v, b, train):
                        return forward(v, b, train=train), None

                    graph_e, forces, _ = energy_forces_from_node_head(
                        apply_fn, variables, batch, train=False)
                    return [graph_e, forces]
            else:
                def head_forward(variables, batch):
                    outputs, _ = forward(variables, batch, train=False)
                    return list(outputs)

            self._jit_forward = jax.jit(head_forward)

        # per-bucket compile cache: bucket -> AOT-compiled executable.
        # The `# guarded-by: _lock` annotations are machine-checked by
        # hydralint's lock-discipline rule: every lexical access outside
        # a `with self._lock:` block (or __init__) fails the lint.
        self._compiled = {}  # guarded-by: _lock
        self.compile_count = 0  # guarded-by: _lock
        # persistent AOT compile store (utils/devices.CompileStore):
        # hits loaded the executable from disk, fresh paid a real
        # compile — a replica warm-started from a populated store
        # reports compile_fresh == 0 (BENCH_SERVE_FLEET adjudication)
        self._compile_store = compile_store
        self.compile_store_hits = 0  # guarded-by: _lock
        self.compile_fresh = 0  # guarded-by: _lock
        self._lock = threading.Lock()

        # dispatcher state + service statistics
        self._queue: "queue.Queue" = queue.Queue()
        self._closed = False  # guarded-by: _lock
        self._fatal: Optional[BaseException] = None  # guarded-by: _lock
        self.requests_done = 0  # guarded-by: _lock
        self.batches_run = 0  # guarded-by: _lock
        self._occupancy_sum = 0.0  # guarded-by: _lock
        self._real_node_slots = 0  # guarded-by: _lock
        self._total_node_slots = 0  # guarded-by: _lock
        self._real_edge_slots = 0  # guarded-by: _lock
        self._total_edge_slots = 0  # guarded-by: _lock
        self.max_queue_depth = 0  # guarded-by: _lock
        self._latencies: List[float] = []  # guarded-by: _lock
        # raw-structure accounting (docs/serving.md): nbr_updates counts
        # neighbor-list builds submit_structure performed, nbr_rebuilds
        # the full (non-incremental) ones — a session-less submit is by
        # definition a rebuild. A scrape comparing the two tells
        # neighbor-bound from compute-bound serving.
        self.structure_requests = 0  # guarded-by: _lock
        self.nbr_updates = 0  # guarded-by: _lock
        self.nbr_rebuilds = 0  # guarded-by: _lock
        # circuit-breaker + failure accounting (all under self._lock)
        self._breaker_state = "closed"  # guarded-by: _lock — closed |
        #                                 open | half_open
        self._consec_failures = 0  # guarded-by: _lock
        self._open_until = 0.0  # guarded-by: _lock — monotonic probe point
        self.trip_count = 0  # guarded-by: _lock
        self.probe_count = 0  # guarded-by: _lock — open -> half_open
        # transitions: how many probes this breaker ever admitted (the
        # fleet hammer test pins exactly one in flight per open window)
        self.batch_failures = 0  # guarded-by: _lock
        self.deadline_expired = 0  # guarded-by: _lock
        self.queue_rejections = 0  # guarded-by: _lock
        self.circuit_rejections = 0  # guarded-by: _lock
        self._metrics_server = None
        # span identifiers (docs/observability.md): every serving span
        # carries the `req` or the `batch` it belongs to
        self._req_ids = itertools.count()
        self._batch_ids = itertools.count()
        # the dispatch pipeline (module docstring): the batches
        # dispatched and not yet completed, oldest first (the
        # dispatcher's own: no other thread touches it), how many there
        # may be, and how many were dispatched while another was owed
        self._owed: Deque[_Dispatched] = collections.deque()
        self.inflight_depth = 1  # guarded-by: _lock — resolved when the
        # largest bucket's program is compiled (`_resolve_depth`)
        self.batches_overlapped = 0  # guarded-by: _lock
        self._dispatcher = threading.Thread(target=self._loop,
                                            name="serve-dispatch",
                                            daemon=True)
        self._dispatcher.start()

    # ------------------------------------------------------------- client API

    def submit(self, sample: GraphSample,
               deadline_ms: Optional[float] = None) -> Future:
        """Enqueue one request; returns a Future resolving to the per-head
        outputs (or raising the per-request failure). Thread-safe.

        Fast-fail admission control (raised HERE, no future is created):
        `QueueFullError` when the bounded queue is at max_queue,
        `CircuitOpenError` while the breaker is open. ``deadline_ms``
        (default: the engine's default_deadline_ms) bounds how long the
        request may wait — once expired it resolves with
        `DeadlineExceededError` instead of occupying a batch slot."""
        t_arrival = time.perf_counter()
        req = next(self._req_ids)
        try:
            return self._submit(sample, deadline_ms, req, t_arrival)
        except BaseException as e:  # noqa: BLE001 — re-raised
            self._span_request(req, t_arrival, error=type(e).__name__)
            raise

    def _submit(self, sample: GraphSample, deadline_ms: Optional[float],
                req: int, t_arrival: float) -> Future:
        """`submit` for a request that arrived at `t_arrival` under the
        identifier `req` (`submit_structure` stamps both before it builds
        the graph). A rejection raised here gets its `serve.request`
        span from the caller, which holds `req`; one resolved here (an
        invalid sample) gets it here."""
        fut: Future = Future()
        err = self._validate(sample)
        if err is not None:
            fut.set_exception(err)
            self._span_request(req, t_arrival, error=type(err).__name__)
            return fut
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        # closed-check + put under the lock: shutdown() flips _closed
        # under the same lock BEFORE enqueuing the sentinel, so a request
        # can never land behind the sentinel on a queue nobody drains
        with self._lock:
            self._admission_check()
            if self._breaker_state == "open":
                # all admission checks passed (so the probe window has
                # elapsed): this request IS the probe
                self._breaker_state = "half_open"
                self.probe_count += 1
            # the queue is unbounded (admission bounding is the qsize
            # check above), so this put never blocks — and it must stay
            # under the lock so a request can never land behind the
            # shutdown sentinel
            self._queue.put(  # hydralint: disable=lock-discipline -- unbounded queue, put cannot block; ordering vs the shutdown sentinel needs the lock
                _Request(sample, fut, deadline_ms=deadline_ms, req=req,
                         t_arrival=t_arrival))
            depth = self._queue.qsize()
            if depth > self.max_queue_depth:
                self.max_queue_depth = depth
        return fut

    def _require_structure(self):
        if self._structure_cfg is None:
            raise RuntimeError(
                "raw-structure serving is off — construct the "
                "InferenceEngine with structure_config=<config dict> "
                "(Serving.structure / HYDRAGNN_SERVE_STRUCTURE wires it "
                "through run_prediction; docs/serving.md)")

    # the ONE copy of the fast-fail admission checks, shared by submit()
    # (authoritative) and the submit_structure precheck. Read-only: the
    # open -> half_open probe reservation stays with submit() — a
    # precheck reserving the probe would make the later authoritative
    # check reject its own request. An open breaker whose window elapsed
    # passes (that request may become the probe).
    # holds-lock: _lock
    def _admission_check(self) -> None:
        if self._closed:
            raise RuntimeError("InferenceEngine is shut down")
        if self._fatal is not None:
            raise RuntimeError(
                "InferenceEngine dispatcher died") from self._fatal
        if self._breaker_state == "half_open":
            # exactly ONE probe at a time: its outcome decides the
            # circuit before anyone else is admitted
            self.circuit_rejections += 1
            raise CircuitOpenError(
                "circuit half-open: probe in flight; retry shortly")
        if self._breaker_state == "open":
            now = time.monotonic()
            if now < self._open_until:
                self.circuit_rejections += 1
                raise CircuitOpenError(
                    f"circuit open after {self.trip_count} trip(s) "
                    f"({self._consec_failures} consecutive batch "
                    f"failures); probing in {self._open_until - now:.2f}s")
        if self.max_queue and self._queue.qsize() >= self.max_queue:
            self.queue_rejections += 1
            raise QueueFullError(
                f"admission queue full ({self.max_queue} pending); "
                "retry with backoff or raise Serving.max_queue")

    def _shed_structure_load(self) -> None:
        """Admission precheck for submit_structure: fast-fail BEFORE the
        host-side neighbor update and graph build so load shedding sheds
        the host work too (submit() re-checks authoritatively)."""
        with self._lock:
            self._admission_check()

    def structure_session(self, skin: Optional[float] = None
                          ) -> "StructureSession":
        """A trajectory client's neighbor-list handle: submit_structure
        calls carrying this session reuse one Verlet-skin NeighborList
        (cutoff/max_neighbours/PBC from the structure config, skin from
        `md_skin` unless overridden), so step t+1 re-filters step t's
        candidate cache instead of rebuilding the cell list. One session
        per SEQUENTIAL client — the neighbor list is stateful and not
        thread-safe; concurrent trajectories each open their own."""
        self._require_structure()
        if self._structure_rot:
            raise ValueError(
                "trajectory sessions need Dataset.rotational_invariance "
                "off — the incremental neighbor list tracks displacements "
                "in the raw frame, per-step rotation normalization would "
                "invalidate them")
        from ..graphs.neighborlist import NeighborList
        return StructureSession(NeighborList(
            self._structure_radius,
            self.md_skin if skin is None else float(skin),
            max_neighbours=self._structure_max_nb,
            pbc=(True, True, True) if self._structure_pbc else None))

    def trajectory_farm(self, *, dt: float, skin: Optional[float] = None,
                        mass: float = 1.0, force_scale: float = 1.0,
                        steps_per_dispatch: Optional[int] = None,
                        cand_headroom: Optional[float] = None,
                        scorer=None):
        """A massively-batched device-resident MD farm over this engine's
        model (docs/serving.md "MD farm"): vmapped velocity-Verlet +
        Verlet-skin re-filter with K steps per dispatch, each trajectory
        BITWISE-equal to the single-session `submit_structure` loop from
        identical initial conditions. Requires the raw-structure +
        ``ef_forward`` configuration and a single-bucket ladder (the
        farm serves every step on ONE compiled shape, the same shape the
        session adjudication reference runs on). Knobs default to
        `serving.config.resolve_md_farm` (HYDRAGNN_MD_FARM_*).

        ``scorer`` (an `md.active.EnsembleScorer`) turns the farm into an
        active-learning producer: uncertainty scored inside the same
        jitted dispatch, deterministic threshold harvest into
        ``result["harvest"]`` (docs/active_learning.md)."""
        self._require_structure()
        if not self.ef_forward:
            raise ValueError(
                "trajectory_farm needs ef_forward=True — the farm "
                "integrates forces served as -dE/dpos")
        if self.num_shards > 1:
            raise ValueError(
                "trajectory_farm is single-shard (like ef_forward "
                "serving) — run one farm per device")
        if self._structure_rot:
            raise ValueError(
                "trajectory farms need Dataset.rotational_invariance off "
                "— the incremental neighbor list tracks displacements in "
                "the raw frame")
        if len(self.buckets) != 1:
            raise ValueError(
                "trajectory_farm needs a single-bucket ladder (e.g. "
                "examples.md_loop.md_buckets) so every step of the farm "
                "and of the session adjudication reference runs the same "
                "compiled shape")
        from ..md.farm import TrajectoryFarm
        from .config import resolve_md_farm
        # the engine holds the full config, so the Serving.md_farm block
        # participates in the documented env-over-config-over-default
        # precedence
        knobs = resolve_md_farm(self._structure_cfg)
        with self._lock:  # hot-swap-consistent snapshot of the served state
            variables = self._variables
        return TrajectoryFarm(
            self._model, variables, self.mcfg, self._structure_cfg,
            bucket=self.buckets[0], dt=dt,
            skin=self.md_skin if skin is None else float(skin),
            mass=mass, force_scale=force_scale,
            steps_per_dispatch=(knobs.steps_per_dispatch
                                if steps_per_dispatch is None
                                else int(steps_per_dispatch)),
            cand_headroom=(knobs.cand_headroom if cand_headroom is None
                           else float(cand_headroom)),
            compute_dtype=self.compute_dtype, scorer=scorer)

    def submit_structure(self, positions, node_features=None, cell=None,
                         graph_feats=None,
                         session: Optional["StructureSession"] = None,
                         deadline_ms: Optional[float] = None) -> Future:
        """Raw-structure request: structure -> radius graph ->
        build_graph_sample -> the bucketed batched forward, one call
        (docs/serving.md). `positions` may be a `serving.config.Structure`
        (then the remaining schema arguments come from it). Without a
        `session` every call builds the graph fresh; with one, the
        session's Verlet-skin NeighborList re-filters its candidate
        cache and only rebuilds past the skin/2 displacement bound —
        either way the edges are bitwise the fresh build's (PR 5 total
        order). The returned future carries `.rebuilt` and
        `.graph_build_ms` breadcrumbs next to the usual `.bucket`."""
        t_arrival = time.perf_counter()
        req = next(self._req_ids)
        try:
            return self._submit_structure(
                req, t_arrival, positions, node_features, cell,
                graph_feats, session, deadline_ms)
        except BaseException as e:  # noqa: BLE001 — re-raised
            # shed load is what an operator traces: a request rejected
            # here (admission, open breaker, a bad structure) still
            # leaves its `serve.request`, with the error
            self._span_request(req, t_arrival, error=type(e).__name__)
            raise

    def _submit_structure(self, req: int, t_arrival: float, positions,
                          node_features, cell, graph_feats, session,
                          deadline_ms) -> Future:
        self._require_structure()
        # load shedding must shed the HOST work too: a read-only
        # admission precheck fast-fails an open breaker / full queue /
        # shutdown BEFORE the neighbor update and graph build (submit()
        # below remains the authoritative, state-transitioning check)
        self._shed_structure_load()
        if isinstance(positions, Structure):
            struct = positions
            positions = struct.positions
            # explicit keyword arguments override the Structure's
            # fields, uniformly across the schema
            node_features = (struct.node_features if node_features is None
                             else node_features)
            cell = struct.cell if cell is None else cell
            graph_feats = (struct.graph_feats if graph_feats is None
                           else graph_feats)
        if node_features is None:
            raise ValueError(
                "submit_structure needs node_features (the "
                "Dataset.node_features layout; target columns may be "
                "zero-filled)")
        from ..preprocess.transforms import build_graph_sample
        t0 = _spans.now()
        pos = np.asarray(positions, dtype=np.float64)
        edges = None
        rebuilt = True
        if session is not None:
            send, recv, shifts, rebuilt = session.nlist.update(
                pos, cell=cell if self._structure_pbc else None)
            edges = (send, recv, shifts)
        sample = build_graph_sample(
            np.asarray(node_features, dtype=np.float32), pos,
            self._structure_cfg, graph_feats=graph_feats, cell=cell,
            edges=edges, with_targets=False)
        build_s = _spans.now() - t0
        rec = _spans.current_recorder()
        if rec is not None:
            rec.add("serve.graph_build", t0, build_s, "serving",
                    {"req": req, "parent": "serve.request",
                     "rebuilt": bool(rebuilt),
                     "incremental": session is not None,
                     "edges": int(sample.num_edges)})
        with self._lock:
            self.structure_requests += 1
            self.nbr_updates += 1
            if rebuilt:
                self.nbr_rebuilds += 1
            updates, rebuilds = self.nbr_updates, self.nbr_rebuilds
        # registry reporting (docs/observability.md): two O(1) dict
        # updates under the registry lock per request — the same cost
        # class as the engine's own counters
        reg = get_registry()
        reg.counter_inc("serve.nbr_updates_total",
                        help="neighbor-list updates by submit_structure")
        if rebuilt:
            reg.counter_inc(
                "serve.nbr_rebuilds_total",
                help="full neighbor-list rebuilds (non-incremental "
                     "updates) by submit_structure")
        reg.gauge_set("serve.nbr_rebuild_fraction", rebuilds / updates,
                      help="rebuilds over neighbor-list updates since "
                           "engine start")
        fut = self._submit(sample, deadline_ms, req, t_arrival)
        fut.rebuilt = bool(rebuilt)  # breadcrumbs beside `.bucket`: did
        fut.graph_build_ms = build_s * 1e3  # this step rebuild, and what
        # the host-side structure -> graph stage cost
        return fut

    def health(self) -> dict:
        """Liveness/saturation snapshot for monitors and load balancers:
        breaker state, queue depth, trip/failure counters, dispatcher
        liveness, model version + uptime (the hot-swap observability
        contract: the version tag is echoed here AND on every resolved
        future, so a swap is verifiable end to end). Cheap — counters
        only, no device work."""
        with self._lock:
            return {
                "state": ("shutdown" if self._closed
                          else self._breaker_state),
                "model_version": self.model_version,
                "tier": self.tier,
                "uptime_s": time.monotonic() - self._started_at,
                "swap_count": self.swap_count,
                "queue_depth": self._queue.qsize(),
                "trip_count": self.trip_count,
                "probe_count": self.probe_count,
                # the router's re-admission hook: an open breaker whose
                # probe window elapsed will admit the next submit as its
                # single half-open probe
                "breaker_probe_due": (
                    self._breaker_state == "open"
                    and time.monotonic() >= self._open_until),
                "consecutive_failures": self._consec_failures,
                "batch_failures": self.batch_failures,
                "deadline_expired": self.deadline_expired,
                "queue_rejections": self.queue_rejections,
                "circuit_rejections": self.circuit_rejections,
                "requests_done": self.requests_done,
                "structure_requests": self.structure_requests,
                "nbr_updates": self.nbr_updates,
                "nbr_rebuilds": self.nbr_rebuilds,
                "nbr_rebuild_fraction": (
                    self.nbr_rebuilds / self.nbr_updates
                    if self.nbr_updates else 0.0),
                "dispatcher_alive": self._dispatcher.is_alive(),
            }

    def predict(self, samples: Sequence[GraphSample], timeout=None):
        """Submit all samples, wait, return the list of results in order."""
        futs = [self.submit(s) for s in samples]
        return [f.result(timeout=timeout) for f in futs]

    def swap_variables(self, variables, version: str) -> str:
        """Zero-downtime model hot-swap: atomically replace the served
        state with `variables` and tag subsequent futures/health with
        `version`; returns the version it replaced.

        The swap is ONE reference assignment under the engine lock —
        every batch snapshots its (variables, version) pair under the
        same lock, so a batch serves entirely-old or entirely-new,
        never a torn mix. The compiled bucket programs take variables
        as a runtime argument, so a swap costs zero recompiles. For the
        fleet's drain contract (requests in flight when the swap lands
        keep their admission-time behavior), the ReplicaRouter drains
        the replica first (docs/serving.md "Fleet").

        Tree structure and leaf shapes/dtypes must match the serving
        state — the compiled programs are shape-specialized, and a
        mismatched checkpoint must fail THIS call, not poison every
        subsequent batch. The ``swap-fail`` fault site fires before any
        mutation, so an injected failure leaves the old version serving
        (tests/test_serving_fleet.py pins the rollback)."""
        fault_point("swap-fail")
        import jax
        new_vars = {"params": variables["params"],
                    "batch_stats": variables.get("batch_stats", {})}
        with self._lock:
            old_vars = self._variables
        old_shapes = jax.tree_util.tree_map(
            lambda a: (getattr(a, "shape", None), getattr(a, "dtype", None)),
            old_vars)
        new_shapes = jax.tree_util.tree_map(
            lambda a: (getattr(a, "shape", None), getattr(a, "dtype", None)),
            new_vars)
        if old_shapes != new_shapes:
            raise ValueError(
                "swap_variables: the new state's tree/shapes/dtypes do "
                "not match the serving state — the compiled programs are "
                "shape-specialized; rebuild the engine for an "
                "architecture change instead of hot-swapping it")
        with self._lock:
            old_version = self.model_version
            self._variables = new_vars
            self.model_version = str(version)
            self.swap_count += 1
        return old_version

    def latency_snapshot(self) -> List[float]:
        """Raw request latencies (seconds) since the last reset — the
        fleet router aggregates these across replicas for fleet-wide
        percentiles (per-replica percentiles cannot be combined)."""
        with self._lock:
            return list(self._latencies)

    def forward_single(self, sample: GraphSample,
                       bucket: Optional[PackBudget] = None):
        """The per-request reference path: one sample, padded alone into
        the smallest bucket that fits it (or an explicit `bucket`), run
        through the SAME compile cache — what a non-batching server would
        execute per request. Bench/tests adjudicate the engine against
        this on identical samples: on the bucket a batch actually ran
        (each resolved future carries it as `.bucket`), outputs must
        match the batched ones bitwise."""
        err = self._validate(sample)
        if err is not None:
            raise err
        req = _Request(sample, Future())
        if bucket is None:
            bucket = select_bucket(self.buckets, 1, req.n, req.e)
        shards = [[req]] + [[] for _ in range(self.num_shards - 1)]
        outs, _ = self._enqueue(shards, bucket, None)
        return self._unpad(shards, bucket, self._fetch(outs, None))[0]

    def warmup(self) -> int:
        """Precompile every bucket (and for `num_shards > 1` the stacked
        SPMD shape) with a zeroed proto batch; returns the number of
        compiled programs. After warmup no request pays a compile — the
        bench's compile-count bound. With a `compile_store`, buckets
        whose executables are already on disk LOAD instead of compiling
        (`compile_store_hits` vs `compile_fresh` in stats() report the
        split; a replica warmed from a populated store reports
        compile_fresh == 0)."""
        for bucket in self.buckets:
            proto = self._collate_bucket([self._proto], bucket)
            if self.num_shards > 1:
                proto = self._stack_shards([proto] + [None] *
                                           (self.num_shards - 1), bucket)
            self._get_compiled(bucket, proto)
        with self._lock:  # counter is written under the lock; read likewise
            return self.compile_count

    def start_metrics_server(self, host: str = "127.0.0.1",
                             port: int = 0):
        """Expose this engine over HTTP (telemetry/http.py): GET /healthz
        returns `health()` as JSON (200 while serving, 503 after
        shutdown/dispatcher death), GET /metrics the Prometheus text
        exposition of `stats()` + the process metrics registry. `port=0`
        binds an ephemeral port; the server object (with `.port`/`.url`)
        is returned and is also stopped automatically by `shutdown()`.
        Loopback-only by default — pass host="0.0.0.0" deliberately."""
        if self._metrics_server is not None:
            return self._metrics_server
        from ..telemetry.http import serve_engine_metrics
        self._metrics_server = serve_engine_metrics(self, host=host,
                                                    port=port)
        return self._metrics_server

    def shutdown(self, wait: bool = True):
        """Stop accepting submissions; the dispatcher drains every queued
        request (no hung callers) and exits. Idempotent."""
        server, self._metrics_server = self._metrics_server, None
        if server is not None:
            server.stop()
        with self._lock:
            if self._closed and not self._dispatcher.is_alive():
                return
            self._closed = True
            # unbounded queue: never blocks; the sentinel must be
            # enqueued under the same lock that flipped _closed so no
            # submit can slip a request in behind it
            self._queue.put(_SHUTDOWN)  # hydralint: disable=lock-discipline -- unbounded queue, put cannot block; sentinel order vs _closed needs the lock
        if wait:
            self._dispatcher.join()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.shutdown(wait=True)
        return False

    def reset_stats(self):
        """Zero the service counters (compile cache untouched) — bench
        phases report closed-loop and open-loop stats separately."""
        with self._lock:
            self.requests_done = 0
            self.batches_run = 0
            self.batches_overlapped = 0
            self._occupancy_sum = 0.0
            self._real_node_slots = 0
            self._total_node_slots = 0
            self._real_edge_slots = 0
            self._total_edge_slots = 0
            self.max_queue_depth = 0
            self._latencies = []
            self.structure_requests = 0
            self.nbr_updates = 0
            self.nbr_rebuilds = 0

    def stats(self) -> dict:
        """Service counters for bench/monitoring: batch occupancy is real
        graphs over graph-slot capacity of the chosen buckets; padding
        fractions are over the node/edge slots the compiled programs
        actually executed. Always includes the full latency-quantile key
        set (zeroed with count 0 before any traffic —
        utils/profiling.latency_percentiles).

        Concurrency contract (PR 7 audit): every counter is snapshotted
        atomically UNDER the engine lock, but the percentile math (numpy
        over potentially thousands of latencies) runs on the copy outside
        it — a monitoring scrape must never stall the dispatcher's next
        batch."""
        from ..utils.profiling import latency_percentiles
        with self._lock:
            latencies = list(self._latencies)
            out = {
                "requests": self.requests_done,
                "batches": self.batches_run,
                # the dispatch pipeline: batches dispatched while another
                # was still owed its completion, and how many may be
                "batches_overlapped": self.batches_overlapped,
                "inflight_depth": self.inflight_depth,
                "batch_occupancy": (self._occupancy_sum / self.batches_run
                                    if self.batches_run else 0.0),
                "padding_frac_nodes": (
                    1.0 - self._real_node_slots / self._total_node_slots
                    if self._total_node_slots else 0.0),
                "padding_frac_edges": (
                    1.0 - self._real_edge_slots / self._total_edge_slots
                    if self._total_edge_slots else 0.0),
                "max_queue_depth": self.max_queue_depth,
                "compile_count": self.compile_count,
                "compile_store_hits": self.compile_store_hits,
                "compile_fresh": self.compile_fresh,
                "num_buckets": len(self.buckets),
                "compute_dtype": self.compute_dtype,
                "parity": self.parity,
                "tier": self.tier,
                "model_version": self.model_version,
                "swap_count": self.swap_count,
                "probe_count": self.probe_count,
                "batch_failures": self.batch_failures,
                "deadline_expired": self.deadline_expired,
                "queue_rejections": self.queue_rejections,
                "circuit_rejections": self.circuit_rejections,
                "trip_count": self.trip_count,
                "structure_requests": self.structure_requests,
                "nbr_updates": self.nbr_updates,
                "nbr_rebuilds": self.nbr_rebuilds,
                "nbr_rebuild_fraction": (
                    self.nbr_rebuilds / self.nbr_updates
                    if self.nbr_updates else 0.0),
            }
        out.update(latency_percentiles(latencies))
        return out

    # --------------------------------------------------------------- plumbing

    def _validate(self, sample: GraphSample) -> Optional[Exception]:
        big = self.buckets[-1]
        if sample.num_nodes > big.cap_nodes or sample.num_edges > big.cap_edges:
            return ValueError(
                f"request ({sample.num_nodes} nodes, {sample.num_edges} "
                f"edges) exceeds the largest serving bucket (capacity "
                f"{big.cap_nodes} nodes / {big.cap_edges} edges) — rebuild "
                "the engine with a larger reference set or explicit buckets")
        p = self._proto
        for name in ("edge_attr", "edge_shifts", "cell"):
            if (getattr(sample, name) is None) != (getattr(p, name) is None):
                return ValueError(
                    f"request field '{name}' is "
                    f"{'missing' if getattr(sample, name) is None else 'present'}"
                    " but the engine was built for the opposite schema — "
                    "all requests must match the reference sample schema")
        if sample.x.shape[1] != p.x.shape[1]:
            return ValueError(
                f"request feature width {sample.x.shape[1]} != engine "
                f"schema width {p.x.shape[1]}")
        if (p.edge_attr is not None
                and sample.edge_attr.shape[1] != p.edge_attr.shape[1]):
            return ValueError(
                f"request edge_attr width {sample.edge_attr.shape[1]} != "
                f"engine schema width {p.edge_attr.shape[1]}")
        return None

    def _collate_bucket(self, samples: List[GraphSample],
                        bucket: PackBudget) -> GraphBatch:
        """One shard's padded batch on `bucket`, label-free, with the
        engine's transform/neighbor tables applied — mirrors
        GraphDataLoader._collate_shard so served numerics match the
        loader-fed eval path."""
        b = collate(samples, n_node=bucket.n_node, n_edge=bucket.n_edge,
                    n_graph=bucket.n_graph, np_out=True)
        b = b.replace(y_graph=None, y_node=None, energy=None, forces=None)
        if self.batch_transform is not None:
            b = self.batch_transform(b)
        if self.neighbor_k is not None:
            from ..graphs.batch import with_neighbor_format
            b = with_neighbor_format(b, k=self.neighbor_k)
        return b

    def _empty_shard(self, bucket: PackBudget) -> GraphBatch:
        """All-padding shard batch (the loader's proto-sample trick): a
        zeroed proto collate whose masks are all False. Every slot of its
        tables is a padding slot at edge E - 1, the last one too, which is
        what `edge_slot` asks of them (GraphBatch.edge_slot)."""
        b = self._collate_bucket([self._proto], bucket)
        zero = lambda a: None if a is None else np.zeros_like(a)

        def pad_full(a, fill):
            return None if a is None else np.full_like(a, fill)

        return b.replace(
            x=zero(b.x), pos=zero(b.pos),
            senders=pad_full(b.senders, bucket.n_node - 1),
            receivers=pad_full(b.receivers, bucket.n_node - 1),
            node_graph=pad_full(b.node_graph, bucket.n_graph - 1),
            node_mask=zero(b.node_mask), edge_mask=zero(b.edge_mask),
            graph_mask=zero(b.graph_mask), edge_attr=zero(b.edge_attr),
            edge_shifts=zero(b.edge_shifts), cell=zero(b.cell),
            triplet_mask=zero(b.triplet_mask),
            nbr=pad_full(b.nbr, bucket.n_node - 1),
            nbr_edge=pad_full(b.nbr_edge, b.num_edges - 1),
            nbr_mask=zero(b.nbr_mask), edge_slot=zero(b.edge_slot))

    def _stack_shards(self, shards: List[Optional[GraphBatch]],
                      bucket: PackBudget) -> GraphBatch:
        from ..datasets.loader import _stack_batches
        filled = [s if s is not None else self._empty_shard(bucket)
                  for s in shards]
        return _stack_batches(filled)

    def _store_key(self, bucket: PackBudget, batch: GraphBatch) -> str:
        """Compile-store fingerprint for one bucket's program: model
        config + bucket shape + everything else that changes the
        compiled artifact (shard count, schema layout, and WHICH fields
        `batch` carries: they are the executable's arguments, so a store
        written by a tree whose batches had one field fewer is a miss,
        not an executable that is handed an argument too many). The store
        itself folds in the jax version and backend platform; the
        precision MODE — compute dtype plus the int8 calibration-scale
        digest — rides the store's labeled `precision` field, so an
        int8 and an fp32 executable for the same bucket can never
        collide on a warm restart, and two int8 programs baked from
        different calibration scales cannot either (the scales are
        constants inside the compiled artifact)."""
        p = self._proto
        schema = tuple(
            (name, None if getattr(p, name) is None
             else tuple(np.asarray(getattr(p, name)).shape[1:]))
            for name in ("x", "pos", "edge_attr", "edge_shifts", "cell"))
        fields = tuple(f.name for f in dataclasses.fields(batch)
                       if getattr(batch, f.name) is not None)
        from ..utils.devices import CompileStore
        return CompileStore.fingerprint(
            self.mcfg, (bucket.n_node, bucket.n_edge, bucket.n_graph),
            self.num_shards, self.neighbor_k,
            self.ef_forward, schema, fields,
            precision=(self.compute_dtype, self._quant_digest))

    def _get_compiled(self, bucket: PackBudget, proto_batch: GraphBatch):
        with self._lock:
            hit = self._compiled.get(bucket)
            variables = self._variables
        if hit is not None:
            return hit
        # persistent AOT store first (docs/serving.md "Fleet"): a hit
        # skips tracing AND compiling entirely; a miss compiles fresh
        # and persists so the NEXT replica (or process) warms from disk
        compiled = None
        from_store = False
        if self._compile_store is not None:
            store_key = self._store_key(bucket, proto_batch)
            compiled = self._compile_store.load(store_key, self.devices)
            from_store = compiled is not None
        if compiled is None:
            compiled = self._jit_forward.lower(variables,
                                               proto_batch).compile()
            if self._compile_store is not None:
                self._compile_store.save(store_key, compiled)
        depth = (self._resolve_depth(compiled)
                 if bucket == self.buckets[-1] else None)
        with self._lock:
            hit = self._compiled.setdefault(bucket, compiled)
            if hit is compiled:
                self.compile_count += 1
                if from_store:
                    self.compile_store_hits += 1
                else:
                    self.compile_fresh += 1
                if depth is not None:
                    self.inflight_depth = depth
        return hit

    def _resolve_depth(self, compiled) -> int:
        """How many batches may be in flight: a runtime may give every
        enqueued execution its own temporaries, so 2 only where two
        executions of the LARGEST bucket's program (arguments, the
        weights among them, outputs and temporaries, by
        `memory_analysis()`) fit what the engine's devices report free
        now that the program is compiled; 1 where they do not, or where
        the executable does not say what it needs. (The v5e's runtime
        keeps ONE scratch reservation for its loaded programs, which the
        executions in flight share: there the second batch costs its
        inputs and outputs only, and the peak stays where it was, 6.06
        GB in PR 27's runs. The bound is for the runtime that does not.)"""
        try:
            need = compiled.memory_analysis()
            one = (need.argument_size_in_bytes + need.output_size_in_bytes
                   + need.temp_size_in_bytes)
            free = _free_device_bytes(self.devices)
        except Exception:  # noqa: BLE001 — no sizes: stay serial
            return 1
        return 2 if free is None or 2 * one <= free else 1

    def _enqueue(self, shards: List[List[_Request]], bucket: PackBudget,
                 batch_id: Optional[int]) -> Tuple[List[Any], str]:
        """Collate and call the compiled program: two spans of the batch
        `batch_id` (None: `forward_single`, no `serve.batch`). The call
        returns once the inputs' placement and the execution are
        enqueued: the outputs that come back are still the device's to
        fill in, and `_fetch` waits for them."""
        ids = {"batch": batch_id, "parent": "serve.batch"}
        with _spans.span("serve.collate", "serving", **ids):
            if self.num_shards > 1:
                parts = [self._collate_bucket([r.sample for r in sh],
                                              bucket)
                         if sh else None for sh in shards]
                batch = self._stack_shards(parts, bucket)
            else:
                batch = self._collate_bucket(
                    [r.sample for r in shards[0]], bucket)
        with _spans.span("serve.dispatch", "serving", **ids):
            compiled = self._get_compiled(bucket, batch)
            # ONE snapshot of the (variables, version) pair: a concurrent
            # hot-swap lands entirely before or entirely after this
            # batch, and the echoed version always names the weights
            # that ran
            with self._lock:
                variables = self._variables
                version = self.model_version
            return compiled(variables, batch), version

    @staticmethod
    def _fetch(outs: List[Any], batch_id: Optional[int]
               ) -> List[np.ndarray]:
        """The outputs as host arrays: waits for the device to finish
        the batch (and whatever it had to run before it)."""
        with _spans.span("serve.fetch", "serving", batch=batch_id,
                         parent="serve.batch"):
            return [np.asarray(o) for o in outs]

    def _unpad(self, shards: List[List[_Request]], bucket: PackBudget,
               outs: List[np.ndarray]) -> List[List[np.ndarray]]:
        """Slice each request's rows back out of the padded head outputs,
        in arrival order (shard fill is contiguous, so shard-major IS
        arrival order).

        Single-shard: request i sits at graph slot i, its nodes at the
        running node offset. SPMD: outputs are device-major concatenated,
        so shard s's slots start at s * n_graph (graphs) / s * n_node
        (nodes)."""
        results: List[List[np.ndarray]] = []
        for s, shard in enumerate(shards):
            g0 = s * bucket.n_graph
            no = s * bucket.n_node
            for i, req in enumerate(shard):
                per_head = []
                for ih, kind in enumerate(self._response_heads):
                    if kind == "graph":
                        per_head.append(outs[ih][g0 + i])
                    else:
                        per_head.append(outs[ih][no:no + req.n])
                results.append(per_head)
                no += req.n
        return results

    @staticmethod
    def _span_request(req: int, t_arrival: float, **args) -> None:
        """The `serve.request` span, as the request resolves (its
        future's result or error set, or its submit call rejected):
        arrival -> now, with its `batch` or the `error` it resolved to.
        Every path that ends a request calls this once."""
        _spans.record("serve.request", t_arrival,
                      _spans.now() - t_arrival, "serving", req=req, **args)

    def _fail_expired(self, req: _Request) -> None:
        with self._lock:
            self.deadline_expired += 1
        if not req.future.done():
            req.future.set_exception(DeadlineExceededError(
                f"deadline expired after "
                f"{(time.perf_counter() - req.t_submit) * 1e3:.1f} ms "
                "in queue"))
            self._span_request(req.req, req.t_arrival,
                               error="DeadlineExceededError")

    def _record_batch_failure(self) -> None:
        with self._lock:
            self.batch_failures += 1
            self._consec_failures += 1
            trip = (self._breaker_state == "half_open"
                    or (self._breaker_state == "closed"
                        and self.breaker_threshold > 0
                        and self._consec_failures >= self.breaker_threshold))
            if trip:
                self._breaker_state = "open"
                self._open_until = time.monotonic() + self.breaker_reset_s
                self.trip_count += 1

    def _record_batch_success(self) -> None:
        with self._lock:
            if self._breaker_state == "open":
                # this batch was already dispatched when the one before
                # it failed and tripped the breaker: the trip stands, and
                # the probe decides
                return
            self._consec_failures = 0
            self._breaker_state = "closed"

    def _execute(self, shards: List[List[_Request]]):
        """Dispatch one coalesced batch; its completion (`_complete`) is
        owed (`_owed`), and `_settle` decides when it is paid."""
        # deadline sweep at dispatch time: requests that expired while
        # coalescing/queueing resolve with DeadlineExceededError and never
        # occupy a batch slot (their FLOPs would be pure waste — nobody is
        # waiting for the answer anymore)
        now = time.perf_counter()
        live: List[List[_Request]] = []
        for sh in shards:
            kept = []
            for r in sh:
                if r.deadline is not None and now > r.deadline:
                    self._fail_expired(r)
                else:
                    kept.append(r)
            live.append(kept)
        shards = live
        reqs = [r for sh in shards for r in sh]
        if not reqs:
            with self._lock:
                if self._breaker_state == "half_open":
                    # the whole batch (the probe included) expired before
                    # executing: re-open so the next submit re-probes
                    self._breaker_state = "open"
            return
        batch_id = next(self._batch_ids)
        flight = _Dispatched(batch_id, shards, reqs,
                             overlapped=bool(self._owed))
        try:
            # deterministic batch-failure injection; counted per executed
            # batch (utils/faults.py serving-dispatch site)
            fault_point("serving-dispatch")
            count = max(len(sh) for sh in shards)
            need_n = max(sum(r.n for r in sh) for sh in shards)
            need_e = max(sum(r.e for r in sh) for sh in shards)
            bucket = select_bucket(self.buckets, count, need_n, need_e)
            if bucket is None:
                raise RuntimeError(
                    "internal error: coalesced batch "
                    f"({count} graphs, {need_n} nodes, {need_e} edges) "
                    "fits no bucket — the coalescer's fill caps must "
                    "bound every batch by the largest bucket")
            flight.bucket = bucket
            # request-lifecycle spans (docs/observability.md): queue-wait
            # per request (submit -> dispatch); then the batch: collate
            # and dispatch here, fetch and unpad at completion (the
            # older `serve.forward` spans collate to fetch), all children
            # of `serve.batch`, which lists its requests and carries the
            # bucket/parity breadcrumbs the futures advertise. One
            # recorder check keeps the disabled path at a single branch
            # per batch.
            flight.rec = rec = _spans.current_recorder()
            if rec is not None:
                flight.t_disp = _spans.now()
                for r in reqs:
                    rec.add("serve.queue_wait", r.t_submit,
                            flight.t_disp - r.t_submit, "serving",
                            {"req": r.req, "batch": batch_id,
                             "parent": "serve.request"})
                flight.t_fwd = _spans.now()
            flight.outs, flight.version = self._enqueue(shards, bucket,
                                                        batch_id)
        except BaseException as e:  # noqa: BLE001 — must reach the callers
            flight.error = e
        self._owed.append(flight)

    def _complete(self) -> None:
        """Pay the oldest owed batch what it is owed: fetch (the wait for
        the device), unpad, statistics, every future's result, the
        batch's spans, the breaker's accounting. A batch that failed, at
        its dispatch or here, fails only ITS OWN futures; the dispatcher
        survives and the breaker decides whether to keep admitting."""
        flight = self._owed[0]
        try:
            self._deliver(flight)
        except BaseException as e:  # noqa: BLE001 — must reach the callers
            self._record_batch_failure()
            self._fail_batch(flight, e)
        else:
            self._record_batch_success()
        finally:
            self._owed.popleft()

    def _fail_batch(self, flight: _Dispatched, error: BaseException
                    ) -> None:
        for req in flight.reqs:
            if not req.future.done():
                req.future.set_exception(error)
                self._span_request(req.req, req.t_arrival,
                                   batch=flight.batch_id,
                                   error=type(error).__name__)

    def _deliver(self, flight: _Dispatched) -> None:
        if flight.error is not None:
            raise flight.error
        batch_id, shards, reqs = flight.batch_id, flight.shards, flight.reqs
        bucket, rec = flight.bucket, flight.rec
        outs = self._fetch(flight.outs, batch_id)
        # deterministic failure of a batch that WAS dispatched, where the
        # device's own error would surface (utils/faults.py serving-fetch
        # site), once per fetched batch
        fault_point("serving-fetch")
        if rec is not None:
            rec.add("serve.forward", flight.t_fwd,
                    _spans.now() - flight.t_fwd, "serving",
                    {"batch": batch_id, "parent": "serve.batch",
                     "bucket": [bucket.n_node, bucket.n_edge,
                                bucket.n_graph],
                     "requests": len(reqs), "parity": self.parity})
        with _spans.span("serve.unpad", "serving", batch=batch_id,
                         parent="serve.batch"):
            results = self._unpad(shards, bucket, outs)
        done = time.perf_counter()
        tot_n = sum(r.n for r in reqs)
        tot_e = sum(r.e for r in reqs)
        with self._lock:
            self.batches_run += 1
            self.batches_overlapped += flight.overlapped
            self.requests_done += len(reqs)
            self._occupancy_sum += len(reqs) / (bucket.cap_graphs *
                                                self.num_shards)
            self._real_node_slots += tot_n
            self._real_edge_slots += tot_e
            self._total_node_slots += bucket.n_node * self.num_shards
            self._total_edge_slots += bucket.n_edge * self.num_shards
            self._latencies.extend(done - r.t_arrival for r in reqs)
        for req, res in zip(reqs, results):
            req.future.bucket = bucket  # adjudication breadcrumbs: the
            req.future.parity = self.parity       # bucket this batch
            req.future.parity_rtol = self.parity_rtol  # ran on + the
            req.future.parity_atol = self.parity_atol  # parity bound
            req.future.model_version = flight.version  # + the hot-swap
            # tag: which weights actually served this request
            req.future.tier = self.tier  # + the fleet tier that
            # served it (int8 fast vs fp32 accurate; serving/fleet.py)
            req.future.set_result(res)
            if rec is not None:
                # the request ends with ITS OWN result, not with the
                # last of the batch's
                self._span_request(req.req, req.t_arrival, batch=batch_id)
        if rec is not None:
            rec.add("serve.batch", flight.t_disp,
                    _spans.now() - flight.t_disp, "serving",
                    {"batch": batch_id, "reqs": [r.req for r in reqs],
                     "bucket": [bucket.n_node, bucket.n_edge,
                                bucket.n_graph]})

    def _settle(self) -> None:
        """Before the dispatcher looks at the queue again: complete every
        owed batch that can be read without waiting (an answer is never
        held for the next batch's sake), and the oldest ones while as
        many are owed as may be: `inflight_depth`, and 1 while the
        breaker is not closed, so that a half-open probe is alone in
        flight and the request after it meets the breaker it left."""
        while self._owed:
            with self._lock:
                depth = (self.inflight_depth
                         if self._breaker_state == "closed" else 1)
            if len(self._owed) < depth and not self._owed[0].ready():
                return
            self._complete()

    def _coalesce(self, first: _Request, wait: bool = True):
        """Greedy arrival-order coalescing into per-shard bins: the
        current shard grows while the next request fits the LARGEST
        bucket's per-shard node/edge budget and per-shard graph capacity,
        then the next shard opens; the batch flushes at max_batch_size
        total requests, when every shard is full, or max_wait_ms after
        `first` was dequeued — whichever first. Returns
        (shards, leftover_or_sentinel). The loop is one
        `serve.coalesce_wait` span: the dispatcher holding the first
        request back for company."""
        big = self.buckets[-1]
        shards: List[List[_Request]] = [[first]]
        rem_n = big.cap_nodes - first.n
        rem_e = big.cap_edges - first.e
        total = 1
        deadline = time.perf_counter() + (self.max_wait_s if wait else 0.0)
        leftover = None
        with _spans.span("serve.coalesce_wait", "serving", req=first.req):
            while total < self.max_batch_size:
                timeout = deadline - time.perf_counter()
                try:
                    nxt = (self._queue.get_nowait() if timeout <= 0
                           else self._queue.get(timeout=timeout))
                except queue.Empty:
                    break
                if nxt is _SHUTDOWN:
                    leftover = nxt
                    break
                if (nxt.deadline is not None
                        and time.perf_counter() > nxt.deadline):
                    self._fail_expired(nxt)
                    continue
                if (nxt.n > rem_n or nxt.e > rem_e
                        or len(shards[-1]) >= self._shard_fill_cap):
                    if len(shards) >= self.num_shards:
                        leftover = nxt
                        break
                    shards.append([])
                    rem_n, rem_e = big.cap_nodes, big.cap_edges
                shards[-1].append(nxt)
                rem_n -= nxt.n
                rem_e -= nxt.e
                total += 1
        while len(shards) < self.num_shards:
            shards.append([])
        return shards, leftover

    def _fast_fail(self, req: _Request) -> bool:
        """Dispatcher-side admission: resolve (with an error, True) a
        dequeued request that must not enter a batch — an expired deadline,
        or a request caught in the queue behind an open breaker. Reaching
        the probe window flips the breaker to half_open and lets the
        request through as the probe."""
        if req.deadline is not None and time.perf_counter() > req.deadline:
            self._fail_expired(req)
            with self._lock:
                if self._breaker_state == "half_open":
                    # the probe expired unexecuted: re-open (the window is
                    # already past) so the next submit becomes the probe —
                    # otherwise half_open would reject everyone forever
                    self._breaker_state = "open"
            return True
        err = None
        with self._lock:
            if self._breaker_state == "open":
                if time.monotonic() < self._open_until:
                    self.circuit_rejections += 1
                    err = CircuitOpenError(
                        f"circuit open after {self.trip_count} trip(s); "
                        "request was queued before the trip")
                else:
                    self._breaker_state = "half_open"
                    self.probe_count += 1
        if err is None:
            return False
        if not req.future.done():
            req.future.set_exception(err)
            self._span_request(req.req, req.t_arrival,
                               error=type(err).__name__)
        return True

    def _loop(self):
        pending = None
        try:
            while True:
                self._settle()
                if self._owed and (self._queue.qsize() + (pending is not None)
                                   < self.max_batch_size):
                    # a batch is in flight and the queue would not fill
                    # the next one without the wait for company: that
                    # wait must not sit between this batch's device work
                    # and its futures, nor be cut short (the batches are
                    # the ones the serial path forms), so complete first
                    self._complete()
                    continue
                if pending is not None:
                    req, pending = pending, None
                elif self._owed:
                    req = self._queue.get()  # a batch's worth is queued
                else:
                    # queue empty: the dispatcher waits for a request
                    with _spans.span("serve.await_request", "serving"):
                        req = self._queue.get()
                if req is _SHUTDOWN:
                    break
                if self._fast_fail(req):
                    continue
                shards, pending = self._coalesce(req, wait=not self._owed)
                self._execute(shards)
                if pending is _SHUTDOWN:
                    break
        except BaseException as e:  # noqa: BLE001
            with self._lock:  # submit() reads _fatal under the lock
                self._fatal = e
        finally:
            # complete what is owed and drain everything still queued — a
            # shutdown (or dispatcher crash) must never leave a caller's
            # future hanging. _fatal is snapshotted under the lock once:
            # only this thread ever writes it, and the write (if any)
            # happened above
            with self._lock:
                fatal = self._fatal
            while self._owed:
                if fatal is not None:
                    self._fail_batch(self._owed.popleft(), fatal)
                else:
                    self._complete()
            while True:
                try:
                    req = self._queue.get_nowait()
                except queue.Empty:
                    break
                if req is _SHUTDOWN:
                    continue
                if fatal is not None:
                    if not req.future.done():
                        req.future.set_exception(fatal)
                        self._span_request(req.req, req.t_arrival,
                                           error=type(fatal).__name__)
                else:
                    shards, leftover = self._coalesce(req, wait=False)
                    self._execute(shards)
                    while self._owed:
                        self._complete()
                    if leftover is not None and leftover is not _SHUTDOWN:
                        self._queue.put(leftover)


class StructureSession:
    """One trajectory client's raw-structure serving handle: wraps the
    Verlet-skin NeighborList `submit_structure` consults so consecutive
    steps of the SAME trajectory share candidate caches. Obtained from
    `InferenceEngine.structure_session()`; use sequentially from one
    client (the neighbor list is stateful and not thread-safe)."""

    __slots__ = ("nlist",)

    def __init__(self, nlist):
        self.nlist = nlist

    @property
    def rebuild_fraction(self) -> float:
        """Rebuilds over updates for THIS trajectory (the engine-wide
        fraction aggregates every client)."""
        return self.nlist.rebuild_fraction
