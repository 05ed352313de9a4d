"""Fleet-grade serving: a replica router over N inference engines.

One engine = one process was the PR 3-10 serving story: a dispatcher
death, a tripped breaker, or a model upgrade takes the whole service
down, and every fresh process recompiles the whole bucket ladder at
warmup. This module composes the existing primitives — per-engine
circuit breakers and admission contracts (PR 4), health()/metrics
(PR 7), the BEST/LATEST checkpoint contract (PR 4), and the persistent
AOT compile store (utils/devices.CompileStore) — into a fleet that
survives replica death and model upgrades with zero lost futures
(docs/serving.md "Fleet"):

* ``ReplicaRouter`` fronts N ``InferenceEngine`` replicas, each built by
  the caller's ``engine_factory(idx)`` with its own device/shard set and
  its OWN breaker — failure isolation is per replica: one replica's
  tripped breaker or dead dispatcher never rejects traffic the others
  can serve.
* Dispatch is least-queue-depth over the routable replicas (breaker
  closed, dispatcher alive, not draining), ties broken by replica index
  — a pure function of the health snapshot.
* A request that fails for REPLICA-level reasons (dead dispatcher,
  breaker rejection, a failed batch) is re-dispatched to another
  replica, bounded by ``max_redispatch`` attempts; the router-level
  future resolves EXACTLY ONCE — a "dead" replica's late resolution is
  detected and dropped (execution is at-least-once under a kill,
  resolution is exactly-once; adjudicated under injected
  ``replica-kill`` faults by tests + BENCH_SERVE_FLEET). Request-level
  failures (deadline expiry, schema validation) resolve immediately —
  they would fail identically anywhere.
* Unhealthy replicas are ejected from rotation by their own breaker
  state; once a breaker's probe window elapses the router routes ONE
  live request to it as the half-open probe (the engine admits exactly
  one fleet-wide per open replica — the hammer test pins it). A
  successful probe closes the breaker and the replica re-enters
  rotation; a failed one re-opens it and the probe request re-dispatches
  to a healthy replica.
* ``hot_swap`` upgrades the model with zero downtime: replicas swap one
  at a time (the rest keep serving) — drain (no new dispatches, wait
  for in-flight requests) → atomic ``engine.swap_variables`` → back in
  rotation. ``hot_swap_from_checkpoint`` feeds it from the PR 4
  BEST/LATEST contract. The version tag is echoed on every future and
  in ``/healthz``. The ``swap-fail`` fault site makes a swap fail
  cleanly BEFORE mutation: the old version keeps serving, no request
  fails.
* ``TierPolicy`` routes by REQUEST PRIORITY across serving tiers
  (docs/serving.md "Tiered fleets"): every engine carries a ``tier``
  tag (the int8 fast students vs the fp32 accurate teacher,
  serving/engine.py), and a request submitted at or above
  ``priority_min`` prefers the accurate tier — bounded by ``quota``,
  the max fraction of total dispatches the accurate tier may absorb
  (exceeding it downgrades the request to the fast tier, counted in
  ``tier_downgrades``). Availability beats affinity: when the
  preferred tier has no routable replica the request falls back
  cross-tier (``tier_fallbacks``) instead of failing — zero lost
  futures is the fleet invariant, tiers only bias placement. The tier
  that actually served is echoed on every future (``.tier``) next to
  ``.bucket``/``.model_version``.
* ``kill_replica`` is the deterministic stand-in for process death
  (driven by the ``replica-kill`` fault site): the replica leaves
  rotation immediately, its in-flight requests re-dispatch, and
  ``restart_replica`` builds a replacement engine from the factory —
  which warms from the persistent compile store in seconds instead of
  recompiling the ladder (0 fresh compiles on a populated store).
* The continuous-learning layer (docs/serving.md "Continuous loop")
  composes on four router primitives added for it: ``set_canary`` /
  ``swap_one`` / ``install_mirror`` give the CheckpointPublisher a
  single out-of-rotation replica serving a deterministic shadow slice
  of live traffic for candidate-vs-incumbent adjudication;
  ``quarantine_version`` bans a rolled-back candidate fleet-wide; and
  ``add_replica`` / ``retire_replica`` let the QueueDepthAutoscaler
  grow/shrink the fleet (scale-up joins disk-warm ON the published
  version via ``record_published`` reconciliation, scale-down drains
  first so zero futures are lost).

Lock discipline (docs/static_analysis.md): this file is in hydralint's
lock-discipline scope — `# guarded-by: _lock` state is machine-checked,
and no blocking call sits under the lock. Engine calls (submit/health/
swap) are made OUTSIDE the router lock; the lock order is always
router -> engine, and engines never call back into the router while
holding their own lock (futures resolve outside the engine lock), so
the two lock classes cannot deadlock.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence

from ..telemetry.registry import get_registry
from ..utils.faults import InjectedFault, fault_point
from .engine import (CircuitOpenError, DeadlineExceededError,
                     InferenceEngine, QueueFullError, ServingError)


class FleetUnavailableError(ServingError):
    """No routable replica: every replica is dead, shut down, or
    breaker-open inside its window (and none is due a probe)."""


class SwapFailedError(ServingError):
    """hot_swap could not swap one or more replicas (the report names
    them); the failed replicas keep serving the OLD version."""


@dataclass(frozen=True)
class TierPolicy:
    """Priority/quota routing between serving tiers (docs/serving.md
    "Tiered fleets").

    `fast`/`accurate` name the two engine tier tags (the engine's
    ``tier`` ctor arg, defaulting to its compute dtype — so an
    int8-quantized student replica is tier "int8" and the fp32 teacher
    is "float32" out of the box). A request with
    ``priority >= priority_min`` prefers the accurate tier; everything
    else prefers the fast tier. ``quota`` in (0, 1] caps the fraction
    of TOTAL fleet dispatches the accurate tier may absorb — a
    priority request over quota is downgraded to the fast tier
    (counted) rather than queued, so a burst of "important" traffic
    cannot starve the teacher replicas into a latency cliff. quota=0
    disables the cap. The policy only BIASES placement: when the
    preferred tier has no routable replica the router falls back
    cross-tier (counted) — availability beats affinity."""

    fast: str = "int8"
    accurate: str = "float32"
    priority_min: int = 1
    quota: float = 0.0

    def __post_init__(self):
        if not (0.0 <= float(self.quota) <= 1.0):
            raise ValueError(
                f"TierPolicy.quota={self.quota!r} must be in [0, 1] — "
                "it is the max fraction of dispatches the accurate "
                "tier may absorb (0 disables the cap)")
        if str(self.fast) == str(self.accurate):
            raise ValueError(
                f"TierPolicy fast and accurate tiers are both "
                f"{self.fast!r} — a one-tier fleet needs no policy")


class _RouterRequest:
    """One router-level request: the caller's future plus the
    re-dispatch bookkeeping. `resolved` flips exactly once under the
    router lock — the idempotency point for late results from killed
    replicas."""

    __slots__ = ("sample", "future", "deadline_ms", "priority",
                 "attempts", "tried", "resolved", "wait_deadline")

    def __init__(self, sample, deadline_ms, priority=0):
        self.sample = sample
        self.future: Future = Future()
        self.deadline_ms = deadline_ms
        self.priority = int(priority)
        self.attempts = 0   # dispatches consumed (first + re-dispatches)
        self.tried = set()  # replica idxs that failed this request
        #                     (membership only — never iterated)
        self.resolved = False
        self.wait_deadline = None  # ONE transient-unavailability wait
        # budget for the request's whole lifetime (set on first
        # _await_routable) — per-call deadlines would reset on every
        # retry and turn the bound into an unbounded spin


class _Replica:
    """Router-side view of one engine replica. Mutable fields are
    guarded by the ROUTER lock (they are router bookkeeping, not engine
    state — the engine's own counters live behind its own lock)."""

    __slots__ = ("idx", "engine", "alive", "draining", "inflight",
                 "dispatched", "canary", "retired")

    def __init__(self, idx: int, engine: InferenceEngine):
        self.idx = idx
        self.engine = engine
        self.alive = True
        self.draining = False
        self.inflight: Dict[_RouterRequest, Future] = {}
        self.dispatched = 0  # router-side dispatch count (health())
        self.canary = False  # out of primary rotation; serves only the
        # mirrored shadow slice during a publish adjudication window
        self.retired = False  # scaled down through drain (autoscale);
        # the slot stays and restart_replica revives it disk-warm


class ReplicaRouter:
    """N-replica serving fleet: least-queue-depth dispatch, per-replica
    failure isolation, exactly-once request resolution under replica
    death, zero-downtime hot-swap, compile-store-warmed restarts.

    `engine_factory(idx)` builds replica `idx`'s InferenceEngine —
    device placement, shard set, and the shared compile store are the
    factory's choice; the router only requires the replicas to accept
    the same request schema. All replicas are built (and optionally
    warmed) at construction."""

    def __init__(self, engine_factory: Callable[[int], InferenceEngine],
                 num_replicas: int, *,
                 max_redispatch: Optional[int] = None,
                 drain_timeout_s: float = 30.0,
                 unavailable_wait_s: float = 5.0,
                 tier_policy: Optional[TierPolicy] = None):
        if num_replicas < 1:
            raise ValueError("ReplicaRouter needs num_replicas >= 1")
        self._factory = engine_factory
        self.tier_policy = tier_policy  # immutable after construction
        self._replicas: List[_Replica] = [
            _Replica(i, engine_factory(i)) for i in range(num_replicas)]
        # one try per replica by default: N replicas = N total dispatch
        # attempts = N - 1 RE-dispatches. A request that failed on every
        # replica has seen the whole fleet — surface the REAL error (the
        # last batch failure), not an extra retry's availability noise
        self.max_redispatch = (int(max_redispatch)
                               if max_redispatch is not None
                               else max(num_replicas - 1, 0))
        self.drain_timeout_s = float(drain_timeout_s)
        # how long submit() waits for a drain/swap to finish before
        # fast-failing when it left no routable replica (single-replica
        # fleets hot-swapping); multi-replica fleets never wait
        self.unavailable_wait_s = float(unavailable_wait_s)
        self._lock = threading.Lock()
        self._closed = False  # guarded-by: _lock
        self.requests_done = 0  # guarded-by: _lock
        self.redispatch_count = 0  # guarded-by: _lock
        self.duplicate_resolutions = 0  # guarded-by: _lock — late results
        #   from killed/raced replicas dropped by the exactly-once gate
        self.stale_failures = 0  # guarded-by: _lock — failures from a
        #   dispatch kill_replica already superseded, dropped (the live
        #   re-dispatched copy owns the outcome)
        self.kill_count = 0  # guarded-by: _lock
        self.restart_count = 0  # guarded-by: _lock
        self.swap_attempts = 0  # guarded-by: _lock
        self.swap_failures = 0  # guarded-by: _lock
        self.tier_fallbacks = 0  # guarded-by: _lock — requests placed on
        #   the NON-preferred tier because the preferred one had no
        #   routable replica (availability beats affinity)
        self.tier_downgrades = 0  # guarded-by: _lock — priority requests
        #   routed to the fast tier because the accurate tier was over
        #   its dispatch quota
        self._tier_dispatches: Dict[str, int] = {}  # guarded-by: _lock —
        #   dispatch counts per engine tier tag (the quota denominator)
        self.shadow_mirrored = 0  # guarded-by: _lock — requests copied
        #   to the canary replica by the publish mirror
        self.shadow_dropped = 0  # guarded-by: _lock — mirror copies the
        #   canary could not accept (never fails the primary request)
        self.retire_count = 0  # guarded-by: _lock — replicas scaled down
        #   through drain (retire_replica)
        self.add_count = 0  # guarded-by: _lock — replicas added to the
        #   fleet after construction (add_replica)
        self._quarantined: Dict[str, str] = {}  # guarded-by: _lock —
        #   version -> reason; hot_swap/swap_one refuse these versions
        self._mirror = None  # guarded-by: _lock — active shadow-mirror
        #   hook: {"replica", "every", "on_pair"} while a canary window
        #   is open, else None
        self._mirror_seq = 0  # guarded-by: _lock — deterministic slice
        #   counter: every `every`-th submit is mirrored
        self._published = None  # guarded-by: _lock — (variables, version)
        #   of the last fleet-wide publish; replicas added/restarted
        #   later reconcile to it before joining rotation, so a scale-up
        #   can never spawn a stale-version replica
        self._metrics_server = None

    # ------------------------------------------------------------ client API

    def submit(self, sample, deadline_ms: Optional[float] = None,
               priority: int = 0) -> Future:
        """Route one request to the best replica; returns a Future that
        resolves exactly once — with the result of whichever replica
        finally served it (re-dispatched transparently across replica
        death / breaker rejection / batch failure), or with the terminal
        error. The resolved future carries the serving replica's
        breadcrumbs (`.bucket`, `.parity*`, `.model_version`, `.tier`)
        plus `.replica` (its index). `priority` only matters under a
        `tier_policy`: at or above its `priority_min` the request
        prefers the accurate tier (subject to quota), below it the fast
        tier — with cross-tier fallback either way."""
        rr = _RouterRequest(sample, deadline_ms, priority=priority)
        mirror = None
        with self._lock:
            if self._mirror is not None:
                self._mirror_seq += 1
                if self._mirror_seq % self._mirror["every"] == 0:
                    mirror = dict(self._mirror)
        self._dispatch(rr)
        if mirror is not None:
            self._mirror_submit(mirror, rr)
        return rr.future

    def predict(self, samples: Sequence, timeout=None):
        """Submit all samples, wait, return results in order."""
        futs = [self.submit(s) for s in samples]
        return [f.result(timeout=timeout) for f in futs]

    def warmup(self) -> List[dict]:
        """Warm every live replica's bucket ladder; per-replica report of
        {replica, compiled, store_hits, fresh, devices} — on a populated
        compile store, `fresh` is 0 (the BENCH_SERVE_FLEET adjudication);
        `devices` names where the replica's programs execute."""
        reports = []
        for rep in self._replicas:
            with self._lock:
                skip = not rep.alive
            if skip:
                continue
            rep.engine.warmup()
            st = rep.engine.stats()
            reports.append({"replica": rep.idx,
                            "compiled": st["compile_count"],
                            "store_hits": st["compile_store_hits"],
                            "fresh": st["compile_fresh"],
                            "devices": ",".join(
                                str(d) for d in rep.engine.devices)})
        return reports

    def health(self) -> dict:
        """Fleet liveness aggregate: "serving" while at least one replica
        is routable (alive + breaker not rejecting), else "unavailable";
        "shutdown" after shutdown(). Includes every replica's own
        health() (model_version/uptime_s included) keyed by index, so
        one probe shows the whole fleet including the hot-swap version
        tags."""
        with self._lock:
            closed = self._closed
            reps = list(self._replicas)
            alive = {r.idx: r.alive for r in reps}
            draining = {r.idx: r.draining for r in reps}
            dispatched = {r.idx: r.dispatched for r in reps}
            canary = {r.idx: r.canary for r in reps}
            retired = {r.idx: r.retired for r in reps}
            counters = {
                "requests_done": self.requests_done,
                "redispatches": self.redispatch_count,
                "duplicate_resolutions": self.duplicate_resolutions,
                "stale_failures": self.stale_failures,
                "kills": self.kill_count,
                "restarts": self.restart_count,
                "swap_attempts": self.swap_attempts,
                "swap_failures": self.swap_failures,
                "tier_fallbacks": self.tier_fallbacks,
                "tier_downgrades": self.tier_downgrades,
                "tier_dispatches": {
                    t: self._tier_dispatches[t]
                    for t in sorted(self._tier_dispatches)},
                "shadow_mirrored": self.shadow_mirrored,
                "shadow_dropped": self.shadow_dropped,
                "retires": self.retire_count,
                "adds": self.add_count,
                "quarantined_versions": sorted(self._quarantined),
            }
        replicas = {}
        routable = 0
        for rep in reps:
            h = rep.engine.health()
            h["alive"] = alive[rep.idx]
            h["draining"] = draining[rep.idx]
            h["dispatched"] = dispatched[rep.idx]
            h["canary"] = canary[rep.idx]
            h["retired"] = retired[rep.idx]
            # routable mirrors _pick EXACTLY: a half_open replica is
            # NOT routable (its probe owns the breaker), and a canary
            # serves only the shadow slice — /healthz must never say
            # "serving" while every dispatch would fail
            if (alive[rep.idx] and not draining[rep.idx]
                    and not canary[rep.idx]
                    and h["dispatcher_alive"]
                    and (h["state"] == "closed"
                         or h.get("breaker_probe_due"))):
                routable += 1
            replicas[str(rep.idx)] = h
        state = ("shutdown" if closed
                 else "serving" if routable else "unavailable")
        out = {"state": state, "num_replicas": len(reps),
               "routable_replicas": routable, "replicas": replicas}
        out.update(counters)
        return out

    def stats(self) -> dict:
        """Fleet-aggregate service stats: counter sums plus TRUE
        fleet-wide latency percentiles computed from the concatenated
        raw per-replica latencies (per-replica percentiles cannot be
        combined)."""
        from ..utils.profiling import latency_percentiles
        with self._lock:
            reps = list(self._replicas)
            out = {
                "requests_done": self.requests_done,
                "redispatches": self.redispatch_count,
                "duplicate_resolutions": self.duplicate_resolutions,
                "stale_failures": self.stale_failures,
                "kills": self.kill_count,
                "restarts": self.restart_count,
                "tier_fallbacks": self.tier_fallbacks,
                "tier_downgrades": self.tier_downgrades,
                "tier_dispatches": {
                    t: self._tier_dispatches[t]
                    for t in sorted(self._tier_dispatches)},
                "shadow_mirrored": self.shadow_mirrored,
                "shadow_dropped": self.shadow_dropped,
                "retires": self.retire_count,
                "adds": self.add_count,
                "quarantined_versions": sorted(self._quarantined),
                "canary_replicas": sorted(r.idx for r in self._replicas
                                          if r.canary),
            }
        latencies: List[float] = []
        per_replica = {}
        for rep in reps:
            st = rep.engine.stats()
            latencies.extend(rep.engine.latency_snapshot())
            per_replica[str(rep.idx)] = st
        out["replicas"] = per_replica
        out["requests"] = sum(st["requests"]
                              for st in per_replica.values())
        out["batches"] = sum(st["batches"] for st in per_replica.values())
        out["batches_overlapped"] = sum(st["batches_overlapped"]
                                        for st in per_replica.values())
        out.update(latency_percentiles(latencies))
        return out

    def reset_stats(self) -> None:
        """Zero every live replica's service counters (compile caches and
        the router's lifecycle counters untouched) — bench phases report
        closed-loop and open-loop stats separately."""
        with self._lock:
            reps = list(self._replicas)
        for rep in reps:
            rep.engine.reset_stats()

    def start_metrics_server(self, host: str = "127.0.0.1", port: int = 0):
        """ONE aggregated HTTP endpoint for the whole fleet
        (telemetry/http.py): GET /healthz -> the fleet health()
        aggregate (200 while >= 1 replica is routable), GET /metrics ->
        per-replica-labeled Prometheus gauges (breaker state one-hot per
        replica, queue depths, model-version info) + fleet counters +
        the process registry. port=0 binds an ephemeral port — N
        replicas' engines and one router can all serve metrics from a
        single process without colliding; the bound port is
        `server.port`."""
        if self._metrics_server is not None:
            return self._metrics_server
        from ..telemetry.http import serve_fleet_metrics
        self._metrics_server = serve_fleet_metrics(self, host=host,
                                                   port=port)
        return self._metrics_server

    def shutdown(self, wait: bool = True):
        """Stop routing and shut every replica down (each drains its own
        queue — no hung callers). Idempotent."""
        server, self._metrics_server = self._metrics_server, None
        if server is not None:
            server.stop()
        with self._lock:
            self._closed = True
            reps = list(self._replicas)
        for rep in reps:
            rep.engine.shutdown(wait=wait)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.shutdown(wait=True)
        return False

    # -------------------------------------------------- failure / lifecycle

    def kill_replica(self, idx: int) -> int:
        """Abrupt replica death (the ``replica-kill`` fault site's
        effect, also callable directly by chaos drivers): the replica
        leaves rotation immediately and every router request in flight
        on it re-dispatches to a healthy replica. Returns the number of
        re-dispatched requests.

        The dying engine is shut down in the background — in-process it
        may still resolve some of its futures, and the exactly-once gate
        drops those late results (`duplicate_resolutions` counts them):
        execution is at-least-once under a kill, resolution is
        exactly-once."""
        with self._lock:
            rep = self._replicas[idx]
            if not rep.alive:
                return 0
            rep.alive = False
            self.kill_count += 1
            victims = list(rep.inflight)
            rep.inflight.clear()
        get_registry().counter_inc(
            "serve.fleet_kills_total",
            help="replicas removed from rotation by kill_replica")
        # non-blocking: the dying dispatcher drains on its own thread;
        # whatever it still resolves is dropped by the exactly-once gate
        rep.engine.shutdown(wait=False)
        moved = 0
        for rr in victims:
            with self._lock:
                if rr.resolved:
                    continue
                rr.tried.add(idx)
                self.redispatch_count += 1
            moved += 1
            get_registry().counter_inc(
                "serve.fleet_redispatches_total",
                help="requests re-dispatched off a dead/failed replica")
            self._dispatch(rr)
        return moved

    def restart_replica(self, idx: int, warmup: bool = True) -> dict:
        """Replace a dead (or live) replica with a fresh engine from the
        factory and return its warmup report — with a shared persistent
        compile store the replacement warms from disk: 0 fresh compiles,
        seconds instead of a ladder recompile (docs/serving.md
        "Fleet"). Restarting a LIVE replica re-dispatches its in-flight
        requests exactly like a kill — the old engine's drain-time
        resolutions are stale, so without the re-dispatch those callers
        would hang."""
        engine = self._factory(idx)
        # join on the fleet's published version BEFORE entering rotation
        # — a disk-warm scale-up or post-swap restart must not serve a
        # stale factory version
        self._reconcile_engine(engine)
        with self._lock:
            rep = self._replicas[idx]
            old_engine, was_alive = rep.engine, rep.alive
            victims = list(rep.inflight)
            rep.engine = engine
            rep.alive = True
            rep.draining = False
            rep.retired = False
            rep.canary = False
            rep.inflight = {}
            self.restart_count += 1
        if was_alive:
            old_engine.shutdown(wait=False)
        for rr in victims:
            with self._lock:
                if rr.resolved:
                    continue
                self.redispatch_count += 1
            self._dispatch(rr)
        report = {"replica": idx, "compiled": 0, "store_hits": 0,
                  "fresh": 0, "warmup_s": 0.0}
        if warmup:
            t0 = time.perf_counter()
            engine.warmup()
            st = engine.stats()
            report.update(compiled=st["compile_count"],
                          store_hits=st["compile_store_hits"],
                          fresh=st["compile_fresh"],
                          warmup_s=time.perf_counter() - t0)
        return report

    def drain_replica(self, idx: int,
                      timeout_s: Optional[float] = None) -> None:
        """Take one replica out of rotation and wait until its in-flight
        requests (router-tracked futures AND its queued engine requests)
        have resolved. The caller re-admits via `undrain_replica` (or
        hot_swap, which wraps drain -> swap -> undrain). Raises
        TimeoutError when the drain outlives `timeout_s`."""
        deadline = time.monotonic() + (self.drain_timeout_s
                                       if timeout_s is None
                                       else float(timeout_s))
        with self._lock:
            rep = self._replicas[idx]
            rep.draining = True
        while True:
            with self._lock:
                inflight = len(rep.inflight)
            depth = rep.engine.health()["queue_depth"]
            if inflight == 0 and depth == 0:
                return
            if time.monotonic() >= deadline:
                with self._lock:
                    rep.draining = False  # re-admit: a wedged drain must
                    # not silently keep capacity out of rotation
                raise TimeoutError(
                    f"replica {idx} did not drain in time "
                    f"({inflight} in flight, queue depth {depth})")
            time.sleep(0.002)

    def undrain_replica(self, idx: int) -> None:
        with self._lock:
            self._replicas[idx].draining = False

    # --------------------------------------------- canary / publish plumbing

    def set_canary(self, idx: int, on: bool = True) -> None:
        """Flag one replica as the canary: it leaves the primary
        rotation (no `_pick` dispatches) but stays alive to serve the
        mirrored shadow slice. The CheckpointPublisher owns the
        transitions; flags are surfaced in health()/metrics."""
        with self._lock:
            self._replicas[idx].canary = bool(on)

    def swap_one(self, idx: int, variables, version: str) -> dict:
        """Drain exactly one replica, swap its variables atomically, and
        re-admit it — the single-replica unit hot_swap composes, exposed
        for the publisher's canary/promote/rollback steps. Raises
        ValueError for a dead/retired replica or a quarantined target
        version; swap failures (the ``swap-fail`` site, a mismatched
        checkpoint) propagate after the replica is re-admitted on its
        OLD version — a failed swap never costs capacity."""
        with self._lock:
            if str(version) in self._quarantined:
                reason = self._quarantined[str(version)]
                raise ValueError(
                    f"version {version!r} is quarantined ({reason}) — "
                    "clear it via quarantine_version bookkeeping before "
                    "re-publishing")
            rep = self._replicas[idx]
            if not rep.alive or rep.retired:
                raise ValueError(
                    f"replica {idx} is "
                    f"{'retired' if rep.retired else 'dead'} — cannot "
                    "swap; restart_replica revives it first")
            self.swap_attempts += 1
        self.drain_replica(idx)
        try:
            old = rep.engine.swap_variables(variables, version)
        except (InjectedFault, ValueError, TimeoutError,
                RuntimeError):
            with self._lock:
                self.swap_failures += 1
            raise
        finally:
            self.undrain_replica(idx)
        return {"replica": idx, "from": old, "to": str(version)}

    def install_mirror(self, idx: int, every: int,
                       on_pair: Callable[[Future, Future], None]) -> None:
        """Start mirroring a deterministic slice of traffic to the
        canary: every `every`-th submit() is ALSO placed on replica
        `idx`'s engine (shadow copy — its outcome never affects the
        primary future), and `on_pair(primary_future, shadow_future)` is
        called so the publisher can adjudicate candidate vs incumbent
        on identical samples."""
        if every < 1:
            raise ValueError(f"mirror every={every!r} must be >= 1")
        with self._lock:
            self._mirror = {"replica": int(idx), "every": int(every),
                            "on_pair": on_pair}
            self._mirror_seq = 0

    def remove_mirror(self) -> None:
        with self._lock:
            self._mirror = None

    def _mirror_submit(self, mirror: dict, rr: _RouterRequest) -> None:
        """Place the shadow copy on the canary engine (OUTSIDE the
        router lock — engine calls never sit under it). A canary that
        cannot accept (draining mid-swap, queue full, dead) drops the
        copy and counts it; the primary request is never affected."""
        with self._lock:
            rep = self._replicas[mirror["replica"]]
            ok = rep.alive and rep.canary and not rep.draining
        if ok:
            try:
                shadow = rep.engine.submit(rr.sample,
                                           deadline_ms=rr.deadline_ms)
            except (ServingError, RuntimeError):
                ok = False
        if not ok:
            with self._lock:
                self.shadow_dropped += 1
            return
        with self._lock:
            self.shadow_mirrored += 1
        try:
            mirror["on_pair"](rr.future, shadow)
        except Exception:  # noqa: BLE001 — adjudication bookkeeping must
            # never break the serving path
            import logging
            logging.getLogger("hydragnn_tpu").warning(
                "shadow-mirror on_pair callback raised", exc_info=True)

    def quarantine_version(self, version: str, reason: str = "") -> None:
        """Ban a model version from the fleet: hot_swap/swap_one refuse
        it and the publisher skips it on re-poll — a poisoned candidate
        is rolled back ONCE, not once per poll."""
        with self._lock:
            self._quarantined[str(version)] = str(reason)
        get_registry().counter_inc(
            "serve.fleet_quarantines_total",
            help="model versions quarantined after a failed canary")

    def quarantined_versions(self) -> Dict[str, str]:
        with self._lock:
            return dict(self._quarantined)

    def record_published(self, variables, version: str) -> None:
        """Record the fleet-wide published weights: replicas added or
        restarted later reconcile to this version before joining
        rotation (scale-up during/after a publish must not spawn a
        stale-version replica). hot_swap records it automatically on a
        fully-successful roll; the publisher records after a promote."""
        with self._lock:
            self._published = (variables, str(version))

    def _reconcile_engine(self, engine) -> None:
        """Swap a freshly built engine to the fleet's published version
        before it joins rotation (no-op when none is recorded or the
        factory already builds the current version)."""
        with self._lock:
            published = self._published
        if published is None:
            return
        variables, version = published
        if getattr(engine, "model_version", None) != version:
            engine.swap_variables(variables, version)

    # ----------------------------------------------------------- autoscaling

    def add_replica(self, warmup: bool = True) -> dict:
        """Grow the fleet by one replica built from the factory — the
        autoscaler's scale-up. With a shared persistent compile store
        the newcomer warms from disk (0 fresh compiles) and it joins
        rotation on the fleet's published version. Returns the warmup
        report (same shape as restart_replica's). Single-scaler
        contract: concurrent add_replica calls are not supported (the
        autoscaler is the one writer; a raced slot raises)."""
        with self._lock:
            if self._closed:
                raise RuntimeError("ReplicaRouter is shut down")
            idx = len(self._replicas)
        engine = self._factory(idx)
        self._reconcile_engine(engine)
        with self._lock:
            if len(self._replicas) != idx:
                raise RuntimeError(
                    "concurrent add_replica detected — the autoscaler "
                    "is the single scale writer")
            self._replicas.append(_Replica(idx, engine))
            self.add_count += 1
        get_registry().counter_inc(
            "serve.fleet_adds_total",
            help="replicas added to the fleet by add_replica")
        report = {"replica": idx, "compiled": 0, "store_hits": 0,
                  "fresh": 0, "warmup_s": 0.0}
        if warmup:
            t0 = time.perf_counter()
            engine.warmup()
            st = engine.stats()
            report.update(compiled=st["compile_count"],
                          store_hits=st["compile_store_hits"],
                          fresh=st["compile_fresh"],
                          warmup_s=time.perf_counter() - t0)
        return report

    def retire_replica(self, idx: int,
                       timeout_s: Optional[float] = None) -> dict:
        """Scale one replica down THROUGH DRAIN — the autoscaler's
        scale-down. The replica leaves rotation, its queue empties (so
        zero futures are lost), then its engine shuts down; the slot is
        flagged `retired` and restart_replica revives it disk-warm on
        the next scale-up. Raises ValueError for a dead/retired/canary
        replica and TimeoutError when the drain outlives `timeout_s`
        (the replica is re-admitted — retry later)."""
        with self._lock:
            rep = self._replicas[idx]
            if not rep.alive or rep.retired:
                raise ValueError(f"replica {idx} is already "
                                 f"{'retired' if rep.retired else 'dead'}")
            if rep.canary:
                raise ValueError(
                    f"replica {idx} is the canary — a publish "
                    "adjudication owns it; retire another replica")
        self.drain_replica(idx, timeout_s)
        # drain_replica returns with `draining` still set, so no new
        # dispatch can land between the drain and the flags below
        with self._lock:
            rep.alive = False
            rep.retired = True
            rep.draining = False
            self.retire_count += 1
        rep.engine.shutdown(wait=False)
        get_registry().counter_inc(
            "serve.fleet_retires_total",
            help="replicas scaled down through drain by retire_replica")
        return {"replica": idx, "retired": True}

    def hot_swap(self, variables, version: str,
                 raise_on_failure: bool = True) -> dict:
        """Zero-downtime rolling model upgrade: for each live replica —
        drain (the REST keep serving) -> atomic ``swap_variables`` ->
        back into rotation. No request fails because of the swap:
        requests in flight on the draining replica complete on the old
        weights, requests arriving during its drain route to the other
        replicas, and the version tag on every future names the weights
        that actually served it.

        A failed swap (the ``swap-fail`` fault site, a mismatched
        checkpoint) leaves THAT replica serving the old version and is
        reported in `failed`; with `raise_on_failure` a SwapFailedError
        summarizes them after the roll completes (never mid-roll — a
        partial fleet on the new version plus an exception would be the
        worst of both)."""
        with self._lock:
            if str(version) in self._quarantined:
                reason = self._quarantined[str(version)]
                raise ValueError(
                    f"version {version!r} is quarantined ({reason}) — "
                    "refusing to roll it out")
            self.swap_attempts += 1
            reps = [r for r in self._replicas if r.alive]
        report = {"version": str(version), "replicas": {}, "failed": []}
        for rep in reps:
            try:
                self.drain_replica(rep.idx)
                try:
                    old = rep.engine.swap_variables(variables, version)
                    report["replicas"][str(rep.idx)] = {
                        "from": old, "to": str(version)}
                finally:
                    self.undrain_replica(rep.idx)
            except (InjectedFault, ValueError, TimeoutError,
                    RuntimeError) as exc:
                with self._lock:
                    self.swap_failures += 1
                report["failed"].append(
                    {"replica": rep.idx, "error":
                     f"{type(exc).__name__}: {exc}"})
                import logging
                logging.getLogger("hydragnn_tpu").warning(
                    "hot-swap to %s failed on replica %d (%s); the old "
                    "version keeps serving there", version, rep.idx, exc)
        get_registry().counter_inc(
            "serve.fleet_swaps_total",
            help="hot-swap rolls attempted across the fleet")
        if not report["failed"]:
            self.record_published(variables, version)
        elif raise_on_failure:
            # the report names BOTH sides of the mixed-version fleet so
            # an operator (or the publisher's rollback) knows exactly
            # which replicas to re-swap
            on_new = sorted(int(i) for i in report["replicas"])
            on_old = sorted(f["replica"] for f in report["failed"])
            exc = SwapFailedError(
                f"hot-swap to {version!r} failed on "
                f"{len(report['failed'])} replica(s): {report['failed']} "
                f"— MIXED-VERSION fleet: replicas {on_new} serve "
                f"{version!r}, replicas {on_old} keep the old version; "
                "fix the checkpoint and re-run hot_swap, or roll the "
                f"{on_new or 'swapped'} replicas back via swap_one")
            exc.report = report
            raise exc
        return report

    def hot_swap_from_checkpoint(self, state_template, log_name: str,
                                 path: str = "./logs",
                                 which: str = "best",
                                 version: Optional[str] = None) -> dict:
        """hot_swap fed from the PR 4 checkpoint contract: restore the
        BEST (or LATEST) committed checkpoint for `log_name` onto
        `state_template` (a TrainState matching the serving
        architecture) and roll it out. The version tag defaults to
        "<which>:step_<n>" so /healthz and every future name the exact
        checkpoint serving."""
        from ..utils.checkpoint import (UncommittedCheckpointError,
                                        load_best_model,
                                        load_existing_model,
                                        marker_target, verify_checkpoint)
        if which not in ("best", "latest"):
            raise ValueError(
                f"which={which!r} — hot_swap_from_checkpoint restores "
                "'best' (the BEST marker) or 'latest' (the LATEST marker)")
        # COMMITTED-only hardening: a marker can name a step dir whose
        # writer died mid-save (or is still writing). Refuse it with an
        # actionable error NAMING the dir instead of falling through to
        # "no checkpoint found" — the states are operationally different
        target = marker_target(log_name, path=path, which=which)
        if target is not None and not verify_checkpoint(target):
            raise UncommittedCheckpointError(
                f"the {which.upper()} marker for run '{log_name}' names "
                f"{target}, which has no COMMITTED marker (a writer died "
                "mid-save or is still writing) — refusing to hot-swap a "
                "torn state. Wait for the in-flight save "
                "(utils.checkpoint.wait_for_checkpoints) or repoint/"
                "delete the marker, then retry")
        if which == "best":
            state = load_best_model(state_template, log_name, path=path)
        else:
            state = load_existing_model(state_template, log_name, path=path)
        if state is None:
            raise FileNotFoundError(
                f"no verified {which.upper()} checkpoint for run "
                f"'{log_name}' under {path}")
        if version is None:
            version = f"{which}:step_{int(state.step)}"
        variables = {"params": state.params,
                     "batch_stats": state.batch_stats}
        return self.hot_swap(variables, version)

    # ------------------------------------------------------------- dispatch

    def _pick(self, rr: _RouterRequest) -> Optional[_Replica]:
        """The routing policy, a pure function of the health snapshot:
        probe-due replicas first (ONE request buys back a whole
        replica's capacity; the engine admits exactly one probe), then
        the closed-breaker replica with the smallest queue depth, ties
        by index. Replicas this request already failed on are avoided
        until only they remain. Under a `tier_policy` the candidate set
        is first narrowed to the request's preferred tier; only when
        that tier has no routable replica does the scan widen to the
        rest of the fleet (a counted fallback) — a tier preference must
        never turn a servable request into a FleetUnavailableError."""
        with self._lock:
            candidates = [r for r in self._replicas
                          if r.alive and not r.draining and not r.canary]
        untried = [r for r in candidates if r.idx not in rr.tried]
        if untried:
            candidates = untried
        preferred = self._preferred_tier(rr)
        if preferred is None:
            return self._pick_from(candidates)
        pref = [r for r in candidates
                if getattr(r.engine, "tier", None) == preferred]
        chosen = self._pick_from(pref) if pref else None
        if chosen is not None:
            return chosen
        rest = [r for r in candidates if r not in pref]
        chosen = self._pick_from(rest)
        if chosen is not None:
            with self._lock:
                self.tier_fallbacks += 1
            get_registry().counter_inc(
                "serve.fleet_tier_fallbacks_total",
                help="requests served by the non-preferred tier because "
                     "the preferred tier had no routable replica")
        return chosen

    def _pick_from(self, candidates: List[_Replica]
                   ) -> Optional[_Replica]:
        """Probe-due first, then min-queue-depth closed, ties by index,
        over an explicit candidate list (dead replicas found during the
        health scan are marked dead as a side effect)."""
        closed = []
        probe_due = []
        for rep in candidates:
            h = rep.engine.health()
            if h["state"] == "shutdown" or not h["dispatcher_alive"]:
                self._mark_dead(rep)
                continue
            if h["state"] == "closed":
                closed.append((h["queue_depth"], rep.idx, rep))
            elif h["state"] == "open" and h["breaker_probe_due"]:
                probe_due.append(rep)
        if probe_due:
            return probe_due[0]
        if closed:
            return min(closed)[2]
        return None

    def _preferred_tier(self, rr: _RouterRequest) -> Optional[str]:
        """The tier tag this request should land on, or None when no
        policy is installed. A priority request over the accurate
        tier's dispatch quota is DOWNGRADED here — it prefers the fast
        tier for its whole lifetime rather than queueing on the
        teacher, and `tier_downgrades` counts the decision once per
        pick so operators can see quota pressure."""
        pol = self.tier_policy
        if pol is None:
            return None
        if rr.priority < pol.priority_min:
            return pol.fast
        if pol.quota > 0.0:
            with self._lock:
                acc = self._tier_dispatches.get(pol.accurate, 0)
                total = sum(self._tier_dispatches.values())
            # would THIS dispatch push the accurate share over quota?
            if total > 0 and (acc + 1) / (total + 1) > pol.quota:
                with self._lock:
                    self.tier_downgrades += 1
                get_registry().counter_inc(
                    "serve.fleet_tier_downgrades_total",
                    help="priority requests routed to the fast tier "
                         "because the accurate tier was over quota")
                return pol.fast
        return pol.accurate

    def _mark_dead(self, rep: _Replica) -> None:
        with self._lock:
            rep.alive = False

    def _dispatch(self, rr: _RouterRequest) -> None:
        """Place `rr` on a replica (or resolve it with the terminal
        error). Runs on the submitting thread for fresh requests and on
        a replica's dispatcher thread for re-dispatches — never holds
        the router lock across an engine call."""
        last_err: Optional[BaseException] = None
        while True:
            with self._lock:
                closed = self._closed
            if closed:
                self._resolve(rr, exc=RuntimeError(
                    "ReplicaRouter is shut down"))
                return
            try:
                # deterministic chaos: replica-kill@k kills the replica
                # the k-th router dispatch selects (utils/faults.py)
                fault_point("replica-kill")
                kill = False
            except InjectedFault:
                kill = True
            rep = self._pick(rr)
            if rep is None:
                if self._await_routable(rr):
                    continue
                self._resolve(rr, exc=FleetUnavailableError(
                    "no routable replica (all dead, draining, or "
                    "breaker-open)" + (f"; last error: {last_err}"
                                       if last_err else "")))
                return
            if kill:
                # the selected replica dies before this request lands on
                # it — its in-flight requests re-dispatch; this request
                # just re-picks (it was never registered there)
                self.kill_replica(rep.idx)
                continue
            tier = getattr(rep.engine, "tier", None)
            with self._lock:
                if not rep.alive:  # killed between _pick and here
                    continue
                rep.inflight[rr] = None  # registered BEFORE submit: a
                # kill landing mid-submit re-dispatches this request
                # instead of stranding it on the dead engine
                rep.dispatched += 1
                rr.attempts += 1
                if tier is not None:  # the quota denominator counts
                    # REGISTERED dispatches, not completions — quota
                    # bounds load placed on the tier, including load
                    # still in its queue
                    self._tier_dispatches[tier] = (
                        self._tier_dispatches.get(tier, 0) + 1)
            try:
                fut = rep.engine.submit(rr.sample,
                                        deadline_ms=rr.deadline_ms)
            except (QueueFullError, CircuitOpenError) as exc:
                with self._lock:
                    rep.inflight.pop(rr, None)
                    rr.tried.add(rep.idx)
                last_err = exc
                if self._budget_spent(rr):
                    self._resolve(rr, exc=exc)
                    return
                continue
            except RuntimeError as exc:
                # dispatcher died / engine shut down underneath us:
                # the replica is gone, not the request
                with self._lock:
                    rep.inflight.pop(rr, None)
                    rr.tried.add(rep.idx)
                self._mark_dead(rep)
                last_err = exc
                if self._budget_spent(rr):
                    self._resolve(rr, exc=exc)
                    return
                continue
            with self._lock:
                if rr in rep.inflight:
                    rep.inflight[rr] = fut
            fut.add_done_callback(
                lambda f, rr=rr, rep=rep: self._on_result(rr, rep, f))
            return

    def _budget_spent(self, rr: _RouterRequest) -> bool:
        # first dispatch is free; re-dispatches consume the budget
        with self._lock:
            return rr.attempts > self.max_redispatch

    def _await_routable(self, rr: _RouterRequest) -> bool:
        """When nothing is routable only TRANSIENTLY — a drain/swap in
        progress, or a half-open probe in flight (it resolves to closed
        or to a re-probeable open in moments) — wait briefly instead of
        failing the request. Returns True to retry the pick; False when
        the fleet is genuinely down (dead replicas, open breakers not
        yet due). The wait budget is PER REQUEST, not per call — the
        dispatch loop re-enters here after every failed pick, and a
        fresh deadline each time would wait forever on a wedged
        probe/drain."""
        if rr.wait_deadline is None:
            rr.wait_deadline = time.monotonic() + self.unavailable_wait_s
        while time.monotonic() < rr.wait_deadline:
            with self._lock:
                alive = [r for r in self._replicas
                         if r.alive and not r.canary]
                transient = any(r.draining for r in alive)
            if not transient:
                transient = any(
                    r.engine.health()["state"] == "half_open"
                    for r in alive)
            if not transient:
                return False  # genuinely unavailable — fail fast
            time.sleep(0.002)
            with self._lock:
                ready = [r for r in self._replicas
                         if r.alive and not r.draining and not r.canary]
            if ready:
                return True  # re-pick: it may now be closed/probe-due
        return False

    def _on_result(self, rr: _RouterRequest, rep: _Replica,
                   fut: Future) -> None:
        """Replica future resolved: settle the router future exactly
        once, or re-dispatch a replica-level failure. Runs on the
        replica's dispatcher thread with NO locks held by the engine."""
        with self._lock:
            registered = rr in rep.inflight
            rep.inflight.pop(rr, None)
            if rr.resolved:
                self.duplicate_resolutions += 1
                return
        exc = fut.exception()
        if exc is None:
            self._resolve(rr, result=fut.result(), source=fut,
                          replica=rep.idx)
            return
        if not registered:
            # kill_replica already moved this request off this replica:
            # the live re-dispatched copy owns the outcome, and a stale
            # failure from the dying dispatcher must neither burn the
            # re-dispatch budget nor resolve the future with an error a
            # concurrent live copy is about to beat
            with self._lock:
                self.stale_failures += 1
            return
        if isinstance(exc, (DeadlineExceededError, ValueError)):
            # request-level: it would fail identically on any replica
            # (the deadline is already gone / the schema is wrong)
            self._resolve(rr, exc=exc)
            return
        # replica-level (dead dispatcher, breaker, failed batch):
        # re-dispatch while the budget lasts
        with self._lock:
            rr.tried.add(rep.idx)
        if self._budget_spent(rr):
            self._resolve(rr, exc=exc)
            return
        with self._lock:
            self.redispatch_count += 1
        get_registry().counter_inc(
            "serve.fleet_redispatches_total",
            help="requests re-dispatched off a dead/failed replica")
        self._dispatch(rr)

    def _resolve(self, rr: _RouterRequest, result=None, exc=None,
                 source: Optional[Future] = None,
                 replica: Optional[int] = None) -> bool:
        """The exactly-once gate: the first resolution wins, every later
        one is counted and dropped."""
        with self._lock:
            if rr.resolved:
                self.duplicate_resolutions += 1
                return False
            rr.resolved = True
            self.requests_done += 1
        if exc is not None:
            rr.future.set_exception(exc)
            return True
        if source is not None:
            # carry the serving engine's breadcrumbs out to the caller
            for attr in ("bucket", "parity", "parity_rtol", "parity_atol",
                         "model_version", "tier", "rebuilt",
                         "graph_build_ms"):
                if hasattr(source, attr):
                    setattr(rr.future, attr, getattr(source, attr))
        if replica is not None:
            rr.future.replica = replica
        rr.future.set_result(result)
        return True
