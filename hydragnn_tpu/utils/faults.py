"""Deterministic fault injection: the spine of the fault-tolerance layer.

Long multi-node campaigns hit preemption, node loss, and flaky filesystems
as a matter of course (DistGNN arxiv 2104.06700 §6, GNNPipe arxiv
2308.10087 §5: at scale the limiting factor shifts from step throughput to
surviving interruptions without losing work). Recovery code that only runs
when real hardware misbehaves is recovery code that has never run — so
every recovery path in this repo is driven by a *deterministic* fault
plan: named failure sites fire at exact invocation indices, and the tier-1
tests assert the recovery outcome (bitwise-identical resumed trajectories,
zero lost serving futures) rather than hoping for it.

Plan grammar (``HYDRAGNN_FAULT_PLAN`` env / ``Training.fault_plan``)::

    plan  := entry (';' entry)*
    entry := site '@' index (',' index)*
    site  := checkpoint-write | loader-fetch | forward-step
             | serving-dispatch | serving-fetch | replica-kill | swap-fail
             | trial-kill | trial-hang | trial-spawn-fail
             | rank-kill | rank-hang | rank-spawn-fail
    index := non-negative int — the 0-based invocation count of that site

Example: ``forward-step@7;serving-dispatch@2,5`` kills the 8th training
step and fails the 3rd and 6th serving dispatches. Each site keeps its own
monotone counter (per installed plan), so a plan is a pure function of the
call sequence — two identical runs fault at identical points.

Faults raise ``InjectedFault``; the ``loader-fetch`` site raises
``InjectedTransientIOError`` (an ``OSError`` subclass) so it exercises the
loader's transient-I/O retry path — a single listed index is recovered by
the retry, while ``attempts`` consecutive indices exhaust it and surface.

Parsing is STRICT in the envflags sense:
a malformed plan or unknown site warns and installs NOTHING — a typo must
degrade to "no faults injected", never to a surprise injection.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Dict, FrozenSet, List, Optional, Tuple

SITES = ("checkpoint-write", "loader-fetch", "forward-step",
         "serving-dispatch", "serving-fetch", "replica-kill", "swap-fail",
         "trial-kill", "trial-hang", "trial-spawn-fail",
         "rank-kill", "rank-hang", "rank-spawn-fail")
# ``serving-dispatch`` fires on the engine's dispatcher thread, once per
# batch, before the batch is collated; ``serving-fetch`` there too, once
# per batch that WAS dispatched, as its outputs are read (where a
# device's own error surfaces): the failure of a batch that another may
# already be running behind.
# Fleet-level sites (docs/fault_tolerance.md, serving/fleet.py):
# ``replica-kill`` fires once per ReplicaRouter dispatch and abruptly
# kills the replica the router selected for that request (its in-flight
# requests re-dispatch to a healthy replica, each resolving exactly
# once); ``swap-fail`` fires once per InferenceEngine.swap_variables and
# makes that hot-swap fail cleanly BEFORE any state mutated (the old
# model version keeps serving).
# Trial-level sites (docs/hpo.md, hpo/supervisor.py): each is consulted
# exactly once per trial at its FIRST launch — first launches happen in
# trial-id order and retries never consult again, so index k
# deterministically names the k-th registered trial no matter how
# retries interleave under concurrency. ``trial-spawn-fail`` makes
# trial k's first launch fail before a child exists (the scheduler
# rejected the job);
# ``trial-hang`` makes trial k stop making progress so the heartbeat
# watchdog must kill it; ``trial-kill`` makes the supervisor SIGKILL
# trial k at its first committed checkpoint (preemption mid-run). All
# three recover through the same bounded retry + resume-from-LATEST
# path.
# Rank-level sites (docs/fault_tolerance.md "Elastic multi-process
# training", elastic/supervisor.py): each is consulted exactly once per
# RANK LAUNCH — the JobSupervisor launches generations sequentially and
# the ranks of a generation in rank order, so consultation index k
# deterministically names the k-th rank launch of the whole job (gen 0
# consumes indices 0..W-1 for ranks 0..W-1, the first restart consumes
# the next W' indices, and so on). ``rank-spawn-fail`` makes that rank's
# launch fail before a child exists; ``rank-hang`` makes that rank stop
# progressing mid-training (every peer then wedges in the next
# collective — the shape only a COORDINATED abort recovers);
# ``rank-kill`` makes the supervisor SIGKILL that rank at its first
# committed checkpoint of the generation. All three recover through the
# same coordinated-abort + whole-job restart-from-LATEST path.


class InjectedFault(RuntimeError):
    """A deterministic failure fired by the active FaultPlan."""


class InjectedTransientIOError(InjectedFault, OSError):
    """Injected at the loader-fetch site: looks like transient filesystem
    I/O to the retry layer (an OSError), so retries genuinely recover it."""


@dataclasses.dataclass
class FaultPlan:
    """Named failure sites firing at fixed invocation indices.

    ``fault_point(site)`` increments the site's counter and raises when the
    current index is listed. Counters are per-plan (installing a plan
    resets them) and thread-safe — loader-fetch fires on collation worker
    threads, serving-dispatch and serving-fetch on the dispatcher
    thread."""

    injections: Dict[str, FrozenSet[int]]

    def __post_init__(self):
        self._counts: Dict[str, int] = {s: 0 for s in self.injections}
        self._fired: List[Tuple[str, int]] = []
        self._lock = threading.Lock()

    def fault_point(self, site: str) -> None:
        hits = self.injections.get(site)
        if hits is None:
            return
        with self._lock:
            idx = self._counts[site]
            self._counts[site] = idx + 1
            fire = idx in hits
            if fire:
                self._fired.append((site, idx))
        if fire:
            if site == "loader-fetch":
                raise InjectedTransientIOError(
                    f"injected fault: {site}@{idx}")
            raise InjectedFault(f"injected fault: {site}@{idx}")

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def fired(self) -> List[Tuple[str, int]]:
        with self._lock:
            return list(self._fired)

    def spec(self) -> str:
        """Canonical plan string (round-trips through parse_fault_plan)."""
        return ";".join(
            f"{site}@{','.join(str(i) for i in sorted(idxs))}"
            for site, idxs in sorted(self.injections.items()))


def parse_fault_plan(spec: str) -> FaultPlan:
    """Parse the plan grammar; raises ValueError on malformed input or an
    unknown site (resolve_fault_plan wraps this with warn-and-ignore)."""
    injections: Dict[str, FrozenSet[int]] = {}
    for entry in spec.split(";"):
        entry = entry.strip()
        if not entry:
            continue
        if "@" not in entry:
            raise ValueError(
                f"fault-plan entry {entry!r} has no '@' (grammar: "
                "site@idx[,idx...])")
        site, _, idx_part = entry.partition("@")
        site = site.strip()
        if site not in SITES:
            raise ValueError(
                f"unknown fault site {site!r} (known: {', '.join(SITES)})")
        idxs = []
        for tok in idx_part.split(","):
            tok = tok.strip()
            if not tok.isdigit():
                raise ValueError(
                    f"fault-plan index {tok!r} for site {site!r} is not a "
                    "non-negative integer")
            idxs.append(int(tok))
        if not idxs:
            raise ValueError(f"fault-plan entry {entry!r} lists no indices")
        injections[site] = injections.get(site, frozenset()) | \
            frozenset(idxs)
    if not injections:
        raise ValueError("fault plan is empty")
    return FaultPlan(injections)


def resolve_fault_plan(train_cfg=None) -> Optional[FaultPlan]:
    """HYDRAGNN_FAULT_PLAN env over Training.fault_plan; None when neither
    is set. Strict: a malformed spec warns and yields None — a typo plan
    must degrade to no injection, never a surprise one."""
    from .envflags import env_is_set, env_str
    spec = env_str("HYDRAGNN_FAULT_PLAN")
    origin = "HYDRAGNN_FAULT_PLAN"
    # a SET-but-empty env is "explicitly no plan" and must mask a
    # config-level plan, not fall back to it
    if spec is None and not env_is_set("HYDRAGNN_FAULT_PLAN") and train_cfg:
        spec = train_cfg.get("fault_plan")
        origin = "Training.fault_plan"
    if spec is None or not str(spec).strip():
        return None
    try:
        return parse_fault_plan(str(spec))
    except ValueError as exc:
        import logging
        logging.getLogger("hydragnn_tpu").warning(
            "%s=%r is not a valid fault plan (%s); injecting nothing",
            origin, spec, exc)
        return None


_ACTIVE: Optional[FaultPlan] = None


def install_fault_plan(plan: Optional[FaultPlan]) -> Optional[FaultPlan]:
    """Set (or clear, with None) the process-wide active plan; returns it.
    Counters start fresh — install-per-run is the determinism contract."""
    global _ACTIVE
    if plan is not None:
        # fresh counters even when re-installing the same object
        plan.__post_init__()
    _ACTIVE = plan
    return plan


def active_fault_plan() -> Optional[FaultPlan]:
    return _ACTIVE


def fault_point(site: str) -> None:
    """Hot-path hook: no-op (one None check) unless a plan is installed."""
    plan = _ACTIVE
    if plan is not None:
        plan.fault_point(site)
