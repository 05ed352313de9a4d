"""Serving knobs: the `Serving` config block + HYDRAGNN_SERVE_* env layer.

Precedence per knob: env var over config block over default — the same
contract as Training.batch_packing / HYDRAGNN_PACKING. All env values are
parsed STRICTLY (utils/envflags.env_strict_*): serving switches the whole
prediction path, so a typo value must warn and fall back to the config
default, never silently flip the engine on.

Config schema (top-level block, alongside "Dataset"/"NeuralNetwork"):

    "Serving": {
        "enabled": false,          # engine path in run_prediction
        "max_batch_size": 32,      # requests coalesced per dispatch
        "max_wait_ms": 5.0,        # batching window for a lone request
        "num_buckets": 0,          # 0 = full capacity ladder
        "bucket_multiple": 64,     # shape rounding (MXU-friendly)
        "max_queue": 0,            # bounded admission queue (0 = unbounded)
        "deadline_ms": 0.0,        # default per-request deadline (0 = none)
        "breaker_threshold": 5,    # consecutive batch failures to trip
        "breaker_reset_s": 30.0,   # open -> half-open probe window
        "precision": null,         # serve-side compute dtype override
        "quant_calib_samples": 32, # int8 calibration-set size
                                   # (precision="int8" only; quant/)
        "metrics_port": 0,         # /healthz + /metrics HTTP port
                                   # (0 = off; see docs/observability.md)
        "structure": false,        # raw-structure serving (submit_structure)
        "md_skin": 0.3,            # Verlet-skin width for trajectory
                                   # sessions (docs/serving.md)
        "md_farm": {               # trajectory-farm knobs (docs/serving.md
                                   # "MD farm"; engine.trajectory_farm)
            "steps_per_dispatch": 8,   # device-resident MD steps per
                                       # dispatch (K)
            "cand_headroom": 0.5       # static candidate/degree capacity
                                       # headroom over the initial builds
        },
        "publish": {               # continuous-learning publisher knobs
                                   # (docs/serving.md "Continuous loop";
                                   # serving/publish.py)
            "poll_interval_s": 1.0,    # BEST-marker poll cadence
            "mirror_every": 2,         # shadow slice: every k-th request
            "window_pairs": 8,         # pairs to adjudicate per canary
            "min_pairs": 3,            # fewer than this at timeout
                                       # aborts the canary (no quarantine)
            "window_timeout_s": 30.0,  # max canary window wall-clock
            "max_rel_err": 0.25,       # candidate-vs-incumbent output
                                       # drift bound (relative)
            "latency_factor": 3.0,     # candidate p99 budget as a factor
                                       # of max(incumbent p99, floor)
            "latency_floor_ms": 50.0   # incumbent-p99 floor for the
                                       # latency gate (noise guard)
        },
        "autoscale": {             # queue-depth autoscaler knobs
                                   # (docs/serving.md "Continuous loop";
                                   # serving/autoscale.py)
            "min_replicas": 1,
            "max_replicas": 4,
            "high_depth": 4.0,         # avg routable queue depth that
                                       # triggers scale-up
            "low_depth": 0.5,          # avg depth that triggers
                                       # scale-down
            "cooldown_s": 5.0,         # min seconds between actions
            "poll_interval_s": 1.0,
            "drain_timeout_s": 30.0    # scale-down drain bound
        },
        "fleet": {                 # replica-router knobs (docs/serving.md
                                   # "Fleet"; serving/fleet.py)
            "replicas": 1,             # engines behind the router
                                       # (<= 1 = single-engine path)
            "compile_store": null,     # persistent AOT executable store
                                       # dir (utils/devices.CompileStore);
                                       # null/"" = off
            "redispatch_max": 0,       # re-dispatch budget per request
                                       # (0 = one try per replica)
            "drain_timeout_s": 30.0,   # hot-swap per-replica drain bound
            "tier_priority_min": 0,    # priority threshold for the
                                       # accurate tier (0 = tier routing
                                       # off; fleet.TierPolicy)
            "tier_quota": 0.0,         # max accurate-tier dispatch
                                       # fraction (0 = no cap)
            "tier_fast": "int8",       # fast-tier engine tag
            "tier_accurate": "float32" # accurate-tier engine tag
        }
    }

The queue/deadline/breaker knobs are the failure-semantics layer
(docs/fault_tolerance.md): QueueFullError backpressure,
DeadlineExceededError expiry, and the dispatcher circuit breaker.

`precision` (env: HYDRAGNN_SERVE_PRECISION; "float32" | "bfloat16" |
"int8") is the serve-side compute-dtype override
(docs/mixed_precision.md): unset, the engine inherits the
train-side policy (HYDRAGNN_PRECISION / Architecture.dtype). A
reduced-precision engine relaxes the PR 3 bitwise-parity adjudication
to the documented tolerance bound — each resolved future carries the
bound (engine.py SERVE_REDUCED_RTOL/ATOL; SERVE_INT8_RTOL/ATOL for the
quantized tier). "int8" is the post-training-quantization serving tier
(quant/): run_prediction calibrates activation scales on the first
`quant_calib_samples` test samples (env: HYDRAGNN_QUANT_CALIB_SAMPLES,
strict int) and every engine serves the quantized conv stack.

`structure` (env: HYDRAGNN_SERVE_STRUCTURE) enables the raw-structure
serving path (docs/serving.md): run_prediction hands the engine the full
config so MD/relaxation/screening clients can call
``engine.submit_structure`` with raw positions instead of prebuilt
graphs. `md_skin` (env: HYDRAGNN_MD_SKIN; cutoff units) is the
Verlet-skin width trajectory sessions build their incremental neighbor
list with — wider = fewer rebuilds but more candidates per re-filter.

`fleet` (env: HYDRAGNN_FLEET_REPLICAS / HYDRAGNN_FLEET_COMPILE_STORE /
HYDRAGNN_FLEET_REDISPATCH_MAX / HYDRAGNN_FLEET_DRAIN_TIMEOUT_S, strict
parsing) configures the replica router (docs/serving.md "Fleet"):
`replicas` > 1 makes run_prediction serve through a ReplicaRouter of
that many engines (least-queue-depth dispatch, per-replica breaker
isolation, re-dispatch off dead replicas); `compile_store` points every
replica at one persistent AOT executable store so warmups load the
bucket ladder from disk.

The `tier_*` fleet knobs (env: HYDRAGNN_FLEET_TIER_PRIORITY_MIN /
HYDRAGNN_FLEET_TIER_QUOTA, strict parsing; HYDRAGNN_FLEET_TIER_FAST /
HYDRAGNN_FLEET_TIER_ACCURATE, plain strings) configure priority/quota
tier routing (docs/serving.md "Tiered fleets"; fleet.TierPolicy):
`tier_priority_min` > 0 installs a TierPolicy — requests submitted at
or above that priority prefer the `tier_accurate` replicas, the rest
prefer `tier_fast`, and `tier_quota` caps the accurate tier's dispatch
share. 0 (the default) keeps the fleet tier-blind.

`publish` (env: HYDRAGNN_PUBLISH_POLL_S / HYDRAGNN_PUBLISH_MIRROR_EVERY
/ HYDRAGNN_PUBLISH_WINDOW_PAIRS / HYDRAGNN_PUBLISH_MIN_PAIRS /
HYDRAGNN_PUBLISH_WINDOW_TIMEOUT_S / HYDRAGNN_PUBLISH_MAX_REL_ERR /
HYDRAGNN_PUBLISH_LATENCY_FACTOR / HYDRAGNN_PUBLISH_LATENCY_FLOOR_MS,
strict parsing) tunes the CheckpointPublisher's canary adjudication
(docs/serving.md "Continuous loop"): `max_rel_err` is a DRIFT bound —
candidate outputs are compared against the incumbent's on identical
mirrored samples, so it must admit a legitimate training update's
output change while rejecting a poisoned/torn candidate (NaN or
blown-up outputs compare as infinite drift).

`autoscale` (env: HYDRAGNN_AUTOSCALE_MIN / HYDRAGNN_AUTOSCALE_MAX /
HYDRAGNN_AUTOSCALE_HIGH_DEPTH / HYDRAGNN_AUTOSCALE_LOW_DEPTH /
HYDRAGNN_AUTOSCALE_COOLDOWN_S / HYDRAGNN_AUTOSCALE_POLL_S /
HYDRAGNN_AUTOSCALE_DRAIN_TIMEOUT_S, strict parsing) sizes the
QueueDepthAutoscaler: watermarks are AVERAGE queue depth over the
routable replicas; the cooldown prevents thrash between opposing
actions.

`md_farm` (env: HYDRAGNN_MD_FARM_STEPS_PER_DISPATCH /
HYDRAGNN_MD_FARM_CAND_HEADROOM, strict parsing) tunes the trajectory
farm (docs/serving.md "MD farm"): `steps_per_dispatch` trades host
round-trips against wasted device iterations after a mid-dispatch
skin-bound violation; `cand_headroom` sizes the static stacked candidate
layout over the initial builds (too small raises mid-run with an
actionable message, too large pays re-filter width for nothing).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional


@dataclasses.dataclass(frozen=True)
class Structure:
    """One raw-structure request (the `submit_structure` schema).

    * ``positions`` — [N, 3] cartesian coordinates;
    * ``node_features`` — [N, sum(Dataset.node_features.dim)] in the
      dataset's node-feature layout. Only the
      ``Variables_of_interest.input_node_features`` columns are read at
      inference; target columns may be zero-filled placeholders;
    * ``cell`` — [3, 3] lattice, required under
      ``periodic_boundary_conditions``;
    * ``graph_feats`` — optional graph-feature vector (ignored at
      inference, accepted for schema symmetry with the dataset loaders).
    """
    positions: Any
    node_features: Any
    cell: Optional[Any] = None
    graph_feats: Optional[Any] = None


@dataclasses.dataclass(frozen=True)
class MdFarm:
    """Trajectory-farm knobs (docs/serving.md "MD farm"). The contract
    surface — grids, selection rules, bucket layout — is NOT knobbed;
    these only trade throughput for memory/round-trips."""
    steps_per_dispatch: int = 8   # device-resident MD steps per dispatch
    cand_headroom: float = 0.5    # static candidate/degree capacity
    # headroom over the initial per-trajectory builds


def resolve_md_farm(config: Optional[Dict[str, Any]] = None) -> MdFarm:
    """Merge the `Serving.md_farm` block and the HYDRAGNN_MD_FARM_* env
    knobs (strict parsing — a typo warns and keeps the default). Shared
    by `InferenceEngine.trajectory_farm` and bench.py BENCH_MD_FARM so
    the precedence cannot drift."""
    from ..utils.envflags import env_strict_float, env_strict_int
    block = ((config or {}).get("Serving", {}) or {}).get("md_farm",
                                                          {}) or {}
    base = MdFarm(
        steps_per_dispatch=int(block.get("steps_per_dispatch", 8)),
        cand_headroom=float(block.get("cand_headroom", 0.5)),
    )
    return MdFarm(
        steps_per_dispatch=env_strict_int(
            "HYDRAGNN_MD_FARM_STEPS_PER_DISPATCH",
            base.steps_per_dispatch),
        cand_headroom=env_strict_float("HYDRAGNN_MD_FARM_CAND_HEADROOM",
                                       base.cand_headroom),
    )


@dataclasses.dataclass(frozen=True)
class ActiveConfig:
    """Active-learning farm knobs (docs/active_learning.md; md/active.py).
    The harvest CONTRACT — rising-edge threshold crossing on the exact
    integrator grid, content-addressed dedup — is not knobbed; these only
    size the ensemble, the threshold, and the fine-tune leg."""
    members: int = 4          # ensemble size M (member 0 unperturbed)
    eps: float = 0.02         # multiplicative head-weight perturbation
    tau: float = 0.1          # uncertainty threshold (model energy units)
    harvest_cap: int = 16     # per-trajectory harvest buffer slots
    seed: int = 0             # ensemble perturbation seed
    finetune_steps: int = 60  # optimizer steps per fine-tune round
    finetune_lr: float = 1e-3


def resolve_active(config: Optional[Dict[str, Any]] = None) -> ActiveConfig:
    """Merge the `Serving.md_active` block and the HYDRAGNN_MD_ACTIVE_*
    env knobs (strict parsing — a typo warns and keeps the default).
    `EnsembleScorer.from_config` is the consumer — deployments size the
    ensemble through config/env without code changes. bench.py's
    BENCH_ACTIVE and the examples driver carry their own bench-shape
    knobs (BENCH_ACTIVE_* / argparse) with deliberately hotter defaults
    (tau 0.0, eps 0.05) sized to DEMONSTRATE learning on the toy LJ
    fixture in a few rounds."""
    from ..utils.envflags import env_strict_float, env_strict_int
    block = ((config or {}).get("Serving", {}) or {}).get("md_active",
                                                          {}) or {}
    base = ActiveConfig(
        members=int(block.get("members", 4) or 4),
        eps=float(block.get("eps", 0.02) or 0.02),
        tau=float(block.get("tau", 0.1) or 0.1),
        harvest_cap=int(block.get("harvest_cap", 16) or 16),
        seed=int(block.get("seed", 0) or 0),
        finetune_steps=int(block.get("finetune_steps", 60) or 60),
        finetune_lr=float(block.get("finetune_lr", 1e-3) or 1e-3),
    )
    return ActiveConfig(
        members=env_strict_int("HYDRAGNN_MD_ACTIVE_MEMBERS", base.members),
        eps=env_strict_float("HYDRAGNN_MD_ACTIVE_EPS", base.eps),
        tau=env_strict_float("HYDRAGNN_MD_ACTIVE_TAU", base.tau),
        harvest_cap=env_strict_int("HYDRAGNN_MD_ACTIVE_HARVEST_CAP",
                                   base.harvest_cap),
        seed=env_strict_int("HYDRAGNN_MD_ACTIVE_SEED", base.seed),
        finetune_steps=env_strict_int("HYDRAGNN_MD_ACTIVE_FINETUNE_STEPS",
                                      base.finetune_steps),
        finetune_lr=env_strict_float("HYDRAGNN_MD_ACTIVE_FINETUNE_LR",
                                     base.finetune_lr),
    )


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Replica-router knobs (docs/serving.md "Fleet"; serving/fleet.py).
    The routing/isolation CONTRACT (least-queue-depth, exactly-once
    resolution, per-replica breakers) is not knobbed — these only size
    the fleet and its recovery budgets."""
    replicas: int = 1             # <= 1 = the single-engine path
    compile_store: Optional[str] = None  # persistent AOT store dir
    redispatch_max: int = 0       # 0 = one try per replica
    drain_timeout_s: float = 30.0
    tier_priority_min: int = 0    # 0 = tier routing off; > 0 installs a
    # TierPolicy with this priority threshold (fleet.TierPolicy)
    tier_quota: float = 0.0       # max accurate-tier dispatch fraction
    # (0 = no cap)
    tier_fast: str = "int8"       # fast-tier engine tag
    tier_accurate: str = "float32"  # accurate-tier engine tag


def resolve_fleet(config: Optional[Dict[str, Any]] = None) -> FleetConfig:
    """Merge the `Serving.fleet` block and the HYDRAGNN_FLEET_* env knobs
    (strict parsing — a typo warns and keeps the default). Shared by
    run_prediction and bench.py so the precedence cannot drift."""
    from ..utils.envflags import env_str, env_strict_float, env_strict_int
    block = ((config or {}).get("Serving", {}) or {}).get("fleet",
                                                          {}) or {}
    base = FleetConfig(
        replicas=int(block.get("replicas", 1) or 1),
        compile_store=(str(block.get("compile_store")).strip() or None
                       if block.get("compile_store") else None),
        redispatch_max=int(block.get("redispatch_max", 0) or 0),
        drain_timeout_s=float(block.get("drain_timeout_s", 30.0) or 30.0),
        tier_priority_min=int(block.get("tier_priority_min", 0) or 0),
        tier_quota=float(block.get("tier_quota", 0.0) or 0.0),
        tier_fast=str(block.get("tier_fast", "int8") or "int8"),
        tier_accurate=str(block.get("tier_accurate", "float32")
                          or "float32"),
    )
    return FleetConfig(
        replicas=env_strict_int("HYDRAGNN_FLEET_REPLICAS", base.replicas),
        compile_store=env_str("HYDRAGNN_FLEET_COMPILE_STORE",
                              base.compile_store),
        redispatch_max=env_strict_int("HYDRAGNN_FLEET_REDISPATCH_MAX",
                                      base.redispatch_max),
        drain_timeout_s=env_strict_float("HYDRAGNN_FLEET_DRAIN_TIMEOUT_S",
                                         base.drain_timeout_s),
        tier_priority_min=env_strict_int("HYDRAGNN_FLEET_TIER_PRIORITY_MIN",
                                         base.tier_priority_min),
        tier_quota=env_strict_float("HYDRAGNN_FLEET_TIER_QUOTA",
                                    base.tier_quota),
        tier_fast=env_str("HYDRAGNN_FLEET_TIER_FAST", base.tier_fast),
        tier_accurate=env_str("HYDRAGNN_FLEET_TIER_ACCURATE",
                              base.tier_accurate),
    )


@dataclasses.dataclass(frozen=True)
class PublishConfig:
    """CheckpointPublisher knobs (docs/serving.md "Continuous loop";
    serving/publish.py). The canary CONTRACT — one replica, shadow
    mirror, promote-or-quarantine, coherent-version rollback — is not
    knobbed; these only size the adjudication window and its bounds."""
    poll_interval_s: float = 1.0   # BEST-marker poll cadence
    mirror_every: int = 2          # shadow slice: every k-th request
    window_pairs: int = 8          # pairs to adjudicate per canary
    min_pairs: int = 3             # fewer at timeout = aborted canary
    window_timeout_s: float = 30.0
    max_rel_err: float = 0.25      # candidate-vs-incumbent drift bound
    latency_factor: float = 3.0    # candidate p99 <= factor *
    # max(incumbent p99, latency_floor_ms)
    latency_floor_ms: float = 50.0


def resolve_publish(config: Optional[Dict[str, Any]] = None
                    ) -> PublishConfig:
    """Merge the `Serving.publish` block and the HYDRAGNN_PUBLISH_* env
    knobs (strict parsing — a typo warns and keeps the default). Shared
    by the publisher's callers and bench.py so precedence cannot
    drift."""
    from ..utils.envflags import env_strict_float, env_strict_int
    block = ((config or {}).get("Serving", {}) or {}).get("publish",
                                                          {}) or {}
    base = PublishConfig(
        poll_interval_s=float(block.get("poll_interval_s", 1.0) or 1.0),
        mirror_every=int(block.get("mirror_every", 2) or 2),
        window_pairs=int(block.get("window_pairs", 8) or 8),
        min_pairs=int(block.get("min_pairs", 3) or 3),
        window_timeout_s=float(block.get("window_timeout_s", 30.0)
                               or 30.0),
        max_rel_err=float(block.get("max_rel_err", 0.25) or 0.25),
        latency_factor=float(block.get("latency_factor", 3.0) or 3.0),
        latency_floor_ms=float(block.get("latency_floor_ms", 50.0)
                               or 50.0),
    )
    return PublishConfig(
        poll_interval_s=env_strict_float("HYDRAGNN_PUBLISH_POLL_S",
                                         base.poll_interval_s),
        mirror_every=env_strict_int("HYDRAGNN_PUBLISH_MIRROR_EVERY",
                                    base.mirror_every),
        window_pairs=env_strict_int("HYDRAGNN_PUBLISH_WINDOW_PAIRS",
                                    base.window_pairs),
        min_pairs=env_strict_int("HYDRAGNN_PUBLISH_MIN_PAIRS",
                                 base.min_pairs),
        window_timeout_s=env_strict_float(
            "HYDRAGNN_PUBLISH_WINDOW_TIMEOUT_S", base.window_timeout_s),
        max_rel_err=env_strict_float("HYDRAGNN_PUBLISH_MAX_REL_ERR",
                                     base.max_rel_err),
        latency_factor=env_strict_float("HYDRAGNN_PUBLISH_LATENCY_FACTOR",
                                        base.latency_factor),
        latency_floor_ms=env_strict_float(
            "HYDRAGNN_PUBLISH_LATENCY_FLOOR_MS", base.latency_floor_ms),
    )


@dataclasses.dataclass(frozen=True)
class AutoscaleConfig:
    """QueueDepthAutoscaler knobs (docs/serving.md "Continuous loop";
    serving/autoscale.py). Scale-down always goes through drain and
    scale-up always reconciles to the published version — only the
    watermarks/bounds are knobbed."""
    min_replicas: int = 1
    max_replicas: int = 4
    high_depth: float = 4.0   # avg routable queue depth -> scale up
    low_depth: float = 0.5    # avg routable queue depth -> scale down
    cooldown_s: float = 5.0   # min seconds between actions
    poll_interval_s: float = 1.0
    drain_timeout_s: float = 30.0
    signal: str = "queue_depth"  # "queue_depth" | "p99_latency" — the
    # pressure signal the watermarks compare against (p99_latency keys
    # off the fleet-wide p99 already in `router.stats()`: the SLO mode)
    high_p99_ms: float = 500.0   # p99 latency -> scale up (SLO mode)
    low_p99_ms: float = 50.0     # p99 latency -> scale down (SLO mode)


def resolve_autoscale(config: Optional[Dict[str, Any]] = None
                      ) -> AutoscaleConfig:
    """Merge the `Serving.autoscale` block and the HYDRAGNN_AUTOSCALE_*
    env knobs (strict parsing — a typo warns and keeps the default)."""
    from ..utils.envflags import (env_strict_choice, env_strict_float,
                                  env_strict_int)
    block = ((config or {}).get("Serving", {}) or {}).get("autoscale",
                                                          {}) or {}
    base = AutoscaleConfig(
        min_replicas=int(block.get("min_replicas", 1) or 1),
        max_replicas=int(block.get("max_replicas", 4) or 4),
        high_depth=float(block.get("high_depth", 4.0) or 4.0),
        low_depth=float(block.get("low_depth", 0.5) or 0.5),
        cooldown_s=float(block.get("cooldown_s", 5.0) or 5.0),
        poll_interval_s=float(block.get("poll_interval_s", 1.0) or 1.0),
        drain_timeout_s=float(block.get("drain_timeout_s", 30.0) or 30.0),
        signal=str(block.get("signal", "queue_depth") or "queue_depth"),
        high_p99_ms=float(block.get("high_p99_ms", 500.0) or 500.0),
        low_p99_ms=float(block.get("low_p99_ms", 50.0) or 50.0),
    )
    return AutoscaleConfig(
        min_replicas=env_strict_int("HYDRAGNN_AUTOSCALE_MIN",
                                    base.min_replicas),
        max_replicas=env_strict_int("HYDRAGNN_AUTOSCALE_MAX",
                                    base.max_replicas),
        high_depth=env_strict_float("HYDRAGNN_AUTOSCALE_HIGH_DEPTH",
                                    base.high_depth),
        low_depth=env_strict_float("HYDRAGNN_AUTOSCALE_LOW_DEPTH",
                                   base.low_depth),
        cooldown_s=env_strict_float("HYDRAGNN_AUTOSCALE_COOLDOWN_S",
                                    base.cooldown_s),
        poll_interval_s=env_strict_float("HYDRAGNN_AUTOSCALE_POLL_S",
                                         base.poll_interval_s),
        drain_timeout_s=env_strict_float(
            "HYDRAGNN_AUTOSCALE_DRAIN_TIMEOUT_S", base.drain_timeout_s),
        signal=env_strict_choice(
            "HYDRAGNN_AUTOSCALE_SIGNAL",
            {"queue_depth": "queue_depth", "p99_latency": "p99_latency"},
            base.signal),
        high_p99_ms=env_strict_float("HYDRAGNN_AUTOSCALE_HIGH_P99_MS",
                                     base.high_p99_ms),
        low_p99_ms=env_strict_float("HYDRAGNN_AUTOSCALE_LOW_P99_MS",
                                    base.low_p99_ms),
    )


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    enabled: bool = False
    max_batch_size: int = 32
    max_wait_ms: float = 5.0
    num_buckets: int = 0          # 0 = full ladder (1, 2, 4, ..., max)
    bucket_multiple: int = 64
    max_queue: int = 0            # 0 = unbounded admission queue
    deadline_ms: float = 0.0      # 0 = no default per-request deadline
    breaker_threshold: int = 5    # 0 disables the circuit breaker
    breaker_reset_s: float = 30.0
    precision: Optional[str] = None  # None = inherit the train-side policy
    quant_calib_samples: int = 32  # int8 calibration-set size (the first
    # N test samples; precision="int8" only — see quant/calibrate.py)
    metrics_port: int = 0         # 0 = no HTTP endpoint; > 0 = bind that
    # port on loopback for /healthz + /metrics (telemetry/http.py)
    structure: bool = False       # raw-structure serving (submit_structure)
    md_skin: float = 0.3          # Verlet-skin width for trajectory
    # sessions (cutoff units; docs/serving.md raw-structure section)


def resolve_serving(config: Optional[Dict[str, Any]]) -> ServingConfig:
    """Merge the `Serving` config block and the HYDRAGNN_SERVE_* env knobs
    into one ServingConfig. Shared by run_prediction and bench.py so the
    precedence cannot drift."""
    from ..train.precision import PRECISION_CHOICES, canonical_precision
    from ..utils.envflags import (env_strict_choice, env_strict_flag,
                                  env_strict_float, env_strict_int)
    block = (config or {}).get("Serving", {}) or {}
    base = ServingConfig(
        enabled=bool(block.get("enabled", False)),
        max_batch_size=int(block.get("max_batch_size", 32)),
        max_wait_ms=float(block.get("max_wait_ms", 5.0)),
        num_buckets=int(block.get("num_buckets", 0)),
        bucket_multiple=int(block.get("bucket_multiple", 64)),
        max_queue=int(block.get("max_queue", 0)),
        deadline_ms=float(block.get("deadline_ms", 0.0)),
        breaker_threshold=int(block.get("breaker_threshold", 5)),
        breaker_reset_s=float(block.get("breaker_reset_s", 30.0)),
        precision=canonical_precision(block.get("precision")),
        quant_calib_samples=int(block.get("quant_calib_samples", 32)
                                or 32),
        metrics_port=int(block.get("metrics_port", 0) or 0),
        structure=bool(block.get("structure", False)),
        md_skin=float(block.get("md_skin", 0.3)),
    )
    return ServingConfig(
        enabled=env_strict_flag("HYDRAGNN_SERVE", base.enabled),
        max_batch_size=env_strict_int("HYDRAGNN_SERVE_MAX_BATCH",
                                      base.max_batch_size),
        max_wait_ms=env_strict_float("HYDRAGNN_SERVE_MAX_WAIT_MS",
                                     base.max_wait_ms),
        num_buckets=env_strict_int("HYDRAGNN_SERVE_BUCKETS",
                                   base.num_buckets),
        bucket_multiple=env_strict_int("HYDRAGNN_SERVE_BUCKET_MULTIPLE",
                                       base.bucket_multiple),
        max_queue=env_strict_int("HYDRAGNN_SERVE_MAX_QUEUE",
                                 base.max_queue),
        deadline_ms=env_strict_float("HYDRAGNN_SERVE_DEADLINE_MS",
                                     base.deadline_ms),
        breaker_threshold=env_strict_int("HYDRAGNN_SERVE_BREAKER_THRESHOLD",
                                         base.breaker_threshold),
        breaker_reset_s=env_strict_float("HYDRAGNN_SERVE_BREAKER_RESET_S",
                                         base.breaker_reset_s),
        precision=env_strict_choice("HYDRAGNN_SERVE_PRECISION",
                                    PRECISION_CHOICES, base.precision),
        quant_calib_samples=env_strict_int("HYDRAGNN_QUANT_CALIB_SAMPLES",
                                           base.quant_calib_samples),
        metrics_port=env_strict_int("HYDRAGNN_SERVE_METRICS_PORT",
                                    base.metrics_port),
        structure=env_strict_flag("HYDRAGNN_SERVE_STRUCTURE",
                                  base.structure),
        md_skin=env_strict_float("HYDRAGNN_MD_SKIN", base.md_skin),
    )
