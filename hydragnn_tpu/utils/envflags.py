"""Uniform parsing for the HYDRAGNN_* env-flag layer
(reference: the flags enumerated at SURVEY.md §5.6 /
hydragnn distributed.py:126-141, train_validate_test.py:46,177,475,640)."""
from __future__ import annotations

import math
import os

_FALSY = ("", "0", "false", "no", "off")


def env_flag(name: str, default: bool = False) -> bool:
    """Boolean env flag: unset -> default; '0'/'false'/'no'/'off' (any
    case) -> False; anything else -> True."""
    val = os.getenv(name)
    if val is None:
        return default
    return val.strip().lower() not in _FALSY


def env_is_set(name: str) -> bool:
    """True when the variable is present in the environment at all —
    even empty. For knobs where set-but-empty means "explicitly off"
    (masking a config-level default) rather than "unset"
    (HYDRAGNN_FAULT_PLAN= must disable a Training.fault_plan, not fall
    back to it)."""
    return os.getenv(name) is not None


def env_str(name: str, default=None):
    """String env knob: unset or whitespace-only -> `default`, otherwise
    the stripped value. The sanctioned spelling for free-form string
    knobs (paths, host:port addresses, plan specs) — hydralint's
    loose-env-read rule requires every env read outside this module to go
    through an envflags helper, and a free-form knob has no stricter
    grammar to enforce than "non-empty"."""
    val = os.getenv(name)
    if val is None:
        return default
    val = val.strip()
    return val if val else default


_TRUTHY_STRICT = ("1", "true", "on")


def env_strict_flag(name: str, default: bool = False) -> bool:
    """Boolean env flag that only accepts explicit truthy values
    ('1'/'true'/'on', any case) as True. Unlike `env_flag`, an
    unrecognized value (a typo like 'ture') does NOT silently enable the
    feature — it logs a warning and returns the default. Use for flags
    that switch in experimental code paths."""
    val = os.getenv(name)
    if val is None:
        return default
    v = val.strip().lower()
    if v in _TRUTHY_STRICT:
        return True
    if v in _FALSY:
        return False
    import logging
    logging.getLogger("hydragnn_tpu").warning(
        "%s=%r is not a recognized boolean (use 1/true/on or 0/false/off); "
        "treating as %s", name, val, default)
    return default


def env_strict_choice(name: str, choices, default=None):
    """String env knob restricted to a canonical choice set. `choices`
    maps accepted (lowercased) spellings to canonical values (e.g.
    {"bf16": "bfloat16", "bfloat16": "bfloat16"}). An unrecognized value
    warns and returns `default` instead of taking effect: for the
    mixed-precision knobs (HYDRAGNN_PRECISION / HYDRAGNN_SERVE_PRECISION)
    a typo must never silently change the compute dtype."""
    val = os.getenv(name)
    if val is None or not val.strip():
        return default
    v = val.strip().lower()
    if v in choices:
        return choices[v]
    import logging
    logging.getLogger("hydragnn_tpu").warning(
        "%s=%r is not one of %s; treating as %r", name, val,
        sorted(set(choices)), default)
    return default


def env_int(name: str, default=None):
    val = os.getenv(name)
    if val is None or not val.strip():
        return default
    return int(val)


def _env_strict_number(name: str, default, conv, kind: str):
    val = os.getenv(name)
    if val is None or not val.strip():
        return default
    try:
        return conv(val.strip())
    except ValueError:
        import logging
        logging.getLogger("hydragnn_tpu").warning(
            "%s=%r is not %s; treating as %r", name, val, kind, default)
        return default


def env_strict_int(name: str, default=None):
    """Integer env knob that warns and falls back to `default` on an
    unparseable value instead of raising mid-startup — the numeric
    counterpart of `env_strict_flag` for serving/packing knobs that must
    never take effect from a typo."""
    return _env_strict_number(name, default, int, "an integer")


def env_strict_float(name: str, default=None):
    """Float counterpart of `env_strict_int`."""
    return _env_strict_number(name, default, float, "a number")


def resolve_packing(train_cfg) -> bool:
    """Budget-packed batching knob (docs/packing.md): the HYDRAGNN_PACKING
    env overrides Training.batch_packing (default off). Strict parsing —
    packing switches batch composition and (multi-process) the data
    distribution contract, so a typo value must warn and fall back, not
    silently enable it. Shared by run_training and bench.py so the
    precedence can't drift."""
    default = bool(train_cfg.get("batch_packing", False))
    if os.getenv("HYDRAGNN_PACKING") is not None:
        return env_strict_flag("HYDRAGNN_PACKING", default)
    return default


def resolve_pack_lookahead(train_cfg) -> "int | None":
    """Bounded first-fit-decreasing window for the pack planner:
    HYDRAGNN_PACK_LOOKAHEAD env over Training.pack_lookahead; None defers
    to the planner default."""
    la = env_int("HYDRAGNN_PACK_LOOKAHEAD")
    if la is not None:
        return la
    la = train_cfg.get("pack_lookahead")
    return None if la is None else int(la)


_LOADER_RETRY_MEMO: dict = {}


def resolve_loader_retries() -> "tuple[int, float]":
    """(attempts, backoff_base_s) for the loader's transient-I/O retry
    (datasets/async_loader.fetch_samples): HYDRAGNN_LOADER_RETRIES bounds
    the total tries per sample fetch (default 3, min 1 — a 0 would mean
    "never even try"), HYDRAGNN_LOADER_RETRY_BACKOFF_S the exponential
    backoff base (default 0.05s, doubling per retry, capped at 1s by the
    retry loop). Strict parsing: a typo value warns and keeps the default
    rather than silently disabling recovery.

    Memoized on the raw env strings: this runs per batch fetch on the
    collation hot path, and a typo value must warn once per distinct
    value, not once per batch."""
    key = (os.getenv("HYDRAGNN_LOADER_RETRIES"),
           os.getenv("HYDRAGNN_LOADER_RETRY_BACKOFF_S"))
    hit = _LOADER_RETRY_MEMO.get(key)
    if hit is None:
        attempts = env_strict_int("HYDRAGNN_LOADER_RETRIES", 3)
        backoff = env_strict_float("HYDRAGNN_LOADER_RETRY_BACKOFF_S", 0.05)
        hit = (max(int(attempts), 1), max(float(backoff), 0.0))
        _LOADER_RETRY_MEMO[key] = hit  # a handful of distinct values per
        # process at most (None + explicit test settings)
    return hit


def resolve_preproc_workers(train_cfg=None) -> int:
    """Preprocessing worker-pool size (docs/preprocessing.md): the
    HYDRAGNN_PREPROC_WORKERS env overrides Training.preprocess_workers
    (default 0 = serial; 0 and 1 are equivalent by the determinism
    contract). Strict parsing — a typo value warns and keeps the default
    instead of silently changing the build path."""
    w = env_strict_int("HYDRAGNN_PREPROC_WORKERS")
    if w is None and train_cfg:
        w = train_cfg.get("preprocess_workers")
    return max(int(w), 0) if w is not None else 0


def resolve_preproc_cache_dir(ds_cfg=None) -> "str | None":
    """Preprocessed-sample cache directory (docs/preprocessing.md):
    HYDRAGNN_PREPROC_CACHE_DIR env over Dataset.preprocessed_cache_dir;
    unset/empty = cache off."""
    d = os.getenv("HYDRAGNN_PREPROC_CACHE_DIR")
    if d is None and ds_cfg:
        d = ds_cfg.get("preprocessed_cache_dir")
    d = (d or "").strip()
    return d or None


def resolve_telemetry(train_cfg=None):
    """Unified-telemetry knobs (docs/observability.md) -> TelemetryConfig.

    Precedence per knob: HYDRAGNN_* env over the Training.Telemetry config
    block over defaults (off). STRICT parsing throughout — telemetry must
    never flip on (or point its artifacts somewhere surprising) from a
    typo value. Resolved HERE, outside the telemetry package, so
    telemetry/ itself stays clean under the traced-env-read lint
    (tools/check_traced_env_reads.py covers it).

    Knobs:
      HYDRAGNN_TELEMETRY            enable the session (JSONL + Chrome
                                    trace + registry exports)
      HYDRAGNN_TELEMETRY_DIR        artifact directory (default:
                                    <run_dir>/telemetry)
      HYDRAGNN_DEVICE_TRACE         opt-in jax.profiler bracket around
                                    one epoch (heavyweight)
      HYDRAGNN_DEVICE_TRACE_EPOCH   which epoch the bracket captures
                                    (default 0)
    """
    from ..telemetry.session import TelemetryConfig
    block = (train_cfg or {}).get("Telemetry", {}) or {}
    out_dir = os.getenv("HYDRAGNN_TELEMETRY_DIR")
    if out_dir is None:
        out_dir = block.get("dir")
    out_dir = (out_dir or "").strip() or None
    return TelemetryConfig(
        enabled=env_strict_flag("HYDRAGNN_TELEMETRY",
                                bool(block.get("enabled", False))),
        out_dir=out_dir,
        device_trace=env_strict_flag("HYDRAGNN_DEVICE_TRACE",
                                     bool(block.get("device_trace",
                                                    False))),
        device_trace_epoch=int(env_strict_int(
            "HYDRAGNN_DEVICE_TRACE_EPOCH",
            int(block.get("device_trace_epoch", 0) or 0))),
    )


def resolve_pipeline(train_cfg, num_stages: int):
    """Pipeline-parallelism knobs (docs/pipeline.md) ->
    (microbatches, schedule, remat_policy_or_None, data_shards).

    Precedence per knob: HYDRAGNN_* env over the Training.* config keys
    over defaults. STRICT parsing throughout — the schedule/remat knobs
    switch the compiled program's structure, so a typo value must warn
    and fall back, never silently take effect. Resolved ONCE here at
    step-construction time; the parallel/ modules take plain values and
    never read the environment
    (tools/check_traced_env_reads.py enforces it).

    Knobs:
      HYDRAGNN_PIPE_MICROBATCHES  microbatches per step
                                  (Training.pipeline_microbatches;
                                  default: pipeline_stages)
      HYDRAGNN_PIPE_SCHEDULE      gpipe | 1f1b
                                  (Training.pipeline_schedule; default
                                  1f1b — O(S) live activations)
      HYDRAGNN_PIPE_REMAT         0/off | 1/full | dots
                                  (Training.pipeline_remat; default off)
    Data-parallel composition (Training.pipeline_data_shards) is
    config-only: it changes the device/loader layout, not a per-run
    tuning choice.
    """
    train_cfg = train_cfg or {}
    micro_default = int(train_cfg.get("pipeline_microbatches",
                                      num_stages) or num_stages)
    microbatches = env_strict_int("HYDRAGNN_PIPE_MICROBATCHES",
                                  micro_default)
    # "explicit" means a VALID explicit choice: a typo'd (or empty) env
    # value falls back through env_strict_choice and must not also
    # disable the backward-compat gpipe fallback below — that would turn
    # warn-and-fall-back into a hard config error
    sched_env = (os.getenv("HYDRAGNN_PIPE_SCHEDULE") or "").strip().lower()
    sched_cfg = str(train_cfg.get("pipeline_schedule") or "").strip().lower()
    sched_explicit = sched_env in ("gpipe", "1f1b") or bool(sched_cfg)
    sched_default = sched_cfg or "1f1b"
    schedule = env_strict_choice(
        "HYDRAGNN_PIPE_SCHEDULE",
        {"gpipe": "gpipe", "1f1b": "1f1b"}, sched_default)
    if (schedule == "1f1b" and not sched_explicit and num_stages > 0
            and microbatches > num_stages
            and microbatches % num_stages):
        # backward compat: 1f1b became the DEFAULT in PR 8, but it
        # windows M into groups of S — a pre-existing config with, say,
        # M=6 over S=4 was valid under gpipe and must not start failing
        # from a changed default. Only an EXPLICIT 1f1b request turns
        # this into the config-time ValueError
        # (pipeline_trainer.validate_pipeline_config).
        import logging
        logging.getLogger("hydragnn_tpu").warning(
            "pipeline_microbatches=%d is not a multiple of "
            "pipeline_stages=%d, which the default 1f1b schedule cannot "
            "window — falling back to gpipe (O(M) live activations). "
            "Set Training.pipeline_schedule/HYDRAGNN_PIPE_SCHEDULE "
            "explicitly to silence this.", microbatches, num_stages)
        schedule = "gpipe"
    # remat: a boolean-ish knob with a policy extension — 1/true/on and
    # "full" mean full rematerialization, "dots" keeps matmul outputs
    remat_map = {"0": None, "false": None, "off": None, "no": None,
                 "1": "full", "true": "full", "on": "full",
                 "full": "full", "dots": "dots"}
    remat_default = train_cfg.get("pipeline_remat", False)
    if isinstance(remat_default, bool):
        default_policy = "full" if remat_default else None
    else:
        key = str(remat_default).strip().lower()
        if key and key not in remat_map:
            import logging
            logging.getLogger("hydragnn_tpu").warning(
                "Training.pipeline_remat=%r is not one of %s; treating "
                "as off", remat_default, sorted(set(remat_map)))
        default_policy = remat_map.get(key)
    policy = env_strict_choice("HYDRAGNN_PIPE_REMAT", remat_map,
                               default_policy)
    data_shards = int(train_cfg.get("pipeline_data_shards", 1) or 1)
    return int(microbatches), schedule, policy, data_shards


def resolve_hpo_supervisor(hpo_cfg=None) -> "tuple[int, float, float, int]":
    """Trial-supervisor knobs (docs/hpo.md) ->
    (max_retries, heartbeat_s, backoff_s, concurrency).

    Precedence per knob: HYDRAGNN_HPO_* env over the optional config dict
    (keys max_retries/heartbeat_s/backoff_s/concurrency) over defaults.
    STRICT parsing — these knobs bound how hard the supervisor fights for
    a dying trial, so a typo value must warn and fall back, never
    silently disable recovery.

    Knobs:
      HYDRAGNN_HPO_MAX_RETRIES  relaunches per trial after preemption/
                                crash/hang before it goes FAILED
                                (default 2, min 0)
      HYDRAGNN_HPO_HEARTBEAT_S  progress deadline — a running trial with
                                no checkpoint or log growth for this long
                                is killed as hung (default 120, min 0.05)
      HYDRAGNN_HPO_BACKOFF_S    relaunch backoff base, doubling per
                                consecutive retry (default 1.0, min 0)
      HYDRAGNN_HPO_CONCURRENCY  concurrent running trials (default 1,
                                min 1)
    """
    cfg = hpo_cfg or {}
    retries = env_strict_int("HYDRAGNN_HPO_MAX_RETRIES",
                             int(cfg.get("max_retries", 2)))
    heartbeat = env_strict_float("HYDRAGNN_HPO_HEARTBEAT_S",
                                 float(cfg.get("heartbeat_s", 120.0)))
    backoff = env_strict_float("HYDRAGNN_HPO_BACKOFF_S",
                               float(cfg.get("backoff_s", 1.0)))
    conc = env_strict_int("HYDRAGNN_HPO_CONCURRENCY",
                          int(cfg.get("concurrency", 1)))
    return (max(int(retries), 0), max(float(heartbeat), 0.05),
            max(float(backoff), 0.0), max(int(conc), 1))


def resolve_elastic(cfg=None) -> "tuple[float, float, float]":
    """Elastic job-supervisor knobs (docs/fault_tolerance.md "Elastic
    multi-process training") -> (max_restarts, heartbeat_s, backoff_s).

    Precedence per knob: HYDRAGNN_ELASTIC_* env over the optional config
    dict (keys max_restarts/heartbeat_s/backoff_s) over defaults. STRICT
    parsing — these knobs bound how hard the supervisor fights for a
    dying job, so a typo value must warn and fall back, never silently
    disable recovery.

    Knobs:
      HYDRAGNN_ELASTIC_MAX_RESTARTS  coordinated restarts after a rank
                                     death/hang/spawn failure before the
                                     job goes FAILED (default 2, min 0)
      HYDRAGNN_ELASTIC_HEARTBEAT_S   progress deadline — a generation
                                     where ANY rank shows no checkpoint
                                     or log growth for this long is
                                     aborted as hung (default 120,
                                     min 0.05; must cover the silent
                                     jax-import/compile window of a
                                     cold rank, the BENCH_HPO lesson)
      HYDRAGNN_ELASTIC_BACKOFF_S     restart backoff base, doubling per
                                     consecutive restart (default 1.0,
                                     min 0)
    """
    cfg = cfg or {}
    restarts = env_strict_int("HYDRAGNN_ELASTIC_MAX_RESTARTS",
                              int(cfg.get("max_restarts", 2)))
    heartbeat = env_strict_float("HYDRAGNN_ELASTIC_HEARTBEAT_S",
                                 float(cfg.get("heartbeat_s", 120.0)))
    backoff = env_strict_float("HYDRAGNN_ELASTIC_BACKOFF_S",
                               float(cfg.get("backoff_s", 1.0)))
    return (max(int(restarts), 0), max(float(heartbeat), 0.05),
            max(float(backoff), 0.0))


def resolve_rendezvous_timeout() -> "float | None":
    """Bounded multi-process rendezvous (docs/fault_tolerance.md):
    HYDRAGNN_RENDEZVOUS_TIMEOUT_S bounds how long
    ``parallel.mesh.init_distributed`` and
    ``parallel.multiprocess.assert_equal_across_processes`` wait for
    peer processes before raising an actionable error instead of
    wedging forever on a rank that never arrives. Strict parsing; unset
    or <= 0 keeps today's unbounded behavior (the jax built-in 300 s
    initialize timeout still applies to the rendezvous itself). The
    elastic launcher sets this in every child rank's env so a
    half-spawned generation self-destructs instead of outliving its
    supervisor's patience."""
    t = env_strict_float("HYDRAGNN_RENDEZVOUS_TIMEOUT_S")
    if t is None:
        return None
    t = float(t)
    return t if t > 0 else None


def resolve_steps_per_call(train_cfg) -> int:
    """Steps-per-call dispatch batching knob: HYDRAGNN_STEPS_PER_CALL env
    overrides Training.steps_per_call (default 1). Shared by run_training
    and the example drivers so the precedence can't drift."""
    spc_env = env_int("HYDRAGNN_STEPS_PER_CALL")
    if spc_env is not None:
        return spc_env
    return int(train_cfg.get("steps_per_call", 1))


def resolve_sampling(train_cfg=None) -> "tuple[tuple, int, int, str]":
    """Giant-graph sampled-training knobs (docs/sampling.md) ->
    (fanouts, staleness_k, partitions, partition_mode).

    Precedence per knob: HYDRAGNN_SAMPLE_* env over the
    Training.Sampling config block over defaults. STRICT parsing
    throughout — fanouts change every compiled shape in the run and
    staleness_k changes the training mathematics, so a typo value must
    warn and fall back, never silently take effect. Resolved ONCE at
    loader construction; preprocess/sampling.py takes plain values and
    never reads the environment (tools/check_traced_env_reads.py
    enforces it).

    Knobs:
      HYDRAGNN_SAMPLE_FANOUTS      comma-separated per-hop fanouts,
                                   e.g. "10,5" (Sampling.fanouts;
                                   default 8,8)
      HYDRAGNN_SAMPLE_STALENESS_K  historical-cache refresh period; 0 =
                                   exact, no cache (Sampling.staleness_k;
                                   default 0)
      HYDRAGNN_SAMPLE_PARTITIONS   feature/owner partitions
                                   (Sampling.partitions; default 1)
    Partition mode (range | hash) is config-only (Sampling.
    partition_mode): it changes the cache key and the ownership layout,
    not a per-run tuning choice.
    """
    block = (train_cfg or {}).get("Sampling", {}) or {}
    fan_default = tuple(int(f) for f in block.get("fanouts", (8, 8)))
    fanouts = fan_default
    raw = os.getenv("HYDRAGNN_SAMPLE_FANOUTS")
    if raw is not None and raw.strip():
        try:
            parsed = tuple(int(p.strip()) for p in raw.split(","))
            if not parsed or any(f <= 0 for f in parsed):
                raise ValueError
            fanouts = parsed
        except ValueError:
            import logging
            logging.getLogger("hydragnn_tpu").warning(
                "HYDRAGNN_SAMPLE_FANOUTS=%r is not a comma-separated "
                "list of positive integers; treating as %r", raw,
                fan_default)
    k = env_strict_int("HYDRAGNN_SAMPLE_STALENESS_K",
                       int(block.get("staleness_k", 0)))
    parts = env_strict_int("HYDRAGNN_SAMPLE_PARTITIONS",
                           int(block.get("partitions", 1)))
    mode = str(block.get("partition_mode", "range"))
    return fanouts, max(int(k), 0), max(int(parts), 1), mode


def resolve_gfm(train_cfg=None) -> "tuple":
    """Multi-dataset GFM mixture knobs (docs/gfm.md) ->
    (mixture weights dict-or-None, head weights tuple-or-None).

    Precedence per knob: HYDRAGNN_GFM_* env over the Training.Gfm config
    block over defaults (None = loader/step defaults: size-proportional
    sampling, cfg.task_weights head combine). STRICT parsing — the
    mixture weights change the epoch's global pack plan and the head
    weights change the training mathematics, so a typo value must warn
    naming the variable and fall back, never silently take effect.
    Resolved ONCE at loader/step construction;
    parallel/multidataset.py and train/gfm.py take plain
    values and never read the environment (the traced-env-read
    discipline, tools/hydralint).

    Knobs:
      HYDRAGNN_GFM_MIXTURE       comma-separated ``name:weight`` pairs,
                                 e.g. "ani1x:2,mptrj:1" (weight omitted
                                 = 1.0); config: Gfm.mixture mapping
                                 name -> weight. Weights must be
                                 positive finite numbers.
      HYDRAGNN_GFM_HEAD_WEIGHTS  comma-separated per-head loss weights,
                                 e.g. "1.0,0.5,0.5" (config:
                                 Gfm.head_weights list). Must be
                                 non-negative finite numbers.
    """
    import logging
    block = (train_cfg or {}).get("Gfm", {}) or {}
    log = logging.getLogger("hydragnn_tpu")

    mixture = None
    if block.get("mixture"):
        mixture = {str(k): float(v) for k, v in block["mixture"].items()}
    raw = os.getenv("HYDRAGNN_GFM_MIXTURE")
    if raw is not None and raw.strip():
        try:
            parsed = {}
            for part in raw.split(","):
                part = part.strip()
                if not part:
                    continue
                name, _, w = part.partition(":")
                if not name.strip():
                    raise ValueError
                weight = float(w) if w.strip() else 1.0
                if not (weight > 0) or not math.isfinite(weight):
                    raise ValueError
                parsed[name.strip()] = weight
            if not parsed:
                raise ValueError
            mixture = parsed
        except ValueError:
            log.warning(
                "HYDRAGNN_GFM_MIXTURE=%r is not a comma-separated list "
                "of name:positive-weight pairs; treating as %r", raw,
                mixture)

    head_weights = None
    if block.get("head_weights"):
        head_weights = tuple(float(v) for v in block["head_weights"])
    raw = os.getenv("HYDRAGNN_GFM_HEAD_WEIGHTS")
    if raw is not None and raw.strip():
        try:
            parsed = tuple(float(p.strip()) for p in raw.split(","))
            if not parsed or any(not math.isfinite(w) or w < 0
                                 for w in parsed):
                raise ValueError
            head_weights = parsed
        except ValueError:
            log.warning(
                "HYDRAGNN_GFM_HEAD_WEIGHTS=%r is not a comma-separated "
                "list of non-negative weights; treating as %r", raw,
                head_weights)
    return mixture, head_weights
