"""Epoch-level training driver.

reference: hydragnn/train/train_validate_test.py:52-311 `train_validate_test`
— epoch loop with per-epoch shuffling, ReduceLROnPlateau on val loss (:195),
TensorBoard scalars (:196-203), best-val-gated checkpointing with warmup
(:237-244; utils/model/model.py:258-298), early stopping (:246-253), and a
SLURM walltime guard (:255-262).
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from ..datasets.loader import prefetch_to_device
from ..parallel.multiprocess import host_replicated_copy
from ..telemetry import spans as _spans
from ..utils.faults import fault_point
from ..utils.print_utils import iterate_tqdm, log, print_distributed
from ..utils.profiling import Tracer
from .optimizer import (get_learning_rate, set_learning_rate,
                        supports_lr_schedule)

# ---------------------------------------------------------------- preemption
# SLURM/TPU preemption delivers SIGTERM with a grace window; the handler
# only sets a flag (signal-safe), and the epoch loop performs ONE final
# synchronous save at the next step boundary before exiting cleanly
# (docs/fault_tolerance.md). Tests drive the same path deterministically
# via request_preemption().

_PREEMPT = threading.Event()
_PREV_SIGTERM: list = [None, False]  # (previous handler, installed?)


def install_sigterm_handler() -> bool:
    """Route SIGTERM to the preemption flag; returns False when not on the
    main thread (signal handlers can only be installed there). The
    previous disposition is remembered (first install wins across nested
    installs) so `restore_sigterm_handler` can put it back after training
    — leaving the flag-only handler installed would make the process
    silently ignore SIGTERM forever after the run completes."""
    import signal

    def _handler(signum, frame):
        _PREEMPT.set()

    try:
        prev = signal.signal(signal.SIGTERM, _handler)
    except ValueError:
        return False
    if not _PREV_SIGTERM[1]:
        _PREV_SIGTERM[0], _PREV_SIGTERM[1] = prev, True
    return True


def restore_sigterm_handler() -> None:
    """Put back the SIGTERM disposition that predated
    `install_sigterm_handler`; no-op when nothing was installed."""
    import signal
    if _PREV_SIGTERM[1]:
        try:
            signal.signal(signal.SIGTERM, _PREV_SIGTERM[0])
        except (ValueError, TypeError):
            pass
        _PREV_SIGTERM[0], _PREV_SIGTERM[1] = None, False


def request_preemption() -> None:
    _PREEMPT.set()


def preemption_requested() -> bool:
    return _PREEMPT.is_set()


def clear_preemption() -> None:
    _PREEMPT.clear()


class EarlyStopping:
    """reference: utils/model/model.py:240-255."""

    def __init__(self, patience: int = 10, min_delta: float = 0.0):
        self.patience = patience
        self.min_delta = min_delta
        self.best = float("inf")
        self.count = 0

    def __call__(self, val_loss: float) -> bool:
        if val_loss < self.best - self.min_delta:
            self.best = val_loss
            self.count = 0
            return False
        self.count += 1
        return self.count >= self.patience


class ReduceLROnPlateau:
    """reference: torch.optim.lr_scheduler.ReduceLROnPlateau used at
    train_validate_test.py:191-195 (factor 0.5, patience 5, min_lr 1e-6 per
    run_training.py:101-104)."""

    def __init__(self, factor: float = 0.5, patience: int = 5,
                 min_lr: float = 1e-6):
        self.factor = factor
        self.patience = patience
        self.min_lr = min_lr
        self.best = float("inf")
        self.count = 0

    def step(self, val_loss: float, lr: float) -> float:
        if val_loss < self.best:
            self.best = val_loss
            self.count = 0
            return lr
        self.count += 1
        if self.count > self.patience:
            self.count = 0
            return max(lr * self.factor, self.min_lr)
        return lr


class CheckpointGate:
    """Best-val-gated checkpoint with warmup epochs
    (reference: utils/model/model.py:258-298)."""

    def __init__(self, warmup: int = 0):
        self.warmup = warmup
        self.best = float("inf")

    def should_save(self, epoch: int, val_loss: float) -> bool:
        if epoch < self.warmup:
            return False
        if val_loss < self.best:
            self.best = val_loss
            return True
        return False


def _walltime_remaining_guard(deadline: Optional[float]) -> bool:
    """reference: check_remaining (distributed.py:331-356) polls squeue; here
    the driver passes an absolute deadline timestamp instead."""
    if deadline is None:
        return True
    return time.time() < deadline


def train_validate_test(
    train_step: Callable,
    eval_step: Callable,
    state,
    train_loader,
    val_loader,
    test_loader,
    num_epochs: int,
    log_name: str = "run",
    log_dir: str = "./logs",
    patience: int = 10,
    use_early_stopping: bool = True,
    checkpoint_warmup: int = 0,
    checkpoint_fn: Optional[Callable] = None,
    plateau: Optional[ReduceLROnPlateau] = None,
    walltime_deadline: Optional[float] = None,
    verbosity: int = 0,
    tracer: Optional[Tracer] = None,
    keep_best: bool = True,
    place_fn: Optional[Callable] = None,
    profiler=None,
    multi_train_step: Optional[Callable] = None,
    steps_per_call: int = 1,
    place_group_fn: Optional[Callable] = None,
    multi_eval_step: Optional[Callable] = None,
    start_epoch: int = 0,
    resume: Optional[Dict[str, Any]] = None,
    checkpoint_every_n_epochs: int = 0,
    periodic_checkpoint_fn: Optional[Callable] = None,
    preempt_save_fn: Optional[Callable] = None,
    initial_best_state=None,
    initial_best_val: Optional[float] = None,
    resume_meta_out: Optional[Dict[str, Any]] = None,
    telemetry=None,
):
    """Returns (final_state, history dict). With `keep_best` the returned
    state is the best-validation one (mirrors the reference's best-val
    checkpoint + reload flow, utils/model/model.py:258-298).

    Fault tolerance (docs/fault_tolerance.md): `start_epoch`/`resume`
    restore a preempted run's trainer state (history, scheduler and
    early-stop counters, best-val) so replayed epochs are bitwise-identical
    to the uninterrupted run; `periodic_checkpoint_fn(state, meta)` fires
    every `checkpoint_every_n_epochs` completed epochs with the resume
    metadata; `preempt_save_fn(state, meta)` fires EXACTLY ONCE when
    SIGTERM (or request_preemption) arrives, then the loop exits cleanly.

    `telemetry` (a telemetry.TelemetrySession, or None) turns on the
    unified observability layer (docs/observability.md): per-epoch
    registry gauges + JSONL epoch events, span tracing of the step
    timeline (dataload_wait / h2d / step_dispatch / device_wait per
    batch, epoch/eval regions via the tracer), and the per-epoch MFU
    gauge (achieved_flops_per_s against the per-backend peak table).
    None — the default — keeps the hot path at its pre-telemetry cost:
    the only additions are one global None-check per batch."""
    run_dir = os.path.join(log_dir, log_name)
    os.makedirs(run_dir, exist_ok=True)
    tb = _tensorboard_writer(run_dir)
    early = EarlyStopping(patience) if use_early_stopping else None
    gate = CheckpointGate(checkpoint_warmup)
    plateau = plateau or ReduceLROnPlateau()
    tr = tracer or Tracer()
    history: Dict[str, List[float]] = {"train_loss": [], "val_loss": [],
                                       "test_loss": [], "lr": []}
    best_state, best_val = initial_best_state, float("inf")
    if resume:
        # restore the trainer-side state the checkpointed pytree doesn't
        # carry: without it the LR plateau / early-stop counters restart
        # from zero and the resumed trajectory diverges from the
        # uninterrupted one
        for k, v in (resume.get("history") or {}).items():
            history[k] = list(v)
        p = resume.get("plateau") or {}
        plateau.best = float(p.get("best", plateau.best))
        plateau.count = int(p.get("count", plateau.count))
        e = resume.get("early") or {}
        if early is not None and e:
            early.best = float(e.get("best", early.best))
            early.count = int(e.get("count", early.count))
        gate.best = float(resume.get("gate_best", gate.best))
        if initial_best_state is not None:
            # adopt the BEST checkpoint's OWN recorded val when available
            # (the marker's line 2): the trainer's in-memory best_val can
            # belong to a failed/warmup-skipped save and would block
            # adoption of genuinely better resumed epochs
            best_val = float(initial_best_val
                             if initial_best_val is not None
                             else resume.get("best_val", best_val))
        # without a restored best-state pytree (no BEST checkpoint, e.g. a
        # periodic-only config) the pre-kill best_val must NOT be adopted:
        # keep_best would then never snapshot a best_state and return the
        # final state instead of the best reachable one — re-track the
        # best over the resumed epochs instead

    def _resume_meta(next_epoch: int, state) -> Dict[str, Any]:
        """Everything a resumed run needs to continue bitwise-identically;
        persisted as resume.json next to the checkpointed pytree. The
        history is SNAPSHOTTED here: async best-val saves serialize the
        metadata later on the commit-watcher thread, and the live dict
        keeps growing — a by-reference capture could commit more epochs
        than next_epoch claims and corrupt the resume."""
        return {
            "next_epoch": int(next_epoch),
            "step": int(state.step),
            # loader permutations are pure functions of (seed, epoch), so
            # the loader epoch always equals next_epoch; recorded
            # explicitly so external tooling can reconstruct the exact
            # resumed data stream from the metadata alone
            "loader_epoch": int(next_epoch),
            # elastic metadata (docs/fault_tolerance.md): the world size
            # that WROTE this checkpoint. Purely informational — the
            # resume contract is world-size-agnostic (global pack plan +
            # global-shape state), so a restart at W' != world_size is
            # legitimate; readers predating this key ignore it (the
            # resume.json forward-compat contract)
            "world_size": int(jax.process_count()),
            "trainer": {
                "history": {k: list(v) for k, v in history.items()},
                "plateau": {"best": plateau.best, "count": plateau.count},
                "early": ({"best": early.best, "count": early.count}
                          if early is not None else None),
                "gate_best": gate.best,
                "best_val": best_val,
            },
        }

    preempt_saved = [False]

    def _preempt_save(next_epoch: int, state) -> None:
        # exactly-once: the batch-level and epoch-level checks can both
        # observe the same SIGTERM
        if preempt_saved[0]:
            return
        preempt_saved[0] = True
        if preempt_save_fn is not None:
            preempt_save_fn(state, _resume_meta(next_epoch, state))
        print_distributed(verbosity, 0,
                          f"preemption: checkpoint saved at epoch "
                          f"{next_epoch} boundary; exiting cleanly")

    # env-flag layer (reference: HYDRAGNN_MAX_NUM_BATCH caps batches/epoch
    # for scaling runs, train_validate_test.py:39-49; HYDRAGNN_VALTEST
    # disables the val/test passes, :177)
    from ..utils.envflags import env_flag, env_int
    max_num_batch = env_int("HYDRAGNN_MAX_NUM_BATCH")
    run_valtest = env_flag("HYDRAGNN_VALTEST", default=True)
    # HYDRAGNN_NUM_WORKERS maps the reference's DataLoader worker count
    # (load_data.py:249-254) onto prefetch depth
    prefetch_depth = max(env_int("HYDRAGNN_NUM_WORKERS", 2), 1)

    from ..telemetry.spans import EpochDeviceTrace
    from ..utils.profiling import HostStallMonitor
    profiler = profiler or EpochDeviceTrace(run_dir, enable=False)
    # host-stall accounting: every epoch reports the fraction of host time
    # blocked on the input pipeline (collation + staging) vs dispatching
    # steps — the input-bound fraction the async loader is meant to erase
    stall = HostStallMonitor(tracer=tr)
    # the global optimizer-step number the step-level host spans carry
    # (`dataload_wait`, `step_dispatch`, `device_wait`, and the
    # StepTraceAnnotation round the step): a span carries the step it
    # precedes or belongs to; a resumed run counts on from its epoch
    stall.step = start_epoch * len(train_loader)
    prev_compiled = 0  # jit-recompile counter baseline (utils/profiling)
    # span taxonomy (docs/observability.md): the placement callables are
    # wrapped so host->device staging shows up as `h2d` spans on the train
    # thread, inside its `dataload_wait`; no-op cost when no recorder is
    # installed
    place_fn = _traced_place(place_fn)
    place_group_fn = _traced_place(place_group_fn)
    # the MFU probe batch: one single-step batch reference (not a copy)
    # kept for the end-of-epoch XLA cost-analysis probe; only taken when
    # a telemetry session is live (telemetry.mfu / ROADMAP item 1)
    flops_probe_batch = None

    import inspect
    ckpt_accepts_meta = False
    if checkpoint_fn is not None:
        try:
            ckpt_accepts_meta = "meta" in inspect.signature(
                checkpoint_fn).parameters
        except (TypeError, ValueError):
            pass

    prev_boundary_committed = False
    for epoch in range(start_epoch, num_epochs):
        train_loader.set_epoch(epoch)
        profiler.set_current_epoch(epoch)
        stall.reset()
        # epoch-start snapshot for the mid-epoch preemption save: resume
        # replays the WHOLE epoch, so the saved pytree must be the state
        # before any of this epoch's updates — saving the partial-epoch
        # state would double-apply the completed batches on replay. One
        # host copy per epoch, only when a preempt save is installed AND
        # the previous boundary's periodic checkpoint doesn't already
        # hold this exact state (then LATEST is the resume point and the
        # copy would be pure waste).
        epoch_start_state = (host_replicated_copy(state)
                             if (preempt_save_fn is not None
                                 and not prev_boundary_committed)
                             else None)
        # ---- train pass (reference: train, :449-565) ----
        acc_train: Dict[str, float] = {}
        nb = 0
        preempted = False
        # ONE step is kept owed on the device: its metrics are fetched
        # after the NEXT step is dispatched, so the `next(stream)` that
        # follows (collation wait, H2D copy of a later batch) runs while
        # that next step is queued behind the owed one, not on an idle
        # chip. (metrics, span args, summed) of the owed step, or None
        owed = None
        # fetches that found their step already finished: the host was the
        # slower side and the device ran dry
        host_bound = 0
        with tr.timer("train_epoch"), profiler:
            # double-buffered device prefetch only when the caller supplies
            # a placement (meshes need mesh-aware sharding; committing to a
            # single device would break multi-device shard_map steps)
            source = train_loader
            group = (multi_train_step is not None and steps_per_call > 1)
            if group:
                # steps-per-call batching: stack S host batches on the
                # leading axis; one device dispatch then scans S optimizer
                # steps (train_step.make_multi_train_step) — amortizes
                # per-dispatch latency that the reference's per-batch loop
                # pays every batch (train_validate_test.py:483-545)
                source = _group_batches(train_loader, steps_per_call)
            # prefetch depth is sized in single batches; a queued group
            # holds S of them, so scale down to keep device memory flat
            depth = (max(1, prefetch_depth // steps_per_call) if group
                     else prefetch_depth)
            pf = (place_group_fn if (group and place_group_fn is not None)
                  else place_fn)
            stream = (prefetch_to_device(source, size=depth, place_fn=pf)
                      if pf is not None else source)
            # every next() on the stream is host time spent on the input
            # (collation, cache lookup, staging), under the owed step —
            # accounted per epoch
            stream = stall.wrap(stream)
            n_items = len(train_loader)
            if group:
                n_items = -(-n_items // steps_per_call)  # stacked groups
            for batch in iterate_tqdm(stream, verbosity,
                                      desc=f"epoch {epoch} train",
                                      total=n_items):
                # step-boundary preemption check: the SIGTERM handler only
                # sets a flag, so the interrupted step always completes and
                # the saved state is a clean step boundary
                if preemption_requested():
                    preempted = True
                    break
                # deterministic crash injection (utils/faults.py): one
                # forward-step index per train-loop dispatch
                fault_point("forward-step")
                if (telemetry is not None and not group
                        and flops_probe_batch is None
                        and not telemetry.flops_probed):
                    flops_probe_batch = batch
                full_group = (group
                              and batch.x.shape[0] == steps_per_call
                              and (max_num_batch is None
                                   or nb + steps_per_call <= max_num_batch))
                nb_before = nb
                with tr.timer("train_step", step=stall.step), \
                        stall.step_timer():
                    if full_group:
                        state, metrics = multi_train_step(state, batch)
                        host_bound += _fetch_owed(acc_train, owed)
                        owed = (metrics, stall.span_args(), True)
                        nb += steps_per_call
                    elif group:
                        # remainder group, or a max_num_batch cap inside
                        # this group: single steps (a smaller scan would
                        # trigger one more long compile)
                        for i in range(batch.x.shape[0]):
                            if (max_num_batch is not None
                                    and nb >= max_num_batch):
                                break
                            b_i = jax.tree_util.tree_map(
                                lambda a, i=i: a[i], batch)
                            state, m = train_step(state, b_i)
                            host_bound += _fetch_owed(acc_train, owed)
                            owed = (m, {"step": stall.step + nb - nb_before},
                                    False)
                            nb += 1
                    else:
                        state, metrics = train_step(state, batch)
                        host_bound += _fetch_owed(acc_train, owed)
                        owed = (metrics, stall.span_args(), False)
                        nb += 1
                stall.step += nb - nb_before
                if max_num_batch is not None and nb >= max_num_batch:
                    break
            # every exit from the pass (its end, the max_num_batch cap, a
            # preemption) fetches the step still owed; its wait is step
            # time, as every other fetch's is
            t0 = time.perf_counter()
            host_bound += _fetch_owed(acc_train, owed)
            stall.step_s += time.perf_counter() - t0
        if preempted:
            # mid-epoch preemption: save the EPOCH-START state with
            # next_epoch = THIS epoch, so the resumed run replays the
            # whole epoch from its deterministic permutation — the partial
            # epoch's updates are discarded in favor of a bitwise-exact
            # trajectory (docs/fault_tolerance.md)
            if epoch_start_state is None and prev_boundary_committed:
                # the previous boundary's periodic checkpoint IS this
                # epoch's start state — LATEST already holds the resume
                # point, a second identical save would only burn grace
                preempt_saved[0] = True
                print_distributed(verbosity, 0,
                                  f"preemption: resuming from the epoch "
                                  f"{epoch} boundary checkpoint; exiting "
                                  "cleanly")
            else:
                _preempt_save(epoch, epoch_start_state)
            break
        train_loss = acc_train.pop("loss", 0.0) / max(nb, 1)
        # NaN/overflow watchdog (train_step._nonfinite_watchdog): COUNT of
        # steps this epoch whose loss or gradients went non-finite — the
        # bf16 mixed-precision canary (docs/mixed_precision.md),
        # a sum not a mean, surfaced next to input_bound_frac
        nonfinite_steps = acc_train.pop("nonfinite_steps", 0.0)
        history.setdefault("nonfinite_steps", []).append(nonfinite_steps)
        history.setdefault("host_bound_steps", []).append(host_bound)
        task_tot = acc_train
        # host-stall report: fraction of the train pass the host was
        # blocked on the input pipeline rather than dispatching steps and
        # fetching their metrics (the device idles only where
        # `host_bound_steps` counts)
        input_bound = stall.input_bound_frac()
        history.setdefault("input_bound_frac", []).append(input_bound)
        # padding-waste report: fraction of the epoch's node/edge slots
        # that were padding (the FLOP waste budget-packed batching cuts —
        # docs/packing.md); loaders without size stats simply skip it
        pad_stats = None
        if callable(getattr(train_loader, "padding_stats", None)):
            try:
                pad_stats = train_loader.padding_stats()
            except Exception:  # noqa: BLE001 — instrumentation only
                pad_stats = None
        if pad_stats is not None:
            for k in ("padding_frac_nodes", "padding_frac_edges"):
                history.setdefault(k, []).append(float(pad_stats[k]))
        # ---- val/test passes ----
        if run_valtest:
            val_loss, val_tasks = _eval_epoch(
                eval_step, state, val_loader, tr, "validate",
                multi_eval_step, steps_per_call, place_fn=place_fn)
            test_loss, test_tasks = _eval_epoch(
                eval_step, state, test_loader, tr, "test",
                multi_eval_step, steps_per_call, place_fn=place_fn)
        else:
            val_loss = test_loss = float("nan")
            val_tasks = test_tasks = {}

        # jit-recompile counter (after ALL of this epoch's step kinds ran):
        # compiled-program count across the step functions minus last
        # epoch's — nonzero after epoch 0 means a batch shape leaked out
        # of the pinned budgets (the packed-vs-fixed adjudication signal,
        # docs/packing.md)
        from ..utils.profiling import jit_cache_total
        compiled = jit_cache_total(train_step, multi_train_step,
                                   eval_step, multi_eval_step)
        recompiles = None
        if compiled is not None:
            recompiles = compiled - prev_compiled
            prev_compiled = compiled
            history.setdefault("jit_recompiles", []).append(recompiles)

        if keep_best and val_loss == val_loss and val_loss < best_val:
            best_val = val_loss
            best_state = host_replicated_copy(state)

        # ---- LR plateau schedule ----
        if supports_lr_schedule(state.opt_state):
            lr = get_learning_rate(state.opt_state)
            # plateau decisions need a real val loss (HYDRAGNN_VALTEST=0
            # suppresses it); the current LR is still reported either way
            if val_loss == val_loss:
                new_lr = plateau.step(val_loss, lr)
                if new_lr != lr:
                    set_learning_rate(state.opt_state, new_lr)
                    print_distributed(verbosity, 1,
                                      f"reducing lr {lr:.2e} -> {new_lr:.2e}")
                lr = new_lr
        else:
            lr = float("nan")

        history["train_loss"].append(train_loss)
        history["val_loss"].append(val_loss)
        history["test_loss"].append(test_loss)
        history["lr"].append(lr)
        # per-task / per-component losses for all three passes (reference:
        # task_loss_train/val/test tracking + TensorBoard scalars,
        # train_validate_test.py:93-96,196-203)
        for k, v in task_tot.items():
            history.setdefault(k, []).append(v / max(nb, 1))
        for prefix, tasks in (("val", val_tasks), ("test", test_tasks)):
            for k, v in tasks.items():
                history.setdefault(f"{prefix}_{k}", []).append(v)
        # ---- unified telemetry (docs/observability.md): per-epoch MFU
        # gauge + registry metrics + one structured JSONL event ----
        achieved = mfu_val = None
        if telemetry is not None:
            from ..telemetry.mfu import achieved_and_mfu
            pinfo = getattr(telemetry, "pipeline_info", None)
            flops = None
            if pinfo:
                # the shard_map-pipelined step's cost analysis is
                # per-partition and counts remat recompute as work — not
                # a useful-work numerator (BENCH_MFU probes the
                # sequential step instead; bench.py run_bench_mfu)
                flops_probe_batch = None
                if epoch == start_epoch:
                    log("telemetry: pipelined run — per-step MFU gauge "
                        "unavailable (the shard_map step's cost analysis "
                        "is per-partition; see BENCH_MFU for the "
                        "sequential-probe numerator)")
            elif flops_probe_batch is not None:
                flops = telemetry.step_flops_once(train_step, state,
                                                  flops_probe_batch)
                # the probe result is memoized in the session — release
                # the pinned device batch for the rest of the run
                flops_probe_batch = None
            elif telemetry.flops_probed:
                flops = telemetry.step_flops_once(train_step)
            elif group and epoch == start_epoch:
                # no silent caps: say WHY the gauge is absent rather
                # than just omitting the rows
                log("telemetry: steps_per_call > 1 — per-step MFU gauge "
                    "unavailable (the scanned multi-step's cost analysis "
                    "is not per-step comparable)")
            # the epoch's dispatch+execute wall time (input wait excluded)
            # is the denominator the bench's timed loop approximates
            achieved, mfu_val = achieved_and_mfu(
                flops, nb, stall.step_s, backend=jax.default_backend(),
                device_kind=jax.devices()[0].device_kind,
                compute_dtype=getattr(telemetry, "compute_dtype",
                                      "float32"))
            if achieved is not None:
                history.setdefault("achieved_flops_per_s", []).append(
                    achieved)
            if mfu_val is not None:
                history.setdefault("mfu", []).append(mfu_val)
            reg = telemetry.registry
            reg.gauge_set("train_loss", train_loss,
                          help="mean train loss this epoch")
            if val_loss == val_loss:
                reg.gauge_set("val_loss", val_loss,
                              help="mean validation loss this epoch")
                reg.gauge_set("test_loss", test_loss,
                              help="mean test loss this epoch")
            reg.gauge_set("train_input_bound_frac", input_bound,
                          help="fraction of the train pass blocked on "
                               "the input pipeline")
            reg.counter_inc("train_nonfinite_steps_total",
                            float(nonfinite_steps),
                            help="steps with non-finite loss/grads")
            if pad_stats is not None:
                reg.gauge_set("train_padding_frac_nodes",
                              float(pad_stats["padding_frac_nodes"]),
                              help="node-slot padding fraction")
                reg.gauge_set("train_padding_frac_edges",
                              float(pad_stats["padding_frac_edges"]),
                              help="edge-slot padding fraction")
            if recompiles is not None:
                reg.counter_inc("train_jit_recompiles_total",
                                float(max(recompiles, 0)),
                                help="new compiled step programs")
            if achieved is not None:
                reg.gauge_set("train_achieved_flops_per_s", achieved,
                              help="XLA-cost-analysis FLOPs x steps over "
                                   "dispatch+execute wall time")
            if mfu_val is not None:
                reg.gauge_set("train_mfu", mfu_val,
                              help="achieved over per-backend peak FLOPs")
            # NaN-valued scalars (HYDRAGNN_VALTEST=0 val/test, schedulers
            # without a readable lr) are OMITTED, not embedded: json.dumps
            # would write a literal `NaN` and break the one-JSON-object-
            # per-line contract for exactly the degraded runs worth
            # inspecting
            # pipelined runs (run_training sets telemetry.pipeline_info):
            # the schedule's closed-form bubble fractions as gauges — a
            # SCHEDULE MODEL, not a device measurement (docs/pipeline.md)
            if pinfo:
                reg.gauge_set("pipeline_bubble_frac",
                              float(pinfo["bubble_frac"]),
                              help="closed-form per-pass schedule bubble "
                                   "(S-1)/(M+S-1)")
                reg.gauge_set("pipeline_train_bubble_frac",
                              float(pinfo["train_bubble_frac"]),
                              help="closed-form fwd+bwd train-step bubble "
                                   "for the active schedule")
            data = {"nonfinite_steps": nonfinite_steps, "batches": nb}
            for k, v in (("train_loss", train_loss),
                         ("val_loss", val_loss),
                         ("test_loss", test_loss), ("lr", lr)):
                if np.isfinite(v):
                    data[k] = v
            if pinfo:
                data["pipeline_schedule"] = pinfo["schedule"]
                data["pipeline_stages"] = int(pinfo["stages"])
                data["pipeline_microbatches"] = int(pinfo["microbatches"])
                data["pipeline_bubble_frac"] = float(pinfo["bubble_frac"])
                data["pipeline_train_bubble_frac"] = float(
                    pinfo["train_bubble_frac"])
            if pad_stats is not None:
                data["padding_frac_nodes"] = float(
                    pad_stats["padding_frac_nodes"])
                data["padding_frac_edges"] = float(
                    pad_stats["padding_frac_edges"])
            if recompiles is not None:
                data["jit_recompiles"] = recompiles
            timing = {"input_bound_frac": input_bound,
                      "epoch_wait_s": stall.wait_s,
                      "epoch_step_s": stall.step_s}
            if achieved is not None:
                timing["achieved_flops_per_s"] = achieved
            if mfu_val is not None:
                timing["mfu"] = mfu_val
            telemetry.epoch_event(epoch, data=data, timing=timing)
        if tb is not None:
            tb.add_scalar("train/loss", train_loss, epoch)
            tb.add_scalar("train/input_bound_frac", input_bound, epoch)
            tb.add_scalar("train/nonfinite_steps", nonfinite_steps, epoch)
            if pad_stats is not None:
                tb.add_scalar("train/padding_frac_nodes",
                              float(pad_stats["padding_frac_nodes"]), epoch)
                tb.add_scalar("train/padding_frac_edges",
                              float(pad_stats["padding_frac_edges"]), epoch)
            if recompiles is not None:
                tb.add_scalar("train/jit_recompiles", recompiles, epoch)
            tb.add_scalar("val/loss", val_loss, epoch)
            tb.add_scalar("test/loss", test_loss, epoch)
            for k, v in task_tot.items():
                tb.add_scalar(f"train/{k}", v / max(nb, 1), epoch)
            for prefix, tasks in (("val", val_tasks), ("test", test_tasks)):
                for k, v in tasks.items():
                    tb.add_scalar(f"{prefix}/{k}", v, epoch)
        extra = ""
        if pad_stats is not None:
            extra += (f" pad_n {pad_stats['padding_frac_nodes']:.3f}"
                      f" pad_e {pad_stats['padding_frac_edges']:.3f}")
        if recompiles is not None:
            extra += f" recompiles {recompiles}"
        if achieved is not None:
            extra += f" flops/s {achieved:.3e}"
        if mfu_val is not None:
            extra += f" mfu {mfu_val:.4f}"
        if nonfinite_steps:
            extra += f" NONFINITE_STEPS {int(nonfinite_steps)}"
        log(f"epoch {epoch}: train {train_loss:.5f} val {val_loss:.5f} "
            f"test {test_loss:.5f} lr {lr:.2e} "
            f"input_bound {input_bound:.3f}" + extra)

        if (checkpoint_fn is not None and val_loss == val_loss
                and gate.should_save(epoch, val_loss)):
            if ckpt_accepts_meta:
                checkpoint_fn(state, epoch, val_loss,
                              meta=_resume_meta(epoch + 1, state))
            else:
                checkpoint_fn(state, epoch, val_loss)
        # periodic preemption-safe checkpoint: every n completed epochs,
        # synchronous, with full resume metadata — the restartable points
        # a SIGTERM-less kill (OOM, node loss) falls back to
        boundary_saved = False
        if (checkpoint_every_n_epochs and periodic_checkpoint_fn is not None
                and (epoch + 1) % checkpoint_every_n_epochs == 0):
            periodic_checkpoint_fn(state, _resume_meta(epoch + 1, state))
            boundary_saved = True
        if preemption_requested():
            if boundary_saved:
                # the periodic save above IS this boundary's resume point;
                # a second identical full save would double exit latency
                # inside the preemption grace window
                preempt_saved[0] = True
                print_distributed(verbosity, 0,
                                  f"preemption: periodic checkpoint at "
                                  f"epoch {epoch + 1} boundary is the "
                                  "resume point; exiting cleanly")
            else:
                _preempt_save(epoch + 1, state)
            break
        prev_boundary_committed = boundary_saved
        if early is not None and val_loss == val_loss and early(val_loss):
            print_distributed(verbosity, 1, f"early stop at epoch {epoch}")
            break
        if not _walltime_remaining_guard(walltime_deadline):
            print_distributed(verbosity, 1, "walltime guard: stopping")
            break

    if jax.process_index() == 0:  # all processes hold identical history
        with open(os.path.join(run_dir, "history.json"), "w") as f:
            json.dump(history, f)
    if tb is not None:
        tb.close()
    if keep_best and best_state is not None:
        state = best_state
    if resume_meta_out is not None:
        # the run-complete resume point (next_epoch = num_epochs) for the
        # caller's final save: carries the FULL trainer state, so a later
        # continue with a raised num_epoch resumes scheduler/early-stop
        # counters and best_val instead of resetting them
        resume_meta_out.update(_resume_meta(num_epochs, state))
    return state, history


def _traced_place(place_fn):
    """Wrap a batch-placement callable so host->device staging shows up
    as `h2d` spans (telemetry/spans.py). No `step` on them: the device
    prefetch places a batch a few steps ahead of its own, and evaluation
    batches come through here too. With no recorder installed the
    per-batch cost is one global read + None check."""
    if place_fn is None:
        return None

    def placed(batch):
        with _spans.span("h2d", "loader"):
            return place_fn(batch)

    return placed


def _group_batches(loader, size):
    """Group fixed-shape batches into [S, ...]-stacked pytrees for the
    scanned multi-steps (datasets.loader._stack_batches handles Optional
    GraphBatch fields); the remainder group keeps its own (smaller)
    leading size."""
    from ..datasets.loader import _stack_batches
    buf = []
    for b in loader:
        buf.append(b)
        if len(buf) == size:
            yield _stack_batches(buf)
            buf = []
    if buf:
        yield _stack_batches(buf)


def _accumulate_metrics(acc: Dict[str, float], metrics, summed=False,
                        span_args=None):
    """Accumulate the loss/per-task scalars from one step (or one stacked
    multi-step, `summed=True`) into `acc` — one host transfer for the whole
    metrics dict, not one per key. The device_get blocks until the step's
    dependency chain is done, so under telemetry it is recorded as the
    `device_wait` span — the dispatch-vs-execute split of the step
    timeline (docs/observability.md)."""
    with _spans.span("device_wait", "device", **(span_args or {})):
        vals = jax.device_get(metrics)
    for k, v in vals.items():
        if (k == "loss" or k == "nonfinite_steps" or k.startswith("task_")
                or k.endswith("_loss")):
            acc[k] = acc.get(k, 0.0) + (float(np.sum(v)) if summed
                                        else float(v))


def _fetch_owed(acc: Dict[str, float], owed) -> int:
    """Fetch the metrics of the step owed on the device, `owed` = (metrics,
    span args, summed) or None, into `acc` (`_accumulate_metrics`). Returns
    1 when that step had finished before the fetch (the host was the slower
    side: the device ran dry), else 0; its `device_wait` span carries the
    same as `ready`."""
    if owed is None:
        return 0
    metrics, span_args, summed = owed
    ready = all(getattr(a, "is_ready", lambda: True)()
                for a in jax.tree_util.tree_leaves(metrics))
    _accumulate_metrics(acc, metrics, summed=summed,
                        span_args={**span_args, "ready": ready})
    return int(ready)


def _eval_one(eval_step, state, batch, acc: Dict[str, float]):
    out = eval_step(state, batch)
    metrics = out[0] if isinstance(out, tuple) else out
    _accumulate_metrics(acc, metrics)


def _eval_epoch(eval_step, state, loader, tr, name: str,
                multi_eval_step=None, steps_per_call: int = 1,
                place_fn=None):
    """Returns (mean loss, {metric: mean}) over the loader — per-task
    losses included (reference: task_loss_val/test tracking,
    train_validate_test.py:93-96,180-187)."""
    if loader is None:
        return float("nan"), {}
    acc: Dict[str, float] = {}
    nb = 0
    # grouping only pays off when at least one full group exists; a loader
    # shorter than S would stack and immediately re-slice for nothing
    grouped = (multi_eval_step is not None and steps_per_call > 1
               and len(loader) >= steps_per_call)
    with tr.timer(name):
        if grouped:
            for stacked in _group_batches(loader, steps_per_call):
                n = stacked.x.shape[0]
                if n == steps_per_call:
                    _accumulate_metrics(
                        acc, multi_eval_step(state, stacked), summed=True)
                else:  # remainder: single steps, no second scan compile
                    for i in range(n):
                        _eval_one(eval_step, state,
                                  jax.tree_util.tree_map(
                                      lambda a, i=i: a[i], stacked), acc)
                nb += n
        else:
            for batch in loader:
                # multi-process meshes need explicit global placement; a
                # single process auto-places per the step's in_specs
                if place_fn is not None:
                    batch = place_fn(batch)
                _eval_one(eval_step, state, batch, acc)
                nb += 1
    means = {k: v / max(nb, 1) for k, v in acc.items()}
    return means.pop("loss", float("nan")), means


def _tensorboard_writer(run_dir: str):
    """TensorBoard scalars via torch (CPU build is baked in) — parity with
    reference SummaryWriter use (utils/model/model.py:82-88; rank-0 only,
    like the reference's get_summary_writer)."""
    from ..utils.envflags import env_flag
    if env_flag("HYDRAGNN_DISABLE_TB") or jax.process_index() != 0:
        return None
    try:
        from torch.utils.tensorboard import SummaryWriter
        return SummaryWriter(run_dir)
    except Exception:
        return None
