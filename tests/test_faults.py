"""Fault-tolerance layer (docs/fault_tolerance.md): deterministic fault
injection, preemption-safe resume, checkpoint integrity + retention.

The adjudication contract (ISSUE 4): a run killed mid-training at an
injected fault and resumed from its checkpoints produces a loss trajectory
BITWISE-identical to the uninterrupted run; corrupt/uncommitted step dirs
are skipped at restore; retention GC keeps best + last-k; the SIGTERM save
fires exactly once; a persistently failing checkpoint path escalates to a
hard error instead of a silent checkpoint-less run."""
import json
import logging
import os
import signal
import time

import numpy as np
import optax
import pytest

from hydragnn_tpu.preprocess.load_data import split_dataset
from hydragnn_tpu.run_training import run_training
from hydragnn_tpu.train.train_step import TrainState
from hydragnn_tpu.utils import checkpoint as ck
from hydragnn_tpu.utils.faults import (InjectedFault,
                                       InjectedTransientIOError,
                                       install_fault_plan, parse_fault_plan,
                                       resolve_fault_plan)

from tests.deterministic_data import deterministic_graph_dataset
from tests.utils import make_config

# the numeric loss trajectory: instrumentation keys (input_bound_frac,
# jit_recompiles) are timing/process dependent and excluded by design
TRAJ_KEYS = ("train_loss", "val_loss", "test_loss", "lr")


@pytest.fixture(autouse=True)
def _clean_fault_state():
    yield
    install_fault_plan(None)
    from hydragnn_tpu.train.trainer import clear_preemption
    clear_preemption()


# ------------------------------------------------------------- plan grammar

def test_parse_fault_plan_grammar():
    plan = parse_fault_plan("forward-step@2; serving-dispatch@0,3")
    assert plan.injections == {"forward-step": frozenset({2}),
                               "serving-dispatch": frozenset({0, 3})}
    # round-trips through the canonical spec
    assert parse_fault_plan(plan.spec()).injections == plan.injections
    # counters are per-site and monotone; listed indices raise
    plan.fault_point("forward-step")  # idx 0
    plan.fault_point("forward-step")  # idx 1
    with pytest.raises(InjectedFault, match="forward-step@2"):
        plan.fault_point("forward-step")
    plan.fault_point("forward-step")  # idx 3: past the listed index
    assert plan.fired() == [("forward-step", 2)]
    assert plan.counts()["forward-step"] == 4
    # unlisted sites are free
    plan.fault_point("checkpoint-write")


def test_parse_fault_plan_trial_sites():
    """The PR 14 trial sites parse, count, and round-trip like every
    other site (docs/hpo.md consumes them once per trial launch)."""
    plan = parse_fault_plan(
        "trial-kill@1;trial-hang@2;trial-spawn-fail@0")
    assert plan.injections == {"trial-kill": frozenset({1}),
                               "trial-hang": frozenset({2}),
                               "trial-spawn-fail": frozenset({0})}
    assert parse_fault_plan(plan.spec()).injections == plan.injections
    with pytest.raises(InjectedFault, match="trial-spawn-fail@0"):
        plan.fault_point("trial-spawn-fail")
    plan.fault_point("trial-kill")  # idx 0: free
    with pytest.raises(InjectedFault, match="trial-kill@1"):
        plan.fault_point("trial-kill")


def test_parse_fault_plan_rejects_malformed():
    for bad in ("forward-step", "warp-core@1", "forward-step@x",
                "forward-step@-1", "forward-step@", "", ";;"):
        with pytest.raises(ValueError):
            parse_fault_plan(bad)


def test_loader_fetch_fault_is_transient_oserror():
    plan = parse_fault_plan("loader-fetch@0")
    with pytest.raises(OSError):
        plan.fault_point("loader-fetch")
    # and still an InjectedFault for blanket chaos accounting
    assert issubclass(InjectedTransientIOError, InjectedFault)


def test_resolve_fault_plan_strict_and_precedence(monkeypatch, caplog):
    monkeypatch.delenv("HYDRAGNN_FAULT_PLAN", raising=False)
    assert resolve_fault_plan({}) is None
    # config block alone
    plan = resolve_fault_plan({"fault_plan": "loader-fetch@1"})
    assert plan is not None and "loader-fetch" in plan.injections
    # env wins over config
    monkeypatch.setenv("HYDRAGNN_FAULT_PLAN", "forward-step@4")
    plan = resolve_fault_plan({"fault_plan": "loader-fetch@1"})
    assert plan.injections == {"forward-step": frozenset({4})}
    # a typo warns and injects NOTHING (strict-parsing ethos)
    monkeypatch.setenv("HYDRAGNN_FAULT_PLAN", "forward-step@oops")
    with caplog.at_level(logging.WARNING, logger="hydragnn_tpu"):
        assert resolve_fault_plan({}) is None
    assert any("fault plan" in r.message for r in caplog.records)


# --------------------------------------------------- checkpoint integrity

def _tiny_state(step=0, scale=1.0):
    import jax.numpy as jnp
    variables = {"params": {"w": jnp.full((3,), scale, jnp.float32)}}
    state = TrainState.create(variables, optax.sgd(0.1))
    return state.replace(step=jnp.asarray(step, jnp.int32))


def test_restore_skips_uncommitted_and_corrupt(tmp_path, caplog):
    run = "integrity_test"
    s0 = _tiny_state(step=0, scale=1.0)
    s1 = _tiny_state(step=1, scale=2.0)
    d = os.path.dirname(ck.save_model(s0, run, path=str(tmp_path)))
    t1 = ck.save_model(s1, run, path=str(tmp_path))
    assert ck.verify_checkpoint(t1)

    # a newest-looking dir with NO commit marker and no orbax metadata
    # (a writer killed mid-save) must be skipped entirely
    os.makedirs(os.path.join(d, "step_99"))
    restored = ck.load_existing_model(s0, run, path=str(tmp_path))
    assert int(restored.step) == 1

    # corrupt the committed newest: orbax metadata gone -> verification
    # fails -> fall back to the previous verified step
    for name in ("_CHECKPOINT_METADATA", "_METADATA", "checkpoint"):
        p = os.path.join(t1, name)
        if os.path.exists(p):
            os.remove(p)
    restored = ck.load_existing_model(s0, run, path=str(tmp_path))
    assert int(restored.step) == 0
    np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                  np.ones((3,), np.float32))

    # metadata round-trip on the surviving save
    meta = {"next_epoch": 7, "trainer": {"best_val": 0.25}}
    t2 = ck.save_model(_tiny_state(step=2), run, path=str(tmp_path),
                       metadata=meta)
    _, got = ck.load_existing_model(s0, run, path=str(tmp_path),
                                    with_metadata=True)
    assert got == meta
    assert ck.load_checkpoint_metadata(t2) == meta


def test_manifest_detects_silently_corrupted_payload(tmp_path, caplog):
    """The COMMITTED marker's sha256 manifest (PR 15): flipping ONE byte
    inside a committed payload file passes the structural check but
    fails the deep verification, and restore falls back to the newest
    verified save with a warning naming the bad file."""
    run = "manifest_test"
    s0 = _tiny_state(step=0, scale=1.0)
    ck.save_model(s0, run, path=str(tmp_path))
    t1 = ck.save_model(_tiny_state(step=1, scale=2.0), run,
                       path=str(tmp_path))
    with open(os.path.join(t1, ck.COMMIT_MARKER)) as f:
        lines = f.read().splitlines()
    assert lines[0] == os.path.basename(t1)
    manifest = [ln.split(" ", 2) for ln in lines[1:]]
    assert manifest and all(len(m) == 3 for m in manifest)
    assert ck.verify_manifest(t1) is None  # pristine save verifies
    # flip one byte in the LARGEST manifested payload (the array data)
    digest, size, rel = max(manifest, key=lambda m: int(m[1]))
    victim = os.path.join(t1, rel)
    with open(victim, "r+b") as f:
        f.seek(int(size) // 2)
        byte = f.read(1)
        f.seek(int(size) // 2)
        f.write(bytes([byte[0] ^ 0xFF]))
    # structural check still passes; the deep check names the file
    assert ck.verify_checkpoint(t1)
    bad = ck.verify_manifest(t1)
    assert bad is not None and rel in bad and "sha256" in bad
    with caplog.at_level(logging.WARNING, logger="hydragnn_tpu"):
        assert not ck.verify_checkpoint(t1, deep=True)
        restored = ck.load_existing_model(s0, run, path=str(tmp_path))
    assert any(rel in r.message for r in caplog.records)
    # fell back to the newest VERIFIED save instead of restoring garbage
    assert int(restored.step) == 0
    np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                  np.ones((3,), np.float32))
    # size mismatch is named too
    with open(victim, "ab") as f:
        f.write(b"x")
    assert "size" in (ck.verify_manifest(t1) or "")


def test_pre_manifest_checkpoint_still_restores(tmp_path):
    """A COMMITTED marker written before the manifest existed (line 1
    only) must keep restoring — the deep check is vacuous for it."""
    run = "legacy_marker_test"
    t = ck.save_model(_tiny_state(step=3, scale=3.0), run,
                      path=str(tmp_path),
                      metadata={"next_epoch": 2, "step": 3})
    # rewrite the marker to the pre-PR single-line form
    with open(os.path.join(t, ck.COMMIT_MARKER), "w") as f:
        f.write(os.path.basename(t))
    assert ck.verify_manifest(t) is None
    assert ck.verify_checkpoint(t, deep=True)
    restored, meta = ck.load_existing_model(_tiny_state(), run,
                                            path=str(tmp_path),
                                            with_metadata=True)
    assert int(restored.step) == 3
    # a pre-elastic resume.json passes the schema gate unchanged
    assert ck.validate_resume_meta(meta) == meta


def test_resume_meta_schema_tolerance():
    """resume.json schema gate: unknown keys are ignored (forward
    compat for the elastic world_size metadata and whatever comes
    next); missing REQUIRED keys raise naming the key."""
    meta = {"next_epoch": 2, "step": 10, "loader_epoch": 2,
            "world_size": 4, "some_future_key": {"x": 1}}
    assert ck.validate_resume_meta(meta) is meta
    with pytest.raises(ValueError, match="'next_epoch'"):
        ck.validate_resume_meta({"step": 1})
    with pytest.raises(ValueError, match="'step'"):
        ck.validate_resume_meta({"next_epoch": 1, "extra": True})


def test_retention_gc_keeps_best_and_last_k(tmp_path):
    run = "retention_test"
    for step in range(1, 6):
        ck.save_model(_tiny_state(step=step), run, path=str(tmp_path),
                      mark_best=(step == 2), keep_last_k=2)
    d = ck._ckpt_dir(run, path=str(tmp_path))
    # crash leftovers: .gc- trash from an interrupted delete and an
    # uncommitted step dir OLDER than the newest committed save (a dead
    # writer) must be reaped by the next GC pass
    os.makedirs(os.path.join(d, ".gc-step_99"))
    os.makedirs(os.path.join(d, "step_3"), exist_ok=True)  # already gone
    os.makedirs(os.path.join(d, "step_0"))  # dead uncommitted writer
    ck.save_model(_tiny_state(step=6), run, path=str(tmp_path),
                  keep_last_k=2)
    assert not os.path.exists(os.path.join(d, ".gc-step_99"))
    assert not os.path.exists(os.path.join(d, "step_0"))
    dirs = sorted(p for p in os.listdir(d) if p.startswith("step_"))
    # newest 2 + the BEST target survive; LATEST names the newest
    assert dirs == ["step_2", "step_5", "step_6"]
    with open(os.path.join(d, "LATEST")) as f:
        assert f.read().strip() == "step_6"
    with open(os.path.join(d, "BEST")) as f:
        assert f.read().strip() == "step_2"
    best = ck.load_best_model(_tiny_state(), run, path=str(tmp_path))
    assert int(best.step) == 2


def test_async_best_ckpt_escalates_after_3_failures(monkeypatch):
    calls = []

    def failing_save(*a, **kw):
        calls.append(1)
        raise OSError("disk full")

    monkeypatch.setattr(ck, "save_model", failing_save)
    fn = ck.make_async_best_checkpoint_fn("escalation_test")
    fn(None, 0, 1.0)  # swallowed (warn)
    fn(None, 1, 0.9)  # swallowed (warn)
    with pytest.raises(RuntimeError, match="3 times in a row"):
        fn(None, 2, 0.8)
    assert len(calls) == 3

    # any success resets the consecutive counter
    outcomes = iter(["fail", "fail", "ok", "fail", "fail", "fail"])

    def flaky_save(*a, **kw):
        if next(outcomes) == "fail":
            raise OSError("transient")
        return "ok"

    monkeypatch.setattr(ck, "save_model", flaky_save)
    fn = ck.make_async_best_checkpoint_fn("escalation_test")
    for epoch in range(5):
        fn(None, epoch, 1.0)  # fail,fail,ok,fail,fail — never 3 straight
    with pytest.raises(RuntimeError):
        fn(None, 5, 1.0)  # the 3rd consecutive


def test_fork_from_corrupt_best_falls_back_to_newest_verified(tmp_path,
                                                              caplog):
    """PBT exploit resilience (PR 14): forking from a BEST marker whose
    target is uncommitted/corrupt must fall back to the newest VERIFIED
    checkpoint with a warning instead of crashing the supervisor; with
    nothing verified it raises an actionable FileNotFoundError."""
    from hydragnn_tpu.hpo import fork_checkpoint, select_fork_source

    run = "fork_fallback_test"
    ck.save_model(_tiny_state(step=1, scale=1.0), run, path=str(tmp_path),
                  mark_best=True, best_val=0.5)
    ck.save_model(_tiny_state(step=2, scale=2.0), run, path=str(tmp_path))
    d = ck._ckpt_dir(run, path=str(tmp_path))

    # corrupt the BEST target: drop its commit marker
    os.remove(os.path.join(d, "step_1", ck.COMMIT_MARKER))
    with caplog.at_level(logging.WARNING, logger="hydragnn_tpu"):
        target, val = select_fork_source(d)
    assert os.path.basename(target) == "step_2"  # newest verified
    assert val is None  # the fallback has no recorded val to adopt
    assert any("falling back" in r.message for r in caplog.records)

    # fork_checkpoint degrades the same way end to end
    dst = str(tmp_path / "forked" / "checkpoint")
    step, val2 = fork_checkpoint(d, dst)
    assert step == 2 and val2 is None
    assert ck.verify_checkpoint(os.path.join(dst, "step_2"))

    # a BEST marker pointing at a missing dir: same fallback
    with open(os.path.join(d, "BEST"), "w") as f:
        f.write("step_99\n0.1")
    target, _ = select_fork_source(d)
    assert os.path.basename(target) == "step_2"

    # an EMPTY (truncated-mid-write) BEST file: fallback, not IndexError
    with open(os.path.join(d, "BEST"), "w") as f:
        f.write("")
    target, _ = select_fork_source(d)
    assert os.path.basename(target) == "step_2"

    # a garbled val line on a VALID target: adopt the state, val unknown
    with open(os.path.join(d, "BEST"), "w") as f:
        f.write("step_2\nnot-a-float")
    target, val3 = select_fork_source(d)
    assert os.path.basename(target) == "step_2" and val3 is None

    # nothing verified at all -> actionable error, not a crash deeper in
    os.remove(os.path.join(d, "step_2", ck.COMMIT_MARKER))
    with pytest.raises(FileNotFoundError, match="no verified checkpoint"):
        select_fork_source(d)
    with pytest.raises(FileNotFoundError):
        select_fork_source(str(tmp_path / "does_not_exist"))


# ----------------------------------------------------- preemption (SIGTERM)

def test_sigterm_sets_preemption_flag():
    from hydragnn_tpu.train import trainer
    assert trainer.install_sigterm_handler()
    trainer.clear_preemption()
    assert not trainer.preemption_requested()
    os.kill(os.getpid(), signal.SIGTERM)
    deadline = time.time() + 5
    while not trainer.preemption_requested() and time.time() < deadline:
        time.sleep(0.01)
    assert trainer.preemption_requested()


def test_preempt_save_fires_exactly_once(tmp_path):
    """A preempted trainer performs ONE final save with resume metadata and
    exits cleanly — even though both the batch-level and epoch-level
    preemption checks observe the same flag."""
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.datasets.loader import GraphDataLoader
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.train import trainer
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.train_step import make_eval_step, make_train_step

    samples = deterministic_graph_dataset(num_configs=16)
    cfg = make_config("GIN")
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    model = create_model(mcfg)
    loader = GraphDataLoader(samples, batch_size=8, shuffle=True, seed=0)
    variables = init_params(model, next(iter(loader)))
    tx = select_optimizer(cfg["NeuralNetwork"]["Training"])
    state = TrainState.create(variables, tx)

    saves = []
    trainer.request_preemption()
    trainer.request_preemption()  # duplicate signal delivery
    final, hist = trainer.train_validate_test(
        make_train_step(model, mcfg, tx), make_eval_step(model, mcfg),
        state, loader, None, None, num_epochs=3,
        log_name="preempt_once", log_dir=str(tmp_path),
        use_early_stopping=False, keep_best=False,
        preempt_save_fn=lambda s, meta: saves.append(meta))
    assert len(saves) == 1, "preempt save must fire exactly once"
    assert saves[0]["next_epoch"] == 0  # epoch 0 was partial: replay it
    assert "trainer" in saves[0] and "history" in saves[0]["trainer"]
    assert hist["train_loss"] == []  # stopped before completing an epoch
    trainer.clear_preemption()


def test_mid_epoch_preempt_saves_epoch_start_state(tmp_path):
    """SIGTERM mid-epoch must checkpoint the EPOCH-START state: resume
    replays the whole epoch, so saving the partial-epoch pytree would
    double-apply the already-completed batches (code-review regression)."""
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.datasets.loader import GraphDataLoader
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.train import trainer
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.train_step import make_eval_step, make_train_step

    samples = deterministic_graph_dataset(num_configs=16)
    cfg = make_config("GIN")
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    model = create_model(mcfg)
    loader = GraphDataLoader(samples, batch_size=8, shuffle=True, seed=0)
    variables = init_params(model, next(iter(loader)))
    tx = select_optimizer(cfg["NeuralNetwork"]["Training"])
    state = TrainState.create(variables, tx)

    real_step = make_train_step(model, mcfg, tx)
    calls = []

    def counting_step(s, batch):
        calls.append(1)
        if len(calls) == 3:  # 2 batches/epoch: epoch 1's first batch
            trainer.request_preemption()
        return real_step(s, batch)

    saves = []
    trainer.clear_preemption()
    _, hist = trainer.train_validate_test(
        counting_step, make_eval_step(model, mcfg), state, loader,
        None, None, num_epochs=4, log_name="preempt_mid", keep_best=False,
        log_dir=str(tmp_path), use_early_stopping=False,
        preempt_save_fn=lambda s, meta: saves.append((s, meta)))
    assert len(saves) == 1
    saved_state, meta = saves[0]
    assert meta["next_epoch"] == 1  # replay epoch 1 from its start
    # one batch of epoch 1 DID run (step 3 on the live state), but the
    # saved resume point is the epoch-1-start state after epoch 0's 2 steps
    assert int(saved_state.step) == 2
    assert len(hist["train_loss"]) == 1  # only epoch 0 completed
    trainer.clear_preemption()


# ------------------------------------------- kill-and-resume (adjudication)

def _resume_cfg(num_epoch=5):
    cfg = make_config("GIN")
    t = cfg["NeuralNetwork"]["Training"]
    t["num_epoch"] = num_epoch
    t["batch_size"] = 8
    t["EarlyStopping"] = False
    t["Checkpoint"] = True
    t["checkpoint_every_n_epochs"] = 1
    t["keep_best"] = False
    return cfg


def test_kill_and_resume_trajectory_bitwise(tmp_path, monkeypatch):
    """The tentpole adjudication: training killed at an injected
    forward-step fault, resumed from the periodic checkpoint, reproduces
    the uninterrupted run's loss trajectory BITWISE (ISSUE 4)."""
    samples = deterministic_graph_dataset(num_configs=24)
    splits = split_dataset(samples, 0.7)

    ref_dir = tmp_path / "ref"
    chaos_dir = tmp_path / "chaos"
    ref_dir.mkdir()
    chaos_dir.mkdir()

    monkeypatch.chdir(ref_dir)
    _, h_ref, _, _ = run_training(_resume_cfg(), datasets=splits,
                                  num_shards=1)

    # kill: 2 train batches/epoch -> forward-step@5 dies mid-epoch 2,
    # after the periodic saves for epochs 0 and 1 committed
    monkeypatch.chdir(chaos_dir)
    cfg = _resume_cfg()
    cfg["NeuralNetwork"]["Training"]["fault_plan"] = "forward-step@5"
    with pytest.raises(InjectedFault, match="forward-step@5"):
        run_training(cfg, datasets=splits, num_shards=1)

    # resume: same run name, no faults
    cfg2 = _resume_cfg()
    cfg2["NeuralNetwork"]["Training"]["continue"] = 1
    state2, h_res, _, _ = run_training(cfg2, datasets=splits, num_shards=1)

    for key in TRAJ_KEYS:
        assert len(h_res[key]) == len(h_ref[key]) == 5, key
        assert h_res[key] == h_ref[key], (
            f"{key} diverged after resume:\n{h_res[key]}\nvs\n{h_ref[key]}")
    # the resumed run ends at the same optimizer step
    assert int(state2.step) == 10


def test_resume_of_completed_run_is_a_noop(tmp_path, monkeypatch):
    """A finished run's final save marks it COMPLETE (next_epoch =
    num_epoch): continue must not silently retrain from epoch 0."""
    samples = deterministic_graph_dataset(num_configs=24)
    splits = split_dataset(samples, 0.7)
    monkeypatch.chdir(tmp_path)
    cfg = _resume_cfg(num_epoch=2)
    state1, h1, _, _ = run_training(cfg, datasets=splits, num_shards=1)

    cfg2 = _resume_cfg(num_epoch=2)
    cfg2["NeuralNetwork"]["Training"]["continue"] = 1
    state2, h2, _, _ = run_training(cfg2, datasets=splits, num_shards=1)
    assert int(state2.step) == int(state1.step)
    # restored history is carried over, no new epochs appended
    assert h2["train_loss"] == h1["train_loss"]


# ------------------------------------------------------- loader-fetch retry

def _batches_equal(a, b):
    import dataclasses
    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        assert (va is None) == (vb is None), f.name
        if va is not None:
            np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))


def test_loader_fetch_retry_recovers_transient_fault(monkeypatch):
    from hydragnn_tpu.datasets.loader import GraphDataLoader
    monkeypatch.setenv("HYDRAGNN_LOADER_RETRY_BACKOFF_S", "0.001")
    samples = deterministic_graph_dataset(num_configs=16)
    ref = list(GraphDataLoader(samples, batch_size=4, shuffle=True, seed=0,
                               async_workers=0))

    # one injected transient I/O failure: retried, stream bitwise intact
    install_fault_plan(parse_fault_plan("loader-fetch@3"))
    got = list(GraphDataLoader(samples, batch_size=4, shuffle=True, seed=0,
                               async_workers=0))
    assert len(got) == len(ref)
    for a, b in zip(got, ref):
        _batches_equal(a, b)

    # ... including through the background collation pool
    install_fault_plan(parse_fault_plan("loader-fetch@3"))
    got_async = list(GraphDataLoader(samples, batch_size=4, shuffle=True,
                                     seed=0, async_workers=2))
    for a, b in zip(got_async, ref):
        _batches_equal(a, b)

    # attempts (default 3) consecutive failures exhaust the retry and
    # surface as the original OSError
    install_fault_plan(parse_fault_plan("loader-fetch@1,2,3"))
    with pytest.raises(OSError):
        list(GraphDataLoader(samples, batch_size=4, shuffle=True, seed=0,
                             async_workers=0))


# --------------------------------------------------- slow-lane chaos smoke

@pytest.mark.slow
def test_bench_faults_chaos_smoke(tmp_path):
    """BENCH_FAULTS end-to-end in a subprocess (the nightly chaos-smoke):
    kill/resume trajectory bitwise-equal, recovered-step fraction
    reported, zero serving futures lost, and the BENCH_FAULTS.json
    artifact emitted."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out_path = os.path.join(str(tmp_path), "BENCH_FAULTS.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_FAULTS="1",
               BENCH_HIDDEN="32",
               BENCH_FAULTS_REQUESTS="32", BENCH_FAULTS_OUT=out_path)
    r = subprocess.run([sys.executable, os.path.join(repo, "bench.py")],
                       env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert os.path.exists(out_path)
    assert out["value"] == 1.0
    assert out["training"]["trajectory_bitwise_equal"] is True
    assert out["training"]["killed"] is True
    assert 0.0 < out["training"]["recovered_step_fraction"] < 1.0
    assert out["serving"]["no_lost_futures"] is True
    assert out["serving"]["unresolved"] == 0
    assert out["serving"]["resolved_error"] > 0  # faults really fired
