"""Bayesian HPO: the in-tree CBO surrogate search + the standing
multi-trial orchestration loop (reference: DeepHyper CBO driver,
examples/multidataset_hpo/gfm_deephyper_multi.py:122-180), plus the
PR 14 satellites: SLURM nodelist expansion over multiple bracketed
groups, strict supervisor knob parsing, and deterministic PBT
fork/perturb (the supervisor itself is tested in
tests/test_hpo_supervisor.py)."""
import json
import logging
import os
import sys
import textwrap

import numpy as np
import pytest

from hydragnn_tpu.utils.bayes_opt import CBO, _GP, _Encoder
from hydragnn_tpu.utils.hpo import (orchestrate, parse_slurm_nodelist,
                                    search)


def test_encoder_roundtrip_types():
    space = {"lr": (1e-5, 1e-1), "width": (4, 64),
             "model": ["GIN", "PNA", "SAGE"], "fixed": 7}
    enc = _Encoder(space)
    rng = np.random.RandomState(0)
    for _ in range(20):
        p = enc.sample(rng)
        assert 1e-5 <= p["lr"] <= 1e-1
        assert 4 <= p["width"] <= 64 and isinstance(p["width"], int)
        assert p["model"] in space["model"]
        assert p["fixed"] == 7
        x = enc.encode(p)
        assert x.shape == (enc.d,)
        assert np.all(x >= -1e-9) and np.all(x <= 1 + 1e-9)


def test_gp_interpolates():
    rng = np.random.RandomState(0)
    X = rng.rand(20, 2)
    y = np.sin(3 * X[:, 0]) + X[:, 1] ** 2
    gp = _GP().fit(X, y)
    mean, std = gp.predict(X)
    np.testing.assert_allclose(mean, y, atol=0.1)
    Xs = rng.rand(5, 2)
    _, std_new = gp.predict(Xs)
    assert np.all(std_new >= 0)


def test_cbo_beats_random_on_quadratic():
    """On a smooth objective the GP search's best-found should match or
    beat pure random at equal budget (deterministic seeds)."""
    def f(p):
        return (p["x"] - 0.3) ** 2 + (p["y"] - 0.7) ** 2

    space = {"x": (0.01, 1.0), "y": (0.01, 1.0)}
    opt = CBO(space, seed=1, n_warmup=6)
    for _ in range(30):
        p = opt.ask()
        opt.tell(p, f(p))
    best_params, best_val = opt.best

    rng = np.random.RandomState(1)
    enc = _Encoder(space)
    rand_best = min(f(enc.sample(rng)) for _ in range(30))
    assert best_val <= rand_best * 1.5
    assert best_val < 0.05


def test_cbo_constant_liar_spreads_parallel_asks():
    space = {"x": (0.01, 1.0)}
    opt = CBO(space, seed=0, n_warmup=2)
    for _ in range(6):
        p = opt.ask()
        opt.tell(p, (p["x"] - 0.5) ** 2)
    batch = [opt.ask() for _ in range(4)]  # no tell in between
    xs = sorted(p["x"] for p in batch)
    assert len(set(round(x, 6) for x in xs)) == 4, xs


def test_search_uses_cbo_without_optuna():
    calls = []

    def obj(p):
        calls.append(p)
        return (p["x"] - 0.25) ** 2

    best, history = search(obj, {"x": (0.01, 1.0)}, num_trials=15, seed=3)
    assert len(history) == 15
    assert abs(best["x"] - 0.25) < 0.2


def test_orchestrate_end_to_end(tmp_path):
    """The standing loop launches trial subprocesses, parses objectives,
    logs trials.jsonl, and resumes from it."""
    script = tmp_path / "trial.py"
    script.write_text(textwrap.dedent("""
        import argparse, json
        p = argparse.ArgumentParser()
        p.add_argument("--x", type=float)
        p.add_argument("--tag", default="")
        a = p.parse_args()
        print(json.dumps({"final_val_loss": (a.x - 0.4) ** 2}))
    """))
    log_dir = str(tmp_path / "hpo")
    result = orchestrate(str(script), {"x": (0.01, 1.0)}, num_trials=6,
                         concurrent=2, seed=0, log_dir=log_dir,
                         extra_args={"tag": "t"}, timeout_s=120)
    assert len(result["history"]) == 6
    assert result["best"]["value"] < 0.3
    lines = open(os.path.join(log_dir, "trials.jsonl")).read().splitlines()
    assert len(lines) == 6
    # resume: two more trials on top of the logged six
    result2 = orchestrate(str(script), {"x": (0.01, 1.0)}, num_trials=8,
                          concurrent=2, seed=0, log_dir=log_dir,
                          extra_args={"tag": "t"}, timeout_s=120)
    assert len(result2["history"]) == 8


def test_cbo_inf_tell_does_not_poison_gp():
    """A failed trial (inf objective) must map to worst-finite inside the
    optimizer — an inf mean would NaN the GP standardization and silently
    degrade the search to random."""
    space = {"x": (0.01, 1.0)}
    opt = CBO(space, seed=0, n_warmup=2)
    for _ in range(4):
        p = opt.ask()
        opt.tell(p, (p["x"] - 0.5) ** 2)
    p = opt.ask()
    opt.tell(p, float("inf"))
    assert all(np.isfinite(v) for v in opt.y)
    p2 = opt.ask()  # GP path (past warmup) must still produce candidates
    assert 0.01 <= p2["x"] <= 1.0
    best_params, best_val = opt.best
    assert np.isfinite(best_val)


def test_orchestrate_failed_trial_scores_worst(tmp_path):
    script = tmp_path / "bad.py"
    script.write_text("import sys; sys.exit(3)\n")
    log_dir = str(tmp_path / "hpo_bad")
    result = orchestrate(str(script), {"x": (0.01, 1.0)}, num_trials=2,
                         concurrent=1, seed=0, log_dir=log_dir,
                         timeout_s=60)
    # failed trials persist as value=null + failed flag (strict JSON —
    # bare Infinity would break jq/strict parsers), rc preserved
    assert all(r["value"] is None and r["failed"] and not r["timed_out"]
               and r["rc"] == 3 for r in result["history"])
    # trials.jsonl must round-trip through a STRICT json parser
    with open(os.path.join(log_dir, "trials.jsonl")) as f:
        for line in f:
            json.loads(line, parse_constant=lambda s: (_ for _ in ()).throw(
                ValueError(f"non-standard JSON constant {s}")))
    # and resume must still poison-guard: a fresh orchestrate over the
    # same log_dir replays the failed trials as worst-finite
    result2 = orchestrate(str(script), {"x": (0.01, 1.0)}, num_trials=2,
                          concurrent=1, seed=0, log_dir=log_dir,
                          timeout_s=60)
    assert len(result2["history"]) == 2  # resumed, nothing re-run


def test_parse_slurm_nodelist_single_group():
    assert parse_slurm_nodelist("frontier[00001-00003,00007]") == [
        "frontier00001", "frontier00002", "frontier00003", "frontier00007"]
    assert parse_slurm_nodelist("node12") == ["node12"]
    assert parse_slurm_nodelist("node1,node2") == ["node1", "node2"]
    assert parse_slurm_nodelist("") == []


def test_parse_slurm_nodelist_multiple_bracketed_groups():
    """Comma-separated bracketed groups, the heterogeneous-allocation
    shape SLURM emits — the old single-trailing-bracket regex silently
    returned a wrong node list for these (PR 14 regression)."""
    assert parse_slurm_nodelist("frontier[001-002],borg[005]") == [
        "frontier001", "frontier002", "borg005"]
    assert parse_slurm_nodelist("a[1-2],b,c[04,06-07]") == [
        "a1", "a2", "b", "c04", "c06", "c07"]
    # zero-padding width follows each group's own lower bound
    assert parse_slurm_nodelist("x[08-10],y[1-2]") == [
        "x08", "x09", "x10", "y1", "y2"]


def test_read_node_list_uses_env(monkeypatch):
    from hydragnn_tpu.utils.hpo import read_node_list
    monkeypatch.setenv("SLURM_NODELIST", "n[1-2],m[7]")
    assert read_node_list() == ["n1", "n2", "m7"]
    monkeypatch.delenv("SLURM_NODELIST", raising=False)
    monkeypatch.setenv("SLURM_JOB_NODELIST", "solo")
    assert read_node_list() == ["solo"]
    monkeypatch.delenv("SLURM_JOB_NODELIST", raising=False)
    assert read_node_list() == []


def test_resolve_hpo_supervisor_strict_and_precedence(monkeypatch, caplog):
    from hydragnn_tpu.utils.envflags import resolve_hpo_supervisor
    for name in ("HYDRAGNN_HPO_MAX_RETRIES", "HYDRAGNN_HPO_HEARTBEAT_S",
                 "HYDRAGNN_HPO_BACKOFF_S", "HYDRAGNN_HPO_CONCURRENCY"):
        monkeypatch.delenv(name, raising=False)
    # defaults
    assert resolve_hpo_supervisor() == (2, 120.0, 1.0, 1)
    # config block
    assert resolve_hpo_supervisor(
        {"max_retries": 5, "heartbeat_s": 9.0, "backoff_s": 0.2,
         "concurrency": 4}) == (5, 9.0, 0.2, 4)
    # env wins over config
    monkeypatch.setenv("HYDRAGNN_HPO_MAX_RETRIES", "1")
    monkeypatch.setenv("HYDRAGNN_HPO_HEARTBEAT_S", "3.5")
    monkeypatch.setenv("HYDRAGNN_HPO_BACKOFF_S", "0")
    monkeypatch.setenv("HYDRAGNN_HPO_CONCURRENCY", "8")
    assert resolve_hpo_supervisor({"max_retries": 5}) == (1, 3.5, 0.0, 8)
    # a typo value warns and falls back instead of taking effect
    monkeypatch.setenv("HYDRAGNN_HPO_MAX_RETRIES", "threeish")
    with caplog.at_level(logging.WARNING, logger="hydragnn_tpu"):
        retries, _, _, conc = resolve_hpo_supervisor({"max_retries": 5})
    assert retries == 5 and conc == 8
    assert any("HYDRAGNN_HPO_MAX_RETRIES" in r.message
               for r in caplog.records)
    # floors: concurrency >= 1, heartbeat > 0, retries >= 0
    monkeypatch.setenv("HYDRAGNN_HPO_MAX_RETRIES", "-3")
    monkeypatch.setenv("HYDRAGNN_HPO_HEARTBEAT_S", "0")
    monkeypatch.setenv("HYDRAGNN_HPO_CONCURRENCY", "0")
    retries, hb, _, conc = resolve_hpo_supervisor()
    assert retries == 0 and hb > 0 and conc == 1


def test_perturb_params_deterministic_and_in_range():
    from hydragnn_tpu.hpo import perturb_params
    space = {"lr": (1e-4, 1e-1), "width": (4, 64),
             "model": ["GIN", "PNA"], "fixed": 7}
    params = {"lr": 0.01, "width": 16, "model": "GIN", "fixed": 7}
    outs = [perturb_params(params, space, seed=123) for _ in range(3)]
    # same seed => bitwise-identical perturbation (the forked trial's
    # start state is a pure function of (donor params, space, seed))
    assert outs[0] == outs[1] == outs[2]
    # different seeds explore
    variants = {json.dumps(perturb_params(params, space, seed=s),
                           sort_keys=True) for s in range(40)}
    assert len(variants) > 1
    for s in range(40):
        p = perturb_params(params, space, seed=s)
        assert 1e-4 <= p["lr"] <= 1e-1
        assert 4 <= p["width"] <= 64 and isinstance(p["width"], int)
        assert p["model"] in space["model"]
        assert p["fixed"] == 7  # fixed values never perturb


def test_fork_checkpoint_adopts_best_state_and_val(tmp_path):
    """fork -> the new checkpoint dir's LATEST names the donor's BEST
    step, the donor's recorded val rides along (the load_best_model
    (state, val) adoption semantics), and the stale resume.json is
    dropped so the fork trains from epoch 0."""
    import jax.numpy as jnp
    import optax

    from hydragnn_tpu.hpo import fork_checkpoint
    from hydragnn_tpu.train.train_step import TrainState
    from hydragnn_tpu.utils import checkpoint as ck

    def state_at(step):
        variables = {"params": {"w": jnp.full((3,), float(step),
                                              jnp.float32)}}
        s = TrainState.create(variables, optax.sgd(0.1))
        return s.replace(step=jnp.asarray(step, jnp.int32))

    run = "fork_donor_test"
    ck.save_model(state_at(1), run, path=str(tmp_path),
                  metadata={"next_epoch": 1}, mark_best=True,
                  best_val=0.25)
    ck.save_model(state_at(2), run, path=str(tmp_path),
                  metadata={"next_epoch": 2})
    src = ck._ckpt_dir(run, path=str(tmp_path))
    dst = str(tmp_path / "forked" / "checkpoint")

    step, val = fork_checkpoint(src, dst)
    assert step == 1 and val == 0.25  # BEST, not LATEST
    with open(os.path.join(dst, "LATEST")) as f:
        assert f.read().strip() == "step_1"
    assert ck.verify_checkpoint(os.path.join(dst, "step_1"))
    # the donor's resume metadata must not ride along
    assert ck.load_checkpoint_metadata(os.path.join(dst, "step_1")) is None
    # the copied weights restore to the donor BEST state
    restored = ck.load_existing_model(state_at(0), "forked",
                                      path=str(tmp_path))
    assert int(restored.step) == 1
    np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                  np.ones((3,), np.float32))
    # fork is deterministic: a second fork of the same donor is identical
    dst2 = str(tmp_path / "forked2" / "checkpoint")
    assert fork_checkpoint(src, dst2) == (step, val)


def test_cbo_non_positive_float_range():
    """Float ranges touching 0/negative use linear scaling (log10 would
    raise); positive ranges keep the log scale."""
    opt = CBO({"lr": (1e-4, 1.0), "shift": (-0.5, 0.5)}, seed=0)
    for _ in range(6):
        p = opt.ask()
        assert -0.5 <= p["shift"] <= 0.5
        assert 1e-4 <= p["lr"] <= 1.0
        opt.tell(p, p["shift"] ** 2 + p["lr"])
    enc = _Encoder({"shift": (-0.5, 0.5)})
    x = enc.encode({"shift": 0.0})
    assert 0.0 <= float(x[0]) <= 1.0
