"""The continuous-learning loop (serving/publish.py, serving/
autoscale.py, docs/serving.md "Continuous loop").

Contract under test:
* ``pair_rel_err`` / ``adjudicate_window`` verdict semantics: drift
  bound, latency budget, shadow failures, starved windows — pure
  functions, no fleet needed,
* the publisher promotes a good BEST/COMMITTED checkpoint through the
  full canary protocol (swap one drained replica, mirror a traffic
  slice, adjudicate, roll the rest) with zero lost futures,
* a poisoned candidate is rolled BACK: the fleet stays coherent on the
  incumbent, the version is quarantined, and a fresh publisher skips
  it at detection time,
* COMMITTED-only hardening: an uncommitted BEST marker makes
  ``hot_swap_from_checkpoint`` raise an UncommittedCheckpointError
  NAMING the torn dir, and the publisher counts-and-retries instead of
  serving it,
* a promote that trips the ``swap-fail`` site mid-roll restores ONE
  coherent version (the incumbent) and quarantines the candidate; a
  plain hot_swap failure names both sides of the mixed-version fleet
  and the router keeps routing,
* the queue-depth autoscaler: watermark decisions, cooldown, min/max
  clamps, canary freeze (unit, fake router) and disk-warm
  add/retire/revive on a real fleet (integration),
* health()/stats()/Prometheus surface the per-replica version +
  canary state, and HYDRAGNN_PUBLISH_* / HYDRAGNN_AUTOSCALE_* knobs
  resolve config/env precedence with strict parsing.

Sized for tier-1: tiny GIN, 2-3 replicas, mirror_every=1 windows of a
few pairs. The BENCH_CONTINUOUS subprocess smoke lives in the `slow`
lane.
"""
import json
import os
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

from hydragnn_tpu.config import build_model_config, update_config
from hydragnn_tpu.graphs.batch import collate
from hydragnn_tpu.models.create import create_model, init_params
from hydragnn_tpu.serving.autoscale import QueueDepthAutoscaler
from hydragnn_tpu.serving.config import (AutoscaleConfig, PublishConfig,
                                         resolve_autoscale,
                                         resolve_publish)
from hydragnn_tpu.serving.engine import InferenceEngine
from hydragnn_tpu.serving.fleet import ReplicaRouter, SwapFailedError
from hydragnn_tpu.serving.publish import (CheckpointPublisher,
                                          adjudicate_window,
                                          pair_rel_err)
from hydragnn_tpu.utils.checkpoint import (UncommittedCheckpointError,
                                           COMMIT_MARKER, marker_target,
                                           save_model)
from hydragnn_tpu.utils.devices import CompileStore
from hydragnn_tpu.utils.faults import (install_fault_plan,
                                       parse_fault_plan)

from tests.deterministic_data import deterministic_graph_dataset
from tests.utils import make_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_fault_state():
    yield
    install_fault_plan(None)


@pytest.fixture(scope="module")
def served():
    samples = deterministic_graph_dataset(num_configs=24)
    cfg = make_config("GIN")
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    model = create_model(mcfg)
    variables = init_params(model, collate(samples[:4]))
    return samples, mcfg, model, variables


def _factory(served, store=None, **kw):
    samples, mcfg, model, variables = served
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_wait_ms", 2.0)
    kw.setdefault("model_version", "v1")

    def make(idx):
        return InferenceEngine(model, variables, mcfg,
                               reference_samples=samples,
                               compile_store=store, **kw)
    return make


def _scaled_variables(served, scale):
    import jax
    _, _, _, variables = served
    return {"params": jax.tree_util.tree_map(lambda a: a * scale,
                                             variables["params"]),
            "batch_stats": variables.get("batch_stats", {})}


def _save_best(served, tmp_path, log, scale):
    """Write a BEST/COMMITTED checkpoint (the PR 4 contract) holding
    the fixture params scaled by `scale`; returns the serving-shape
    TrainState template."""
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.train_step import TrainState
    _, _, _, variables = served
    tx = select_optimizer({"Optimizer": {"type": "AdamW",
                                         "learning_rate": 1e-3}})
    state = TrainState.create(
        {"params": _scaled_variables(served, scale)["params"],
         "batch_stats": variables.get("batch_stats", {})}, tx)
    save_model(state, log, path=str(tmp_path), mark_best=True,
               best_val=0.5)
    return TrainState.create(
        {"params": variables["params"],
         "batch_stats": variables.get("batch_stats", {})}, tx)


_FAST_CFG = dict(poll_interval_s=0.05, mirror_every=1, window_pairs=4,
                 min_pairs=2, window_timeout_s=30.0, max_rel_err=5.0,
                 latency_factor=100.0, latency_floor_ms=1000.0)


def _run_with_traffic(router, samples, fn, max_submits=4000):
    """Run `fn` (a publish/poll call) on a thread while the main thread
    pumps open-loop traffic — the shadow window only fills under load.
    Returns (fn result, all primary futures submitted)."""
    box = {}

    def _target():
        box["out"] = fn()

    t = threading.Thread(target=_target)
    t.start()
    futs = []
    i = 0
    while t.is_alive() and i < max_submits:
        f = router.submit(samples[i % len(samples)])
        futs.append(f)
        f.exception(timeout=60)  # paced: resolve before the next submit
        i += 1
    t.join(timeout=120)
    assert not t.is_alive(), "publish did not finish under traffic"
    return box.get("out"), futs


# ---------------------------------------------------------- adjudication

def test_pair_rel_err_semantics():
    a = [np.ones((3, 2)), np.full((4,), 2.0)]
    assert pair_rel_err(a, [x.copy() for x in a]) == 0.0
    drift = pair_rel_err(a, [x * 1.1 for x in a])
    assert 0.05 < drift < 0.2
    # non-finite, shape mismatch, and tree mismatch all fail closed
    bad = [np.ones((3, 2)), np.array([1.0, np.nan, 1.0, 1.0])]
    assert pair_rel_err(a, bad) == float("inf")
    assert pair_rel_err(a, [np.ones((2, 3)), a[1]]) == float("inf")
    assert pair_rel_err(a, [a[0]]) == float("inf")


def test_adjudicate_window_verdicts():
    cfg = PublishConfig(min_pairs=3, max_rel_err=0.25,
                        latency_factor=2.0, latency_floor_ms=1.0)
    good = [{"err": 0.01, "primary_ms": 10.0, "shadow_ms": 12.0}
            for _ in range(4)]
    v = adjudicate_window(good, 0, cfg)
    assert v["promote"] and v["enough"] and v["error_ok"]
    assert v["latency_ok"]
    assert v["incumbent_p99_ms"] == pytest.approx(10.0)
    assert v["candidate_p99_ms"] == pytest.approx(12.0)
    # starved window: not enough pairs — no promote, but not an error
    v = adjudicate_window(good[:2], 0, cfg)
    assert not v["enough"] and not v["promote"] and v["error_ok"]
    # drift beyond the bound fails error_ok
    drifty = good[:3] + [{"err": 0.9, "primary_ms": 10.0,
                          "shadow_ms": 10.0}]
    v = adjudicate_window(drifty, 0, cfg)
    assert v["enough"] and not v["error_ok"] and not v["promote"]
    # ANY shadow failure fails error_ok regardless of drift
    v = adjudicate_window(good, 1, cfg)
    assert not v["error_ok"] and not v["promote"]
    # candidate p99 over budget fails latency_ok
    slow = [{"err": 0.0, "primary_ms": 10.0, "shadow_ms": 50.0}
            for _ in range(4)]
    v = adjudicate_window(slow, 0, cfg)
    assert v["error_ok"] and not v["latency_ok"] and not v["promote"]
    assert v["latency_budget_ms"] == pytest.approx(20.0)


# -------------------------------------------------------- promote path

def test_publisher_promotes_good_candidate(served, tmp_path):
    samples, _, _, _ = served
    template = _save_best(served, tmp_path, "pub_good", 1.001)
    router = ReplicaRouter(_factory(served), 2)
    try:
        pub = CheckpointPublisher(
            router, template, "pub_good", path=str(tmp_path),
            incumbent_variables=_scaled_variables(served, 1.0),
            incumbent_version="v1",
            config=PublishConfig(**_FAST_CFG))
        out, futs = _run_with_traffic(router, samples, pub.poll_once)
        assert out is not None and out["action"] == "promoted", out
        assert out["version"] == "best:step_0"
        assert out["verdict"]["pairs"] >= 2
        # the WHOLE fleet serves the candidate — one coherent version
        health = router.health()
        assert {h["model_version"]
                for h in health["replicas"].values()} == {"best:step_0"}
        assert not any(h["canary"] for h in health["replicas"].values())
        snap = pub.snapshot()
        assert snap["incumbent_version"] == "best:step_0"
        assert snap["promote_count"] == 1 and snap["rollback_count"] == 0
        assert [e["event"] for e in snap["history"]] == [
            "canary_start", "promoted"]
        # zero lost futures across the whole roll
        assert all(f.exception(timeout=0) is None for f in futs)
        # nothing new on disk -> the next poll is a no-op
        assert pub.poll_once() is None
    finally:
        router.shutdown()


def test_publisher_rolls_back_poisoned_candidate(served, tmp_path):
    samples, _, _, _ = served
    template = _save_best(served, tmp_path, "pub_poison", 1e3)
    router = ReplicaRouter(_factory(served), 2)
    try:
        pub = CheckpointPublisher(
            router, template, "pub_poison", path=str(tmp_path),
            incumbent_variables=_scaled_variables(served, 1.0),
            incumbent_version="v1",
            config=PublishConfig(**_FAST_CFG))
        out, futs = _run_with_traffic(router, samples, pub.poll_once)
        assert out is not None and out["action"] == "rolled_back", out
        # coherent fleet on the incumbent; the poison never served a
        # primary request (every version tag is the incumbent's)
        health = router.health()
        assert {h["model_version"]
                for h in health["replicas"].values()} == {"v1"}
        assert all(f.exception(timeout=0) is None for f in futs)
        assert {f.model_version for f in futs} == {"v1"}
        assert "best:step_0" in router.quarantined_versions()
        snap = pub.snapshot()
        assert snap["rollback_count"] == 1 and snap["promote_count"] == 0
        # a FRESH publisher (restarted process) skips the quarantined
        # version at detection time — rolled back once, not per poll
        pub2 = CheckpointPublisher(
            router, template, "pub_poison", path=str(tmp_path),
            incumbent_variables=_scaled_variables(served, 1.0),
            incumbent_version="v1",
            config=PublishConfig(**_FAST_CFG))
        assert pub2.poll_once() is None
        hist2 = pub2.snapshot()["history"]
        assert [e["event"] for e in hist2] == ["skipped_quarantined"]
        assert router.health()["swap_failures"] == 0
    finally:
        router.shutdown()


# ------------------------------------------- COMMITTED-only hardening

def test_uncommitted_marker_refused_and_named(served, tmp_path):
    """Satellite: a BEST marker naming a torn (uncommitted) save is an
    actionable error for the manual entry point and a counted retry for
    the publisher — never a silent fall-through."""
    template = _save_best(served, tmp_path, "pub_torn", 1.001)
    target = marker_target("pub_torn", path=str(tmp_path), which="best")
    os.remove(os.path.join(target, COMMIT_MARKER))  # simulate mid-write
    router = ReplicaRouter(_factory(served), 2)
    try:
        with pytest.raises(UncommittedCheckpointError) as ei:
            router.hot_swap_from_checkpoint(template, "pub_torn",
                                            path=str(tmp_path))
        msg = str(ei.value)
        assert target in msg  # NAMES the torn dir
        assert "COMMITTED" in msg and "wait_for_checkpoints" in msg
        # swap never started: the fleet still serves the factory version
        assert {h["model_version"] for h in
                router.health()["replicas"].values()} == {"v1"}
        pub = CheckpointPublisher(
            router, template, "pub_torn", path=str(tmp_path),
            incumbent_variables=_scaled_variables(served, 1.0),
            incumbent_version="v1", config=PublishConfig(**_FAST_CFG))
        assert pub.poll_once() is None
        assert pub.snapshot()["skipped_uncommitted"] == 1
        assert pub.snapshot()["last_step"] == -1  # will retry next poll
    finally:
        router.shutdown()


# ------------------------------------------------- failed-swap recovery

def test_hot_swap_failure_names_mixed_fleet(served):
    """Satellite: a partial hot_swap raises a SwapFailedError whose
    report/message name BOTH sides of the mixed-version fleet, and the
    router keeps routing throughout."""
    samples, _, _, _ = served
    router = ReplicaRouter(_factory(served), 3)
    try:
        install_fault_plan(parse_fault_plan("swap-fail@1"))
        with pytest.raises(SwapFailedError) as ei:
            router.hot_swap(_scaled_variables(served, 2.0), "v2")
        msg = str(ei.value)
        assert "MIXED-VERSION" in msg
        report = ei.value.report
        assert sorted(int(i) for i in report["replicas"]) == [0, 2]
        assert [f["replica"] for f in report["failed"]] == [1]
        health = router.health()
        assert health["replicas"]["0"]["model_version"] == "v2"
        assert health["replicas"]["1"]["model_version"] == "v1"
        assert health["replicas"]["2"]["model_version"] == "v2"
        # the mixed fleet still serves — no replica was lost to the
        # failed swap (re-admitted on its old version)
        futs = [router.submit(s) for s in samples[:6]]
        assert all(f.exception(timeout=60) is None for f in futs)
        assert {f.model_version for f in futs} <= {"v1", "v2"}
        # the plan is exhausted: re-running the swap converges the fleet
        report = router.hot_swap(_scaled_variables(served, 2.0), "v2")
        assert report["failed"] == []
    finally:
        router.shutdown()


def test_promote_failure_restores_one_coherent_version(served):
    """A canary that adjudicates clean but trips ``swap-fail`` while
    rolling the rest is fully unwound: every replica back on the
    incumbent, candidate quarantined, zero lost futures."""
    samples, _, _, _ = served
    router = ReplicaRouter(_factory(served), 3)
    try:
        pub = CheckpointPublisher(
            router, None, "unused",
            incumbent_variables=_scaled_variables(served, 1.0),
            incumbent_version="v1", config=PublishConfig(**_FAST_CFG))
        # consultation 0 = the canary swap (succeeds); 1 = the first
        # promote swap (replica 0) fails; the rollback swaps run on an
        # exhausted plan
        install_fault_plan(parse_fault_plan("swap-fail@1"))
        out, futs = _run_with_traffic(
            router, samples,
            lambda: pub.publish(_scaled_variables(served, 1.001), "v2"))
        assert out["action"] == "rolled_back", out
        assert "promote failed on replica 0" in out["reason"]
        health = router.health()
        assert {h["model_version"]
                for h in health["replicas"].values()} == {"v1"}
        assert not any(h["canary"] for h in health["replicas"].values())
        assert "v2" in router.quarantined_versions()
        assert all(f.exception(timeout=0) is None for f in futs)
        # quarantine holds: even a direct re-roll of v2 is refused
        with pytest.raises(ValueError, match="quarantined"):
            router.hot_swap(_scaled_variables(served, 1.001), "v2")
    finally:
        router.shutdown()


# ------------------------------------------------------------ autoscaler

class _FakeRouter:
    """health()-shaped stub so watermark/cooldown policy is tested
    without engines. Depths are set per test; scale calls are
    recorded and mutate the fake fleet."""

    def __init__(self, depths, canary=None, retired=()):
        self.depth = {i: float(d) for i, d in enumerate(depths)}
        self.retired = set(retired)
        self.canary = canary
        self.calls = []

    def health(self):
        reps = {}
        for i in sorted(set(self.depth) | self.retired):
            dead = i in self.retired
            reps[str(i)] = {"alive": not dead, "retired": dead,
                            "draining": False, "dispatcher_alive": not dead,
                            "canary": i == self.canary,
                            "queue_depth": self.depth.get(i, 0.0)}
        return {"state": "serving", "replicas": reps}

    def restart_replica(self, idx):
        self.calls.append(("restart", idx))
        self.retired.discard(idx)
        self.depth[idx] = 0.0
        return {"replica": idx, "fresh": 0, "warmup_s": 0.0}

    def add_replica(self):
        idx = len(self.depth) + len(self.retired)
        self.calls.append(("add", idx))
        self.depth[idx] = 0.0
        return {"replica": idx, "fresh": 0, "warmup_s": 0.0}

    def retire_replica(self, idx, timeout_s=None):
        self.calls.append(("retire", idx))
        self.retired.add(idx)
        self.depth.pop(idx, None)
        return {"replica": idx, "retired": True}

    def stats(self):
        # fleet-wide latency stats (ReplicaRouter.stats shape): zeroed
        # placeholder when no requests resolved, count disambiguates
        lat = getattr(self, "latencies_ms", [])
        if not lat:
            return {"count": 0, "p50_ms": 0.0, "p95_ms": 0.0,
                    "p99_ms": 0.0, "mean_ms": 0.0}
        arr = sorted(float(x) for x in lat)
        return {"count": len(arr), "p50_ms": arr[len(arr) // 2],
                "p95_ms": arr[-1], "p99_ms": arr[-1],
                "mean_ms": sum(arr) / len(arr)}


def _as_cfg(**kw):
    kw.setdefault("cooldown_s", 0.0)
    return AutoscaleConfig(**kw)


def test_autoscaler_watermarks_and_clamps():
    # high depth + room -> scale up (appends: nothing retired)
    fr = _FakeRouter([6.0, 6.0])
    a = QueueDepthAutoscaler(fr, config=_as_cfg(max_replicas=3))
    ev = a.step()
    assert ev["action"] == "scale_up" and not ev["revived"]
    assert fr.calls == [("add", 2)]
    # at max_replicas the same pressure is a no-op
    fr = _FakeRouter([6.0, 6.0])
    a = QueueDepthAutoscaler(fr, config=_as_cfg(max_replicas=2))
    assert a.step() is None and fr.calls == []
    # low depth + slack -> retire the HIGHEST-index live replica
    fr = _FakeRouter([0.0, 0.0, 0.0])
    a = QueueDepthAutoscaler(fr, config=_as_cfg(max_replicas=4))
    ev = a.step()
    assert ev["action"] == "scale_down" and ev["replica"] == 2
    # at min_replicas the trough is a no-op
    fr = _FakeRouter([0.0])
    a = QueueDepthAutoscaler(fr, config=_as_cfg())
    assert a.step() is None
    # mid-band depth: no action either way
    fr = _FakeRouter([2.0, 2.0])
    a = QueueDepthAutoscaler(fr, config=_as_cfg(max_replicas=4))
    assert a.step() is None


def test_autoscaler_p99_latency_signal():
    """signal="p99_latency": watermarks key off the fleet-wide p99 in
    router.stats() instead of queue depth — breach scales up, a calm
    tail scales down, and an EMPTY stats window (count == 0, the zeroed
    placeholder) takes no action even when queue depths would have."""
    cfg = _as_cfg(signal="p99_latency", high_p99_ms=100.0,
                  low_p99_ms=10.0, max_replicas=4)
    # p99 breach -> scale up, even though depths sit below high_depth
    fr = _FakeRouter([0.0, 0.0])
    fr.latencies_ms = [5.0, 8.0, 250.0]
    a = QueueDepthAutoscaler(fr, config=cfg)
    ev = a.step()
    assert ev["action"] == "scale_up" and ev["signal"] == "p99_latency"
    assert ev["avg_depth"] == 250.0  # historical key carries the signal
    # calm tail -> scale down despite deep queues (the SLO is met)
    fr = _FakeRouter([9.0, 9.0, 9.0])
    fr.latencies_ms = [1.0, 2.0, 3.0]
    a = QueueDepthAutoscaler(fr, config=cfg)
    ev = a.step()
    assert ev["action"] == "scale_down"
    # zero resolved requests -> no action (idle != fast)
    fr = _FakeRouter([9.0, 9.0, 9.0])
    a = QueueDepthAutoscaler(fr, config=cfg)
    assert a.step() is None and fr.calls == []


def test_autoscaler_revives_retired_slot_first():
    fr = _FakeRouter([6.0], retired={1})
    a = QueueDepthAutoscaler(fr, config=_as_cfg(max_replicas=3))
    ev = a.step()
    assert ev["action"] == "scale_up" and ev["revived"]
    assert fr.calls == [("restart", 1)]
    assert ev["fresh_compiles"] == 0


def test_autoscaler_cooldown_and_canary_freeze():
    fr = _FakeRouter([6.0, 6.0])
    a = QueueDepthAutoscaler(
        fr, config=_as_cfg(max_replicas=8, cooldown_s=3600.0))
    assert a.step() is not None
    fr.depth = {i: 6.0 for i in fr.depth}
    assert a.step() is None  # cooling — no thrash
    assert a.snapshot()["scale_up_count"] == 1
    # a live canary freezes every decision
    fr = _FakeRouter([6.0, 6.0], canary=1)
    a = QueueDepthAutoscaler(fr, config=_as_cfg(max_replicas=4))
    assert a.step() is None
    assert a.snapshot()["skipped_canary"] == 1
    # config validation fails closed
    with pytest.raises(ValueError, match="min_replicas"):
        QueueDepthAutoscaler(fr, config=AutoscaleConfig(min_replicas=0))
    with pytest.raises(ValueError, match="max_replicas"):
        QueueDepthAutoscaler(fr, config=AutoscaleConfig(
            min_replicas=3, max_replicas=2))


def test_autoscale_cycle_on_real_fleet(served, tmp_path):
    """Integration: add_replica is disk-warm off the shared store and
    joins on the PUBLISHED version; retire goes through drain (zero
    lost futures); restart_replica revives the retired slot."""
    samples, _, _, _ = served
    store = CompileStore(str(tmp_path / "store"))
    router = ReplicaRouter(_factory(served, store), 1)
    try:
        router.warmup()  # seeds the persistent store
        router.hot_swap(_scaled_variables(served, 2.0), "v2")
        report = router.add_replica()
        assert report["replica"] == 1
        assert report["fresh"] == 0  # disk-warm: zero fresh compiles
        assert report["store_hits"] > 0
        health = router.health()
        # the newcomer reconciled to the published version pre-rotation
        assert health["replicas"]["1"]["model_version"] == "v2"
        futs = [router.submit(s) for s in samples[:8]]
        assert all(f.exception(timeout=60) is None for f in futs)
        # scale down through drain, then revive the SAME slot
        router.retire_replica(1)
        health = router.health()
        assert health["replicas"]["1"]["retired"]
        assert not health["replicas"]["1"]["alive"]
        assert health["retires"] == 1
        with pytest.raises(ValueError, match="retired"):
            router.retire_replica(1)
        futs = [router.submit(s) for s in samples[:4]]
        assert all(f.exception(timeout=60) is None for f in futs)
        assert {f.replica for f in futs} == {0}
        report = router.restart_replica(1)
        assert report["fresh"] == 0
        h1 = router.health()["replicas"]["1"]
        assert h1["alive"] and not h1["retired"]
        assert h1["model_version"] == "v2"
    finally:
        router.shutdown()


# --------------------------------------------------------- observability

def test_health_stats_and_metrics_surface_canary_state(served):
    samples, _, _, _ = served
    router = ReplicaRouter(_factory(served), 2)
    try:
        router.submit(samples[0]).result(timeout=60)
        router.set_canary(1, True)
        router.quarantine_version("bad:step_9", "test poison")
        health = router.health()
        assert health["replicas"]["1"]["canary"]
        assert not health["replicas"]["0"]["canary"]
        assert health["quarantined_versions"] == ["bad:step_9"]
        st = router.stats()
        assert st["canary_replicas"] == [1]
        assert st["quarantined_versions"] == ["bad:step_9"]
        # a canary is NOT routable: primaries all land on replica 0
        futs = [router.submit(s) for s in samples[:6]]
        assert all(f.exception(timeout=60) is None for f in futs)
        assert {f.replica for f in futs} == {0}
        server = router.start_metrics_server(port=0)
        with urllib.request.urlopen(f"{server.url}/metrics") as r:
            text = r.read().decode()
        assert ('hydragnn_serving_replica_version_info{replica="0",'
                'state="primary",version="v1"} 1' in text)
        assert ('hydragnn_serving_replica_version_info{replica="1",'
                'state="canary",version="v1"} 1' in text)
        assert ('hydragnn_serving_replica_canary_state{replica="1",'
                'state="canary"} 1' in text)
        assert ('hydragnn_serving_replica_canary_state{replica="1",'
                'state="primary"} 0' in text)
        assert ('hydragnn_serving_replica_canary_state{replica="0",'
                'state="primary"} 1' in text)
        assert 'hydragnn_serving_fleet_quarantined_versions 1' in text
        assert ('hydragnn_serving_fleet_quarantined_info'
                '{version="bad:step_9"} 1' in text)
    finally:
        router.shutdown()


# ---------------------------------------------------------------- config

def test_resolve_publish_precedence(monkeypatch, caplog):
    cfg = {"Serving": {"publish": {"window_pairs": 16,
                                   "max_rel_err": 0.1}}}
    p = resolve_publish(cfg)
    assert p.window_pairs == 16 and p.max_rel_err == 0.1
    assert p.mirror_every == 2  # untouched default
    monkeypatch.setenv("HYDRAGNN_PUBLISH_WINDOW_PAIRS", "32")
    monkeypatch.setenv("HYDRAGNN_PUBLISH_LATENCY_FACTOR", "5.5")
    p = resolve_publish(cfg)
    assert p.window_pairs == 32  # env beats config block
    assert p.latency_factor == 5.5
    assert p.max_rel_err == 0.1  # config block beats default
    # strict parsing: a typo warns and falls back, never half-applies
    monkeypatch.setenv("HYDRAGNN_PUBLISH_WINDOW_PAIRS", "lots")
    with caplog.at_level("WARNING", logger="hydragnn_tpu"):
        p = resolve_publish(cfg)
    assert p.window_pairs == 16
    assert "HYDRAGNN_PUBLISH_WINDOW_PAIRS" in caplog.text


def test_resolve_autoscale_precedence(monkeypatch, caplog):
    cfg = {"Serving": {"autoscale": {"max_replicas": 8,
                                     "high_depth": 12.0}}}
    a = resolve_autoscale(cfg)
    assert a.max_replicas == 8 and a.high_depth == 12.0
    monkeypatch.setenv("HYDRAGNN_AUTOSCALE_MAX", "6")
    monkeypatch.setenv("HYDRAGNN_AUTOSCALE_LOW_DEPTH", "0.25")
    a = resolve_autoscale(cfg)
    assert a.max_replicas == 6 and a.low_depth == 0.25
    monkeypatch.setenv("HYDRAGNN_AUTOSCALE_MAX", "many")
    with caplog.at_level("WARNING", logger="hydragnn_tpu"):
        a = resolve_autoscale(cfg)
    assert a.max_replicas == 8
    assert "HYDRAGNN_AUTOSCALE_MAX" in caplog.text
    # the latency-SLO knobs follow the same precedence + strict parsing
    monkeypatch.delenv("HYDRAGNN_AUTOSCALE_MAX")
    assert resolve_autoscale(cfg).signal == "queue_depth"  # default
    monkeypatch.setenv("HYDRAGNN_AUTOSCALE_SIGNAL", "p99_latency")
    monkeypatch.setenv("HYDRAGNN_AUTOSCALE_HIGH_P99_MS", "150")
    a = resolve_autoscale(cfg)
    assert a.signal == "p99_latency" and a.high_p99_ms == 150.0
    monkeypatch.setenv("HYDRAGNN_AUTOSCALE_SIGNAL", "p99")  # typo
    with caplog.at_level("WARNING", logger="hydragnn_tpu"):
        a = resolve_autoscale(cfg)
    assert a.signal == "queue_depth"  # fell back, never half-applied
    assert "HYDRAGNN_AUTOSCALE_SIGNAL" in caplog.text
    cfg2 = {"Serving": {"autoscale": {"signal": "p99_latency",
                                      "low_p99_ms": 5.0}}}
    monkeypatch.delenv("HYDRAGNN_AUTOSCALE_SIGNAL")
    a = resolve_autoscale(cfg2)
    assert a.signal == "p99_latency" and a.low_p99_ms == 5.0


# ------------------------------------------------------------ slow lane

@pytest.mark.slow
def test_bench_continuous_smoke(tmp_path):
    """BENCH_CONTINUOUS end-to-end in a subprocess at CI scale: one run
    adjudicates all three chaos legs (trainer preempted + resumed, a
    poisoned candidate rolled back, load doubled then halved) with
    zero lost futures and a coherent final version."""
    out_path = str(tmp_path / "BENCH_CONTINUOUS.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_CONTINUOUS="1",
               BENCH_HIDDEN="32", BENCH_CONTINUOUS_OUT=out_path,
               BENCH_CONTINUOUS_SAVES="3",
               BENCH_CONTINUOUS_SAVE_GAP_S="2.0")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True,
                       timeout=1200)
    assert r.returncode == 0, r.stderr[-2000:]
    with open(out_path) as f:
        out = json.load(f)
    assert out["passed"], out
    assert out["trainer"]["preempted_and_resumed"]
    assert out["publish"]["rollback_count"] == 1
    assert out["publish"]["poison_quarantined"]
    assert out["fleet"]["coherent_final_version"]
    assert out["fleet"]["no_lost_futures"]
    assert out["autoscale"]["scaled_up_and_down"]
    assert out["autoscale"]["scale_up_fresh_compiles"] == 0
    assert out["open_loop"]["p99_ms"] > 0
