"""Fleet-grade serving (serving/fleet.py, docs/serving.md "Fleet").

Contract under test:
* least-queue-depth dispatch over routable replicas, ties by index,
* an injected ``replica-kill`` re-dispatches in-flight requests with
  EXACTLY-ONCE resolution and zero lost futures,
* failure isolation: one replica's tripped breaker never stops the
  others; the ejected replica is re-admitted after its half-open probe,
* zero-downtime hot-swap: drain -> atomic swap, version tag echoed on
  futures/health; an injected ``swap-fail`` rolls back cleanly; the
  BEST-checkpoint entry point tags the restored step,
* the persistent AOT compile store: a second replica (and a restarted
  one) warms with 0 fresh compiles; corrupt entries degrade to a miss,
* ONE aggregated /healthz + /metrics endpoint with per-replica labels;
  ephemeral ports never collide in one process,
* HYDRAGNN_FLEET_* knobs resolve config/env precedence with strict
  (warn-and-fall-back) parsing.

Sized for tier-1: tiny GIN, 2 replicas, single-bucket ladders. The
end-to-end stream + BENCH_SERVE_FLEET subprocess smoke live in the
`slow` lane (the PR 12 budget satellite).
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from hydragnn_tpu.config import build_model_config, update_config
from hydragnn_tpu.graphs.batch import collate
from hydragnn_tpu.models.create import create_model, init_params
from hydragnn_tpu.serving.config import FleetConfig, resolve_fleet
from hydragnn_tpu.serving.engine import InferenceEngine
from hydragnn_tpu.serving.fleet import (FleetUnavailableError,
                                        ReplicaRouter, SwapFailedError)
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


def _drain_futs(futs, timeout=60):
    for f in futs:
        f.exception(timeout=timeout)


# ---------------------------------------------------------------- routing

class _Park:
    """Deterministically park one engine's dispatcher inside _execute so
    a test controls queue depths instead of racing the batch loop."""

    def __init__(self, eng):
        self.entered = threading.Event()
        self.release = threading.Event()
        orig = eng._execute

        def blocked(shards):
            self.entered.set()
            assert self.release.wait(30)
            return orig(shards)

        eng._execute = blocked


def test_least_queue_depth_routing(served):
    samples, _, _, _ = served
    router = ReplicaRouter(_factory(served), 2)
    try:
        # tie at depth 0 -> lowest index
        f0 = router.submit(samples[0])
        assert f0.result(timeout=60) is not None
        assert f0.replica == 0
        # park BOTH dispatchers: queue depths are then a pure function
        # of the submits below (in-flight parked batches do not count)
        parks = [_Park(router._replicas[i].engine) for i in (0, 1)]
        try:
            fa = router.submit(samples[1])  # tie (0,0) -> replica 0
            assert parks[0].entered.wait(30)  # dequeued, parked: depth 0
            fb = router.submit(samples[2])  # tie (0,0) -> replica 0; its
            # dispatcher is parked, so fb STAYS queued: depth (1,0)
            fc = router.submit(samples[3])  # (1,0) -> replica 1
            assert parks[1].entered.wait(30)  # dequeued, parked: (1,0)
            fd = router.submit(samples[4])  # (1,0) -> replica 1: (1,1)
            fe = router.submit(samples[5])  # tie (1,1) -> replica 0
        finally:
            for p in parks:
                p.release.set()
        futs = [fa, fb, fc, fd, fe]
        _drain_futs(futs)
        assert [f.replica for f in futs] == [0, 0, 1, 1, 0]
        assert all(f.exception(timeout=0) is None for f in futs)
    finally:
        router.shutdown()


def test_replica_kill_redispatches_exactly_once(served):
    """The tentpole adjudication at unit scale: a replica killed by the
    injected ``replica-kill`` fault loses ZERO futures — its in-flight
    requests re-dispatch and each resolves exactly once."""
    samples, _, _, _ = served
    router = ReplicaRouter(_factory(served), 2)
    try:
        install_fault_plan(parse_fault_plan("replica-kill@2"))
        futs = [router.submit(s) for s in samples[:10]]
        _drain_futs(futs)
        assert all(f.done() for f in futs)
        assert all(f.exception(timeout=0) is None for f in futs)
        assert router.kill_count == 1
        assert router.requests_done == 10  # exactly one resolution each
        # every future carries the serving breadcrumbs
        assert all(hasattr(f, "model_version") and hasattr(f, "replica")
                   for f in futs)
        health = router.health()
        dead = [i for i, h in sorted(health["replicas"].items())
                if not h["alive"]]
        assert len(dead) == 1
        assert health["state"] == "serving"  # the survivor keeps serving
        # the dead replica never gets routed again
        f = router.submit(samples[0])
        assert f.result(timeout=60) is not None
        assert str(f.replica) != dead[0]
    finally:
        router.shutdown()


def test_fleet_unavailable_fast_fails(served):
    samples, _, _, _ = served
    router = ReplicaRouter(_factory(served), 2, unavailable_wait_s=0.1)
    try:
        router.kill_replica(0)
        router.kill_replica(1)
        assert router.health()["state"] == "unavailable"
        with pytest.raises(FleetUnavailableError):
            router.submit(samples[0]).result(timeout=60)
    finally:
        router.shutdown()


# ------------------------------------------------- breaker isolation

def test_breaker_isolation_and_probe_readmission(served):
    """One replica's tripped breaker is ITS failure: the request that
    tripped it re-dispatches and succeeds elsewhere, traffic routes
    around the open breaker, and once the probe window elapses ONE
    request re-admits the replica."""
    samples, _, _, _ = served
    router = ReplicaRouter(
        _factory(served, breaker_threshold=1, breaker_reset_s=1.0), 2)
    try:
        router.warmup()  # cold compiles must not eat the probe window
        # the first EXECUTED batch fleet-wide fails -> that replica trips
        install_fault_plan(parse_fault_plan("serving-dispatch@0"))
        f = router.submit(samples[0])
        assert f.result(timeout=60) is not None  # re-dispatch absorbed it
        assert router.redispatch_count >= 1
        states = {i: h["state"]
                  for i, h in router.health()["replicas"].items()}
        assert sorted(states.values()) == ["closed", "open"]  # isolation
        tripped = next(i for i, s in sorted(states.items()) if s == "open")
        healthy = next(i for i, s in sorted(states.items())
                       if s == "closed")
        # traffic routes around the open breaker
        for s in samples[1:4]:
            g = router.submit(s)
            assert g.result(timeout=60) is not None
            assert str(g.replica) == healthy
        time.sleep(1.1)  # probe window elapses
        g = router.submit(samples[4])  # routed as the half-open probe
        assert g.result(timeout=60) is not None
        assert str(g.replica) == tripped  # probe priority
        health = router.health()["replicas"][tripped]
        assert health["state"] == "closed"  # re-admitted
        assert health["probe_count"] == 1
        assert health["trip_count"] == 1
    finally:
        router.shutdown()


# ------------------------------------------------------------ hot swap

def _scaled_variables(served, scale):
    import jax
    _, _, _, variables = served
    return {"params": jax.tree_util.tree_map(lambda a: a * scale,
                                             variables["params"]),
            "batch_stats": variables.get("batch_stats", {})}


def test_hot_swap_changes_echoed_version(served):
    samples, _, _, _ = served
    router = ReplicaRouter(_factory(served), 2)
    try:
        before = [router.submit(s) for s in samples[:4]]
        _drain_futs(before)
        assert {f.model_version for f in before} == {"v1"}
        report = router.hot_swap(_scaled_variables(served, 2.0), "v2")
        assert report["failed"] == []
        assert sorted(report["replicas"]) == ["0", "1"]
        after = [router.submit(s) for s in samples[:4]]
        _drain_futs(after)
        assert {f.model_version for f in after} == {"v2"}
        # the swap genuinely changed the served weights
        a = np.asarray(before[0].result(timeout=0)[0])
        b = np.asarray(after[0].result(timeout=0)[0])
        assert not np.array_equal(a, b)
        # no request failed across the swap
        assert all(f.exception(timeout=0) is None
                   for f in before + after)
        health = router.health()
        assert all(h["model_version"] == "v2"
                   for h in health["replicas"].values())
    finally:
        router.shutdown()


def test_swap_fail_injection_rolls_back(served):
    """``swap-fail`` fires BEFORE any mutation: the old version keeps
    serving on the failed replica and no request fails."""
    samples, _, _, _ = served
    router = ReplicaRouter(_factory(served), 2)
    try:
        install_fault_plan(parse_fault_plan("swap-fail@0,1"))
        with pytest.raises(SwapFailedError):
            router.hot_swap(_scaled_variables(served, 2.0), "v2")
        futs = [router.submit(s) for s in samples[:4]]
        _drain_futs(futs)
        assert all(f.exception(timeout=0) is None for f in futs)
        assert {f.model_version for f in futs} == {"v1"}  # rolled back
        # the plan is exhausted: the retry succeeds
        report = router.hot_swap(_scaled_variables(served, 2.0), "v2")
        assert report["failed"] == []
        f = router.submit(samples[0])
        f.result(timeout=60)
        assert f.model_version == "v2"
        assert router.health()["swap_failures"] == 2
    finally:
        router.shutdown()


def test_swap_variables_shape_mismatch_rejected(served):
    samples, mcfg, model, variables = served
    eng = _factory(served)(0)
    try:
        eng.warmup()
        import jax
        bad = {"params": jax.tree_util.tree_map(
            lambda a: np.zeros(tuple(s + 1 for s in a.shape), a.dtype),
            variables["params"])}
        with pytest.raises(ValueError, match="shape"):
            eng.swap_variables(bad, "v2")
        assert eng.health()["model_version"] == "v1"  # untouched
        assert eng.submit(samples[0]).result(timeout=60) is not None
    finally:
        eng.shutdown()


def test_hot_swap_from_best_checkpoint(served, tmp_path):
    """The PR 4 contract feeds the swap: save a state through
    save_model(mark_best=True), roll it out via the BEST marker, and the
    echoed tag names the restored step."""
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.train_step import TrainState
    from hydragnn_tpu.utils.checkpoint import save_model
    samples, _, _, variables = served
    tx = select_optimizer({"Optimizer": {"type": "AdamW",
                                         "learning_rate": 1e-3}})
    state = TrainState.create(
        {"params": _scaled_variables(served, 3.0)["params"],
         "batch_stats": variables.get("batch_stats", {})}, tx)
    save_model(state, "fleet_test", path=str(tmp_path), mark_best=True,
               best_val=0.5)
    template = TrainState.create(
        {"params": variables["params"],
         "batch_stats": variables.get("batch_stats", {})}, tx)
    router = ReplicaRouter(_factory(served), 2)
    try:
        report = router.hot_swap_from_checkpoint(
            template, "fleet_test", path=str(tmp_path), which="best")
        assert report["version"] == "best:step_0"
        f = router.submit(samples[0])
        f.result(timeout=60)
        assert f.model_version == "best:step_0"
    finally:
        router.shutdown()


# ------------------------------------------------------- compile store

def test_compile_store_warms_second_replica_and_restart(served, tmp_path):
    store = CompileStore(str(tmp_path / "store"))
    router = ReplicaRouter(_factory(served, store=store), 2)
    try:
        reports = router.warmup()
        assert reports[0]["fresh"] == reports[0]["compiled"] > 0
        assert reports[1]["fresh"] == 0  # warmed entirely from disk
        assert reports[1]["store_hits"] == reports[1]["compiled"]
        # a replacement replica warms from the store too
        router.kill_replica(0)
        restart = router.restart_replica(0)
        assert restart["fresh"] == 0
        assert restart["store_hits"] == restart["compiled"] > 0
        # and it actually serves (bitwise the same program contract:
        # same bucket outputs equal across replicas)
        samples, _, _, _ = served
        f = router.submit(samples[0])
        assert f.result(timeout=60) is not None
        assert router.health()["state"] == "serving"
    finally:
        router.shutdown()


def test_compile_store_corrupt_entry_degrades_to_miss(tmp_path, caplog):
    import jax
    store = CompileStore(str(tmp_path))
    compiled = jax.jit(lambda x: x * 2).lower(np.ones(4, np.float32)
                                              ).compile()
    key = CompileStore.fingerprint("unit", (4,))
    assert store.save(key, compiled)
    # round trip on the multi-device CPU mesh: the one-device artifact
    # must reload as a ONE-device program (jax 0.9's default spreads it
    # over every device of the backend and the call fails with a
    # shard-count mismatch) and execute where the caller said
    one = jax.devices()[:1]
    loaded = store.load(key, one)
    assert loaded is not None
    out = loaded(np.ones(4, np.float32))
    np.testing.assert_array_equal(np.asarray(out), np.full(4, 2.0))
    assert out.sharding.device_set == set(one)
    # corrupt the entry: load must warn and miss, never raise
    with open(store._path(key), "wb") as f:
        f.write(b"not a pickle")
    with caplog.at_level("WARNING", logger="hydragnn_tpu"):
        assert store.load(key, one) is None
    assert "compiling fresh" in caplog.text
    st = store.stats()
    assert st["errors"] == 1 and st["hits"] == 1


def test_compile_store_key_sensitivity():
    a = CompileStore.fingerprint("cfg", (64, 128, 3), "float32")
    b = CompileStore.fingerprint("cfg", (64, 128, 3), "bfloat16")
    c = CompileStore.fingerprint("cfg", (64, 256, 3), "float32")
    assert len({a, b, c}) == 3
    assert a == CompileStore.fingerprint("cfg", (64, 128, 3), "float32")


# ------------------------------------------------------- observability

def test_fleet_metrics_endpoint_aggregates(served):
    samples, _, _, _ = served
    router = ReplicaRouter(_factory(served), 2)
    try:
        router.submit(samples[0]).result(timeout=60)
        server = router.start_metrics_server(port=0)
        assert server.port != 0  # the actually-bound ephemeral port
        with urllib.request.urlopen(f"{server.url}/healthz") as r:
            assert r.status == 200
            health = json.loads(r.read())
        assert health["state"] == "serving"
        assert health["replicas"]["0"]["model_version"] == "v1"
        assert health["replicas"]["1"]["uptime_s"] >= 0.0
        with urllib.request.urlopen(f"{server.url}/metrics") as r:
            text = r.read().decode()
        assert ('hydragnn_serving_replica_breaker_state{replica="0",'
                'state="closed"} 1' in text)
        assert ('hydragnn_serving_replica_breaker_state{replica="1",'
                'state="open"} 0' in text)
        assert 'hydragnn_serving_fleet_replicas 2' in text
        assert ('hydragnn_serving_replica_model{replica="0",'
                'version="v1"} 1' in text)
        assert "hydragnn_serving_fleet_latency_ms" in text
    finally:
        router.shutdown()


def test_engine_ephemeral_metrics_ports_do_not_collide(served):
    """The satellite claim: N replicas in one process each bind their
    own ephemeral port with port=0 — no fixed-port collision."""
    e1, e2 = _factory(served)(0), _factory(served)(1)
    try:
        s1 = e1.start_metrics_server(port=0)
        s2 = e2.start_metrics_server(port=0)
        assert s1.port != 0 and s2.port != 0
        assert s1.port != s2.port
        for s in (s1, s2):
            with urllib.request.urlopen(f"{s.url}/healthz") as r:
                h = json.loads(r.read())
            assert "model_version" in h and "uptime_s" in h
    finally:
        e1.shutdown()
        e2.shutdown()


def test_engine_health_gains_version_and_uptime(served):
    eng = _factory(served)(0)
    try:
        h = eng.health()
        assert h["model_version"] == "v1"
        assert h["uptime_s"] >= 0.0
        assert h["swap_count"] == 0
        t0 = h["uptime_s"]
        time.sleep(0.01)
        assert eng.health()["uptime_s"] > t0
        st = eng.stats()
        assert st["model_version"] == "v1"
        assert {"compile_store_hits", "compile_fresh",
                "probe_count"} <= set(st)
    finally:
        eng.shutdown()


def test_run_prediction_fleet_matches_legacy(served, tmp_path):
    """Serving.fleet.replicas > 1 routes run_prediction's engine path
    through a ReplicaRouter — outputs match the legacy loop, and the
    shared compile store gives the second replica a 0-fresh warmup."""
    import copy
    from hydragnn_tpu.run_prediction import run_prediction
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.train_step import TrainState
    samples, mcfg, model, variables = served
    cfg = make_config("GIN")
    cfg = update_config(cfg, samples)
    n = len(samples)
    splits = (samples[:int(0.6 * n)], samples[int(0.6 * n):int(0.8 * n)],
              samples[int(0.8 * n):])
    state = TrainState.create(
        variables, select_optimizer(cfg["NeuralNetwork"]["Training"]))
    t0, p0 = run_prediction(copy.deepcopy(cfg), datasets=splits,
                            state=state, model=model, serve=False)
    fleet_cfg = copy.deepcopy(cfg)
    fleet_cfg["Serving"] = {
        "enabled": True, "max_batch_size": 2,
        "fleet": {"replicas": 2,
                  "compile_store": str(tmp_path / "store")}}
    t1, p1 = run_prediction(fleet_cfg, datasets=splits, state=state,
                            model=model, serve=True)
    for a, b in zip(t0, t1):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(p0, p1):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)
    # the shared store was populated by replica 0's warmup
    assert any(f.endswith(CompileStore.SUFFIX)
               for f in os.listdir(tmp_path / "store"))


# ------------------------------------------------------------- knobs

def test_resolve_fleet_precedence(monkeypatch):
    cfg = {"Serving": {"fleet": {"replicas": 3,
                                 "compile_store": "/tmp/store",
                                 "redispatch_max": 5,
                                 "drain_timeout_s": 7.0}}}
    fc = resolve_fleet(cfg)
    assert fc == FleetConfig(replicas=3, compile_store="/tmp/store",
                             redispatch_max=5, drain_timeout_s=7.0)
    monkeypatch.setenv("HYDRAGNN_FLEET_REPLICAS", "4")
    monkeypatch.setenv("HYDRAGNN_FLEET_COMPILE_STORE", "/env/store")
    monkeypatch.setenv("HYDRAGNN_FLEET_REDISPATCH_MAX", "2")
    monkeypatch.setenv("HYDRAGNN_FLEET_DRAIN_TIMEOUT_S", "9.5")
    fc = resolve_fleet(cfg)  # env wins over config
    assert fc == FleetConfig(replicas=4, compile_store="/env/store",
                             redispatch_max=2, drain_timeout_s=9.5)
    assert resolve_fleet(None).replicas == 4  # env over defaults too


def test_resolve_fleet_strict_typo_parsing(monkeypatch, caplog):
    monkeypatch.setenv("HYDRAGNN_FLEET_REPLICAS", "three")
    monkeypatch.setenv("HYDRAGNN_FLEET_DRAIN_TIMEOUT_S", "soon")
    with caplog.at_level("WARNING", logger="hydragnn_tpu"):
        fc = resolve_fleet({"Serving": {"fleet": {"replicas": 2}}})
    # a typo warns and falls back to the config value, never takes effect
    assert fc.replicas == 2
    assert fc.drain_timeout_s == 30.0
    assert "HYDRAGNN_FLEET_REPLICAS" in caplog.text


# ------------------------------------------------------------ slow lane

@pytest.mark.slow
def test_kill_and_swap_under_open_loop_stream(served):
    """End-to-end: a Poisson-ish stream with a kill AND a rolling swap
    in flight — zero lost futures, exactly-once, both versions echoed."""
    samples, _, _, _ = served
    router = ReplicaRouter(_factory(served), 2)
    try:
        install_fault_plan(parse_fault_plan("replica-kill@6"))
        futs = []
        swap_thread = None
        for i in range(3):
            for s in samples:
                futs.append(router.submit(s))
                time.sleep(0.001)
            if i == 1:
                swap_thread = threading.Thread(
                    target=router.hot_swap,
                    args=(_scaled_variables(served, 2.0), "v2"))
                swap_thread.start()
        swap_thread.join(timeout=120)
        _drain_futs(futs, timeout=120)
        assert all(f.done() for f in futs)
        assert all(f.exception(timeout=0) is None for f in futs)
        assert router.requests_done == len(futs)
        versions = {f.model_version for f in futs}
        assert versions == {"v1", "v2"}
        assert router.kill_count == 1
    finally:
        router.shutdown()


@pytest.mark.slow
def test_bench_serve_fleet_smoke(tmp_path):
    """BENCH_SERVE_FLEET end-to-end in a subprocess at CI scale: the
    artifact's own pass verdict (zero lost futures, exactly-once,
    version change, warm restarts) must hold."""
    out_path = str(tmp_path / "BENCH_SERVE_FLEET.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_SERVE_FLEET="1",
               BENCH_SERVE_FLEET_REQUESTS="48", BENCH_HIDDEN="32",
               BENCH_SERVE_FLEET_OUT=out_path)
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True,
                       timeout=1200)
    assert r.returncode == 0, r.stderr[-2000:]
    with open(out_path) as f:
        out = json.load(f)
    assert out["passed"], out
    assert out["fault"]["no_lost_futures"]
    assert out["fault"]["resolved_exactly_once"]
    assert out["fault"]["request_failures"] == 0
    assert out["hot_swap"]["version_changed_mid_stream"]
    assert out["compile_store"]["warm_replicas_zero_fresh"]
    assert out["compile_store"]["restart_fresh_compiles"] == 0
    assert out["open_loop"]["p99_ms"] > 0
