"""Serving failure semantics (docs/fault_tolerance.md):

* every accepted submit() future resolves — result or error — under
  injected dispatch faults (the ISSUE 4 zero-lost-futures adjudication),
* the bounded admission queue fast-fails with QueueFullError without
  blocking the dispatcher,
* deadline-expired requests resolve with DeadlineExceededError and never
  occupy a batch slot,
* the consecutive-failure circuit breaker trips, fast-fails, and recovers
  through a half-open probe — deterministically, driven by the fault plan.
"""
import threading
import time

import numpy as np
import pytest

from hydragnn_tpu.config import build_model_config, update_config
from hydragnn_tpu.models.create import create_model, init_params
from hydragnn_tpu.graphs.batch import collate
from hydragnn_tpu.serving.engine import (CircuitOpenError,
                                         DeadlineExceededError,
                                         InferenceEngine, QueueFullError)
from hydragnn_tpu.utils.faults import (InjectedFault, install_fault_plan,
                                       parse_fault_plan)

from tests.deterministic_data import deterministic_graph_dataset
from tests.utils import make_config


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


def _engine(served, **kw):
    samples, mcfg, model, variables = served
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("max_wait_ms", 5.0)
    return InferenceEngine(model, variables, mcfg,
                           reference_samples=samples, **kw)


class _BlockedDispatcher:
    """Deterministically park the dispatcher inside its first _execute so
    tests can fill/expire the queue without racing the batch loop.
    `then`: an exception the released dispatcher dies of instead of
    executing."""

    def __init__(self, eng, then=None):
        self.entered = threading.Event()
        self.release = threading.Event()
        self._orig = eng._execute

        def blocked(shards):
            self.entered.set()
            assert self.release.wait(30)
            if then is not None:
                raise then
            return self._orig(shards)

        eng._execute = blocked


class _Computing:
    """An output the device has not finished: not ready until released."""

    def __init__(self, out, release):
        self.out, self.release = out, release

    def is_ready(self):
        return self.release.is_set()

    def __array__(self, *args, **kwargs):
        return np.asarray(self.out)


class _ParkedFetch:
    """A device that is still computing until `release` is set: a
    dispatched batch's outputs are not ready, and the dispatcher is held
    inside the first fetch it enters, so tests can look at an engine with
    batches in flight. `forward_single` (no batch id) is never held."""

    def __init__(self, eng):
        self.eng = eng
        self.entered = threading.Event()
        self.release = threading.Event()
        fetch, enqueue = eng._fetch, eng._enqueue

        def computing(shards, bucket, batch_id):
            outs, version = enqueue(shards, bucket, batch_id)
            if batch_id is not None:
                outs = [_Computing(o, self.release) for o in outs]
            return outs, version

        def parked(outs, batch_id):
            if batch_id is not None:
                self.entered.set()
                assert self.release.wait(30)
            return fetch(outs, batch_id)

        eng._enqueue = computing
        eng._fetch = parked

    def await_inflight(self, n, timeout=30.0):
        """True once `n` batches are dispatched and not completed."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if len(self.eng._owed) == n:
                return True
            time.sleep(0.002)
        return False


def _queued_behind_a_parked_dispatcher(eng, samples):
    """Submit `samples` so that all of them are queued before the
    dispatcher dispatches the first batch: the queue is never empty
    between two dispatches, as under load."""
    block = _BlockedDispatcher(eng)
    futs = [eng.submit(s) for s in samples]
    assert block.entered.wait(30)
    eng._execute = block._orig
    block.release.set()
    return futs


# ------------------------------------------------------- injected failures

def test_dispatch_fault_resolves_only_its_batch(served):
    samples, _, _, _ = served
    eng = _engine(served, max_batch_size=2, breaker_threshold=0)
    try:
        install_fault_plan(parse_fault_plan("serving-dispatch@0"))
        futs = [eng.submit(s) for s in samples[:8]]
        for f in futs:
            f.exception(timeout=60)  # blocks until resolved either way
        assert all(f.done() for f in futs)  # EVERY future resolved
        errs = [f for f in futs if f.exception(timeout=0) is not None]
        oks = [f for f in futs if f.exception(timeout=0) is None]
        # exactly the first executed batch failed (<= max_batch_size
        # requests); everyone else was served by the surviving dispatcher
        assert 1 <= len(errs) <= 2
        for f in errs:
            assert isinstance(f.exception(timeout=0), InjectedFault)
        assert oks, "dispatcher must survive a failed batch"
        for s, f in zip(samples[:8], futs):
            if f.exception(timeout=0) is None:
                ref = eng.forward_single(s, bucket=f.bucket)
                for a, b in zip(f.result(timeout=0), ref):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))
        assert eng.health()["batch_failures"] == 1
    finally:
        eng.shutdown()


def test_no_futures_lost_under_repeated_faults(served):
    """The ISSUE 4 serving adjudication: with dispatch faults injected
    mid-stream, zero futures are left unresolved."""
    samples, _, _, _ = served
    eng = _engine(served, max_batch_size=2, breaker_threshold=0)
    try:
        install_fault_plan(parse_fault_plan("serving-dispatch@0,2,4"))
        futs = [eng.submit(s) for s in samples[:16]]
        for f in futs:
            f.exception(timeout=60)  # blocks until resolved either way
        assert all(f.done() for f in futs)
        health = eng.health()
        assert health["batch_failures"] == 3
        assert health["dispatcher_alive"]
        # the engine still serves cleanly afterwards
        assert eng.submit(samples[0]).result(timeout=60) is not None
    finally:
        eng.shutdown()


def test_fetch_fault_fails_only_its_batch(served):
    """A batch that fails AFTER it was dispatched (`serving-fetch`) fails
    its own futures; the batch dispatched behind it, already on the device,
    is served."""
    samples, _, _, _ = served
    eng = _engine(served, max_batch_size=2, max_wait_ms=500.0,
                  breaker_threshold=0)
    try:
        eng.warmup()
        assert eng.stats()["inflight_depth"] == 2
        install_fault_plan(parse_fault_plan("serving-fetch@0"))
        park = _ParkedFetch(eng)
        futs = _queued_behind_a_parked_dispatcher(eng, samples[:4])
        assert park.entered.wait(30)
        assert park.await_inflight(2), "the second batch never overlapped"
        park.release.set()
        for f in futs[:2]:
            assert isinstance(f.exception(timeout=60), InjectedFault)
        for s, f in zip(samples[2:4], futs[2:]):
            got = f.result(timeout=60)
            ref = eng.forward_single(s, bucket=f.bucket)
            for a, b in zip(got, ref):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        health = eng.health()
        assert health["batch_failures"] == 1
        assert health["dispatcher_alive"]
        assert eng.stats()["batches"] == 1
    finally:
        eng.shutdown()


def test_batch_behind_the_tripping_one_is_served_and_breaker_stays_open(
        served):
    """With two in flight the batch dispatched behind the one that trips
    the breaker still runs and is answered, but its success does not close
    an open breaker: the probe does, alone in flight."""
    samples, _, _, _ = served
    eng = _engine(served, max_batch_size=2, max_wait_ms=500.0,
                  breaker_threshold=1, breaker_reset_s=0.2)
    try:
        eng.warmup()
        install_fault_plan(parse_fault_plan("serving-fetch@0"))
        park = _ParkedFetch(eng)
        futs = _queued_behind_a_parked_dispatcher(eng, samples[:4])
        assert park.entered.wait(30) and park.await_inflight(2)
        park.release.set()
        for f in futs[:2]:
            assert isinstance(f.exception(timeout=60), InjectedFault)
        for f in futs[2:]:
            assert f.result(timeout=60) is not None
        assert park.await_inflight(0)
        health = eng.health()
        assert health["state"] == "open" and health["trip_count"] == 1
        with pytest.raises(CircuitOpenError):
            eng.submit(samples[0])
        time.sleep(0.25)  # the window elapses: the next submit probes
        assert eng.submit(samples[0]).result(timeout=60) is not None
        assert park.await_inflight(0)
        assert eng.health()["state"] == "closed"
        assert eng.health()["probe_count"] == 1
    finally:
        eng.shutdown()


def test_shutdown_with_two_in_flight_serves_everything(served):
    samples, _, _, _ = served
    eng = _engine(served, max_batch_size=2, max_wait_ms=500.0)
    eng.warmup()
    park = _ParkedFetch(eng)
    futs = _queued_behind_a_parked_dispatcher(eng, samples[:8])
    assert park.entered.wait(30) and park.await_inflight(2)
    eng.shutdown(wait=False)
    assert not any(f.done() for f in futs)
    park.release.set()
    eng.shutdown(wait=True)
    assert not eng._owed
    for f in futs:  # dispatched, queued: every one is answered
        assert f.result(timeout=0) is not None
    assert eng.stats()["batches"] == 4


def test_dying_dispatcher_with_two_in_flight_leaves_no_future_pending(
        served):
    """The dispatcher dies with two batches dispatched and not completed:
    they fail with the fatal error, as does what is queued."""
    samples, _, _, _ = served
    eng = _engine(served, max_batch_size=2, max_wait_ms=500.0)
    try:
        eng.warmup()
        owed_at_death = []

        def dies():
            owed_at_death.append(len(eng._owed))
            raise MemoryError("died")

        eng._complete = dies
        futs = _queued_behind_a_parked_dispatcher(eng, samples[:6])
        for f in futs:
            assert isinstance(f.exception(timeout=60), MemoryError)
        assert owed_at_death == [2]
        eng._dispatcher.join(30)
        assert not eng.health()["dispatcher_alive"]
        assert not eng._owed
        with pytest.raises(RuntimeError, match="dispatcher died"):
            eng.submit(samples[0])
    finally:
        eng.shutdown()


# -------------------------------------------------------------- admission

def test_queue_full_fast_fails_without_blocking(served):
    samples, _, _, _ = served
    eng = _engine(served, max_batch_size=1, max_wait_ms=0.0, max_queue=2)
    block = _BlockedDispatcher(eng)
    try:
        f1 = eng.submit(samples[0])
        assert block.entered.wait(30)  # dispatcher is parked mid-batch
        f2 = eng.submit(samples[1])
        f3 = eng.submit(samples[2])
        t0 = time.perf_counter()
        with pytest.raises(QueueFullError):
            eng.submit(samples[3])
        assert time.perf_counter() - t0 < 1.0  # fast-fail, no blocking
        assert eng.health()["queue_rejections"] == 1
        block.release.set()
        for f in (f1, f2, f3):
            assert f.result(timeout=60) is not None
    finally:
        block.release.set()
        eng.shutdown()


def test_deadline_expired_never_enters_a_batch(served):
    samples, _, _, _ = served
    eng = _engine(served, max_batch_size=1, max_wait_ms=0.0)
    block = _BlockedDispatcher(eng)
    try:
        f1 = eng.submit(samples[0])
        assert block.entered.wait(30)
        f2 = eng.submit(samples[1], deadline_ms=1.0)
        time.sleep(0.05)  # let the deadline lapse while queued
        block.release.set()
        assert f1.result(timeout=60) is not None
        with pytest.raises(DeadlineExceededError):
            f2.result(timeout=60)
        st = eng.stats()
        assert st["deadline_expired"] == 1
        assert st["requests"] == 1  # the expired request ran NO batch
    finally:
        block.release.set()
        eng.shutdown()


# --------------------------------------------------------- circuit breaker

def test_circuit_breaker_trips_and_recovers(served):
    samples, _, _, _ = served
    eng = _engine(served, max_batch_size=1, max_wait_ms=0.0,
                  breaker_threshold=2, breaker_reset_s=0.2)
    try:
        install_fault_plan(parse_fault_plan("serving-dispatch@0,1"))
        for i in range(2):  # two consecutive failed batches -> trip
            with pytest.raises(InjectedFault):
                eng.submit(samples[i]).result(timeout=60)
        health = eng.health()
        assert health["state"] == "open"
        assert health["trip_count"] == 1
        assert health["consecutive_failures"] == 2
        # open: fast-fail at submit, no future created
        with pytest.raises(CircuitOpenError):
            eng.submit(samples[2])
        assert eng.health()["circuit_rejections"] == 1

        time.sleep(0.25)  # past breaker_reset_s: probe window
        probe = eng.submit(samples[3])  # admitted as the half-open probe
        assert probe.result(timeout=60) is not None
        health = eng.health()
        assert health["state"] == "closed"
        assert health["consecutive_failures"] == 0
        # normal service resumed
        assert eng.submit(samples[4]).result(timeout=60) is not None
    finally:
        eng.shutdown()


def test_breaker_reopens_on_failed_probe(served):
    samples, _, _, _ = served
    eng = _engine(served, max_batch_size=1, max_wait_ms=0.0,
                  breaker_threshold=1, breaker_reset_s=0.15)
    try:
        # batch 0 fails (trip #1); the probe batch 1 fails too -> re-trip
        install_fault_plan(parse_fault_plan("serving-dispatch@0,1"))
        with pytest.raises(InjectedFault):
            eng.submit(samples[0]).result(timeout=60)
        assert eng.health()["state"] == "open"
        time.sleep(0.2)
        with pytest.raises(InjectedFault):
            eng.submit(samples[1]).result(timeout=60)  # failed probe
        health = eng.health()
        assert health["state"] == "open"
        assert health["trip_count"] == 2
        time.sleep(0.2)
        assert eng.submit(samples[2]).result(timeout=60) is not None
        assert eng.health()["state"] == "closed"
    finally:
        eng.shutdown()


def test_fleet_half_open_single_probe_hammer(served):
    """The fleet probe contract under concurrency (PR 12 satellite):
    with BOTH replicas' breakers open and their windows elapsed, a
    concurrent submit hammer through the router admits EXACTLY ONE
    half-open probe per open replica fleet-wide (engine.probe_count),
    the probes succeed, and every hammered future resolves."""
    from hydragnn_tpu.serving.fleet import ReplicaRouter
    samples, mcfg, model, variables = served

    def factory(idx):
        return InferenceEngine(model, variables, mcfg,
                               reference_samples=samples,
                               max_batch_size=2, max_wait_ms=0.0,
                               breaker_threshold=1, breaker_reset_s=0.3)

    router = ReplicaRouter(factory, 2)
    try:
        router.warmup()  # cold compiles must not eat the probe windows
        # one poisoned request trips BOTH breakers: its batch fails on
        # the first replica (dispatch fault 0), re-dispatches, and fails
        # on the second (dispatch fault 1). The budget is one try per
        # replica, so the REAL error (the injected batch failure)
        # surfaces — not an extra retry's availability noise
        install_fault_plan(parse_fault_plan("serving-dispatch@0,1"))
        with pytest.raises(InjectedFault):
            router.submit(samples[0]).result(timeout=60)
        states = [h["state"]
                  for _, h in sorted(router.health()["replicas"].items())]
        assert states == ["open", "open"]
        probes_before = [h["probe_count"] for _, h in
                         sorted(router.health()["replicas"].items())]
        assert probes_before == [0, 0]

        time.sleep(0.35)  # both probe windows elapse
        barrier = threading.Barrier(8)
        futs = []
        futs_lock = threading.Lock()

        def hammer(k):
            barrier.wait()
            for s in samples[1 + 2 * k:3 + 2 * k]:
                f = router.submit(s)
                with futs_lock:
                    futs.append(f)

        threads = [threading.Thread(target=hammer, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        for f in futs:
            f.exception(timeout=60)
        assert all(f.done() for f in futs)  # nothing hangs or leaks
        health = router.health()
        per_rep = [h for _, h in sorted(health["replicas"].items())]
        # the pinned claim: exactly ONE probe admitted per open replica,
        # regardless of 16 concurrent submits racing the window
        assert [h["probe_count"] for h in per_rep] == [1, 1]
        assert [h["trip_count"] for h in per_rep] == [1, 1]
        assert [h["state"] for h in per_rep] == ["closed", "closed"]
        # post-recovery the fleet serves normally
        assert router.submit(samples[0]).result(timeout=60) is not None
    finally:
        router.shutdown()


def test_expired_probe_reopens_instead_of_wedging(served):
    """A probe that expires unexecuted must RE-OPEN the breaker (so the
    next submit becomes a fresh probe) — not wedge half-open forever."""
    samples, _, _, _ = served
    eng = _engine(served, max_batch_size=1, max_wait_ms=0.0,
                  breaker_threshold=1, breaker_reset_s=0.1)
    block = None
    try:
        eng.warmup()
        install_fault_plan(parse_fault_plan("serving-dispatch@0"))
        with pytest.raises(InjectedFault):
            eng.submit(samples[0]).result(timeout=60)
        assert eng.health()["state"] == "open"
        time.sleep(0.15)  # window elapses
        block = _BlockedDispatcher(eng)
        probe = eng.submit(samples[1], deadline_ms=20.0)  # THE probe
        assert eng.health()["state"] == "half_open"
        assert eng.health()["probe_count"] == 1
        # concurrent submits are rejected while the probe is in flight
        with pytest.raises(CircuitOpenError):
            eng.submit(samples[2])
        time.sleep(0.05)  # the probe's deadline lapses while queued
        block.release.set()
        with pytest.raises(DeadlineExceededError):
            probe.result(timeout=60)
        assert eng.health()["state"] == "open"  # re-opened, not wedged
        # the window is already past: the next submit is a NEW probe and
        # recovery completes
        f = eng.submit(samples[3])
        assert f.result(timeout=60) is not None
        assert eng.health()["state"] == "closed"
        assert eng.health()["probe_count"] == 2
    finally:
        if block is not None:
            block.release.set()
        eng.shutdown()


def test_queued_requests_fail_fast_behind_open_breaker(served):
    """Requests already queued when the breaker trips must not hang: the
    dispatcher resolves them with CircuitOpenError."""
    samples, _, _, _ = served
    eng = _engine(served, max_batch_size=1, max_wait_ms=0.0,
                  breaker_threshold=1, breaker_reset_s=30.0)
    block = _BlockedDispatcher(eng)
    try:
        install_fault_plan(parse_fault_plan("serving-dispatch@0"))
        f1 = eng.submit(samples[0])
        assert block.entered.wait(30)
        f2 = eng.submit(samples[1])  # queued before the trip
        block.release.set()
        with pytest.raises(InjectedFault):
            f1.result(timeout=60)
        with pytest.raises(CircuitOpenError):
            f2.result(timeout=60)
    finally:
        block.release.set()
        eng.shutdown()
