"""Batched inference serving engine (hydragnn_tpu/serving/, docs/serving.md).

Contract under test:
* batched outputs are BITWISE-identical to the single-request forward on
  the same bucket (the tentpole's numerics guarantee),
* the bucket ladder and bucket selection are pure deterministic functions,
* a lone request flushes after max_wait_ms (no starvation),
* per-request failures reach the owning future — callers never hang,
* shutdown drains queued requests cleanly,
* the engine path through run_prediction matches the legacy loop,
* serving knobs resolve config/env precedence with strict parsing.
"""
import copy
import json
import os
import subprocess
import sys
import time
from concurrent.futures import Future

import numpy as np
import pytest

from hydragnn_tpu.config import build_model_config, update_config
from hydragnn_tpu.graphs.batch import GraphSample, collate
from hydragnn_tpu.models.create import create_model, init_params
from hydragnn_tpu.serving.config import ServingConfig, resolve_serving
from hydragnn_tpu.serving.engine import (InferenceEngine, _Request,
                                         bucket_ladder, select_bucket)

from tests.deterministic_data import deterministic_graph_dataset
from tests.test_serving_faults import (_BlockedDispatcher, _ParkedFetch,
                                       _queued_behind_a_parked_dispatcher)
from tests.utils import make_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def served():
    samples = deterministic_graph_dataset(num_configs=48,
                                          heads=("graph", "node"))
    cfg = make_config("PNA", heads=("graph", "node"))
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    model = create_model(mcfg)
    variables = init_params(model, collate(samples[:4]))
    return samples, cfg, mcfg, model, variables


@pytest.fixture(scope="module")
def engine(served):
    samples, _, mcfg, model, variables = served
    eng = InferenceEngine(model, variables, mcfg,
                          reference_samples=samples, max_batch_size=8,
                          max_wait_ms=50.0, neighbor_format=True)
    eng.warmup()
    yield eng
    eng.shutdown()


# ------------------------------------------------------------- bucket ladder

def test_bucket_ladder_deterministic_and_monotone(served):
    samples, _, _, _, _ = served
    from hydragnn_tpu.graphs.packing import sample_sizes
    nodes, edges = sample_sizes(samples)
    a = bucket_ladder(nodes, edges, 16)
    b = bucket_ladder(nodes, edges, 16)
    assert a == b, "ladder must be a pure function of the histogram"
    shapes = [(x.n_node, x.n_edge) for x in a]
    assert shapes == sorted(shapes)
    assert len(a) <= 5  # {1, 2, 4, 8, 16} minus dedup
    # every single sample fits the smallest bucket
    assert max(nodes) <= a[0].cap_nodes
    assert max(edges) <= a[0].cap_edges
    # num_buckets keeps the largest capacities
    short = bucket_ladder(nodes, edges, 16, num_buckets=2)
    assert len(short) <= 2
    assert (short[-1].n_node, short[-1].n_edge) == shapes[-1]


def test_select_bucket_first_fit(served):
    samples, _, _, _, _ = served
    from hydragnn_tpu.graphs.packing import sample_sizes
    nodes, edges = sample_sizes(samples)
    ladder = bucket_ladder(nodes, edges, 16)
    for count, tn, te in ((1, 4, 10), (3, 40, 200), (16, 300, 1500)):
        got = select_bucket(ladder, count, tn, te)
        if got is not None:
            # smallest fitting: every smaller ladder entry must NOT fit
            for b in ladder:
                if b is got:
                    break
                assert (count > b.cap_graphs or tn > b.cap_nodes
                        or te > b.cap_edges)
    assert select_bucket(ladder, 1, 10 ** 9, 1) is None


def test_coalesce_deterministic_bucket_selection(served):
    """Same request stream -> same per-shard bins -> same bucket, across
    two independent engines (threads out of the picture: the dispatcher
    is stopped and _coalesce is driven directly)."""
    samples, _, mcfg, model, variables = served

    def plan(eng):
        eng.shutdown()
        reqs = [_Request(s, Future()) for s in samples]
        for r in reqs[1:]:
            eng._queue.put(r)
        plans = []
        first = reqs[0]
        while True:
            shards, leftover = eng._coalesce(first, wait=False)
            count = max(len(sh) for sh in shards)
            need_n = max(sum(r.n for r in sh) for sh in shards)
            need_e = max(sum(r.e for r in sh) for sh in shards)
            bucket = select_bucket(eng.buckets, count, need_n, need_e)
            plans.append(([[id(r.sample) for r in sh] for sh in shards],
                          (bucket.n_node, bucket.n_edge, bucket.n_graph)))
            if leftover is None:
                break
            first = leftover
        return plans

    mk = lambda: InferenceEngine(model, variables, mcfg,
                                 reference_samples=samples,
                                 max_batch_size=8, neighbor_format=True)
    assert plan(mk()) == plan(mk())


# ----------------------------------------------------------------- numerics

def test_bitwise_parity_with_single_request_forward(served, engine):
    """The tentpole contract: every request's batched output equals the
    single-request forward on the bucket its batch ran on, bit for bit."""
    samples, _, _, _, _ = served
    futs = [engine.submit(s) for s in samples]
    results = [f.result(timeout=120) for f in futs]
    assert engine.compile_count <= len(engine.buckets)
    for s, f, res in zip(samples, futs, results):
        ref = engine.forward_single(s, bucket=f.bucket)
        for a, b in zip(res, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resubmission_bitwise_deterministic(served, engine):
    samples, _, _, _, _ = served
    r1 = engine.predict(samples[:16], timeout=120)
    r2 = engine.predict(samples[:16], timeout=120)
    for a, b in zip(r1, r2):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


def test_unpad_shapes(served, engine):
    samples, _, mcfg, _, _ = served
    res = engine.predict(samples[:3], timeout=120)
    for s, r in zip(samples[:3], res):
        assert len(r) == len(mcfg.heads)
        for ih, head in enumerate(mcfg.heads):
            if head.head_type == "graph":
                assert r[ih].shape == (head.output_dim,)
            else:
                assert r[ih].shape == (s.num_nodes, head.output_dim)


def test_spmd_serving_matches_single_shard(served, engine):
    """num_shards=2: per-shard sub-batches on one bucket through the SPMD
    forward, outputs unpadded device-major — numerics match the
    single-shard engine. Also exercises the empty-shard path (1 request
    over 2 shards)."""
    samples, _, mcfg, model, variables = served
    eng2 = InferenceEngine(model, variables, mcfg,
                           reference_samples=samples, max_batch_size=8,
                           max_wait_ms=50.0, num_shards=2,
                           neighbor_format=True)
    try:
        for batch in ([samples[0]], samples[:7]):
            res2 = eng2.predict(batch, timeout=120)
            for s, r2 in zip(batch, res2):
                ref = engine.forward_single(s)
                for a, b in zip(r2, ref):
                    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                               rtol=2e-6, atol=2e-6)
    finally:
        eng2.shutdown()


# ------------------------------------------------------------------ batching

def test_max_wait_flushes_partial_batch(served):
    samples, _, mcfg, model, variables = served
    eng = InferenceEngine(model, variables, mcfg,
                          reference_samples=samples, max_batch_size=64,
                          max_wait_ms=60.0, neighbor_format=True)
    try:
        t0 = time.perf_counter()
        futs = [eng.submit(s) for s in samples[:3]]
        for f in futs:
            f.result(timeout=60)
        elapsed = time.perf_counter() - t0
        st = eng.stats()
        assert st["requests"] == 3
        assert st["batches"] == 1, "3 quick submits must coalesce into 1"
        # flushed by the wait window, not by a full batch (64 never arrives)
        assert elapsed < 60.0
    finally:
        eng.shutdown()


# --------------------------------------------------------- dispatch pipeline

def _pipelined(served, **kw):
    samples, _, mcfg, model, variables = served
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_wait_ms", 500.0)
    eng = InferenceEngine(model, variables, mcfg,
                          reference_samples=samples, neighbor_format=True,
                          **kw)
    eng.warmup()
    return eng


def _assert_bitwise(eng, sample, fut):
    got = fut.result(timeout=60)
    ref = eng.forward_single(sample, bucket=fut.bucket)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_next_batch_is_dispatched_while_the_last_one_runs(served):
    """With a queue longer than one bucket, batch n+1 is collated and
    dispatched before batch n's futures resolve, and `batches_overlapped`
    counts it; the answers are the serial path's, bit for bit."""
    samples = served[0]
    eng = _pipelined(served)
    try:
        assert eng.stats()["inflight_depth"] == 2
        park = _ParkedFetch(eng)
        futs = _queued_behind_a_parked_dispatcher(eng, samples[:6])
        assert park.entered.wait(30), "batch 0 was never fetched"
        # batch 0 is being fetched, and batch 1 is on the device behind it
        assert park.await_inflight(2), "batch 1 waited for batch 0"
        assert not any(f.done() for f in futs)
        # never a third: the depth bounds what is enqueued on the device
        assert eng._queue.qsize() == 2
        park.release.set()
        for s, f in zip(samples, futs):
            _assert_bitwise(eng, s, f)
        st = eng.stats()
        assert st["batches"] == 3 and st["requests"] == 6
        # batch 1 left behind batch 0; once the device was done batch 1
        # could be read, so it was answered BEFORE batch 2 was prepared
        assert st["batches_overlapped"] == 1
        eng.reset_stats()
        assert eng.stats()["batches_overlapped"] == 0
        assert eng.stats()["inflight_depth"] == 2
    finally:
        eng.shutdown()


def test_lone_request_is_answered_without_a_successor(served):
    """With less than a batch's worth queued nothing overlaps: a lone
    request is dispatched after its own window and answered at once; a
    full batch's answers never wait for the window of the request behind
    it, and that window is not cut short either."""
    samples = served[0]
    eng = _pipelined(served, max_wait_ms=1000.0)
    try:
        t0 = time.perf_counter()
        lone = eng.submit(samples[0])
        _assert_bitwise(eng, samples[0], lone)
        assert time.perf_counter() - t0 >= 0.9, "its own window"
        st = eng.stats()
        assert st["batches"] == 1 and st["batches_overlapped"] == 0
        t0 = time.perf_counter()
        full = [eng.submit(s) for s in samples[1:3]]   # no window: full
        behind = eng.submit(samples[3])     # not a batch's worth: it waits
        for f in full:
            f.result(timeout=60)
        assert time.perf_counter() - t0 < 0.9
        assert not behind.done(), "its window was cut short"
        _assert_bitwise(eng, samples[3], behind)
        assert time.perf_counter() - t0 >= 0.9
        st = eng.stats()
        assert st["batches"] == 3 and st["batches_overlapped"] == 0
    finally:
        eng.shutdown()


def test_hot_swap_between_two_in_flight_batches(served):
    """A swap that lands between two dispatches, both batches in flight:
    each carries the version, and the answers, of the weights it ran
    with."""
    import jax
    samples, _, _, _, variables = served
    eng = _pipelined(served)
    try:
        old = [eng.forward_single(s, bucket=eng.buckets[-1])
               for s in samples[:4]]
        swapped = {"params": jax.tree_util.tree_map(
            lambda a: a * 1.25, variables["params"]),
            "batch_stats": variables.get("batch_stats", {})}
        enqueue, swaps = eng._enqueue, []

        def swap_after_the_first(shards, bucket, batch_id):
            out = enqueue(shards, bucket, batch_id)
            if batch_id is not None and not swaps:
                swaps.append(eng.swap_variables(swapped, "v1"))
            return out

        eng._enqueue = swap_after_the_first
        park = _ParkedFetch(eng)
        futs = _queued_behind_a_parked_dispatcher(eng, samples[:4])
        assert park.entered.wait(30) and park.await_inflight(2)
        assert swaps == ["v0"]
        park.release.set()
        first, second = futs[:2], futs[2:]
        for f in futs:
            f.result(timeout=60)
        assert [f.model_version for f in futs] == ["v0", "v0", "v1", "v1"]
        for s, f in zip(samples[2:4], second):
            _assert_bitwise(eng, s, f)     # the engine serves v1 now
        # the swap moved the numbers, and back under the old weights the
        # first batch's answers are the single-request ones
        for f, before in zip(second, old[2:]):
            assert any(not np.array_equal(np.asarray(a), np.asarray(b))
                       for a, b in zip(f.result(), before))
        eng.swap_variables(variables, "v0")
        for s, f in zip(samples, first):
            _assert_bitwise(eng, s, f)
    finally:
        eng.shutdown()


def test_pipeline_stress_every_future_resolves_once(served):
    """Sixteen clients against the dispatcher, with the interpreter
    switching threads every 10 us: every future resolves once, to the
    single-request answer; the counters add up and nothing is left in
    flight."""
    import threading
    samples = served[0]
    eng = _pipelined(served, max_batch_size=4, max_wait_ms=1.0)
    resolved, mine = [], []
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def client(k):
            for i in range(6):
                s = samples[(k * 6 + i) % len(samples)]
                f = eng.submit(s)
                f.add_done_callback(lambda _f: resolved.append(1))
                mine.append((s, f))

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
        for _, f in mine:
            f.result(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    try:
        st = eng.stats()
        assert len(mine) == 96 and st["requests"] == 96
        assert len(resolved) == 96, "a callback ran twice, or never"
        assert 0 <= st["batches_overlapped"] < st["batches"] <= 96
        for s, f in mine[::7]:
            _assert_bitwise(eng, s, f)
    finally:
        eng.shutdown()
    assert not eng._owed


@pytest.mark.parametrize("free,depth", [(1, 1), (1 << 60, 2), (None, 2)],
                         ids=["no_room", "room", "no_statistics"])
def test_inflight_depth_follows_free_device_memory(served, monkeypatch,
                                                   free, depth):
    """Depth 2 only where two executions of the largest bucket's program
    fit the memory the device reports free; 1, the serial path, where they
    do not: then batch 1 is not dispatched until batch 0 is answered."""
    from hydragnn_tpu.serving import engine as engine_mod
    monkeypatch.setattr(engine_mod, "_free_device_bytes",
                        lambda devices: free)
    samples, _, mcfg, model, variables = served
    eng = InferenceEngine(model, variables, mcfg, reference_samples=samples,
                          max_batch_size=2, max_wait_ms=500.0,
                          neighbor_format=True)
    try:
        assert eng.stats()["inflight_depth"] == 1, "unresolved: serial"
        eng.warmup()
        assert eng.stats()["inflight_depth"] == depth
        park = _ParkedFetch(eng)
        futs = _queued_behind_a_parked_dispatcher(eng, samples[:4])
        assert park.entered.wait(30) and park.await_inflight(depth)
        assert eng._queue.qsize() == 2 * (2 - depth)
        park.release.set()
        for s, f in zip(samples, futs):
            _assert_bitwise(eng, s, f)
        assert eng.stats()["batches_overlapped"] == depth - 1
    finally:
        eng.shutdown()


def test_occupancy_and_padding_stats(served, engine):
    engine.reset_stats()
    samples, _, _, _, _ = served
    engine.predict(samples, timeout=120)
    st = engine.stats()
    assert st["requests"] == len(samples)
    assert 0.0 < st["batch_occupancy"] <= 1.0
    assert 0.0 <= st["padding_frac_nodes"] < 1.0
    assert st["p99_ms"] >= st["p50_ms"] >= 0.0
    assert st["max_queue_depth"] >= 1
    assert st["compile_count"] <= st["num_buckets"]


def test_explicit_buckets_with_small_graph_cap(served):
    """Regression: an explicit ladder whose largest bucket holds fewer
    graph slots than max_batch_size must cap the coalesced shard at
    cap_graphs — not assert in bucket selection and fail the batch."""
    import dataclasses
    from hydragnn_tpu.graphs.packing import sample_sizes
    samples, _, mcfg, model, variables = served
    nodes, edges = sample_sizes(samples)
    ladder = bucket_ladder(nodes, edges, 16)
    small_cap = tuple(dataclasses.replace(b, n_graph=min(b.n_graph, 5))
                      for b in ladder)
    eng = InferenceEngine(model, variables, mcfg, buckets=small_cap,
                          proto_sample=samples[0], max_batch_size=16,
                          max_wait_ms=50.0, neighbor_format=True,
                          neighbor_k=8 * 8)
    try:
        res = eng.predict(samples[:10], timeout=120)
        assert len(res) == 10
        assert eng.stats()["batches"] >= 3  # 10 requests, <=4 per batch
    finally:
        eng.shutdown()
    with pytest.raises(ValueError, match="n_graph >= 2"):
        InferenceEngine(model, variables, mcfg,
                        buckets=(dataclasses.replace(ladder[0], n_graph=1),),
                        proto_sample=samples[0])


# ------------------------------------------------------------------ failures

def test_oversized_request_fails_its_future(served, engine):
    samples, _, _, _, _ = served
    big_n = engine.buckets[-1].cap_nodes + 8
    n = big_n + 1
    huge = GraphSample(x=np.ones((n, 1), np.float32),
                       pos=np.zeros((n, 3), np.float32),
                       senders=np.zeros((4,), np.int32),
                       receivers=np.zeros((4,), np.int32))
    fut = engine.submit(huge)
    with pytest.raises(ValueError, match="largest serving bucket"):
        fut.result(timeout=10)
    # the engine keeps serving afterwards
    ok = engine.submit(samples[0])
    assert ok.result(timeout=60) is not None


def test_schema_mismatch_fails_its_future(served, engine):
    fut = engine.submit(GraphSample(
        x=np.ones((4, 7), np.float32), pos=np.zeros((4, 3), np.float32),
        senders=np.asarray([0, 1], np.int32),
        receivers=np.asarray([1, 0], np.int32)))
    with pytest.raises(ValueError, match="width"):
        fut.result(timeout=10)


def test_execute_failure_propagates_not_hangs(served):
    samples, _, mcfg, model, variables = served
    eng = InferenceEngine(model, variables, mcfg,
                          reference_samples=samples, max_batch_size=4,
                          max_wait_ms=5.0, neighbor_format=True)
    try:
        def boom(*a, **kw):
            raise RuntimeError("injected forward failure")
        eng._enqueue = boom
        futs = [eng.submit(s) for s in samples[:6]]
        for f in futs:
            with pytest.raises(RuntimeError, match="injected"):
                f.result(timeout=30)
    finally:
        eng.shutdown()


def test_clean_shutdown_drains_queued_requests(served):
    samples, _, mcfg, model, variables = served
    eng = InferenceEngine(model, variables, mcfg,
                          reference_samples=samples, max_batch_size=8,
                          max_wait_ms=200.0, neighbor_format=True)
    futs = [eng.submit(s) for s in samples[:20]]
    eng.shutdown(wait=True)  # queued requests must still be served
    assert all(f.done() for f in futs), "shutdown left callers hanging"
    for s, f in zip(samples[:20], futs):
        res = f.result(timeout=0)
        ref = eng.forward_single(s, bucket=f.bucket)
        for a, b in zip(res, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(RuntimeError):
        eng.submit(samples[0])
    eng.shutdown()  # idempotent


# ------------------------------------------------------- run_prediction path

def test_run_prediction_engine_matches_legacy(served):
    from hydragnn_tpu.run_prediction import run_prediction
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.train_step import TrainState
    samples, cfg, mcfg, model, variables = served
    n = len(samples)
    splits = (samples[:int(0.6 * n)], samples[int(0.6 * n):int(0.8 * n)],
              samples[int(0.8 * n):])
    state = TrainState.create(
        variables, select_optimizer(cfg["NeuralNetwork"]["Training"]))
    t0, p0 = run_prediction(copy.deepcopy(cfg), datasets=splits,
                            state=state, model=model, serve=False)
    t1, p1 = run_prediction(copy.deepcopy(cfg), datasets=splits,
                            state=state, model=model, serve=True)
    for a, b in zip(t0, t1):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(p0, p1):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)


# ------------------------------------------------------------------- config

def test_resolve_serving_precedence(monkeypatch):
    for var in ("HYDRAGNN_SERVE", "HYDRAGNN_SERVE_MAX_BATCH",
                "HYDRAGNN_SERVE_MAX_WAIT_MS", "HYDRAGNN_SERVE_BUCKETS"):
        monkeypatch.delenv(var, raising=False)
    assert resolve_serving({}) == ServingConfig()
    cfg = {"Serving": {"enabled": True, "max_batch_size": 64,
                       "max_wait_ms": 1.5}}
    sv = resolve_serving(cfg)
    assert sv.enabled and sv.max_batch_size == 64 and sv.max_wait_ms == 1.5
    monkeypatch.setenv("HYDRAGNN_SERVE", "0")
    monkeypatch.setenv("HYDRAGNN_SERVE_MAX_BATCH", "16")
    sv = resolve_serving(cfg)
    assert not sv.enabled and sv.max_batch_size == 16


def test_resolve_serving_strict_parsing(monkeypatch, caplog):
    """Typo values warn and fall back — never silently enable."""
    import logging
    monkeypatch.setenv("HYDRAGNN_SERVE", "ture")  # typo
    monkeypatch.setenv("HYDRAGNN_SERVE_MAX_BATCH", "thirty-two")
    with caplog.at_level(logging.WARNING, logger="hydragnn_tpu"):
        sv = resolve_serving({})
    assert sv.enabled is False
    assert sv.max_batch_size == 32
    assert sum("HYDRAGNN_SERVE" in r.message for r in caplog.records) >= 2


def test_env_strict_number_helpers(monkeypatch, caplog):
    import logging
    from hydragnn_tpu.utils.envflags import env_strict_float, env_strict_int
    monkeypatch.setenv("HYDRAGNN_TEST_INT", "12")
    monkeypatch.setenv("HYDRAGNN_TEST_FLOAT", "2.5")
    assert env_strict_int("HYDRAGNN_TEST_INT", 1) == 12
    assert env_strict_float("HYDRAGNN_TEST_FLOAT", 1.0) == 2.5
    monkeypatch.setenv("HYDRAGNN_TEST_INT", "oops")
    with caplog.at_level(logging.WARNING, logger="hydragnn_tpu"):
        assert env_strict_int("HYDRAGNN_TEST_INT", 7) == 7
    assert any("HYDRAGNN_TEST_INT" in r.message for r in caplog.records)
    assert env_strict_int("HYDRAGNN_TEST_UNSET_XYZ", None) is None


# ----------------------------------------------------- stats concurrency (PR 7)

def test_stats_concurrent_with_submit_and_reset(served, engine):
    """The stats()/reset_stats()/health() surface must be safe against
    the dispatcher and concurrent submitters (PR 7 audit: counters are
    snapshotted atomically under the engine lock; percentile math runs
    on the copy OUTSIDE it). Hammer all three from threads while
    submitting; then quiesce, reset once, and account exactly.

    Reuses the warm module engine (no extra bucket compiles); it runs
    after the stats-reading tests and leaves the engine serviceable —
    only the resettable counters are touched."""
    import threading
    samples, _, _, _, _ = served
    eng = engine
    stop = threading.Event()
    errors = []

    def scrape():
        while not stop.is_set():
            try:
                st = eng.stats()
                assert st["requests"] >= 0
                assert st["count"] >= 0  # latency key always present
                eng.health()
                eng.reset_stats()
            except Exception as exc:  # noqa: BLE001 — collected
                errors.append(exc)
                return

    def submit_many(out):
        try:
            futs = [eng.submit(s) for s in samples]
            out.extend(f.result(timeout=60) for f in futs)
        except Exception as exc:  # noqa: BLE001 — collected
            errors.append(exc)

    scraper = threading.Thread(target=scrape)
    results_a, results_b = [], []
    sub_a = threading.Thread(target=submit_many, args=(results_a,))
    sub_b = threading.Thread(target=submit_many, args=(results_b,))
    scraper.start()
    sub_a.start()
    sub_b.start()
    sub_a.join(timeout=120)
    sub_b.join(timeout=120)
    stop.set()
    scraper.join(timeout=30)
    assert not errors, errors
    assert len(results_a) == len(samples)
    assert len(results_b) == len(samples)
    # quiesced accounting: one reset, then a known batch of submits
    # must be counted exactly (no lost or double-counted requests)
    eng.reset_stats()
    futs = [eng.submit(s) for s in samples[:10]]
    for f in futs:
        f.result(timeout=60)
    st = eng.stats()
    assert st["requests"] == 10
    assert st["count"] == 10  # one latency sample per request
    assert st["batches"] >= 1


# ------------------------------------------------------- slow-lane load smoke

@pytest.mark.slow
def test_bench_serve_load_smoke():
    """BENCH_SERVE end-to-end in a subprocess at CI scale: emits the
    BENCH_SERVE.json artifact, bounds the compile count by the bucket
    ladder, requires bitwise same-bucket parity, and guards a (loose —
    wall-clock on a shared CI box) speedup floor."""
    out_path = os.path.join(REPO, "BENCH_SERVE.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_SERVE="1",
               BENCH_SERVE_REQUESTS="64", BENCH_BATCH="16",
               BENCH_HIDDEN="32", BENCH_SERVE_VERIFY="8",
               BENCH_SERVE_OUT=out_path)
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert os.path.exists(out_path)
    assert out["compile_count"] <= len(out["buckets"])
    assert out["outputs_bitwise_equal_same_bucket"] is True
    assert out["open_loop"]["p99_ms"] >= out["open_loop"]["p50_ms"]
    # the CPU acceptance target is 3x (ISSUE 3); the CI guard is looser
    # to keep a busy shared box from flaking the lane
    assert out["speedup_vs_per_request"] >= 1.5, out


# ------------------------------------------------- raw-structure serving

@pytest.fixture(scope="module")
def structured():
    """A raw-structure engine: samples built THROUGH build_graph_sample
    from the same config the engine holds, so submit_structure's
    structure -> graph path and the prebuilt path share one schema."""
    from hydragnn_tpu.preprocess.transforms import build_graph_sample
    rng = np.random.RandomState(0)
    cfg = make_config("PNA")
    structures = []
    for _ in range(16):
        n = int(rng.randint(8, 16))
        structures.append((rng.rand(n, 3).astype(np.float64) * 1.8,
                           rng.rand(n, 3).astype(np.float32),
                           rng.rand(1).astype(np.float32)))
    samples = [build_graph_sample(nfm, pos, cfg, graph_feats=gf)
               for pos, nfm, gf in structures]
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    model = create_model(mcfg)
    variables = init_params(model, collate(samples[:4]))
    # max_batch_size 1: trajectory-shaped traffic (one request at a
    # time) and a single warmup compile — tier-1 budget discipline
    eng = InferenceEngine(model, variables, mcfg,
                          reference_samples=samples, max_batch_size=1,
                          max_wait_ms=0.0, structure_config=cfg,
                          md_skin=0.25)
    eng.warmup()
    yield structures, samples, cfg, eng
    eng.shutdown()


def test_submit_structure_matches_prebuilt_submit(structured):
    """structure -> graph -> forward in one call == building the sample
    offline and submitting it, bitwise; futures carry the .rebuilt /
    .graph_build_ms breadcrumbs next to .bucket."""
    from hydragnn_tpu.preprocess.transforms import build_graph_sample
    structures, _, cfg, eng = structured
    for pos, nfm, _ in structures[:4]:
        fut = eng.submit_structure(pos, nfm)
        res = fut.result(timeout=60)
        sample = build_graph_sample(nfm, pos, cfg, with_targets=False)
        ref = eng.submit(sample).result(timeout=60)
        assert all(np.array_equal(a, b) for a, b in zip(res, ref))
        assert fut.rebuilt is True  # session-less = fresh build
        assert fut.graph_build_ms >= 0.0
        assert fut.bucket in eng.buckets


def test_structure_schema_object(structured):
    from hydragnn_tpu.serving.config import Structure
    structures, _, _, eng = structured
    pos, nfm, _ = structures[0]
    a = eng.submit_structure(Structure(positions=pos,
                                       node_features=nfm)).result(60)
    b = eng.submit_structure(pos, nfm).result(60)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    with pytest.raises(ValueError, match="node_features"):
        eng.submit_structure(pos)


def test_structure_session_incremental_bitwise(structured):
    """A trajectory session reuses its Verlet-skin list (rebuilds <
    steps), marks the futures accordingly, and every step's outputs
    equal the session-less fresh-build path bitwise."""
    structures, _, _, eng = structured
    rng = np.random.RandomState(1)
    pos, nfm, _ = structures[0]
    pos = pos.copy()
    sess = eng.structure_session()
    rebuilds = 0
    for step in range(8):
        pos = pos + rng.randn(*pos.shape) * 0.004
        fut = eng.submit_structure(pos, nfm, session=sess)
        res = fut.result(timeout=60)
        fresh = eng.submit_structure(pos, nfm).result(timeout=60)
        assert all(np.array_equal(a, b) for a, b in zip(res, fresh)), step
        rebuilds += int(fut.rebuilt)
    assert rebuilds < 8, "session never reused its candidate cache"
    assert sess.rebuild_fraction < 1.0
    assert sess.nlist.updates == 8


def test_structure_requires_config(served):
    samples, _, mcfg, model, variables = served
    eng = InferenceEngine(model, variables, mcfg,
                          reference_samples=samples, max_batch_size=2,
                          max_wait_ms=0.0)
    try:
        with pytest.raises(RuntimeError, match="structure_config"):
            eng.submit_structure(np.zeros((4, 3)), np.zeros((4, 1)))
        with pytest.raises(RuntimeError, match="structure_config"):
            eng.structure_session()
    finally:
        eng.shutdown()


def test_structure_session_rejects_rotational_invariance(structured):
    import copy as _copy
    structures, samples, cfg, _ = structured
    rcfg = _copy.deepcopy(cfg)
    rcfg["Dataset"]["rotational_invariance"] = True
    mcfg = build_model_config(rcfg)
    model = create_model(mcfg)
    variables = init_params(model, collate(samples[:4]))
    eng = InferenceEngine(model, variables, mcfg,
                          reference_samples=samples, max_batch_size=2,
                          max_wait_ms=0.0, structure_config=rcfg)
    try:
        with pytest.raises(ValueError, match="rotational_invariance"):
            eng.structure_session()
    finally:
        eng.shutdown()


@pytest.fixture(scope="module")
def served_spans(structured):
    """The spans of 12 raw-structure requests, sent from three threads to
    an engine that coalesces (4 a batch, 20 ms of company), with what the
    engine reports of them."""
    import threading
    from hydragnn_tpu.telemetry import spans as tspans
    structures, samples, cfg, base = structured
    eng = InferenceEngine(base._model, base._variables, base.mcfg,
                          reference_samples=samples, max_batch_size=4,
                          max_wait_ms=20.0, structure_config=cfg)
    eng.warmup()
    rec = tspans.SpanRecorder("test")
    previous = tspans.install_recorder(rec)
    try:
        def client(k):
            for pos, nfm, _ in structures[k::3][:4]:
                eng.submit_structure(pos, nfm).result(timeout=60)
                time.sleep(0.003)
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        time.sleep(0.05)      # the dispatcher is back in await_request
        stats = eng.stats()
    finally:
        tspans.install_recorder(previous)
        eng.shutdown()
    events = [e for e in rec.chrome_trace()["traceEvents"]
              if e.get("ph") == "X"]
    return events, stats, eng._dispatcher.ident


def _inside(inner, outer, slack_us=50.0):
    return (outer["ts"] - slack_us <= inner["ts"]
            and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + slack_us)


def test_request_span_contains_its_children(served_spans):
    """`serve.request` runs from ARRIVAL to the result being set: it
    contains its `serve.graph_build`, its `serve.queue_wait` and its
    batch's `serve.fetch`, all found through one `req`."""
    events, _, _ = served_spans
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    requests = by_name["serve.request"]
    assert len(requests) == 12
    assert sorted(e["args"]["req"] for e in requests) == sorted(
        set(e["args"]["req"] for e in requests)), "req is unique"
    batches = {e["args"]["batch"]: e for e in by_name["serve.batch"]}
    assert sorted(r for b in batches.values()
                  for r in b["args"]["reqs"]) == sorted(
        e["args"]["req"] for e in requests)
    for request in requests:
        req, batch = request["args"]["req"], request["args"]["batch"]
        assert req in batches[batch]["args"]["reqs"]
        for child in ("serve.graph_build", "serve.queue_wait"):
            (span,) = [e for e in by_name[child]
                       if e["args"]["req"] == req]
            assert span["args"]["parent"] == "serve.request"
            assert _inside(span, request), (child, span, request)
        for child in ("serve.collate", "serve.dispatch", "serve.fetch",
                      "serve.unpad", "serve.forward"):
            (span,) = [e for e in by_name[child]
                       if e["args"]["batch"] == batch]
            assert span["args"]["parent"] == "serve.batch"
            assert _inside(span, batches[batch]), (child, span)
            assert _inside(span, request), (child, span, request)
        # the build comes first, then the wait, then the batch
        (build,) = [e for e in by_name["serve.graph_build"]
                    if e["args"]["req"] == req]
        (wait,) = [e for e in by_name["serve.queue_wait"]
                   if e["args"]["req"] == req]
        assert build["ts"] + build["dur"] <= wait["ts"] + 50.0
        assert wait["ts"] + wait["dur"] <= batches[batch]["ts"] + 50.0


def test_engine_latency_runs_from_arrival(served_spans):
    """`engine.stats()` (and so /metrics) counts the graph build: its p50
    is no less than the build's median, and equals the median of the
    `serve.request` spans."""
    events, stats, _ = served_spans
    build = np.median([e["dur"] for e in events
                       if e["name"] == "serve.graph_build"]) * 1e-3
    request = np.median([e["dur"] for e in events
                         if e["name"] == "serve.request"]) * 1e-3
    assert stats["count"] == 12
    assert stats["p50_ms"] >= build
    assert stats["p50_ms"] == pytest.approx(request, abs=0.2)
    waits = [e["dur"] for e in events if e["name"] == "serve.queue_wait"]
    requests = sorted(e["dur"] for e in events
                      if e["name"] == "serve.request")
    assert min(requests) > min(waits)


def test_dispatcher_spans_do_not_overlap(served_spans):
    """`serve.await_request`, `serve.coalesce_wait` and the four steps of
    a batch (`serve.collate`, `.dispatch`, `.fetch`, `.unpad`) are one
    thread's time and never overlap each other, so no share of a window
    made of them can pass 100%. A `serve.batch` runs from its dispatch to
    its results with the next batch's dispatch possibly in between: two
    may overlap, never more than `inflight_depth` are open at once, and
    every child names `serve.batch` as its parent and its `batch`."""
    events, stats, dispatcher = served_spans
    waits = ("serve.await_request", "serve.coalesce_wait")
    steps = ("serve.collate", "serve.dispatch", "serve.fetch",
             "serve.unpad")
    mine = sorted((e for e in events if e["name"] in waits + steps),
                  key=lambda e: e["ts"])
    assert {e["name"] for e in mine} == set(waits + steps)
    batches = [e for e in events if e["name"] == "serve.batch"]
    assert {e["tid"] for e in mine + batches} == {dispatcher}
    for a, b in zip(mine, mine[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1.0, (a, b)
    span = mine[-1]["ts"] + mine[-1]["dur"] - mine[0]["ts"]
    assert sum(e["dur"] for e in mine) <= span + 1.0
    # every coalesce names the request it held back
    reqs = {e["args"]["req"] for e in events if e["name"] == "serve.request"}
    assert {e["args"]["req"] for e in mine
            if e["name"] == "serve.coalesce_wait"} <= reqs
    # at most `inflight_depth` batches between dispatch and results
    assert stats["inflight_depth"] == 2
    edges = sorted([(e["ts"], 1) for e in batches]
                   + [(e["ts"] + e["dur"], -1) for e in batches])
    open_now = most = 0
    for _, step in edges:
        open_now += step
        most = max(most, open_now)
    assert 1 <= most <= stats["inflight_depth"]
    ids = {e["args"]["batch"] for e in batches}
    assert len(ids) == len(batches)
    for child in steps + ("serve.forward",):
        spans = [e for e in events if e["name"] == child]
        assert {e["args"]["batch"] for e in spans} == ids, child
        assert {e["args"]["parent"] for e in spans} == {"serve.batch"}


def _ends_shed_by_admission(eng, structures, park):
    """Two requests fail with their batch, a third is shed at the door
    (full queue) before its graph is built, a fourth after shutdown."""
    from hydragnn_tpu.serving.engine import QueueFullError
    (p0, n0, _), (p1, n1, _), (p2, n2, _) = structures[:3]
    f0 = eng.submit_structure(p0, n0)
    assert park.entered.wait(30)
    f1 = eng.submit_structure(p1, n1)
    with pytest.raises(QueueFullError):
        eng.submit_structure(p2, n2)
    park.release.set()
    for f in (f0, f1):
        assert f.exception(timeout=60) is not None
    eng.shutdown()
    with pytest.raises(RuntimeError, match="shut down"):
        eng.submit_structure(p2, n2)
    return {0: "InjectedFault", 1: "InjectedFault", 2: "QueueFullError",
            3: "RuntimeError"}, {2, 3}


def _ends_behind_the_breaker(eng, structures, park):
    """The first batch fails and trips the breaker: the request queued
    behind it is failed by the dispatcher, the next is shed at the door."""
    from hydragnn_tpu.serving.engine import CircuitOpenError
    (p0, n0, _), (p1, n1, _), (p2, n2, _) = structures[:3]
    f0 = eng.submit_structure(p0, n0)
    assert park.entered.wait(30)
    f1 = eng.submit_structure(p1, n1)
    park.release.set()
    assert f0.exception(timeout=60) is not None
    assert isinstance(f1.exception(timeout=60), CircuitOpenError)
    with pytest.raises(CircuitOpenError):
        eng.submit_structure(p2, n2)
    return {0: "InjectedFault", 1: "CircuitOpenError",
            2: "CircuitOpenError"}, {2}


def _ends_expired_or_invalid(eng, structures, park):
    """A deadline that lapses in the queue, a structure without features
    (raised by `submit_structure`) and a sample of the wrong width
    (resolved by `submit`)."""
    from hydragnn_tpu.serving.engine import DeadlineExceededError
    (p0, n0, _), (p1, n1, _) = structures[:2]
    f0 = eng.submit_structure(p0, n0)
    assert park.entered.wait(30)
    f1 = eng.submit_structure(p1, n1, deadline_ms=1.0)
    time.sleep(0.05)
    with pytest.raises(ValueError, match="node_features"):
        eng.submit_structure(p0)
    wide = copy.copy(eng._proto)
    wide.x = np.zeros((wide.x.shape[0], wide.x.shape[1] + 1), np.float32)
    f3 = eng.submit(wide)
    assert isinstance(f3.exception(timeout=0), ValueError)
    park.release.set()
    assert f0.exception(timeout=60) is not None
    assert isinstance(f1.exception(timeout=60), DeadlineExceededError)
    return {0: "InjectedFault", 1: "DeadlineExceededError",
            2: "ValueError", 3: "ValueError"}, {2, 3}


def _ends_drained_after_the_dispatcher_died(eng, structures, park):
    """The dispatcher dies inside a batch (whose own request is lost with
    it): what is still queued is drained with the fatal error."""
    (p0, n0, _), (p1, n1, _) = structures[:2]
    eng.submit_structure(p0, n0)
    assert park.entered.wait(30)
    f1 = eng.submit_structure(p1, n1)
    park.release.set()
    assert isinstance(f1.exception(timeout=60), MemoryError)
    return {1: "MemoryError"}, set()


@pytest.mark.parametrize("scenario,engine_kw,dies", [
    (_ends_shed_by_admission, {"max_queue": 1, "breaker_threshold": 0},
     None),
    (_ends_behind_the_breaker,
     {"breaker_threshold": 1, "breaker_reset_s": 30.0}, None),
    (_ends_expired_or_invalid, {"breaker_threshold": 0}, None),
    (_ends_drained_after_the_dispatcher_died, {}, MemoryError("died")),
], ids=["admission", "breaker", "deadline_invalid", "drain"])
def test_a_request_that_ends_in_an_error_leaves_its_request_span(
        structured, scenario, engine_kw, dies):
    """Shed load is what an operator traces: whichever way a request ends
    (rejected at the door by `submit_structure` or `submit`, failed with
    its batch, expired, caught behind the open breaker, drained), it
    leaves exactly one `serve.request`, with the error's class. Every
    executed batch is made to fail before it compiles anything."""
    from hydragnn_tpu.telemetry import spans as tspans
    from hydragnn_tpu.utils.faults import (install_fault_plan,
                                           parse_fault_plan)
    structures, samples, cfg, base = structured
    eng = InferenceEngine(base._model, base._variables, base.mcfg,
                          reference_samples=samples, max_batch_size=1,
                          max_wait_ms=0.0, structure_config=cfg,
                          **engine_kw)
    park = _BlockedDispatcher(eng, then=dies)
    rec = tspans.SpanRecorder("test")
    previous = tspans.install_recorder(rec)
    install_fault_plan(parse_fault_plan("serving-dispatch@0,1,2,3"))
    try:
        expected, unbuilt = scenario(eng, structures, park)
    finally:
        park.release.set()
        eng.shutdown()
        install_fault_plan(None)
        tspans.install_recorder(previous)
    events = [e for e in rec.chrome_trace()["traceEvents"]
              if e.get("ph") == "X"]
    ended = {}
    for e in events:
        if e["name"] == "serve.request":
            assert e["args"]["req"] not in ended, "one span a request"
            ended[e["args"]["req"]] = e["args"].get("error")
    assert ended == expected
    # no span names a request that left no `serve.request` (the one the
    # dying dispatcher took with it apart)
    named = {e["args"]["req"] for e in events
             if isinstance(e.get("args", {}).get("req"), int)}
    assert named - set(ended) <= ({0} if dies else set())
    # a request shed at the door never built its graph
    assert not unbuilt & {e["args"]["req"] for e in events
                          if e["name"] == "serve.graph_build"}


def test_structure_counters_health_metrics_registry(structured):
    """Rebuild counts flow everywhere a monitor looks: health(),
    stats(), the /metrics exposition, and the process registry
    (serve.nbr_rebuilds_total + the rebuild-fraction gauge)."""
    from hydragnn_tpu.telemetry.http import engine_prometheus
    from hydragnn_tpu.telemetry.registry import get_registry
    structures, _, _, eng = structured
    rng = np.random.RandomState(2)
    pos, nfm, _ = structures[1]
    pos = pos.copy()
    eng.reset_stats()
    sess = eng.structure_session()
    for _ in range(5):
        pos = pos + rng.randn(*pos.shape) * 0.003
        eng.submit_structure(pos, nfm, session=sess).result(timeout=60)
    h = eng.health()
    assert h["structure_requests"] == 5
    assert h["nbr_updates"] == 5
    assert 1 <= h["nbr_rebuilds"] < 5
    assert 0.0 < h["nbr_rebuild_fraction"] < 1.0
    st = eng.stats()
    assert st["nbr_rebuilds"] == h["nbr_rebuilds"]
    text = engine_prometheus(eng)
    assert "hydragnn_serving_nbr_rebuilds_total" in text
    assert "hydragnn_serving_nbr_rebuild_fraction" in text
    assert "hydragnn_serving_structure_requests_total" in text
    snap = get_registry().snapshot()
    assert "serve.nbr_rebuilds_total" in snap
    assert "serve.nbr_updates_total" in snap
    assert "serve.nbr_rebuild_fraction" in snap


@pytest.mark.slow
def test_ef_forward_serving(served):
    """ef_forward engine: responses become [energy [1], forces [n, 3]]
    with forces = -dE/dpos of the node-energy head — bitwise equal to
    the same computation run directly, and to forward_single on the
    batch's bucket (the same-bucket contract extends to EF mode)."""
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.ops.segment import global_sum_pool
    from hydragnn_tpu.train.train_step import make_forward_fn
    samples = deterministic_graph_dataset(num_configs=12, heads=("node",))
    cfg = make_config("SchNet", heads=("node",))
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    model = create_model(mcfg)
    variables = init_params(model, collate(samples[:4]))
    eng = InferenceEngine(model, variables, mcfg,
                          reference_samples=samples, max_batch_size=4,
                          max_wait_ms=5.0, ef_forward=True)
    try:
        eng.warmup()
        futs = [eng.submit(s) for s in samples[:3]]
        results = [f.result(timeout=120) for f in futs]
        for s, res in zip(samples[:3], results):
            assert res[0].shape == (1,)
            assert res[1].shape == (s.num_nodes, 3)
        # same-bucket single-request parity, EF mode
        ref = eng.forward_single(samples[0], bucket=futs[0].bucket)
        assert all(np.array_equal(a, b) for a, b in zip(results[0], ref))

        # direct reference computation on the padded batch
        bucket = futs[0].bucket
        batch = eng._collate_bucket([samples[0]], bucket)
        forward = make_forward_fn(model, mcfg, "float32")

        def total_energy(p):
            b = batch.replace(pos=p)
            outputs, _ = forward(eng._variables, b, train=False)
            ge = global_sum_pool(outputs[0][:, :1], b.node_graph,
                                 b.num_graphs, b.node_mask)
            return (jnp.sum(jnp.where(b.graph_mask[:, None], ge, 0.0)),
                    ge)

        (_, ge), neg = jax.jit(jax.value_and_grad(
            total_energy, has_aux=True))(batch.pos)
        np.testing.assert_array_equal(results[0][0], np.asarray(ge)[0])
        np.testing.assert_array_equal(
            results[0][1], np.asarray(-neg)[:samples[0].num_nodes])
    finally:
        eng.shutdown()


def test_ef_forward_requires_node_head(served):
    samples, _, mcfg, model, variables = served  # head 0 is graph-level
    with pytest.raises(ValueError, match="node-level energy head"):
        InferenceEngine(model, variables, mcfg,
                        reference_samples=samples, ef_forward=True)


def test_resolve_serving_structure_knobs(monkeypatch):
    cfg = {"Serving": {"structure": True, "md_skin": 0.5}}
    s = resolve_serving(cfg)
    assert s.structure is True and s.md_skin == 0.5
    monkeypatch.setenv("HYDRAGNN_SERVE_STRUCTURE", "0")
    monkeypatch.setenv("HYDRAGNN_MD_SKIN", "0.75")
    s = resolve_serving(cfg)
    assert s.structure is False and s.md_skin == 0.75
    # strict parsing: a typo warns and keeps the config value
    monkeypatch.setenv("HYDRAGNN_SERVE_STRUCTURE", "ture")
    monkeypatch.setenv("HYDRAGNN_MD_SKIN", "wide")
    s = resolve_serving(cfg)
    assert s.structure is True and s.md_skin == 0.5
    # without a config block the typo values fall back to the defaults
    s = resolve_serving(None)
    assert s.structure is False and s.md_skin == 0.3
    monkeypatch.setenv("HYDRAGNN_MD_SKIN", "0.75")
    assert resolve_serving(None).md_skin == 0.75


def test_compile_store_key_folds_the_batch_fields(served, tmp_path):
    """The executable's arguments are the batch's present fields: a store
    written by a tree whose batches lacked one (`edge_slot`, before PR 29)
    must be a miss, not an executable handed one argument too many."""
    from hydragnn_tpu.utils.devices import CompileStore
    samples, _, mcfg, model, variables = served
    store = CompileStore(str(tmp_path / "store"))
    mk = lambda: InferenceEngine(model, variables, mcfg,
                                 reference_samples=samples,
                                 max_batch_size=2, neighbor_format=True,
                                 compile_store=store)
    old, new, again = mk(), mk(), mk()
    try:
        # the older tree: the same engine, its batches without the field
        collate_bucket = old._collate_bucket
        old._collate_bucket = lambda *a: collate_bucket(*a).replace(
            edge_slot=None)
        bucket = new.buckets[0]
        proto = new._collate_bucket([new._proto], bucket)
        assert proto.edge_slot is not None
        assert new._store_key(bucket, proto) != new._store_key(
            bucket, proto.replace(edge_slot=None))
        assert new._store_key(bucket, proto) == again._store_key(
            bucket, again._collate_bucket([again._proto], bucket))
        n = old.warmup()
        assert old.stats()["compile_fresh"] == n > 0
        assert new.warmup() == n
        assert new.stats()["compile_fresh"] == n, "the old entries miss"
        assert again.warmup() == n
        assert again.stats()["compile_fresh"] == 0
        assert again.stats()["compile_store_hits"] == n
        assert new.submit(samples[0]).result(timeout=60) is not None
        assert again.submit(samples[0]).result(timeout=60) is not None
    finally:
        for eng in (old, new, again):
            eng.shutdown()
