"""The open-loop generator: reproducible from the seed, and it times from
the moment a request was DUE."""
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from benchmark import loadgen


def test_arrivals_repeat_with_the_seed_and_offer_a_fixed_count():
    a = loadgen.arrivals(200.0, 20.0, seed=3)
    b = loadgen.arrivals(200.0, 20.0, seed=3)
    c = loadgen.arrivals(200.0, 20.0, seed=4)
    assert np.array_equal(a, b) and not np.array_equal(a[:50], c[:50])
    assert len(a) == len(c) == 4000, "the same work in every run"
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 20.0
    gaps = np.diff(a)
    # exponential gaps: the coefficient of variation of a Poisson process
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1
    assert abs(np.sum(a < 10.0) - 2000) < 4 * np.sqrt(1000)


def test_bursts_keep_the_count_and_raise_the_rate_inside_a_burst():
    burst = {"every_s": 5.0, "length_s": 1.0, "factor": 4.0}
    due = loadgen.arrivals(200.0, 40.0, seed=1, burst=burst)
    assert len(due) == 8000 and due[-1] < 40.0 and np.all(np.diff(due) >= 0)
    inside = np.sum((due % 5.0) < 1.0) / (40.0 / 5.0 * 1.0)
    outside = np.sum((due % 5.0) >= 1.0) / (40.0 / 5.0 * 4.0)
    assert inside / outside == pytest.approx(4.0, rel=0.1)


def test_percentile_is_numpys_linear_interpolation_in_ms():
    lat = [0.001, 0.002, 0.003, 0.004]
    assert loadgen.percentile_ms(lat, 50) == pytest.approx(2.5)
    assert loadgen.percentile_ms(lat, 95) == pytest.approx(3.85)


class FakeEngine:
    """Answers requests one at a time, `service_s` each, on one thread;
    `stall_s` holds the first answer back."""

    def __init__(self, service_s, stall_s=0.0, refuse=()):
        self.service_s, self.stall_s, self.refuse = service_s, stall_s, refuse
        self.queue, self.lock = [], threading.Condition()
        self.closed = False
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def submit(self, payload):
        if payload in self.refuse:
            raise RuntimeError("refused")
        future = Future()
        with self.lock:
            self.queue.append((payload, future))
            self.lock.notify()
        return future

    def _serve(self):
        first = True
        while True:
            with self.lock:
                while not self.queue and not self.closed:
                    self.lock.wait()
                if self.closed and not self.queue:
                    return
                payload, future = self.queue.pop(0)
            time.sleep(self.service_s + (self.stall_s if first else 0.0))
            first = False
            if payload == "poison":
                future.set_exception(ValueError("poison"))
            else:
                future.set_result(payload)

    def close(self):
        with self.lock:
            self.closed = True
            self.lock.notify()
        self.thread.join(5)
        assert not self.thread.is_alive()


def test_a_stalled_engine_shows_the_queueing_delay_from_due_time():
    """Ten requests due 10 ms apart, a server that stalls 300 ms on the
    first: every request waits behind the stall, and its latency counts
    from when it was DUE — near 300 ms for all of them — although each was
    sent on time and served in 1 ms once its turn came."""
    engine = FakeEngine(service_s=0.001, stall_s=0.3)
    due = np.arange(10) * 0.010
    loop = loadgen.OpenLoop(engine.submit, due, list(range(10)), threads=2)
    loop.start()
    loop.join(timeout_s=5.0)
    engine.close()
    lat = loop.latencies_s(timeout_s=5.0)
    assert not loop.failed.any() and loop.results == list(range(10))
    assert np.all(loop.late_s() < 0.05), "the generator itself was on time"
    assert lat[0] == pytest.approx(0.301, abs=0.05)
    assert np.all(lat > 0.2), "the stall delays every request behind it"
    assert np.all(np.diff(loop.done) > 0)
    # what an engine-side clock that starts at submit would also see, but
    # a generator that sleeps AFTER each submit would not have sent by then
    assert np.all(loop.sent - due < 0.05)


def test_refused_failed_and_unanswered_requests_count_as_the_timeout():
    engine = FakeEngine(service_s=0.001, refuse=("refuse",))
    payloads = ["ok", "refuse", "poison", "ok"]
    loop = loadgen.OpenLoop(engine.submit, np.arange(4) * 0.005, payloads,
                            threads=1)
    loop.start()
    loop.join(timeout_s=2.0)
    engine.close()
    assert loop.failed.tolist() == [False, True, True, False]
    lat = loop.latencies_s(timeout_s=2.0)
    assert lat[1] == lat[2] == 2.0 and lat[0] < 0.5 and lat[3] < 0.5

    slow = FakeEngine(service_s=0.5)
    loop = loadgen.OpenLoop(slow.submit, np.array([0.0, 0.0]), ["a", "b"],
                            threads=1)
    loop.start()
    loop.join(timeout_s=0.7)   # the second answer needs a full second
    assert loop.failed.tolist() == [False, True]
    assert loop.latencies_s(0.7)[1] == 0.7
    slow.close()
