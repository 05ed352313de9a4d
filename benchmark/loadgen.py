"""Open-loop load on a due-time schedule, and the latency arithmetic.

Arrivals are drawn once from the seed, a fixed number of them for a rate
and a window; each request has a time at which it is DUE, and its latency runs from that time to the delivery of its result,
whenever the generator managed to send it. A server that stalls therefore
shows the wait it imposes on the requests behind the stall, and a starved
generator shows up in ``late`` (send time minus due time) and not as a
fast server. (``bench.py``'s serve mode slept the gap after each submit and
read latency from the engine's own clock, which starts after the host has
built the graph: PERF.md, verdicts.)
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Sequence

import numpy as np


def arrivals(rate_rps: float, seconds: float, seed: int,
             burst: Dict = None) -> np.ndarray:
    """Due times in [0, seconds), in order: the arrivals of a Poisson
    process of `rate_rps`, GIVEN that round(rate x seconds) of them fall in
    the window — which makes them sorted uniform draws. Every run then
    offers the same number of requests, and gaps are still exponential.
    With ``burst = {"every_s", "length_s", "factor"}`` the rate is `factor`
    times higher for `length_s` out of every `every_s`, at the same count."""
    rng = np.random.RandomState(seed)
    spread = np.sort(rng.uniform(size=int(round(rate_rps * seconds))))
    if not burst:
        return spread * seconds
    every, on, factor = (float(burst[k]) for k in (
        "every_s", "length_s", "factor"))
    # cumulative intensity, piecewise linear in time; invert it
    grid = np.arange(0.0, seconds, every)
    knots = np.minimum(np.sort(np.concatenate([grid, grid + on, [seconds]])),
                       seconds)
    rates = np.where((knots[:-1] % every) < on - 1e-12, factor, 1.0)
    cum = np.concatenate([[0.0], np.cumsum(rates * np.diff(knots))])
    return np.interp(spread * cum[-1], cum, knots)


def percentile_ms(latencies_s: Sequence[float], q: float) -> float:
    """`utils/profiling.latency_percentiles`'s arithmetic: numpy's linear
    interpolation on seconds, reported in milliseconds."""
    return float(np.percentile(np.asarray(latencies_s, np.float64), q) * 1e3)


class OpenLoop:
    """Sends `payloads[k]` through `submit` at `due[k]` seconds after
    `start()`, from `threads` sender threads (request k goes to thread
    k mod threads), and keeps for every request when it was due, sent and
    delivered. `submit(payload)` returns a `concurrent.futures.Future` or
    raises (a refusal)."""

    def __init__(self, submit: Callable, due: np.ndarray,
                 payloads: Sequence, threads: int = 4):
        self.submit = submit
        self.due = np.asarray(due, np.float64)
        self.payloads = payloads
        self.threads = max(1, int(threads))
        n = len(self.due)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.failed = np.zeros(n, bool)
        self.results: List = [None] * n
        self._futures: List = [None] * n
        self._workers: List[threading.Thread] = []
        self.t0 = 0.0

    def _finish(self, k: int, future) -> None:
        delivered = time.perf_counter() - self.t0
        try:
            self.results[k] = future.result()
        except Exception:  # noqa: BLE001 — any failure is a failed request
            self.failed[k] = True
        self.done[k] = delivered  # last: `join` reads it as "all is set"

    def _sender(self, lane: int) -> None:
        for k in range(lane, len(self.due), self.threads):
            wait = self.t0 + self.due[k] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.sent[k] = time.perf_counter() - self.t0
            try:
                future = self.submit(self.payloads[k])
            except Exception:  # noqa: BLE001 — a refused request
                self.failed[k] = True
                continue
            self._futures[k] = future
            future.add_done_callback(
                lambda f, k=k: self._finish(k, f))

    def start(self) -> None:
        self.t0 = time.perf_counter()
        self._workers = [threading.Thread(target=self._sender, args=(i,),
                                          name=f"loadgen-{i}", daemon=True)
                         for i in range(self.threads)]
        for w in self._workers:
            w.start()

    def join(self, timeout_s: float) -> None:
        """Wait for the senders, then for every result, until `timeout_s`
        after the last request was due. What is still out then has failed."""
        deadline = self.t0 + (self.due[-1] if len(self.due) else 0.0) \
            + timeout_s
        for w in self._workers:
            w.join(max(deadline - time.perf_counter(), 0.0))
        for k, future in enumerate(self._futures):
            if future is None:
                continue
            while not np.isfinite(self.done[k]) and not self.failed[k]:
                if time.perf_counter() > deadline:
                    self.failed[k] = True
                    break
                time.sleep(0.0005)
        self.failed |= ~np.isfinite(self.done)

    def latencies_s(self, timeout_s: float) -> np.ndarray:
        """Delivered minus due; a failed, refused or unanswered request
        counts as `timeout_s`."""
        lat = self.done - self.due
        return np.where(self.failed | ~np.isfinite(lat), timeout_s, lat)

    def late_s(self) -> np.ndarray:
        return self.sent[np.isfinite(self.sent)] - self.due[
            np.isfinite(self.sent)]
