"""Single-structure energy+force requests from independent users: an open
loop. Raw structures go through ``submit_structure`` (host radius graph ->
bucketed forward), no session, on a seeded schedule of due times
(``loadgen.arrivals``) at the rate the traffic mix states, from a few
sender threads. Latency runs from the time a request was DUE to the
delivery of its result; a failed, refused or unanswered request counts as
the timeout.

The rate is fixed in the mix. ``benchmark/calibrate.py knee`` finds the
highest rate the system sustains. Every mix is judged on
`infer_graphs_per_s`, the requests completed inside the window per second:
below the knee that is the offered rate for as long as the front end keeps
up, above it the front end's capacity. The latencies (p50, p95) are
per-layer metrics: on a one-chip machine, whose host shares its CPU cores,
the median of this host-bound path spread by 6-8% between runs of the same
code, more than a bound may cover (PERF.md, PR 22).
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

from .. import loadgen, say
from . import checks
from .serving import Served


def drive(served: Served, rate_rps: float, seconds: float, seed: int,
          threads: int = 4, timeout_s: float = 10.0, burst: Dict = None,
          on_start=None, on_end=None) -> Dict:
    """One open-loop window against a warmed engine. The check structures
    ride along at seeded places, twice each, so that served answers can be
    held to the reference."""
    engine = served.engine
    due = loadgen.arrivals(rate_rps, seconds, seed, burst)
    rng = np.random.RandomState(seed)
    # a fixed amount of work: every run serves the SAME requests, the head
    # of the structures (again from the start when it runs out) with the
    # check structures in the last places, in an order drawn from the seed.
    # A subset drawn anew in every run moves the median size, and the
    # median latency with it (PERF.md, PR 22)
    riders = min(2 * len(served.check), len(due))
    work = [served.structures[i % len(served.structures)]
            for i in range(len(due) - riders)]
    work += [served.check[slot % len(served.check)]
             for slot in range(riders)]
    order = rng.permutation(len(due))
    payloads = [work[i] for i in order]
    # places[slot]: where the rider of that slot went
    places = np.argsort(order)[len(due) - riders:]

    def submit(s):
        return engine.submit_structure(s.pos, node_features=s.x, cell=s.cell)

    loop = loadgen.OpenLoop(submit, due, payloads, threads=threads)
    engine.reset_stats()
    if on_start:
        on_start()
    loop.start()
    time.sleep(max(loop.t0 + seconds / 2 - time.perf_counter(), 0))
    depth_mid = engine.health()["queue_depth"]
    time.sleep(max(loop.t0 + seconds - time.perf_counter(), 0))
    depth_end = engine.health()["queue_depth"]
    in_window = int(np.sum(loop.done <= seconds))
    loop.join(timeout_s)
    if on_end:
        on_end()
    lat = loop.latencies_s(timeout_s)
    late = loop.late_s()
    answered = [p for p, bad in zip(payloads, loop.failed) if not bad]
    return {
        "loop": loop, "places": places, "stats": engine.stats(),
        "requests": len(due), "failed": int(loop.failed.sum()),
        "offered_rps": len(due) / seconds,
        "completed_rps": in_window / seconds,
        "p50_ms": loadgen.percentile_ms(lat, 50),
        "p90_ms": loadgen.percentile_ms(lat, 90),
        "p95_ms": loadgen.percentile_ms(lat, 95),
        "mean_ms": float(np.mean(lat) * 1e3),
        "late_p95_ms": loadgen.percentile_ms(late, 95),
        "queue_depth_mid": depth_mid, "queue_depth_end": depth_end,
        # a pool structure carries the graph `submit_structure` builds from
        # its positions (same search, cutoff and cap), so its edges count
        "atoms": sum(p.num_nodes for p in answered),
        "edges": sum(p.num_edges for p in answered)}


def served_against_reference(served: Served, got: Dict,
                             out: checks.Compared) -> None:
    loop, places = got["loop"], got["places"]
    ok = [k for k in places if not loop.failed[k]]
    ref_e, ref_f, struct = served.reference()
    starts = np.concatenate([[0], np.cumsum(np.bincount(
        struct["node_graph"]))])
    which = [int(np.nonzero(places == k)[0][0]) % len(served.check)
             for k in ok]
    out.arrays(
        "served_as_run", np.array([loop.results[k][0][0] for k in ok]),
        np.concatenate([loop.results[k][1] for k in ok]), ref_e[which],
        np.concatenate([ref_f[starts[g]:starts[g + 1]] for g in which]),
        checks.AS_RUN_TOL, f"{len(ok)} requests served inside the window")
    out.flag("every_check_request_answered", len(ok) == len(places))


def run(ctx) -> Dict:
    served = Served(ctx)
    try:
        served.warm_up()
        seconds = ctx.window_seconds()
        got = drive(served, float(ctx.param("rate_rps")), seconds, ctx.seed,
                    threads=int(ctx.param("sender_threads", 4)),
                    timeout_s=float(ctx.param("timeout_s", 10.0)),
                    burst=ctx.param("burst"), on_start=ctx.open_window,
                    on_end=ctx.close_window)
        stats = got["stats"]
        say(f"{got['requests']} requests due in {seconds:.1f} s "
            f"({got['offered_rps']:.1f}/s offered, "
            f"{got['completed_rps']:.1f}/s completed inside the window), "
            f"{got['failed']} failed; queue depth {got['queue_depth_mid']} "
            f"at the middle, {got['queue_depth_end']} at the end; "
            f"{stats['batches']} batches, occupancy "
            f"{stats['batch_occupancy']:.3f}; generator late p95 "
            f"{got['late_p95_ms']:.3f} ms; latency from due time: mean "
            f"{got['mean_ms']:.3f}, p50 {got['p50_ms']:.3f}, p90 "
            f"{got['p90_ms']:.3f}, p95 {got['p95_ms']:.3f} ms")
        results = served.judge()
        served_against_reference(served, got, results)
    finally:
        served.engine.shutdown()
    return {
        "end_to_end": {"serve_p50_ms": got["p50_ms"],
                       "serve_p90_ms": got["p90_ms"],
                       "serve_p95_ms": got["p95_ms"],
                       "serve_mean_ms": got["mean_ms"],
                       "infer_graphs_per_s": got["completed_rps"]},
        "attempted": got["requests"], "failed": got["failed"],
        "checks": results,
        "counters": {
            "batch_occupancy": stats["batch_occupancy"],
            "pad_node_share": stats["padding_frac_nodes"],
            "batches": stats["batches"],
            "loadgen_late_p95_ms": got["late_p95_ms"],
            "latency_p50_ms": got["p50_ms"],
            "latency_p95_ms": got["p95_ms"],
            "queue_depth_mid": got["queue_depth_mid"],
            "queue_depth_end": got["queue_depth_end"],
            "offered_rps": got["offered_rps"],
            "completed_rps": got["completed_rps"]},
        "work": {"graphs": got["requests"] - got["failed"],
                 "atoms": got["atoms"], "edges": got["edges"]},
        "arch": served.arch}
