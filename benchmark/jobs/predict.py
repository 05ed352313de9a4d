"""Offline prediction through the serving engine: submit every structure,
wait for all of them, again, until the window's time has passed — what
``run_prediction`` does with ``Serving.enabled``, with energies AND forces
(``ef_forward``). `infer_graphs_per_s` is structures delivered over the
wall time of the rounds that finished."""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

from .. import say, system
from .serving import Served

TIMEOUT_S = 600.0


def run(ctx) -> Dict:
    served = Served(ctx)
    engine = served.engine
    try:
        served.warm_up()
        order = system.seeded_order(len(served.structures), ctx.seed)
        batch = [served.structures[i] for i in order]
        engine.reset_stats()
        ctx.open_window()
        t0 = time.perf_counter()
        delivered = failed = rounds = 0
        while True:
            futures = [engine.submit(s) for s in batch]
            answers = []
            for f in futures:
                try:
                    answers.append(f.result(timeout=TIMEOUT_S))
                except Exception:  # noqa: BLE001 — a failed structure
                    answers.append(None)
                    failed += 1
            delivered += len(batch)
            rounds += 1
            t1 = time.perf_counter()
            if t1 - t0 >= ctx.window_seconds():
                break
        ctx.close_window()
        seconds = t1 - t0
        stats = engine.stats()
        atoms = rounds * sum(s.num_nodes for s in batch)
        edges = rounds * sum(s.num_edges for s in batch)
        say(f"{rounds} rounds of {len(batch)} structures in {seconds:.3f} "
            f"s: {atoms / seconds:.1f} real atoms/s, {edges / seconds:.1f} "
            f"real edges/s; {stats['batches']} batches, occupancy "
            f"{stats['batch_occupancy']:.3f}")
        # a batched answer equals the single-request forward on the bucket
        # the batch ran on, bit for bit (the engine's float32 contract)
        stride = int(ctx.param("bitwise_stride", 32))
        same = all(
            answers[i] is not None and all(
                np.array_equal(a, b) for a, b in zip(
                    answers[i], engine.forward_single(
                        batch[i], bucket=futures[i].bucket)))
            for i in range(0, len(batch), stride))
        results = served.judge()
        results.flag("batched_equals_single_bitwise", bool(same))
        results.flag("every_structure_answered", failed == 0)
    finally:
        engine.shutdown()
    return {
        "end_to_end": {"infer_graphs_per_s": (delivered - failed) / seconds},
        "attempted": delivered, "failed": failed, "checks": results,
        "counters": {"batch_occupancy": stats["batch_occupancy"],
                     "pad_node_share": stats["padding_frac_nodes"],
                     "batches": stats["batches"]},
        "work": {"graphs": delivered, "atoms": atoms, "edges": edges},
        "arch": served.arch}
