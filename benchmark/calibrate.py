"""The one-off measurements a cell's parameters are set from. None of this
runs in a check; a later `benchmark` PR repeats it the same way
(README.md, "Calibration"). Results go to standard output and, with
``--out``, to a JSON file; benchmark/calibration.json keeps what PR 22 read.

    python3 -m benchmark.calibrate knee --workload schnet-s2ef.serve-open \\
        --rates 50,100,150,200,300 --seconds 10          (on the chip)
    python3 -m benchmark.calibrate tolerance --workload <cell>  (on the chip)
    python3 -m benchmark.calibrate memory --workload <cell> --sizes 16,32,64
                                           (here: compiles for a described v5e)
    python3 -m benchmark.calibrate spread runs.jsonl
    python3 -m benchmark.calibrate xplane <trace_dir or .xplane.pb>
    python3 -m benchmark.calibrate record <trace_dir> --out <file.xplane.pb>
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import shutil
import sys
import time
from typing import Dict, List

import numpy as np

from . import cells, say


LATE_LIMIT_MS = 100.0


def _context(workload: str, seed: int, seconds: float, need_chip: bool):
    from . import run, system
    from hydragnn_tpu.utils.profiling import CompileWatch
    cell = cells.load_cell(workload)
    real = run.gate(cell.chips)
    if need_chip and not real:
        say("NOT ON THE CHIP: tiny preset, the numbers below mean nothing")
    system.enable_compile_cache()
    return run.Context(cell=cell, seed=seed, seconds=seconds, tiny=not real,
                       watch=CompileWatch())


def knee(args) -> Dict:
    """Offered rate against completed rate, tails and queue depth, one
    open-loop window per rate on one warmed engine. A rate is SUSTAINED when
    completed/s >= 0.98 x offered/s inside the window, the queue is no
    deeper at the window's end than at its middle (+1), nothing failed, and
    the generator's own p95 lateness stays under LATE_LIMIT_MS:
    `submit_structure` builds the graph on the caller's thread, so a front
    end that cannot keep up holds the SENDERS back instead of growing a
    queue, and a rate the senders cannot send is not offered as stated. The
    knee is the highest sustained rate below the first that is not."""
    from .jobs import serve_open
    from .jobs.serving import Served
    ctx = _context(args.workload, args.seed, args.seconds, True)
    served = Served(ctx)
    rows = []
    try:
        served.warm_up()
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            got = serve_open.drive(
                served, rate, args.seconds, args.seed + i,
                threads=int(ctx.param("sender_threads", 4)),
                timeout_s=float(ctx.param("timeout_s", 10.0)))
            row = {k: got[k] for k in (
                "offered_rps", "completed_rps", "p50_ms", "p90_ms", "p95_ms",
                "late_p95_ms", "queue_depth_mid", "queue_depth_end",
                "failed", "requests")}
            row["rate_rps"] = rate
            row["occupancy"] = got["stats"]["batch_occupancy"]
            row["sustained"] = bool(
                got["completed_rps"] >= 0.98 * got["offered_rps"]
                and got["queue_depth_end"] <= got["queue_depth_mid"] + 1
                and got["failed"] == 0
                and got["late_p95_ms"] <= LATE_LIMIT_MS)
            say(json.dumps(row))
            rows.append(row)
            # the next rate starts on an empty engine
            waited = time.perf_counter()
            while (served.engine.health()["queue_depth"]
                   and time.perf_counter() - waited < 120):
                time.sleep(0.05)
            if len(rows) >= 2 and not (rows[-1]["sustained"]
                                       or rows[-2]["sustained"]):
                break
    finally:
        served.engine.shutdown()
    sustained = []
    for row in rows:
        if not row["sustained"]:
            break
        sustained.append(row["rate_rps"])
    return {"workload": args.workload, "seconds": args.seconds, "rows": rows,
            "knee_rps": max(sustained) if sustained else None}


def tolerance(args) -> Dict:
    """How far the system is from its plain reference on the check
    structures: as it runs (float32, default matmul precision), at highest
    matmul precision, and with the whole computation in bfloat16
    (Architecture.dtype) at highest. `jobs/checks.py` sets HIGHEST_TOL
    between the second and the third reading, AS_RUN_TOL above the first."""
    import jax
    from . import system
    from .jobs import checks
    ctx = _context(args.workload, args.seed, 1.0, True)
    doc = (system.apply_tiny(ctx.cell.config_doc) if ctx.tiny
           else ctx.cell.config_doc)
    pools = system.load_pools(doc)
    chk = system.check_structures(pools[2])
    out = {"workload": args.workload, "structures": len(chk),
           "device": jax.devices()[0].device_kind}
    for dtype, precision in (("float32", None), ("float32", "highest"),
                             ("bfloat16", "highest")):
        d = copy.deepcopy(doc)
        d["hydragnn"]["NeuralNetwork"]["Architecture"]["dtype"] = dtype
        config = system.complete_config(d, pools, 32)
        comp = system.Training(config, pools, 1)
        state = comp.initial_state(args.seed)
        variables = {"params": state.params,
                     "batch_stats": state.batch_stats}
        ref_e, ref_f, _ = system.reference_energy_forces(
            doc, config, variables, chk, train=False)
        with jax.default_matmul_precision(precision):
            _, (energy, forces) = comp.eval_step(
                state, comp.place(comp.collate(chk)))
        e, f = checks.unpad_ef(energy, forces, chk)
        key = f"{dtype} at {precision or 'default'} precision"
        out[key] = {"energy": system.relative_error(e, ref_e),
                    "forces": system.relative_error(f, ref_f)}
        say(f"{key}: {out[key]}")
    return out


def memory(args) -> Dict:
    """Bytes the main program of a cell needs on one described v5e chip,
    by batch size, from the TPU compiler (no chip: section 2 of the
    on-chip-measurement guide). For a train cell the sizes are graphs a
    chip; for a serving cell, max_batch_size (the largest bucket)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from . import system
    cell = cells.load_cell(args.workload)
    doc = cell.config_doc
    pools = system.load_pools(doc, args.cache or system.DATA_CACHE_DIR)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    rows = []
    for size in (int(s) for s in args.sizes.split(",")):
        try:
            if cell.traffic["job"] == "train":
                lowered, shape = system.lower_train_step(
                    doc, pools, size, 1, topo.devices[:1])
            else:
                lowered, shape = system.lower_largest_bucket(
                    doc, pools, cell.traffic, size, one)
            mem = lowered.compile().memory_analysis()
            row = {"size": size, **shape,
                   "temp_gib": mem.temp_size_in_bytes / 2 ** 30,
                   "argument_gib": mem.argument_size_in_bytes / 2 ** 30,
                   "output_gib": mem.output_size_in_bytes / 2 ** 30}
        except jax.errors.JaxRuntimeError as exc:
            row = {"size": size, "refused": str(exc).split("\n")[0][:200]}
        say(json.dumps(row))
        rows.append(row)
    return {"workload": args.workload, "compiled_for": "v5e:2x2, one chip",
            "rows": rows}


def spread(args) -> Dict:
    """Median and relative quartile spread (IQR / median) of each metric
    over the result lines of repeated runs of one cell (one JSON line per
    run, as the benchmark prints them)."""
    with open(args.path) as f:
        lines = [json.loads(line) for line in f if line.startswith("{")]
    out: Dict[str, Dict] = {}
    names = sorted({k for line in lines for k in line["metrics"]})
    for name in names:
        v = np.array([line["metrics"][name]["value"] for line in lines
                      if name in line["metrics"]])
        q1, med, q3 = np.percentile(v, [25, 50, 75])
        out[name] = {"runs": int(v.size), "median": float(med),
                     "spread": float((q3 - q1) / med), "min": float(v.min()),
                     "max": float(v.max())}
    return out


def xplane(args) -> Dict:
    """What a recorded trace holds: planes, lines, event counts and the first
    events — look at one by hand before trusting the reduction — and then
    what ``trace/reduce.py`` makes of it."""
    from .trace import reduce as tr
    path = tr.find_xplane(args.path) if os.path.isdir(args.path) \
        else args.path
    planes = tr.load_xplane(path)
    for plane in planes:
        print("PLANE", plane.name)
        for name, events in plane.lines.items():
            print(f"  LINE {name!r}: {len(events)} events")
            for ev in events[:args.events]:
                print(f"     {ev.name[:90]!r} start {ev.start_ns:.0f} dur "
                      f"{ev.dur_ns:.0f}")
    out = {"path": path}
    if tr.device_planes(planes):
        red = tr.reduce_planes(planes)
        out.update(window_s=red.window_s, busy_s=red.busy_s,
                   busy_s_per_device=red.busy_s_per_device,
                   idle_in_programs_s=red.idle_in_programs_s,
                   programs={k: [len(v), float(np.median(v))]
                             for k, v in red.program_runs.items()},
                   collective_s=red.collective_s,
                   collective_exposed_s=red.collective_exposed_s,
                   top_ops=tr.top(red.op_seconds, 10))
    return out


def record(args) -> Dict:
    """Record a small trace on the chip for the reduction's test: three
    marked steps of a jitted matmul-and-gather chain inside a
    `bench.window`, a pause between them. Copies the ``.xplane.pb`` to
    ``--out``."""
    import jax
    import jax.numpy as jnp
    from . import run
    from .trace import reduce as tr
    from .trace.capture import TraceSession
    if not run.gate(1):
        say("NOT ON THE CHIP: a CPU trace has no device plane")

    @jax.jit
    def small_step(x, idx):
        for _ in range(3):
            x = jnp.tanh(x @ x)[idx]
        return x.sum()

    x = jnp.ones((512, 512), jnp.float32) * 0.01
    idx = jnp.arange(512)[::-1]
    small_step(x, idx).block_until_ready()
    session = TraceSession(args.path)
    session.open()
    for i in range(3):
        with jax.profiler.StepTraceAnnotation("small_step", step_num=i):
            small_step(x, idx).block_until_ready()
        time.sleep(0.002)
    session.close()
    found = tr.find_xplane(args.path)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        shutil.copy(found, args.out)
        args.out = None
    return {"xplane": found, "bytes": os.path.getsize(found)}


def main(argv: List[str] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = parser.add_subparsers(dest="command", required=True)
    for name in ("knee", "tolerance", "memory"):
        p = sub.add_parser(name)
        p.add_argument("--workload", required=True)
        p.add_argument("--seed", type=int, default=0)
        p.add_argument("--out")
    sub.choices["knee"].add_argument("--rates", required=True)
    sub.choices["knee"].add_argument("--seconds", type=float, default=10.0)
    sub.choices["memory"].add_argument("--sizes", required=True)
    sub.choices["memory"].add_argument("--cache")
    for name in ("spread", "xplane", "record"):
        p = sub.add_parser(name)
        p.add_argument("path")
        p.add_argument("--out")
    sub.choices["xplane"].add_argument("--events", type=int, default=3)
    args = parser.parse_args(argv)
    result = globals()[args.command](args)
    print(json.dumps(result, indent=1))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
