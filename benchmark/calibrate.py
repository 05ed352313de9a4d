"""The one-off measurements a cell's parameters are set from. None of this
runs in a check; a later `benchmark` PR repeats it the same way
(README.md, "Calibration"). Results go to standard output and, with
``--out``, to a JSON file; benchmark/calibration.json keeps what PR 22 read.

    python3 -m benchmark.calibrate knee --workload schnet-s2ef.serve-open \\
        --rates 50,100,150,200,300 --seconds 10          (on the chip)
    python3 -m benchmark.calibrate tolerance --workload <cell> --seeds 64 \\
        [--also 2071849904 --diagnose 2071849904]        (on the chip)
    python3 -m benchmark.calibrate loss_fell --workload <train cell> \\
        --seeds 16 [--fault state_unchanged]             (on the chip)
    python3 -m benchmark.calibrate verdict benchmark/calibration.json
                      (here: the kept sweeps against the present tolerances)
    python3 -m benchmark.calibrate memory --workload <cell> --sizes 16,32,64
                                           (here: compiles for a described v5e)
    python3 -m benchmark.calibrate spread runs.jsonl
    python3 -m benchmark.calibrate xplane <trace_dir or .xplane.pb>
    python3 -m benchmark.calibrate record <trace_dir> --out <file.xplane.pb>
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import dataclasses
import json
import os
import re
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


def _seeds(args) -> List[int]:
    """`--seeds` large seeds drawn from `--seed` (the driver's are large),
    after the ones named with `--also`."""
    drawn = np.random.RandomState(args.seed).randint(
        1, 2 ** 31 - 1, size=args.seeds)
    also = [int(s) for s in args.also.split(",") if s]
    return also + [int(s) for s in drawn][:max(args.seeds - len(also), 0)]


def _distribution(rows: List[Dict], keys: Dict[str, str] = None
                  ) -> Dict[str, Dict]:
    """min, median, p90, max and the seeds of both ends of every reading
    of `rows` ({"seed": n, name: value, ...}); with `keys`, also the entry
    of the tolerance table that judges each (`judged_by`; null: the number
    is printed and not judged)."""
    out = {}
    for name in [k for k in rows[0] if k != "seed"]:
        v = np.array([r[name] for r in rows], np.float64)
        out[name] = {
            "seeds": len(rows), "min": float(v.min()),
            "median": float(np.median(v)),
            "p90": float(np.percentile(v, 90)), "max": float(v.max()),
            "seed_of_max": rows[int(v.argmax())]["seed"],
            "seed_of_min": rows[int(v.argmin())]["seed"]}
        if keys is not None:
            out[name]["judged_by"] = keys.get(name)
    return out


SEEDS_FLOOR = 12    # seeds a kept `loss_fell` sweep holds, unless its file
#                     names fewer and says why (`seeds_floor`)
SEPARATES = 3.0     # a control's narrowest reading over the sound program's
#                     widest, from which a number is held against it


def _verdict(sound: Dict, controls: Dict[str, Dict]) -> Dict:
    """The rule of jobs/checks.py applied to the distributions of a sweep
    and the PRESENT `HIGHEST_TOL`. Every judged number (`judged_by`): its
    limit at least 3 x the sound program's widest reading, and below the
    narrowest reading of every control that separates from the sound
    program by `SEPARATES` or more. Every control: some judged number
    whose narrowest reading over the control's seeds is at least 3 x its
    limit (so at every seed that number fails by 3 x or more)."""
    from .jobs import checks
    limit = {name: checks.HIGHEST_TOL[dist["judged_by"]]
             for name, dist in sound.items() if dist.get("judged_by")}
    out = {"limits": {}, "controls": {}}
    for name, lim in limit.items():
        widest = max(sound[name]["max"], 1e-300)
        held = {c: d[name]["min"] for c, d in controls.items()
                if name in d and d[name]["min"] >= SEPARATES * widest}
        out["limits"][name] = {
            "limit": lim, "sound_max": sound[name]["max"],
            "room_above_sound": lim / widest,
            "held_against": held,
            "room_below_controls": min(
                [v / lim for v in held.values()], default=None)}
    for control, dists in controls.items():
        caught_by = max((n for n in limit if n in dists),
                        key=lambda n: dists[n]["min"] / limit[n])
        out["controls"][control] = {
            "seeds": dists[caught_by]["seeds"], "caught_by": caught_by,
            "narrowest_reading": dists[caught_by]["min"],
            "seed_of_narrowest": dists[caught_by]["seed_of_min"],
            "room_below_control": dists[caught_by]["min"]
            / limit[caught_by]}
    out["holds"] = bool(
        all(v["room_above_sound"] >= 3
            and (v["room_below_controls"] or 2) > 1
            for v in out["limits"].values())
        and all(v["room_below_control"] >= 3
                for v in out["controls"].values()))
    return out


def verdict(args) -> Dict:
    """The sweeps kept in calibration.json (or one sweep's `--out` file)
    judged again by the present `HIGHEST_TOL`: what a `benchmark` PR runs,
    here, before and after it moves a tolerance."""
    with open(args.path) as f:
        doc = json.load(f)
    if "loss_fell" in doc:      # a file of `loss_fell` sweeps
        return {cell: _loss_fell_verdict(
            kept, int(cells.load_cell(cell).traffic["trace_steps"]))
            for cell, kept in doc["loss_fell"]["cells"].items()}
    sweeps = doc.get("tolerance", {}).get("cells") or {
        doc["workload"]: doc}

    def measured(dists: Dict) -> Dict:
        # a reading reconstructed from a sweep's log is kept for the
        # reader and sets no limit
        return {name: dist for name, dist in dists.items()
                if not dist.get("derived_from_log")}
    out = {}
    for cell, sweep in sweeps.items():
        # the widest sound reading and the narrowest of each control are
        # those of every sweep kept for the cell
        sound: Dict[str, Dict] = {}
        controls: Dict[str, Dict] = {}
        for one in [sweep] + sweep.get("more_sweeps", []):
            for name, dist in measured(one["at_highest"]).items():
                kept = sound.setdefault(name, dict(dist))
                kept["max"] = max(kept["max"], dist["max"])
            for control, dists in one.get("controls_at_highest",
                                          {}).items():
                for name, dist in measured(dists).items():
                    kept = controls.setdefault(control, {}).setdefault(
                        name, dict(dist))
                    if dist["min"] < kept["min"]:
                        kept.update(min=dist["min"],
                                    seed_of_min=dist["seed_of_min"])
                    kept["seeds"] = max(kept["seeds"], dist["seeds"])
        out[cell] = _verdict(sound, controls)
    return out


READING = re.compile(r"ratio(_one_state)?(?:_at_(\d+)_steps)?")


def _loss_fell_verdict(kept: Dict, trace_steps: int) -> Dict:
    """The present `checks.LOSS_FELL` and the mix's present `trace_steps`
    against the `loss_fell` sweeps kept for one train cell: `sound` (a
    list of outputs of `calibrate loss_fell`), `controls` (the same with
    each of `LOSS_FELL_FAULTS` planted) and, where there are any,
    `one_state`: sweeps of the number the check judged first, one state's
    reading, which bounds the judged one from above seed by seed. The
    lower reading is the widest of the sound program in EVERY reading of
    the judged number from `trace_steps` steps on, at whatever count a
    sweep's window closed; the one-state sweeps stand in for it only where
    a cell has no other (`stands_in`), and their widest is reported beside
    it. The upper is the narrowest of the controls. The limit holds when
    the upper reading separates (3 x the lower or more) and the limit lies
    1.5 x or more above the lower and under the upper."""
    from .jobs import checks

    def widest(sweeps: List[Dict], one_state: bool) -> float:
        found = [dist["max"] for sweep in sweeps
                 for name, dist in sweep["distribution"].items()
                 for m in [READING.fullmatch(name)]
                 if m and bool(m.group(1)) == one_state
                 and (m.group(2) is None or int(m.group(2)) >= trace_steps)]
        return max(found) if found else None
    earlier = kept.get("one_state", [])
    sound = kept["sound"] or earlier
    lower = widest(sound, False)
    controls = {fault: min(dist["min"]
                           for name, dist in sweep["distribution"].items()
                           if READING.fullmatch(name))
                for fault, sweep in kept["controls"].items()}
    upper = min(controls.values())
    limit = checks.LOSS_FELL
    at_trace = f"ratio_at_{trace_steps}_steps"
    return {"limit": limit, "trace_steps": trace_steps, "sound_max": lower,
            "stands_in": not kept["sound"],
            "one_state_max": max(
                [v for v in (widest(kept["sound"], True),
                             widest(earlier, False)) if v is not None],
                default=None),
            "controls": controls,
            # a sound sweep without the reading where a traced run closes
            # is a KeyError, not a pass
            "seeds": sum(sweep["distribution"][at_trace]["seeds"]
                         for sweep in sound),
            "seeds_floor": kept.get("seeds_floor", SEEDS_FLOOR),
            "room_above_sound": limit / lower,
            "room_below_controls": upper / limit,
            "holds": bool(upper >= SEPARATES * lower
                          and 1.5 * lower <= limit < upper)}


def _readings(compared, highest_only: bool = False) -> Dict[str, float]:
    """Every reading of one judged run, judged or recorded; `highest_only`
    leaves the as-run ones out."""
    out = {k: v[0] for k, v in compared.numbers.items()}
    out.update(compared.recorded)
    return {k: v for k, v in out.items()
            if "at_highest" in k or not highest_only}


def _out_of_time(args) -> bool:
    """`--max-seconds` of the process have passed: a chip call's time limit
    is near. A train cell's sweep then judges no further seed beyond those
    that run the controls, starts no control that has to compile, and its
    distributions say how many seeds they hold (a four-chip call that is
    cut leaves nothing and costs the same)."""
    from . import START
    return bool(args.max_seconds
                and time.perf_counter() - START > args.max_seconds)


def _with_dtype(doc: Dict, dtype: str) -> Dict:
    doc = copy.deepcopy(doc)
    doc["hydragnn"]["NeuralNetwork"]["Architecture"]["dtype"] = dtype
    return doc


def tolerance(args) -> Dict:
    """The distribution over seeds of every number `correct` judges, from
    the jobs' own `judge` (a train cell: the train-mode step loss and the
    eval step, on as many shards as the cell runs; a serving cell: the
    engine's answers), as run and at highest matmul precision; beside it
    the negative controls of `jobs/checks.CONTROLS` at the first
    `--control-seeds` seeds. One process: weights are arguments of the
    compiled programs, so each seed costs an initialisation and no
    compile. `jobs/checks.HIGHEST_TOL` is set from what this prints
    (README.md, "Tolerance of `correct`")."""
    import jax
    ctx = _context(args.workload, args.seed, 1.0, True)
    seeds = _seeds(args)
    job = ctx.cell.traffic["job"]
    sweep = _tolerance_train if job == "train" else _tolerance_serving
    sound, controls, keys, extra = sweep(ctx, args, seeds)
    dist = _distribution([{k: v for k, v in r.items()
                           if k == "seed" or "at_highest" in k}
                          for r in sound], keys)
    controls = {k: _distribution(v) for k, v in controls.items()}
    return {"workload": args.workload, "device": jax.devices()[0].device_kind,
            "devices": ctx.cell.chips, **extra,
            "at_highest": dist,
            "as_run": _distribution(
                [{k: v for k, v in r.items() if "at_highest" not in k}
                 for r in sound]),
            "controls_at_highest": controls,
            "verdict": _verdict(dist, controls)}


def _tolerance_train(ctx, args, seeds):
    import jax
    from . import system
    from .jobs import train
    doc = (system.apply_tiny(ctx.cell.config_doc) if ctx.tiny
           else ctx.cell.config_doc)
    pools = system.load_pools(doc)
    chips = ctx.cell.chips
    per_chip = int(ctx.param("graphs_per_chip"))
    config = system.complete_config(doc, pools, per_chip * chips,
                                    training=ctx.param("training"))
    comp = system.Training(config, pools, chips)
    chk = train.Checks(comp, doc, config)
    extra = {"structures": len(chk.chk), "per_shard": chk.per_shard}
    diagnosed = [int(s) for s in args.diagnose.split(",") if s]
    if diagnosed:
        one = comp if chips == 1 else system.Training(
            system.complete_config(doc, pools, per_chip,
                                   training=ctx.param("training")),
            pools, 1)
        extra["diagnosis"] = [
            _diagnose(train.Checks(comp, doc, config, per_shard=n), one,
                      comp.initial_state(seed), seed)
            for seed in diagnosed
            for n in sorted({2, chk.per_shard} if chips > 1
                            else {chk.per_shard})]
    sound = []
    controls = {"edge_mask": [], "bfloat16": []}
    for i, seed in enumerate(seeds):
        if i >= max(args.control_seeds, 1) and _out_of_time(args):
            break
        say(f"--- seed {seed} ({i + 1} of {len(seeds)})")
        chk.as_run(comp.initial_state(seed), warm=False)
        judged = chk.judge()
        sound.append({"seed": seed, **_readings(judged)})
        if i < args.control_seeds:
            controls["edge_mask"].append(
                {"seed": seed,
                 **_readings(chk.control("edge_mask"), True)})
    if args.control_seeds and _out_of_time(args):
        say("out of time: the bfloat16 control is not read")
    elif args.control_seeds:
        low = _with_dtype(doc, "bfloat16")
        comp16 = system.Training(
            system.complete_config(low, pools, per_chip * chips,
                                   training=ctx.param("training")),
            pools, chips)
        # the reference stays the float32 one: `doc`, `config`
        chk16 = train.Checks(comp16, doc, config)
        for seed in seeds[:args.control_seeds]:
            say(f"--- bfloat16 control, seed {seed}")
            # its first step too at highest: only the at-highest numbers
            # are read, and two large programs fewer are compiled
            with jax.default_matmul_precision("highest"):
                chk16.as_run(comp16.initial_state(seed), warm=False)
            controls["bfloat16"].append(
                {"seed": seed, **_readings(chk16.judge(), True)})
    return (sound, {k: v for k, v in controls.items() if v}, judged.keys,
            extra)


def _diagnose(chk, one, state, seed: int) -> Dict:
    """Who is off, shard by shard (ISSUE 25, step 1): the data-parallel
    step's composed losses at highest precision; each shard's own losses
    from the ONE-device step of the program on that shard's structures (a
    second path of the program); the plain reference in float32 on the
    chip and in float64 on the CPU backend."""
    import jax
    from .jobs import checks, train
    chk.as_run(state, warm=False)
    first, evaluated = chk.at_highest()
    ref32, ref64 = chk.reference(), chk.reference(float64=True)
    state, stepped = jax.device_get((state, chk.stepped))
    rows = []
    for i, members in enumerate(chk.shards):
        batch = one.place(one.collate([chk.chk[g] for g in members]))
        with jax.default_matmul_precision("highest"):
            _, m = one.train_step(train.copy_state(state), batch)
            ev = one.eval_step(stepped, batch)
        ev = ev[0] if isinstance(ev, tuple) else ev
        row = {"shard": i, "atoms": sum(chk.chk[g].num_nodes
                                        for g in members)}
        for mode, got, key32 in (("train", m, "terms"),
                                 ("eval", ev, "eval_terms")):
            r32, r64 = ref32[key32][i], ref64[key32][i]
            for key in ("energy_loss", "force_loss"):
                row[f"{mode}_{key}"] = {
                    "system": float(got[key]), "ref32": r32[key],
                    "ref64": r64[key],
                    "system_minus_ref64": float(got[key]) - r64[key],
                    "ref32_minus_ref64": r32[key] - r64[key]}
        say(f"diagnosis seed {seed}, {chk.per_shard} a shard: "
            + json.dumps(row))
        rows.append(row)
    composed = {
        "train_loss": {"system_spmd": first["loss"],
                       "ref32": ref32["train"]["loss"],
                       "ref64": ref64["train"]["loss"],
                       "one_device_steps_composed": float(np.mean(
                           [r["train_energy_loss"]["system"]
                            + r["train_force_loss"]["system"]
                            for r in rows]))}}
    if chk.comp.num_shards > 1:
        for key in ("energy_loss", "force_loss"):
            composed[f"eval_{key}"] = {
                "system_spmd": float(evaluated[key]),
                "ref32": ref32["eval"][key], "ref64": ref64["eval"][key]}
    for name, v in composed.items():
        v["system_vs_ref64"] = abs(v["system_spmd"] - v["ref64"]) / abs(
            v["ref64"])
        v["ref32_vs_ref64"] = abs(v["ref32"] - v["ref64"]) / abs(v["ref64"])
        v["system_vs_ref32"] = abs(v["system_spmd"] - v["ref32"]) / abs(
            v["ref32"])
        say(f"diagnosis seed {seed}, {chk.per_shard} a shard, composed "
            f"{name}: " + json.dumps(v))
    return {"seed": seed, "per_shard": chk.per_shard, "shards": rows,
            "composed": composed}


def _tolerance_serving(ctx, args, seeds):
    import jax
    from hydragnn_tpu.config import build_model_config
    from hydragnn_tpu.models.create import create_model
    from . import system
    from .jobs import checks
    from .jobs.serving import Served
    served = Served(ctx)
    check = served.check
    sound = []
    controls = {"edge_mask": [], "bfloat16": []}
    starts = np.concatenate([[0], np.cumsum([s.num_nodes for s in check])])
    exact = served.highest_engine()
    init = system.initialiser(served.model, check)
    config16 = system.complete_config(
        _with_dtype(served.doc, "bfloat16"), served.pools,
        served.config["NeuralNetwork"]["Training"]["batch_size"],
        serving=ctx.param("serving"))
    mcfg16 = build_model_config(config16)
    exact16 = (served.highest_engine(config16, create_model(mcfg16), mcfg16)
               if args.control_seeds else None)
    try:
        for i, seed in enumerate(seeds):
            say(f"--- seed {seed} ({i + 1} of {len(seeds)})")
            variables = init(jax.random.PRNGKey(seed))
            served.variables, served._reference = variables, None
            judged = checks.Compared(say)
            for label, engine, tol in (
                    ("engine_at_highest", exact, checks.HIGHEST_TOL),
                    ("engine_as_run", served.engine, checks.AS_RUN_TOL)):
                engine.swap_variables(variables, str(seed))
                served.compare(judged, label, tol,
                               engine.predict(check, timeout=600))
            sound.append({"seed": seed, **_readings(judged)})
            if i >= args.control_seeds:
                continue
            e, f, _ = system.reference_energy_forces(
                served.doc, served.config, variables,
                [system.drop_edges(s) for s in check], train=False)
            exact16.swap_variables(variables, str(seed))
            for fault, got in (
                    ("edge_mask", [((e[g],), f[starts[g]:starts[g + 1]])
                                   for g in range(len(check))]),
                    ("bfloat16", exact16.predict(check, timeout=600))):
                out = checks.Compared(say)
                served.compare(out, "engine_at_highest", checks.HIGHEST_TOL,
                               got)
                controls[fault].append({"seed": seed, **_readings(out)})
    finally:
        for engine in (exact, exact16, served.engine):
            if engine is not None:
                engine.shutdown()
    return (sound, {k: v for k, v in controls.items() if v}, judged.keys,
            {"structures": len(check)})


# the two controls of `loss_fell` (jobs/checks.LOSS_FELL): each leaves
# the weights where they were, and each has to FAIL the check
LOSS_FELL_FAULTS = ("state_unchanged", "zero_learning_rate")


@contextlib.contextmanager
def planted(fault: str, cell):
    """The train job of `cell` with `fault` planted under it, the way the
    tests break a step (tests/benchmark/test_bench_checks.py).
    `state_unchanged`: every composition's train step computes its
    metrics and hands back the state it was given. `zero_learning_rate`:
    the configuration's optimizer at learning rate 0, through the mix's
    `training` keys: only BatchNorm's running statistics move."""
    from . import system
    from .jobs import train
    built, traffic = system.Training.__init__, cell.traffic
    if fault == "state_unchanged":
        def broken(self, *args, **kwargs):
            built(self, *args, **kwargs)
            step = self.train_step

            def train_step(state, batch):
                _, metrics = step(train.copy_state(state), batch)
                return state, metrics
            self.train_step = train_step
        system.Training.__init__ = broken
    elif fault == "zero_learning_rate":
        optimizer = cell.config_doc["hydragnn"]["NeuralNetwork"][
            "Training"]["Optimizer"]

        def stilled(block: Dict) -> Dict:
            return dict(block, training=dict(
                block.get("training") or {},
                Optimizer=dict(optimizer, learning_rate=0.0)))
        # the rehearsal's block too: a `training` of its own would
        # override the mix's
        cell.traffic = stilled(dict(traffic,
                                    tiny=stilled(traffic.get("tiny", {}))))
    elif fault:
        raise ValueError(f"no fault {fault!r}: {LOSS_FELL_FAULTS}")
    try:
        yield
    finally:
        system.Training.__init__, cell.traffic = built, traffic


_COMPOSED: Dict = {}    # `swept`: completed configuration -> composition


@contextlib.contextmanager
def swept(counts: List[int], seen: Dict):
    """The train job as a sweep over seeds runs it, by the same kind of
    wrapping as `planted`; the timed job itself knows nothing of it.
    (1) Inside the window, the loss of the trainer's first batches is also
    read with the state that each of `counts` steps returned, and each of
    the `LATE_STEPS` steps after it, as a run reads them past its close:
    into `seen`, {steps: loss}; `seen["step"]` is the job's `WindowedStep`.
    A reading waits for the device, so such a window is no measurement.
    (2) The comparisons with the plain reference are left out (`judge`
    gives an empty record): they read the same at every count of steps,
    and at `dimenetpp-s2ef.train` they are 190 s of the 270 s a seed cost.
    (3) Every composition of one configuration takes the model, the
    optimizer and the jitted steps of the first one this process built
    (`_COMPOSED`): they hang on the configuration and only the loaders'
    order on the seed, so a sweep traces and compiles once, not once a
    seed, and the controls after it not at all."""
    from . import system
    from .jobs import checks, train
    stepped, judge = train.WindowedStep.__call__, train.Checks.judge
    built, first = system.Training.__init__, _COMPOSED
    wanted = {n + k for n in counts for k in range(train.LATE_STEPS + 1)}

    def call(self, state, batch):
        state, metrics = stepped(self, state, batch)
        seen["step"] = self
        # (not before the trainer has fed all the batches a run reads)
        if (self.t1 is None and self.work["steps"] in wanted
                and self.calls >= self.keep):
            seen[self.work["steps"]] = self.read(state, self.first_batches)
        return state, metrics

    def rebuilt(self, config, *args, **kwargs):
        built(self, config, *args, **kwargs)
        key = json.dumps(config, sort_keys=True,
                         default=lambda a: a.tolist())
        if key in first:
            for name in ("model", "tx", "mesh", "train_step", "eval_step",
                         "place"):
                setattr(self, name, getattr(first[key], name))
            self.__dict__["_initialiser"] = first[key]._initialiser
        first.setdefault(key, self)
    train.WindowedStep.__call__ = call
    train.Checks.judge = lambda self: checks.Compared(say)
    system.Training.__init__ = rebuilt
    try:
        yield
    finally:
        train.WindowedStep.__call__, train.Checks.judge = stepped, judge
        system.Training.__init__ = built


def loss_fell(args) -> Dict:
    """`loss_fell` over seeds: the train job itself (`swept`), once a seed
    in one process, untraced at `--seconds`. A row holds the ratio the run
    was judged on (`ratio`: the least of the state that closed the window
    and the `LATE_STEPS` after it), the same read inside the window from
    the mix's `trace_steps` steps on, where a traced run closes
    (`ratio_at_<n>_steps`), and from each count of `--probe-at`; beside
    each the one state's own reading (`ratio_one_state...`), which is what
    the least of five is steadier than. With `--fault` the job runs with
    that control planted and has to read over `checks.LOSS_FELL` at every
    seed. `jobs/checks.LOSS_FELL` is set from what this prints (README.md,
    "Tolerance of `correct`")."""
    import jax
    from .jobs import checks, train
    ctx = _context(args.workload, args.seed, args.seconds, True)
    steps = int(ctx.param("trace_steps"))
    counts = sorted({steps} | {int(n) for n in args.probe_at.split(",")
                               if n})
    rows, seen = [], {}
    with swept(counts, seen), planted(args.fault, ctx.cell):
        for i, seed in enumerate(_seeds(args)):
            if i and _out_of_time(args):
                break
            say(f"--- seed {seed} ({i + 1} of {args.seeds})")
            seen.clear()
            got = train.run(dataclasses.replace(ctx, seed=seed))
            judged, after = got["checks"], seen["step"].after
            ratio = judged.numbers["loss_fell"][0]
            fresh = float(np.min(after)) / ratio
            row = {"seed": seed, "steps": got["work"]["steps"],
                   "ratio": ratio, "ratio_one_state": after[0] / fresh}
            late = range(train.LATE_STEPS + 1)
            for n in counts:
                if all(n + k in seen for k in late):
                    row[f"ratio_at_{n}_steps"] = min(
                        seen[n + k] for k in late) / fresh
                    row[f"ratio_one_state_at_{n}_steps"] = seen[n] / fresh
            row["other_checks_failed"] = sorted(
                k for k, ok in judged.ok.items()
                if not ok and k != "loss_fell")
            say(json.dumps(row))
            rows.append(row)
    # what a run is judged on: the ratio where an untraced window closes
    # and where a traced one does
    ratios = [r[k] for r in rows
              for k in ("ratio", f"ratio_at_{steps}_steps") if k in r]
    return {"workload": args.workload, "fault": args.fault or None,
            "device": jax.devices()[0].device_kind,
            "devices": ctx.cell.chips, "seconds": args.seconds,
            "trace_steps": steps, "probe_at": counts,
            "late_steps": train.LATE_STEPS,
            "batches": len(seen["step"].first_batches),
            "limit": checks.LOSS_FELL, "rows": rows,
            "steps": [r["steps"] for r in rows],
            # of the readings that every seed's window reached
            "distribution": _distribution(
                [{k: r[k] for k in rows[0] if k == "seed" or "ratio" in k
                  and all(k in other for other in rows)} for r in rows]),
            "other_checks_failed": {str(r["seed"]): r["other_checks_failed"]
                                    for r in rows
                                    if r["other_checks_failed"]},
            "every_run_as_it_should_be": bool(
                min(ratios) > checks.LOSS_FELL if args.fault
                else max(ratios) <= checks.LOSS_FELL)}


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
    for name in ("knee", "tolerance", "loss_fell", "memory"):
        p = sub.add_parser(name)
        p.add_argument("--workload", required=True)
        p.add_argument("--seed", type=int, default=0)
        p.add_argument("--out")
    tol = sub.choices["tolerance"]
    for p in (tol, sub.choices["loss_fell"]):
        p.add_argument("--seeds", type=int, default=64,
                       help="how many seeds to judge (drawn from --seed)")
        p.add_argument("--also", default="",
                       help="seeds to judge first, by name, "
                            "comma-separated")
        p.add_argument("--max-seconds", type=float, default=0.0,
                       help="seconds of the process after which no "
                            "further seed is judged (0: all of them)")
    tol.add_argument("--control-seeds", type=int, default=8,
                     help="how many of the seeds also run the controls")
    tol.add_argument("--diagnose", default="",
                     help="seeds to take apart shard by shard, with the "
                          "reference in float64 on the CPU backend")
    sub.choices["knee"].add_argument("--rates", required=True)
    sub.choices["knee"].add_argument("--seconds", type=float, default=10.0)
    sub.choices["loss_fell"].add_argument(
        "--seconds", type=float, default=20.0,
        help="the untraced window (BENCHMARK.json's run_seconds)")
    sub.choices["loss_fell"].add_argument(
        "--probe-at", default="",
        help="further counts of the window's steps to read the probe "
             "after, comma-separated (a candidate for `trace_steps`)")
    sub.choices["loss_fell"].add_argument(
        "--fault", default="", choices=("",) + LOSS_FELL_FAULTS,
        help="plant this control under the job: it has to fail")
    sub.choices["memory"].add_argument("--sizes", required=True)
    sub.choices["memory"].add_argument("--cache")
    for name in ("spread", "xplane", "record", "verdict"):
        p = sub.add_parser(name)
        p.add_argument("path")
        p.add_argument("--out")
    sub.choices["xplane"].add_argument("--events", type=int, default=3)
    args = parser.parse_args(argv)
    if getattr(args, "diagnose", "") and os.environ.get(
            "JAX_PLATFORMS") == "tpu":
        # the float64 reference runs on the CPU backend beside the chip
        os.environ["JAX_PLATFORMS"] = "tpu,cpu"
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
