#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the
chip. Not a benchmark: it prints set-up seconds and facts, never a rate.

    python chip_smoke.py [--devices N]        (run through the chip tool)

Drives the library's main path once, through the entry points a user
calls, at the full width of one supported model: PNAPlus (PNAConv with the
in-model Bessel radial embedding — plain PNA reads no geometry, so its
forces are identically zero), 3 conv layers, hidden_dim 128, node head
summed to energy, ``compute_grad_energy`` (energy + force double backward),
on seeded periodic Lennard-Jones cells (64 atoms, ~29 neighbours/atom,
batch 32, dense neighbour layout, float32):

  train     run_training: 256 optimizer steps over 16 epochs (validation
            pass, best-val checkpoint, epoch boundary)
  predict   run_prediction through the legacy loop and through the
            serving engine (top-level Serving.enabled) on the same samples
  serve     single-structure energy+force requests through
            InferenceEngine.submit_structure
  sync      one timed step ended by block_until_ready vs one ended by a
            value fetch; first-step loss from the seed
  devices   (--devices N > 1) every device holds a batch shard and live
            memory; sharded predictions equal single-device ones; a
            two-replica fleet warms replica 1 from the compile store

Any failed check raises. The script exits non-zero, printing no result,
unless ``jax.default_backend() == "tpu"``. It touches JAX in this process
only and starts no process that needs the chip. The last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import tempfile
import time

import numpy as np

# the flagship widths (bench.py's PNA workload; BENCH_SWEEP_TPU.json) —
# depth and data may be cut for a smoke, widths may not. 16 epochs x 16
# steps: forces are the slow part of the fit (CPU calibration at these
# sizes, PR 21: r = 0.28 after 48 steps, 0.87 after 256) and a step is
# ~25 ms on the chip, so the extra epochs cost seconds.
FULL = dict(atoms_per_dim=4, cutoff=2.4, n_train=512, n_val=64, n_test=64,
            batch_size=32, hidden_dim=128, num_conv_layers=3, num_epoch=16,
            learning_rate=5e-3, structure_requests=4, timed_steps=5,
            min_force_corr=0.5)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


def smoke_config(sz: dict) -> dict:
    """The config as a user would write it (JSON-able plain dict)."""
    h = sz["hidden_dim"]
    return {
        "Verbosity": {"level": 1},
        "Dataset": {
            "name": "lj_smoke",
            "node_features": {"name": ["species"], "dim": [1],
                              "column_index": [0]},
        },
        "NeuralNetwork": {
            "Architecture": {
                "model_type": "PNAPlus",
                "radius": sz["cutoff"],
                "max_neighbours": 64,
                "num_radial": 6,
                "envelope_exponent": 5,
                "periodic_boundary_conditions": True,
                "hidden_dim": h,
                "num_conv_layers": sz["num_conv_layers"],
                "output_heads": {
                    "node": {"num_headlayers": 2,
                             "dim_headlayers": [h, h], "type": "mlp"},
                },
                "task_weights": [1.0],
            },
            "Variables_of_interest": {
                "input_node_features": [0],
                "output_index": [0],
                "type": ["node"],
                "output_dim": [1],
                "output_names": ["node_energy"],
            },
            "Training": {
                "num_epoch": sz["num_epoch"],
                "batch_size": sz["batch_size"],
                "loss_function_type": "mae",
                "compute_grad_energy": True,
                "Checkpoint": True,
                "Optimizer": {"type": "AdamW",
                              "learning_rate": sz["learning_rate"]},
            },
        },
    }


def make_data(sz: dict):
    from examples.LennardJones.lj_data import generate_lj_dataset
    n = sz["n_train"] + sz["n_val"] + sz["n_test"]
    samples = generate_lj_dataset(
        num_configs=n, atoms_per_dim=sz["atoms_per_dim"],
        cutoff=sz["cutoff"], seed=0)
    a, b = sz["n_train"], sz["n_train"] + sz["n_val"]
    return samples[:a], samples[a:b], samples[b:]


# ------------------------------------------------------------------ phases

def phase_train(config, datasets, num_shards, sz, report):
    from hydragnn_tpu import run_training
    t0 = time.perf_counter()
    state, history, model, completed = run_training(
        config, datasets=datasets, num_shards=num_shards)
    losses = [float(v) for v in history["train_loss"]]
    steps = sz["num_epoch"] * (sz["n_train"] // sz["batch_size"])
    report["train"] = {
        "seconds": round(time.perf_counter() - t0, 2),
        "optimizer_steps": steps, "epochs": len(losses),
        "train_loss": losses,
        "val_loss": [float(v) for v in history["val_loss"]],
        "jit_recompiles_per_epoch": history.get("jit_recompiles"),
    }
    say(f"train: {steps} steps / {len(losses)} epochs, train loss "
        f"{losses[0]:.5f} -> {losses[-1]:.5f}, val "
        f"{history['val_loss'][0]:.5f} -> {history['val_loss'][-1]:.5f}")
    check(len(losses) == sz["num_epoch"] >= 2, "every epoch ran")
    check(all(np.isfinite(losses)) and all(np.isfinite(history["val_loss"])),
          "train/val losses finite")
    check(losses[-1] < losses[0], "training loss fell")
    rec = history.get("jit_recompiles")
    check(rec is not None and rec[0] >= 1,
          "the jit recompile counter counts (utils/profiling)")
    check(all(r == 0 for r in rec[1:]),
          f"zero recompiles after the first epoch (got {rec})")
    return state, model, completed


def phase_predict(completed, datasets, state, model, num_shards, report):
    import copy
    from hydragnn_tpu import run_prediction
    _, p_loop = run_prediction(completed, datasets=datasets, state=state,
                               model=model, num_shards=num_shards,
                               serve=False)
    served = copy.deepcopy(completed)
    served["Serving"] = {"enabled": True}
    _, p_eng = run_prediction(served, datasets=datasets, state=state,
                              model=model, num_shards=num_shards)
    n_atoms = sum(s.num_nodes for s in datasets[2])
    check(p_loop[0].shape == p_eng[0].shape == (n_atoms, 1),
          "prediction shapes [atoms, 1]")
    check(np.isfinite(p_loop[0]).all() and np.isfinite(p_eng[0]).all(),
          "predictions finite")
    bitwise = bool(np.array_equal(p_loop[0], p_eng[0]))
    diff = float(np.max(np.abs(p_loop[0] - p_eng[0])))
    scale = float(np.max(np.abs(p_loop[0])))
    # the CPU contract (tests/test_serving.py) is bitwise on equal bucket
    # shapes; the engine's buckets and the loader's batch differ in padded
    # shape here, so a compiler that tiles by shape may differ in the last
    # bits — then f32 resolution over a 3-layer stack is the bound
    tol = 1e-5 * max(scale, 1.0)
    report["predict"] = {"engine_equals_loop_bitwise": bitwise,
                         "max_abs_diff": diff, "tolerance": tol,
                         "pred_abs_max": scale}
    say(f"predict: engine vs serve=False loop on {len(datasets[2])} "
        f"samples: bitwise={bitwise} max|diff|={diff:.3g} (tol {tol:.3g})")
    check(bitwise or diff <= tol, "engine predictions equal the loop's")
    return p_loop


def phase_serve(completed, datasets, state, model, sz, watch, report):
    """Single-structure energy+force requests: raw positions -> radius
    graph -> bucketed AOT EF forward, one future per structure."""
    from hydragnn_tpu.datasets.async_loader import neighbor_budget
    from hydragnn_tpu.config import build_model_config
    from hydragnn_tpu.serving.engine import InferenceEngine
    mcfg = build_model_config(completed)
    testset = datasets[2]
    variables = {"params": state.params, "batch_stats": state.batch_stats}
    engine = InferenceEngine(
        model, variables, mcfg, reference_samples=testset,
        max_batch_size=sz["structure_requests"], neighbor_format=True,
        neighbor_k=neighbor_budget([s for d in datasets for s in d]),
        structure_config=completed, ef_forward=True)
    try:
        engine.warmup()
        compiled, warm = engine.compile_count, watch.count
        reqs = testset[:sz["structure_requests"]]
        futs = [engine.submit_structure(s.pos, node_features=s.x,
                                        cell=s.cell) for s in reqs]
        results = [f.result(timeout=600) for f in futs]
        check(engine.compile_count == compiled and watch.count == warm,
              "zero compiles after engine warm-up "
              f"(engine {compiled}->{engine.compile_count}, process "
              f"{warm}->{watch.count})")
    finally:
        engine.shutdown()
    f_pred = np.concatenate([r[1] for r in results])
    f_true = np.concatenate([s.forces for s in reqs])
    e_pred = np.asarray([r[0][0] for r in results])
    check(f_pred.shape == f_true.shape and e_pred.shape == (len(reqs),),
          "EF response shapes: energy [1], forces [atoms, 3]")
    check(np.isfinite(f_pred).all() and np.isfinite(e_pred).all(),
          "served energies/forces finite")
    corr = float(np.corrcoef(f_pred.ravel(), f_true.ravel())[0, 1])
    report["serve"] = {"structure_requests": len(reqs),
                       "engine_programs": compiled,
                       "compiles_after_warmup": 0,
                       "force_corr_vs_closed_form_lj": corr,
                       "buckets": sorted({str(f.bucket) for f in futs})}
    say(f"serve: {len(reqs)} structure requests over {compiled} warmed "
        f"programs, 0 compiles after warm-up; force correlation with "
        f"closed-form LJ r={corr:.3f}")
    check(corr >= sz["min_force_corr"],
          f"predicted forces correlate with LJ forces (r={corr:.3f})")


def phase_sync(completed, datasets, model, num_shards, sz, watch, report):
    """block_until_ready must really block: a step timed to it agrees
    with a step timed to a fetched value. The step is built from the
    public factories on a fresh seed-0 state, so its first loss is the
    first-step loss of the run above."""
    import jax
    from hydragnn_tpu.config import build_model_config
    from hydragnn_tpu.graphs.batch import collate
    from hydragnn_tpu.models.create import init_params
    from hydragnn_tpu.preprocess.load_data import create_dataloaders
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.train_step import TrainState, make_train_step
    mcfg = build_model_config(completed)
    tcfg = completed["NeuralNetwork"]["Training"]
    tx = select_optimizer(tcfg)
    loader, _, _ = create_dataloaders(*datasets, sz["batch_size"],
                                      num_shards=num_shards,
                                      neighbor_format=True)
    loader.set_epoch(0)
    kw = dict(loss_name=tcfg["loss_function_type"], compute_grad_energy=True)
    if num_shards > 1:
        from hydragnn_tpu.parallel.mesh import make_mesh, shard_batch
        from hydragnn_tpu.parallel.spmd import make_spmd_train_step
        mesh = make_mesh((("data", num_shards),))
        step = make_spmd_train_step(model, mcfg, tx, mesh, **kw)
        place = lambda b: shard_batch(b, mesh)
    else:
        step = make_train_step(model, mcfg, tx, **kw)
        place = lambda b: jax.tree_util.tree_map(
            lambda a: None if a is None else jax.device_put(a), b)
    init_batch = collate(datasets[0][:loader.graphs_per_shard],
                         n_node=loader.n_node, n_edge=loader.n_edge,
                         n_graph=loader.n_graph, np_out=True)
    state = TrainState.create(init_params(model, init_batch), tx)
    batches = [place(b) for b in loader]
    state, metrics = step(state, batches[0])
    first_loss = float(metrics["loss"])
    state, metrics = step(state, batches[1])
    jax.block_until_ready((state, metrics))
    warm = watch.count
    n = sz["timed_steps"]
    t_block, t_fetch = [], []
    for i in range(n):
        t0 = time.perf_counter()
        state, metrics = step(state, batches[(2 + i) % len(batches)])
        jax.block_until_ready((state, metrics))
        t_block.append(time.perf_counter() - t0)
    for i in range(n):
        t0 = time.perf_counter()
        state, metrics = step(state, batches[(2 + n + i) % len(batches)])
        float(metrics["loss"])
        t_fetch.append(time.perf_counter() - t0)
    check(watch.count == warm, "zero compiles in the timed steps")
    tb, tf = float(np.median(t_block)), float(np.median(t_fetch))
    report["sync"] = {"first_step_loss": first_loss,
                      "step_s_block_until_ready": tb,
                      "step_s_value_fetch": tf,
                      "compiles_in_timed_steps": 0}
    say(f"sync: first-step loss {first_loss:.6f}; one step ended by "
        f"block_until_ready {tb * 1e3:.2f} ms, by a value fetch "
        f"{tf * 1e3:.2f} ms (medians of {n}; an observation, not a "
        "benchmark)")
    check(np.isfinite(first_loss), "first-step loss finite")
    # a block_until_ready that returned early would time the enqueue —
    # a small fraction of a step — so a factor of two tells them apart
    check(0.5 <= tb / tf <= 2.0,
          "block_until_ready blocks: a step timed to it agrees with a "
          f"step timed to a fetched value ({tb:.5f}s vs {tf:.5f}s)")


def phase_devices(completed, datasets, state, model, p_loop, num_shards,
                  sz, report):
    """Multi-chip facts: shard residency, live memory on every device,
    sharded == single-device predictions, compile-store-warmed fleet."""
    import copy
    import jax
    from hydragnn_tpu import run_prediction
    from hydragnn_tpu.parallel.mesh import make_mesh, shard_batch
    from hydragnn_tpu.preprocess.load_data import create_dataloaders
    devices = jax.devices()[:num_shards]
    mesh = make_mesh((("data", num_shards),))
    loader, _, _ = create_dataloaders(*datasets, sz["batch_size"],
                                      num_shards=num_shards,
                                      neighbor_format=True)
    batch = shard_batch(next(iter(loader)), mesh)
    shards = {sh.device: sh.data.shape for sh in batch.x.addressable_shards}
    # memory_stats() is the accelerator runtime's; None off the chip
    in_use = {str(d): int((d.memory_stats() or {}).get("bytes_in_use", 0))
              for d in devices}
    say(f"devices: batch.x {batch.x.shape} sharded as {shards}; "
        f"bytes_in_use {in_use}")
    check(set(shards) == set(devices)
          and all(s[0] == 1 for s in shards.values()),
          "every device holds one shard of the batch")
    check(all(v > 0 for v in in_use.values())
          or jax.default_backend() != "tpu",
          "every device reports live memory")

    # same weights, eval-mode BatchNorm: sharding must not change outputs
    _, p_one = run_prediction(completed, datasets=datasets, state=state,
                              model=model, num_shards=1, serve=False)
    diff = float(np.max(np.abs(p_one[0] - p_loop[0])))
    tol = 1e-5 * max(float(np.max(np.abs(p_one[0]))), 1.0)
    say(f"devices: {num_shards}-shard vs 1-shard predictions "
        f"max|diff|={diff:.3g} (tol {tol:.3g})")
    check(diff <= tol, "sharded predictions equal single-device ones")

    # two-replica fleet over one persistent compile store: replica 1 must
    # warm from the store (fresh: 0) and the fleet must answer
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    logger = logging.getLogger("hydragnn_tpu")
    logger.addHandler(handler)
    old_level = logger.level
    logger.setLevel(logging.INFO)
    fleet = copy.deepcopy(completed)
    with tempfile.TemporaryDirectory(prefix="smoke_store_") as store:
        fleet["Serving"] = {"enabled": True,
                            "fleet": {"replicas": 2,
                                      "compile_store": store}}
        try:
            _, p_fleet = run_prediction(fleet, datasets=datasets,
                                        state=state, model=model,
                                        num_shards=1)
        finally:
            logger.removeHandler(handler)
            logger.setLevel(old_level)
        stored = len(os.listdir(store))
    warm = [r.args[0] for r in records
            if str(r.msg).startswith("serving warm-up")]
    check(len(warm) == 1 and len(warm[0]) == 2,
          f"one fleet warm-up report for two replicas (got {warm})")
    reports = warm[0]
    say(f"devices: fleet warm-up {reports}; {stored} programs in the "
        "compile store")
    check(reports[0]["fresh"] == reports[0]["compiled"] == stored > 0,
          "replica 0 compiled the ladder fresh and stored it")
    check(reports[1]["fresh"] == 0
          and reports[1]["store_hits"] == reports[1]["compiled"] == stored,
          "replica 1 warmed entirely from the compile store (fresh: 0)")
    diff = float(np.max(np.abs(p_fleet[0] - p_one[0])))
    check(diff <= tol, "fleet predictions equal single-device ones "
          f"(max|diff|={diff:.3g})")
    report["devices"] = {
        "shards": {str(d): list(s) for d, s in shards.items()},
        "bytes_in_use": in_use, "fleet_warmup": reports,
        "fleet_replica_devices": sorted({r["devices"] for r in reports}),
        "compile_store_programs": stored}


# -------------------------------------------------------------------- main

def run(sz: dict, num_shards: int) -> dict:
    """All phases at sizes `sz`; raises on the first failed check."""
    from hydragnn_tpu.utils.devices import enable_compile_cache
    from hydragnn_tpu.utils.profiling import CompileWatch
    report = {"compile_cache_dir": enable_compile_cache(), "sizes": sz}
    say(f"compile cache: {report['compile_cache_dir']}")
    with CompileWatch() as watch:
        datasets = make_data(sz)
        deg = np.concatenate([np.bincount(s.receivers,
                                          minlength=s.num_nodes)
                              for s in datasets[0][:64]])
        say(f"data: {sum(map(len, datasets))} LJ cells x "
            f"{datasets[0][0].num_nodes} atoms, mean degree "
            f"{deg.mean():.1f} (max {deg.max()})")
        config = smoke_config(sz)
        state, model, completed = phase_train(config, datasets, num_shards,
                                              sz, report)
        p_loop = phase_predict(completed, datasets, state, model,
                               num_shards, report)
        phase_serve(completed, datasets, state, model, sz, watch, report)
        phase_sync(completed, datasets, model, num_shards, sz, watch,
                   report)
        main_path = (watch.count, watch.seconds, watch.cache_hits)
        if num_shards > 1:
            phase_devices(completed, datasets, state, model, p_loop,
                          num_shards, sz, report)
        report["compile"] = {
            "main_path_programs": main_path[0],
            "main_path_seconds": round(main_path[1], 2),
            "main_path_persistent_cache_hits": main_path[2],
            "all_programs": watch.count,
            "all_seconds": round(watch.seconds, 2),
            "compiles_after_warmup": 0}
    say(f"compile: {main_path[0]} programs in {main_path[1]:.1f}s on the "
        f"main path ({main_path[2]} from the persistent cache); "
        f"{watch.count} programs in {watch.seconds:.1f}s overall; "
        "0 after warm-up")
    return report


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--devices", type=int, default=1,
                   help="data-parallel shards = chips to use (default 1)")
    args = p.parse_args(argv)

    import jax
    backend = jax.default_backend()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found backend {backend!r} "
              f"({device}). Run it through the chip tool.", file=sys.stderr)
        return 2
    if not 1 <= args.devices <= len(devs):
        print(f"chip_smoke: --devices {args.devices} but JAX sees "
              f"{len(devs)}", file=sys.stderr)
        return 2
    import hydragnn_tpu  # noqa: F401 — fail before the first output line
    # when the script was copied out of its checkout
    say(f"platform={device['platform']} device_kind={device['kind']!r} "
        f"count={device['count']} (using {args.devices}); "
        f"jax {jax.__version__}")
    report = run(dict(FULL), args.devices)
    report["device"] = device
    print("SMOKE_REPORT " + json.dumps(report, default=str), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
