"""Top-level inference driver.

reference: hydragnn/run_prediction.py:34-107 — load model from a run dir,
evaluate the test set, optionally denormalize outputs.
"""
from __future__ import annotations

from typing import Optional, Tuple

import os
import jax
import numpy as np

from .config import build_model_config, get_log_name_config, load_config, update_config
from .graphs.batch import collate
from .models.create import create_model, init_params
from .postprocess.postprocess import output_denormalize
from .preprocess.load_data import create_dataloaders
from .train.loss import head_targets
from .train.optimizer import select_optimizer
from .train.train_step import TrainState, make_eval_step
from .utils.checkpoint import load_existing_model


def run_prediction(config_or_path, datasets: Optional[Tuple] = None,
                   state: Optional[TrainState] = None, model=None,
                   num_shards: Optional[int] = None,
                   serve: Optional[bool] = None):
    """Returns (true_values, predicted_values) per head
    (reference: run_prediction.py:48-107, test() gathering at
    train_validate_test.py:709-737).

    `num_shards > 1` evaluates the test set SPMD over a data mesh (the
    reference predicts under the same DDP layout as training); default is
    single-program.

    `serve` (default: the `Serving` config block / HYDRAGNN_SERVE env,
    serving/config.py) routes the prediction loop through the batched
    inference engine (serving/engine.py) — request micro-batching over a
    bucketed compile cache — instead of the legacy per-loader-batch eval
    loop. Outputs are bitwise-identical between the two paths on the same
    bucket shapes (tests/test_serving.py)."""
    config = load_config(config_or_path)
    from .utils.devices import enable_compile_cache
    enable_compile_cache()
    if datasets is None:
        from .run_training import _load_datasets_from_config
        datasets = _load_datasets_from_config(config)
    trainset, valset, testset = (list(d) for d in datasets)
    config = update_config(config, trainset, valset, testset)
    mcfg = build_model_config(config)

    train_cfg = config["NeuralNetwork"]["Training"]
    batch_size = int(train_cfg["batch_size"])
    from .parallel.mesh import resolve_num_shards
    num_shards = resolve_num_shards(num_shards or 1, batch_size)
    from .utils.envflags import env_flag
    arch = config["NeuralNetwork"]["Architecture"]
    nbr_fmt = env_flag("HYDRAGNN_NEIGHBOR_FORMAT",
                       bool(arch.get("neighbor_format", True)))
    from .graphs.triplets import maybe_triplet_transform
    batch_transform = maybe_triplet_transform(
        mcfg.model_type, trainset + valset + testset,
        max(batch_size // max(num_shards, 1), 1), nbr_fmt)
    _, _, test_loader = create_dataloaders(trainset, valset, testset,
                                           batch_size,
                                           num_shards=num_shards,
                                           batch_transform=batch_transform,
                                           neighbor_format=nbr_fmt)
    if model is None:
        model = create_model(mcfg)
    if state is None:
        init_batch = collate(
            testset[:min(len(testset), test_loader.graphs_per_shard)],
            n_node=test_loader.n_node, n_edge=test_loader.n_edge,
            n_graph=test_loader.n_graph, np_out=True)
        if batch_transform is not None:
            init_batch = batch_transform(init_batch)
        if test_loader.neighbor_k is not None:
            from .graphs.batch import with_neighbor_format
            init_batch = with_neighbor_format(init_batch,
                                              k=test_loader.neighbor_k)
        variables = init_params(model, init_batch)
        tx = select_optimizer(train_cfg)
        template = TrainState.create(variables, tx)
        log_name = get_log_name_config(config)
        state = load_existing_model(template, log_name)
        if state is None:
            raise FileNotFoundError(
                f"no checkpoint found for run '{log_name}' — train first "
                "or point Training.log_name at an existing run")

    from .serving.config import resolve_serving
    serving = resolve_serving(config)
    use_engine = serving.enabled if serve is None else bool(serve)
    if use_engine and batch_transform is not None:
        # the host-built triplet list (DimeNet WITHOUT the dense neighbour
        # table) needs per-batch index tables the engine does not rebuild
        # per bucket; with the table on there is no transform and the
        # engine serves DimeNet like any stack (docs/serving.md)
        import logging
        logging.getLogger("hydragnn_tpu").warning(
            "serving engine does not support the host-built triplet list "
            "(DimeNet with neighbor_format off); falling back to the "
            "legacy prediction loop")
        use_engine = False

    if use_engine:
        trues, preds = _predict_with_engine(
            model, state, mcfg, testset, serving, num_shards,
            nbr_fmt, test_loader.neighbor_k, config)
    else:
        trues, preds = _predict_with_loader(
            model, state, mcfg, test_loader, train_cfg, num_shards)

    voi = config["NeuralNetwork"]["Variables_of_interest"]
    if voi.get("denormalize_output") and "y_minmax" in voi:
        trues, preds = output_denormalize(voi["y_minmax"], trues, preds)

    # per-head true/pred pickle dump (reference: HYDRAGNN_DUMP_TESTDATA,
    # train_validate_test.py:640-703 writes rank-local test-data pickles)
    from .utils.envflags import env_flag
    if env_flag("HYDRAGNN_DUMP_TESTDATA"):
        import pickle
        log_name = get_log_name_config(config)
        dump_dir = os.path.join("./logs", log_name)
        os.makedirs(dump_dir, exist_ok=True)
        names = voi.get("output_names",
                        [f"head_{i}" for i in range(len(trues))])
        with open(os.path.join(dump_dir, "test_data.pk"), "wb") as f:
            pickle.dump({name: {"true": t, "pred": p}
                         for name, t, p in zip(names, trues, preds)}, f)
    return trues, preds


def _predict_with_loader(model, state, mcfg, test_loader, train_cfg,
                         num_shards):
    """Legacy per-loader-batch eval loop (one padded forward per batch of
    `batch_size` test samples)."""
    if num_shards > 1:
        from .parallel.mesh import make_mesh, shard_batch
        from .parallel.spmd import make_spmd_predict_step
        mesh = make_mesh((("data", num_shards),))
        predict = make_spmd_predict_step(model, mesh, mcfg)

        def step(state, batch):
            outputs = predict(state, shard_batch(batch, mesh))
            # device-major flatten: [D, X, ...] batch <-> [D*X, ...] outputs
            flat = jax.tree_util.tree_map(
                lambda a: None if a is None else np.asarray(a).reshape(
                    (-1,) + a.shape[2:]), batch)
            return outputs, flat
    else:
        eval_step = make_eval_step(model, mcfg,
                                   train_cfg.get("loss_function_type",
                                                 "mse"))

        def step(state, batch):
            _, outputs = eval_step(state, batch)
            return outputs, batch

    trues = [[] for _ in mcfg.heads]
    preds = [[] for _ in mcfg.heads]
    for batch in test_loader:
        outputs, flat = step(state, batch)
        targets = head_targets(mcfg, flat)
        gm = np.asarray(flat.graph_mask)
        nm = np.asarray(flat.node_mask)
        for ih, head in enumerate(mcfg.heads):
            mask = gm if head.head_type == "graph" else nm
            trues[ih].append(np.asarray(targets[ih])[mask])
            preds[ih].append(np.asarray(outputs[ih])[mask])
    return ([np.concatenate(t) for t in trues],
            [np.concatenate(p) for p in preds])


def _sample_targets(mcfg, sample):
    """Per-head targets straight off one GraphSample — the sample-level
    mirror of train.loss.head_targets (same offsets, same error
    contract), rows shaped exactly as the masked batch gathering yields
    them (graph head: [1, D]; node head: [num_nodes, D])."""
    targets = []
    for head in mcfg.heads:
        if head.head_type == "graph":
            y = sample.y_graph
            end = head.offset + head.output_dim
            if y is None or y.shape[0] < end:
                have = 0 if y is None else y.shape[0]
                raise ValueError(
                    f"graph head needs packed label columns "
                    f"[{head.offset}:{end}) but the sample carries {have}")
            targets.append(np.asarray(y[head.offset:end],
                                      np.float32)[None, :])
        else:
            y = sample.y_node
            end = head.offset + head.output_dim
            if y is None or y.shape[1] < end:
                have = 0 if y is None else y.shape[1]
                raise ValueError(
                    f"node head needs packed label columns "
                    f"[{head.offset}:{end}) but the sample carries {have}")
            targets.append(np.asarray(y[:, head.offset:end], np.float32))
    return targets


def _predict_with_engine(model, state, mcfg, testset, serving, num_shards,
                         neighbor_format, neighbor_k, config=None):
    """Engine path: every test sample becomes one serving request; the
    background dispatcher coalesces them into bucketed padded batches
    (serving/engine.py) — the same numerics as the legacy loop, measured
    3x+ faster per request on CPU (BENCH_SERVE).

    With `Serving.fleet.replicas` > 1 (HYDRAGNN_FLEET_REPLICAS) the
    requests route through a ReplicaRouter of that many engines instead
    — per-replica breaker isolation, re-dispatch off dead replicas, and
    a shared persistent compile store when `Serving.fleet.compile_store`
    names one (docs/serving.md "Fleet"). The results are identical
    either way: every replica serves the same checkpoint on the same
    bucket ladder."""
    from .serving.config import resolve_fleet
    from .serving.engine import InferenceEngine
    variables = {"params": state.params, "batch_stats": state.batch_stats}
    fleet = resolve_fleet(config)
    compile_store = None
    if fleet.compile_store:
        from .utils.devices import CompileStore
        compile_store = CompileStore(fleet.compile_store)

    quant_calibration = None
    if serving.precision == "int8":
        # calibrate ONCE and share the scales across every replica:
        # identical scales -> identical traced programs -> identical
        # compile-store keys, so a fleet of int8 replicas warms from one
        # store entry per bucket (quant/calibrate.py; docs/serving.md)
        from .quant import calibrate
        quant_calibration = calibrate(
            model, variables, mcfg, testset,
            num_samples=serving.quant_calib_samples,
            batch_transform=None)

    def make_engine(replica_idx=0):
        return InferenceEngine(
            model, variables, mcfg, reference_samples=testset,
            max_batch_size=serving.max_batch_size,
            max_wait_ms=serving.max_wait_ms,
            num_buckets=serving.num_buckets,
            bucket_multiple=serving.bucket_multiple,
            num_shards=num_shards if num_shards and num_shards > 1 else 1,
            neighbor_format=neighbor_format, neighbor_k=neighbor_k,
            # serve-side precision override (Serving.precision /
            # HYDRAGNN_SERVE_PRECISION, docs/mixed_precision.md);
            # None inherits the train-side policy
            compute_dtype=serving.precision,
            quant_calibration=quant_calibration,
            quant_calib_samples=serving.quant_calib_samples,
            # the failure-semantics knobs (max_queue/deadline_ms/breaker_*)
            # deliberately stay at their permissive defaults here: this is
            # the OFFLINE batch-predict path, which submits the whole
            # testset at once — an online admission bound or deadline tuned
            # for a deployment would fast-fail/expire a perfectly good
            # prediction run (docs/fault_tolerance.md). They apply to
            # engines serving live traffic via the InferenceEngine API.
            breaker_threshold=0,
            # Serving.structure / HYDRAGNN_SERVE_STRUCTURE: hand the engine
            # the full config so raw-structure clients (submit_structure /
            # trajectory sessions, docs/serving.md) can use this engine
            # too; the offline testset prediction below is unaffected
            structure_config=config if serving.structure else None,
            md_skin=serving.md_skin,
            compile_store=compile_store,
            # the hot-swap version tag names the restored checkpoint step
            model_version=f"step_{int(state.step)}")

    if fleet.replicas > 1:
        from .serving.fleet import ReplicaRouter, TierPolicy
        tier_policy = None
        if fleet.tier_priority_min > 0:
            # Serving.fleet.tier_* / HYDRAGNN_FLEET_TIER_*: priority/
            # quota routing across engine tiers (docs/serving.md
            # "Tiered fleets"); the offline predict below submits at
            # priority 0, so the policy only matters for live traffic
            # sharing this router
            tier_policy = TierPolicy(
                fast=fleet.tier_fast, accurate=fleet.tier_accurate,
                priority_min=fleet.tier_priority_min,
                quota=fleet.tier_quota)
        server = ReplicaRouter(
            make_engine, fleet.replicas,
            max_redispatch=fleet.redispatch_max or None,
            drain_timeout_s=fleet.drain_timeout_s,
            tier_policy=tier_policy)
    else:
        server = make_engine()
    try:
        if serving.metrics_port:
            # Serving.metrics_port / HYDRAGNN_SERVE_METRICS_PORT:
            # /healthz + /metrics over HTTP for the run's duration
            # (docs/observability.md); loopback-only here — fleet
            # exposure is a deliberate API decision. A fleet exposes ONE
            # aggregated endpoint with per-replica labels.
            http = server.start_metrics_server(port=serving.metrics_port)
            import logging
            logging.getLogger("hydragnn_tpu").info(
                "serving metrics endpoint at %s/metrics", http.url)
        warm = server.warmup()
        if fleet.replicas > 1:
            # per replica: programs compiled fresh vs loaded from the
            # compile store, and the devices they execute on
            import logging
            logging.getLogger("hydragnn_tpu").info(
                "serving warm-up: %s", warm)
        results = server.predict(testset)
    finally:
        server.shutdown()
    trues = [[] for _ in mcfg.heads]
    preds = [[] for _ in mcfg.heads]
    for sample, res in zip(testset, results):
        targets = _sample_targets(mcfg, sample)
        for ih, head in enumerate(mcfg.heads):
            trues[ih].append(targets[ih])
            preds[ih].append(res[ih][None, :]
                             if head.head_type == "graph" else res[ih])
    return ([np.concatenate(t) for t in trues],
            [np.concatenate(p) for p in preds])
