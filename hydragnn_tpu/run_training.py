"""Top-level training driver.

reference: hydragnn/run_training.py:48-182 — config dispatch, distributed
setup, data loading, config completion, model/optimizer construction, the
epoch loop, final save + timer report.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax

from .config import (build_model_config, get_log_name_config, load_config,
                     save_config, update_config)
from .datasets.loader import GraphDataLoader
from .graphs.batch import GraphSample
from .models.create import create_model, init_params
from .parallel.mesh import init_distributed, make_mesh
from .parallel.spmd import make_spmd_eval_step, make_spmd_train_step
from .preprocess.load_data import create_dataloaders, split_dataset
from .train.optimizer import select_optimizer
from .train.train_step import TrainState, make_eval_step, make_train_step
from .train.trainer import train_validate_test
from .utils import profiling as tr
from .utils.checkpoint import save_model
from .utils.print_utils import log, print_peak_memory, setup_log


def _load_datasets_from_config(config):
    """Config-driven dataset loading (reference:
    dataset_loading_and_splitting, preprocess/load_data.py:206-222)."""
    ds = config["Dataset"]
    fmt = ds.get("format", "pickle")
    if fmt == "pickle":
        from .datasets.pickledataset import SimplePickleDataset
        if "total" in ds["path"]:
            total = list(SimplePickleDataset(ds["path"]["total"]))
            perc = config["NeuralNetwork"]["Training"].get("perc_train", 0.7)
            return split_dataset(
                total, perc,
                ds.get("compositional_stratified_splitting", False))
        return tuple(list(SimplePickleDataset(ds["path"][k]))
                     for k in ("train", "validate", "test"))
    if fmt in ("unit_test", "LSMS"):
        from .datasets.lsmsdataset import load_lsms_splits
        return load_lsms_splits(config)
    if fmt == "adios":
        from .datasets.gsdataset import GraphStoreDataset
        # multi-host data sharding (tools/tpu_pod_launch.py): when
        # HYDRAGNN_GS_SHARD_DIR names this process's shard directory, its
        # split subdirs override the config paths — each host streams only
        # its own bytes; splits absent from the shard (typically
        # validate/test, replicated) still come from the config.
        # HYDRAGNN_GS_SHARD_ROOT is the same, resolved per process — the
        # gcloud --worker=all launch runs ONE identical command on every
        # worker, so the shard index must come from the runtime.
        from .utils.envflags import env_str
        shard = env_str("HYDRAGNN_GS_SHARD_DIR")
        root = env_str("HYDRAGNN_GS_SHARD_ROOT")
        if not shard and root:
            shard = os.path.join(root,
                                 f"shard_{jax.process_index()}")

        def _split_path(k):
            if shard and os.path.isdir(os.path.join(shard, k)):
                return os.path.join(shard, k)
            return ds["path"][k]
        return tuple(GraphStoreDataset(_split_path(k))
                     for k in ("train", "validate", "test"))
    if fmt == "XYZ":
        from .datasets.xyzdataset import load_xyz_splits
        return load_xyz_splits(config)
    if fmt == "CFG":
        from .datasets.cfgdataset import load_cfg_splits
        return load_cfg_splits(config)
    raise ValueError(f"unsupported Dataset.format '{fmt}'")


def run_training(config_or_path, datasets: Optional[Tuple] = None,
                 use_spmd: Optional[bool] = None, num_shards: Optional[int] = None):
    """Train end-to-end from a JSON config (path or dict)
    (reference: run_training.py:48-62 singledispatch on str/dict).

    `datasets` optionally bypasses config-driven loading with in-memory
    (train, val, test) GraphSample sequences — the examples' "preonly" path.
    Returns (state, history, model, completed_config).
    """
    config = load_config(config_or_path)
    verbosity = config.get("Verbosity", {}).get("level", 0)

    from .utils.envflags import (env_flag, env_int, resolve_pack_lookahead,
                                 resolve_packing, resolve_steps_per_call)
    # deterministic fault injection (docs/fault_tolerance.md): the plan —
    # HYDRAGNN_FAULT_PLAN env over Training.fault_plan, strict parsing —
    # is installed per run so site counters start fresh; a stale
    # preemption flag from an earlier run in this process is cleared
    from .train.trainer import clear_preemption
    from .utils.faults import install_fault_plan, resolve_fault_plan
    install_fault_plan(resolve_fault_plan(
        config.get("NeuralNetwork", {}).get("Training", {})))
    clear_preemption()
    init_distributed()
    # persistent XLA compilation cache (utils/devices.enable_compile_cache
    # holds the one placement rule); after the rendezvous because the rule
    # reads the backend
    from .utils.devices import enable_compile_cache
    enable_compile_cache()
    # TRACE_LEVEL>0 also turns on synchronous region timing (the cudasync
    # analogue: block_until_ready before closing a span — reference:
    # tracer.py:106-127)
    tr.initialize(sync=(env_int("HYDRAGNN_TRACE_LEVEL", 0) or 0) > 0)

    if datasets is None:
        # preprocessing fast path (docs/preprocessing.md): worker-pool
        # sample builds + the content-addressed preprocessed cache, both
        # resolved once here so the startup log names what the loaders use
        from .preprocess.load_data import resolve_preprocess_settings
        pp_workers, pp_cache = resolve_preprocess_settings(config)
        if pp_workers or pp_cache:
            log(f"preprocessing: workers={pp_workers} "
                f"cache={'on at ' + pp_cache if pp_cache else 'off'}")
        datasets = _load_datasets_from_config(config)
    trainset, valset, testset = datasets
    trainset = list(trainset)
    valset = list(valset)
    testset = list(testset)

    datasets = (trainset, valset, testset)

    config = update_config(config, trainset, valset, testset)

    # budget-packed batching (docs/packing.md): pack a VARIABLE number of
    # graphs into a fixed (n_node, n_edge, n_graph) budget sized for the
    # mean batch content — one compiled program, a fraction of the padding
    # FLOPs. Resolved here, before the multi-process data wiring, because
    # packing changes how data is distributed (global plan, not sliced
    # samples).
    packing = resolve_packing(config["NeuralNetwork"]["Training"])
    pack_lookahead = resolve_pack_lookahead(
        config["NeuralNetwork"]["Training"])
    _arch0 = config["NeuralNetwork"]["Architecture"]
    _tcfg0 = config["NeuralNetwork"]["Training"]
    if (packing and _arch0["model_type"] == "DimeNet"
            and not env_flag("HYDRAGNN_NEIGHBOR_FORMAT",
                             bool(_arch0.get("neighbor_format", True)))):
        # with the dense table (the default) DimeNet derives its pairs on
        # the device and packs like any stack
        log("batch_packing: DimeNet without the dense neighbour table "
            "takes the host-built triplet list, whose static budget is "
            "not pack-aware; falling back to fixed-shape batching")
        packing = False
    if packing and (int(_arch0.get("graph_shards", 1) or 1) > 1
                    or int(_tcfg0.get("pipeline_stages", 1) or 1) > 1):
        log("batch_packing: not composed with graph_shards/pipeline_stages "
            "meshes yet; falling back to fixed-shape batching")
        packing = False
    pack_rank, pack_nproc = 0, 1

    # multi-process (multi-host) data wiring: with replicated inputs every
    # process keeps its contiguous slice (stats above saw the full data);
    # with per-host shards (GraphStore shard dirs) the data is already
    # local and the data-derived config stats must be globally reduced
    # instead (reference analogue: DistributedSampler + the MPI allreduces
    # in AbstractRawDataset, load_data.py:236-244 / raw_dataset_loader)
    from .parallel.multiprocess import is_multiprocess
    if is_multiprocess():
        from .parallel.multiprocess import (slice_by_process,
                                            sync_config_stats)
        from .utils.envflags import env_str
        mp_data = env_str("HYDRAGNN_MP_DATA")
        if mp_data is None:
            mp_data = ("local" if (env_str("HYDRAGNN_GS_SHARD_DIR")
                                   or env_str("HYDRAGNN_GS_SHARD_ROOT"))
                       else "replicated")
        if packing:
            # the pack plan must be computed from the GLOBAL order before
            # any per-process slicing: every process keeps the full
            # replicated splits, packs the same global plan, and takes its
            # rank's bin slice per step — identical step counts on every
            # rank by construction (raises for per-host local shards)
            from .parallel.multiprocess import packing_process_coords
            pack_rank, pack_nproc = packing_process_coords(mp_data)
        elif mp_data == "replicated":
            # train: too few samples to shard is fatal (empty shards would
            # train on nothing); val/test: replicate the split instead so
            # keep_best/LR-plateau never see a bogus 0.0 eval loss
            trainset = slice_by_process(trainset, what="train split")
            valset = slice_by_process(valset, what="validate split",
                                      underflow="replicate")
            testset = slice_by_process(testset, what="test split",
                                       underflow="replicate")
            datasets = (trainset, valset, testset)
        else:
            config = sync_config_stats(config)
    log_name = get_log_name_config(config)
    setup_log(log_name)
    save_config(config, log_name)

    nn = config["NeuralNetwork"]
    train_cfg = nn["Training"]
    batch_size = int(train_cfg["batch_size"])

    # unified telemetry (docs/observability.md): HYDRAGNN_TELEMETRY /
    # Training.Telemetry resolved ONCE here (strict parsing, outside any
    # traced code). The session itself starts adjacent to the epoch-loop
    # try below — start_session installs a process-wide registry/recorder
    # whose uninstall lives in that try's finally, so an exception during
    # the setup between here and there can never leak telemetry state
    # into a later run in this process.
    from .utils.envflags import resolve_telemetry
    tel_cfg = resolve_telemetry(train_cfg)
    tel_out = tel_cfg.resolve_out_dir(os.path.join("./logs", log_name))
    telemetry = None

    # Architecture.graph_shards > 1: composed (data x graph) mesh — each
    # data shard's edge set is sharded over the graph axis
    # (parallel/composite.py). The graph axis claims its devices first;
    # data parallelism gets the rest.
    graph_shards = int(nn["Architecture"].get("graph_shards", 1) or 1)
    ndev = jax.device_count()
    if graph_shards > 1 and ndev % graph_shards != 0:
        raise ValueError(
            f"Architecture.graph_shards={graph_shards} does not divide the "
            f"device count {ndev}")

    # Training.pipeline_stages > 1: pipelined layer parallelism over a
    # "pipe" mesh axis (parallel/pipeline_trainer.py, docs/pipeline.md).
    # The loader's device-stacked output doubles as the microbatch axis.
    # Schedule/remat/microbatch knobs resolve ONCE here, strictly, at
    # step-construction time (utils/envflags.resolve_pipeline — typo env
    # values warn and fall back).
    pipeline_stages = int(train_cfg.get("pipeline_stages", 1) or 1)
    from .utils.envflags import resolve_pipeline
    (microbatches, pipe_schedule, pipe_remat,
     pipe_data_shards) = resolve_pipeline(train_cfg, pipeline_stages)
    if pipeline_stages > 1 and graph_shards > 1:
        raise ValueError("pipeline_stages and graph_shards cannot be "
                         "combined yet")

    mcfg = build_model_config(config)

    from .parallel.mesh import resolve_num_shards
    if pipeline_stages > 1:
        # validate before the loader asserts on batch/shard divisibility
        # with a less actionable message (ValueError here, never a bare
        # assert — asserts vanish under python -O)
        from .parallel.pipeline_trainer import (
            require_pipeline_norm_optin, validate_pipeline_config)
        require_pipeline_norm_optin(train_cfg)
        validate_pipeline_config(mcfg, pipeline_stages, batch_size,
                                 microbatches, schedule=pipe_schedule,
                                 data_shards=pipe_data_shards)
        # loader stacking = (data replica x microbatch) axis, d-major
        num_shards = microbatches * pipe_data_shards
        log(f"pipeline: stages={pipeline_stages} "
            f"microbatches={microbatches} schedule={pipe_schedule} "
            f"remat={pipe_remat or 'off'} "
            f"data_shards={pipe_data_shards}")
        if (pipe_data_shards == 1 and bool(
                train_cfg.get("Optimizer", {}).get(
                    "use_zero_redundancy", False))):
            # ZeRO shards opt state over the data axis; with one data
            # shard there is nothing to shard over and the knob would
            # silently do nothing — say so (the strict-knob rule)
            import logging
            logging.getLogger("hydragnn_tpu").warning(
                "Optimizer.use_zero_redundancy has no effect on a "
                "pipeline run with pipeline_data_shards=1: opt state "
                "shards over the data mesh axis. Set "
                "Training.pipeline_data_shards > 1 to shard it.")
    else:
        num_shards = resolve_num_shards(
            num_shards, batch_size, use_spmd,
            device_budget=(ndev // graph_shards) if graph_shards > 1
            else None)

    # multi-process SPMD: the global shard/batch budget splits across
    # processes — each loader feeds only its local devices' slice
    mp_spmd = (is_multiprocess() and pipeline_stages == 1
               and graph_shards == 1 and num_shards > 1)
    if is_multiprocess() and not mp_spmd:
        # per-process data + local loader budgets compose ONLY with the
        # plain SPMD path; on any other path processes would compile
        # different programs over the shared mesh (or skip gradient sync)
        raise ValueError(
            "multi-process runs support the plain SPMD data-parallel "
            "path only: pipeline_stages and graph_shards must be 1 and "
            f"num_shards > 1 (got pipeline_stages={pipeline_stages}, "
            f"graph_shards={graph_shards}, num_shards={num_shards})")
    local_shards, local_batch = num_shards, batch_size
    if mp_spmd:
        from .parallel.multiprocess import validate_multiprocess_spmd
        local_shards, local_batch = validate_multiprocess_spmd(
            num_shards, batch_size)

    # dense neighbor-list layout (zero-scatter aggregation): default-on —
    # every stack consumes it when present (cross-layout equivalence is
    # tested for all 13 in tests/test_graph_core.py); K pinned across
    # splits by create_dataloaders. Architecture.neighbor_format or
    # HYDRAGNN_NEIGHBOR_FORMAT overrides.
    nbr_fmt = bool(nn["Architecture"].get("neighbor_format", True))
    nbr_fmt = env_flag("HYDRAGNN_NEIGHBOR_FORMAT", nbr_fmt)
    if graph_shards > 1 and nbr_fmt:
        # the dense [N, K] layout is node-major — edge sharding needs the
        # edge-leading segment path
        log("graph_shards > 1: disabling the dense neighbor-list layout "
            "(edge-sharded aggregation uses the segment path)")
        nbr_fmt = False

    # DimeNet derives its edge-pair space on the device from the dense
    # table; only without the table does it need the host-built list
    from .graphs.triplets import maybe_triplet_transform
    batch_transform = maybe_triplet_transform(
        nn["Architecture"]["model_type"], trainset + valset + testset,
        max(batch_size // max(num_shards, 1), 1), nbr_fmt)

    # HYDRAGNN_USE_ddstore serves training samples from the C++ DDStore
    # (reference: the --ddstore path wrapping datasets in DistDataset,
    # utils/datasets/distdataset.py:22-183). Single-process wiring here (one
    # local shard); multi-host peer wiring is example-level because it needs
    # per-host addresses.
    train_source = trainset
    if env_flag("HYDRAGNN_USE_ddstore") and trainset:
        from .datasets.ddstore import DistDataset
        dd = DistDataset(rank=0, world=1)
        dd.populate(trainset, 0, len(trainset), [0, len(trainset)])
        train_source = dd

    # the padded batch shape and neighbor K shape the compiled program —
    # in a multi-process run they must be computed from GLOBAL statistics
    # or processes would compile different programs and deadlock
    mp_loader_kwargs = {}
    if mp_spmd:
        if batch_transform is not None:
            raise ValueError(
                "multi-process SPMD does not support the host-built "
                "triplet list (its static budget is not globally reduced); "
                "keep the dense neighbour table on (neighbor_format), with "
                "which DimeNet derives its pairs on the device")
        if not packing:
            from .parallel.multiprocess import allreduce_max_int
            from .preprocess.load_data import loader_budgets
            n_node, n_edge, k_glob = loader_budgets(
                trainset + valset + testset,
                max(local_batch // local_shards, 1), nbr_fmt,
                reduce_fn=lambda *v: allreduce_max_int(*v))
            mp_loader_kwargs = dict(n_node_per_shard=n_node,
                                    n_edge_per_shard=n_edge)
            if nbr_fmt:
                mp_loader_kwargs["neighbor_k"] = k_glob
        # packed multi-process runs keep the FULL replicated splits on
        # every rank, so the pack budget (and neighbor K) computed inside
        # create_dataloaders is already identical on every process

    train_loader, val_loader, test_loader = create_dataloaders(
        train_source, valset, testset, local_batch,
        num_shards=local_shards,
        batch_transform=batch_transform, neighbor_format=nbr_fmt,
        # async input pipeline (docs/input_pipeline.md): config overrides
        # win over the HYDRAGNN_ASYNC_LOADER / HYDRAGNN_BATCH_CACHE_MB env
        # knobs; None defers to them
        async_workers=train_cfg.get("async_loader_workers"),
        cache_mb=train_cfg.get("batch_cache_mb"),
        packing=packing, pack_lookahead=pack_lookahead,
        pack_rank=pack_rank, pack_nproc=pack_nproc,
        **mp_loader_kwargs)
    if packing:
        b = train_loader.pack_budget
        # plan_fp: fingerprint of the epoch-0 GLOBAL pack plan (computed
        # before per-process slicing) — every rank of a run, and a
        # world-size-elastic restart at W' != W, must log the SAME value
        # or the data-distribution contract is broken (BENCH_ELASTIC
        # greps it per rank as the cross-world adjudication breadcrumb)
        log(f"batch_packing: budget n_node={b.n_node} n_edge={b.n_edge} "
            f"n_graph={b.n_graph} lookahead={b.lookahead} "
            f"plan_fp={train_loader.global_plan_fingerprint()} "
            f"(fixed-shape batching would pad every batch to the "
            f"worst case)")

    if mp_spmd:
        # unequal per-host step counts deadlock the collectives
        from .parallel.multiprocess import assert_equal_across_processes
        for name, ld in (("train", train_loader), ("validate", val_loader),
                         ("test", test_loader)):
            assert_equal_across_processes(len(ld), f"{name} batches/epoch")

    # init on one shard-shaped batch; flax init only needs the static
    # shapes, so in packing mode a single sample padded to the pack budget
    # suffices (graphs_per_shard samples could overflow a mean-sized budget)
    from .graphs.batch import collate
    init_count = 1 if packing else train_loader.graphs_per_shard
    init_batch = collate(trainset[:min(len(trainset), init_count)],
                         n_node=train_loader.n_node, n_edge=train_loader.n_edge,
                         n_graph=train_loader.n_graph, np_out=True)
    if batch_transform is not None:
        init_batch = batch_transform(init_batch)
    if train_loader.neighbor_k is not None:
        # the layout the loader makes: a stack that derives index spaces
        # from the dense table (DimeNet) has nothing else to init on
        from .graphs.batch import with_neighbor_format
        init_batch = with_neighbor_format(init_batch,
                                          k=train_loader.neighbor_k)
    tx = select_optimizer(train_cfg)
    if pipeline_stages > 1:
        # (config already validated before the loader was built)
        from .parallel.pipeline_trainer import init_pipeline_params
        model = None  # pipelined params are a plain pytree, not a flax stack
        pparams = init_pipeline_params(jax.random.PRNGKey(0), mcfg,
                                       init_batch)
        state = TrainState.create({"params": pparams}, tx)
    else:
        model = create_model(mcfg)
        variables = init_params(model, init_batch)
        state = TrainState.create(variables, tx)
        if nbr_fmt:
            # edge_slot=True: the backward pass of the edge -> slot gather
            # is a gather too (ops/segment.edge_gather)
            layout = (f"layout: neighbor_format=True "
                      f"K={train_loader.neighbor_k} "
                      f"edge_slot={init_batch.edge_slot is not None}")
            # the order the stack's per-edge inputs are made in, read off
            # the shapes `conv_args` gives on this layout: [N, K, ...] is
            # slot order, once a step, and no conv converts a layout
            made = jax.tree_util.tree_leaves(
                jax.eval_shape(model.conv_args, init_batch))
            if made:
                in_slots = any(a.shape[:2] == init_batch.nbr.shape
                               for a in made)
                layout += ("; per-edge inputs made in "
                           f"{'slot' if in_slots else 'edge'} order")
            if getattr(model, "derives_pair_space", False):
                pad = train_loader.padding_stats(pair_space=True) or {}
                share = pad.get("pad_pair_share")
                layout += ("; the stack derives its [N, K, K] pair space on "
                           "the device" + ("" if share is None else
                                           f", pad_pair_share={share:.3f}"))
            log(layout)

    # resume / transfer: Training.continue + startfrom name the run whose
    # checkpoint seeds this one (reference: load_existing_model_config,
    # utils/model/model.py:91-98, called from run_training.py:113-115)
    start_epoch, resume_trainer = 0, None
    best_state0, best_val0 = None, None
    if train_cfg.get("continue"):
        from .utils.checkpoint import load_best_model, load_existing_model
        start_name = train_cfg.get("startfrom") or log_name
        try:
            restored, ckpt_meta = load_existing_model(
                state, start_name, with_metadata=True)
        except Exception as exc:  # noqa: BLE001 — orbax raises opaque
            # tree-mismatch errors when the checkpointed optimizer state
            # doesn't match this config's (different Optimizer.type /
            # gradient_accumulation_steps / use_zero_redundancy)
            raise ValueError(
                f"could not restore run '{start_name}' for "
                "Training.continue: the checkpointed state does not match "
                "this config (changed Architecture/Optimizer settings?) "
                f"or the checkpoint is unreadable "
                f"({type(exc).__name__}: {exc})") from exc
        if restored is None:
            raise ValueError(
                f"Training.continue is set but run '{start_name}' has no "
                "checkpoint under ./logs")
        # orbax hands back leaves COMMITTED to its restore placement
        # (single-device) — a committed leaf clashes in jit with a batch
        # sharded over this run's mesh. Hand the step factories HOST
        # arrays instead: the compiled step's shardings then place them
        # under THIS run's mesh, which may have a different world size /
        # device count than the writer's (the elastic W -> W' restore,
        # docs/fault_tolerance.md — checkpointed shapes are global, so
        # placement is the only thing that changes)
        import numpy as _np
        state = jax.tree_util.tree_map(_np.asarray, restored)
        # resume metadata (epoch/step/scheduler counters/history) only
        # applies when continuing the SAME run: a startfrom transfer from
        # another run seeds weights but trains from epoch 0, the
        # reference's transfer-learning semantics
        if ckpt_meta and start_name == log_name:
            # schema gate (docs/fault_tolerance.md): unknown keys pass
            # through (elastic world_size and whatever comes next);
            # missing REQUIRED keys raise naming the key instead of
            # silently resuming from epoch 0
            from .utils.checkpoint import validate_resume_meta
            validate_resume_meta(ckpt_meta)
            start_epoch = int(ckpt_meta.get("next_epoch", 0))
            resume_trainer = ckpt_meta.get("trainer")
            if bool(train_cfg.get("keep_best", True)):
                best_state0, best_val0 = load_best_model(state, start_name,
                                                         with_val=True)
        log(f"resumed from '{start_name}' at step {int(state.step)}"
            + (f" (epoch {start_epoch})" if start_epoch else ""))

    accum = int(train_cfg.get("gradient_accumulation_steps", 1) or 1)
    if accum > 1 and len(train_loader) % accum:
        import logging
        logging.getLogger("hydragnn_tpu").warning(
            "gradient_accumulation_steps=%d does not divide the %d train "
            "batches/epoch: the trailing micro-batch's gradient carries "
            "into the next epoch's first update (and is dropped after the "
            "last epoch) — same micro-step counting as DeepSpeed's",
            accum, len(train_loader))

    loss_name = train_cfg.get("loss_function_type", "mse")
    cge = bool(train_cfg.get("compute_grad_energy", False))
    # energy/force loss weights: force_loss_weight "auto" reproduces the
    # reference's magnitude balancing (Base.energy_force_loss,
    # Base.py:400-404); default 1.0 keeps the calibrated battery behavior
    e_w = float(train_cfg.get("energy_loss_weight", 1.0))
    f_w = train_cfg.get("force_loss_weight", 1.0)
    f_w = f_w if f_w == "auto" else float(f_w)
    if pipeline_stages > 1:
        from .parallel.pipeline_trainer import (make_pipeline_ef_eval_step,
                                                make_pipeline_ef_train_step,
                                                make_pipeline_eval_step,
                                                make_pipeline_train_step)
        if pipe_data_shards > 1:
            mesh = make_mesh((("pipe", pipeline_stages),
                              ("data", pipe_data_shards)))
        else:
            mesh = make_mesh((("pipe", pipeline_stages),))
        opt_cfg = train_cfg.get("Optimizer", {})
        pipe_kwargs = dict(
            schedule=pipe_schedule,
            remat=pipe_remat is not None, remat_policy=pipe_remat,
            data_shards=pipe_data_shards,
            zero_opt=(pipe_data_shards > 1
                      and bool(opt_cfg.get("use_zero_redundancy", False))),
            zero_min_size=int(opt_cfg.get("zero_min_shard_size", 2 ** 14)))
        if cge:
            # energy-force through the pipeline: the force grad and the
            # params grad both differentiate through the schedule
            # (1f1b windows included)
            train_step = make_pipeline_ef_train_step(
                mcfg, mesh, pipeline_stages, tx, loss_name,
                energy_weight=e_w, force_weight=f_w, **pipe_kwargs)
            eval_step = make_pipeline_ef_eval_step(
                mcfg, mesh, pipeline_stages, loss_name,
                energy_weight=e_w, force_weight=f_w)
        else:
            train_step = make_pipeline_train_step(
                mcfg, mesh, pipeline_stages, tx, loss_name, **pipe_kwargs)
            eval_step = make_pipeline_eval_step(mcfg, mesh, pipeline_stages,
                                                loss_name)
    elif graph_shards > 1:
        from .parallel.composite import (make_composed_eval_step,
                                         make_composed_train_step)
        mesh = make_mesh((("data", num_shards), ("graph", graph_shards)))
        opt_cfg = train_cfg.get("Optimizer", {})
        train_step = make_composed_train_step(
            model, mcfg, tx, mesh, loss_name, compute_grad_energy=cge,
            energy_weight=e_w, force_weight=f_w,
            zero_opt=bool(opt_cfg.get("use_zero_redundancy", False)),
            zero_min_size=int(opt_cfg.get("zero_min_shard_size", 2 ** 14)))
        eval_step = make_composed_eval_step(model, mcfg, loss_name,
                                            compute_grad_energy=cge,
                                            energy_weight=e_w,
                                            force_weight=f_w)
    elif num_shards > 1:
        if mp_spmd:
            from .parallel.multiprocess import spmd_mesh_devices
            mesh = make_mesh((("data", num_shards),),
                             devices=spmd_mesh_devices(num_shards))
        else:
            mesh = make_mesh((("data", num_shards),))
        # ZeRO-equivalent optimizer-state sharding (reference:
        # Training.Optimizer.use_zero_redundancy, optimizer.py:104-113)
        opt_cfg = train_cfg.get("Optimizer", {})
        zero_opt = bool(opt_cfg.get("use_zero_redundancy", False))
        zero_min = int(opt_cfg.get("zero_min_shard_size", 2 ** 14))
        train_step = make_spmd_train_step(model, mcfg, tx, mesh, loss_name,
                                          compute_grad_energy=cge,
                                          energy_weight=e_w,
                                          force_weight=f_w,
                                          zero_opt=zero_opt,
                                          zero_min_size=zero_min)
        eval_step = make_spmd_eval_step(model, mcfg, mesh, loss_name,
                                        compute_grad_energy=cge,
                                        energy_weight=e_w,
                                        force_weight=f_w)
    else:
        train_step = make_train_step(model, mcfg, tx, loss_name,
                                     compute_grad_energy=cge,
                                     energy_weight=e_w, force_weight=f_w)
        eval_step = make_eval_step(model, mcfg, loss_name,
                                   compute_grad_energy=cge,
                                   energy_weight=e_w, force_weight=f_w)

    # steps-per-call dispatch batching: scan S optimizer steps per device
    # call (Training.steps_per_call / HYDRAGNN_STEPS_PER_CALL). Identical
    # math to the per-batch loop; amortizes host dispatch latency.
    multi_step = multi_eval = place_group_fn = None
    steps_per_call = resolve_steps_per_call(train_cfg)
    if graph_shards > 1 or pipeline_stages > 1 or mp_spmd:
        steps_per_call = 1  # dispatch grouping not composed with the
        # (data x graph) / pipeline meshes or multi-process placement yet
    elif num_shards == 1 and steps_per_call > 1:
        from .train.train_step import (make_multi_eval_step,
                                       make_multi_train_step)
        multi_step = make_multi_train_step(model, mcfg, tx,
                                           loss_name=loss_name,
                                           compute_grad_energy=cge,
                                           energy_weight=e_w,
                                           force_weight=f_w)
        multi_eval = make_multi_eval_step(model, mcfg, loss_name=loss_name,
                                          compute_grad_energy=cge,
                                          energy_weight=e_w,
                                          force_weight=f_w)
    elif steps_per_call > 1:
        from .parallel.spmd import make_spmd_dispatch_group
        multi_step, place_group_fn = make_spmd_dispatch_group(
            model, mcfg, tx, mesh, steps_per_call, loss_name=loss_name,
            compute_grad_energy=cge, energy_weight=e_w, force_weight=f_w,
            zero_opt=zero_opt, zero_min_size=zero_min)

    # mid-training best-val saves run async so the epoch loop never blocks
    # on filesystem writes; the final save below synchronizes. Installed on
    # ALL ranks — orbax save() is a multihost collective; gating it to rank
    # 0 deadlocked multi-process runs (checkpoint.make_async_best_checkpoint_fn)
    keep_last_k = int(train_cfg.get("checkpoint_keep_last_k", 3) or 3)
    ckpt_every = int(train_cfg.get("checkpoint_every_n_epochs", 0) or 0)
    ckpt_fn = None
    if train_cfg.get("Checkpoint", False):
        from .utils.checkpoint import make_async_best_checkpoint_fn
        ckpt_fn = make_async_best_checkpoint_fn(log_name,
                                                keep_last_k=keep_last_k)

    # preemption-safe periodic/final saves (docs/fault_tolerance.md):
    # synchronous, with resume metadata, serialized behind any in-flight
    # async best-val save — both can target the same step dir and two
    # concurrent force-writes would race
    periodic_fn = preempt_fn = None
    if ckpt_every or train_cfg.get("Checkpoint", False):
        from .utils.checkpoint import wait_for_checkpoints

        def _sync_checkpoint(ckpt_state, meta):
            try:
                wait_for_checkpoints()
            except Exception as exc:  # noqa: BLE001 — a failed OPTIONAL
                # best-val save must not abort the periodic save
                import logging
                logging.getLogger("hydragnn_tpu").warning(
                    "async checkpoint failed: %s", exc)
            save_model(ckpt_state, log_name, metadata=meta,
                       keep_last_k=keep_last_k)

        periodic_fn = preempt_fn = _sync_checkpoint

    # visualization wiring (reference: run_training.py:76-78 reads the
    # Visualization section; train_validate_test.py:100-125,264-311 builds
    # the Visualizer, initial-solution scatter, and final plots)
    viz_cfg = config.get("Visualization", {})
    create_plots = bool(viz_cfg.get("create_plots", False))
    if create_plots and model is None:
        log("pipeline_stages > 1: prediction-based plots are not wired "
            "for the pipelined parameter layout; skipping")
        create_plots = False
    visualizer = None
    if create_plots:
        from .postprocess.visualizer import Visualizer
        from .run_prediction import run_prediction
        voi = nn["Variables_of_interest"]
        out_names = voi.get("output_names",
                            [f"head_{i}" for i in range(len(mcfg.heads))])
        visualizer = Visualizer(
            log_name, num_heads=len(mcfg.heads),
            head_dims=[h.output_dim for h in mcfg.heads],
            num_nodes_list=[s.num_nodes for s in testset])
        visualizer.num_nodes_plot()
        if viz_cfg.get("plot_init_solution", False):
            t0, p0 = run_prediction(config, datasets=datasets, state=state,
                                    model=model)
            visualizer.create_scatter_plots(t0, p0, output_names=out_names,
                                            iepoch=-1)

    if pipeline_stages > 1:
        from .parallel.pipeline_trainer import place_pipeline_batch
        place_fn = lambda b: place_pipeline_batch(
            b, mesh, data_shards=pipe_data_shards)
    elif graph_shards > 1:
        from .parallel.composite import place_composed_batch

        def place_fn(b):
            if num_shards == 1:  # loader emits unstacked batches for one
                # data shard; the composed step vmaps a leading shard axis
                b = jax.tree_util.tree_map(
                    lambda a: None if a is None else a[None], b)
            return place_composed_batch(b, mesh)
    elif num_shards > 1:
        if mp_spmd:
            from .parallel.multiprocess import make_multiprocess_place_fn
            mp_place = make_multiprocess_place_fn(mesh)
            if local_shards == 1:
                # one data shard per process: the loader emits UNSTACKED
                # batches — restore the leading shard axis before the
                # global assembly or P("data") would shard the node axis
                place_fn = lambda b: mp_place(jax.tree_util.tree_map(
                    lambda a: None if a is None else a[None], b))
            else:
                place_fn = mp_place
        else:
            from .parallel.mesh import shard_batch
            place_fn = lambda b: shard_batch(b, mesh)
    else:
        place_fn = lambda b: jax.tree_util.tree_map(
            lambda a: None if a is None else jax.device_put(a), b)
    # epoch-targeted device profiling (reference: `Profile` config section,
    # run_training via train_validate_test.py:128-130; profile.py:32-42).
    # One facility (telemetry.EpochDeviceTrace): the `Profile` block keeps
    # its reference semantics, and a telemetry session's opt-in
    # HYDRAGNN_DEVICE_TRACE bracket rides the same class targeting
    # HYDRAGNN_DEVICE_TRACE_EPOCH.
    profiler = None
    if "Profile" in config:
        from .telemetry import EpochDeviceTrace
        profiler = EpochDeviceTrace(os.path.join("./logs", log_name))
        profiler.setup(config["Profile"])
    elif tel_cfg.device_trace:
        # honored STANDALONE: HYDRAGNN_DEVICE_TRACE=1 captures the
        # target epoch even without the full telemetry session — the
        # bracket needs no registry/recorder, and silently requiring
        # HYDRAGNN_TELEMETRY too would be a footgun
        from .telemetry import EpochDeviceTrace
        profiler = EpochDeviceTrace(
            tel_out, enable=True,
            target_epoch=tel_cfg.device_trace_epoch)

    # walltime guard (reference: Training.CheckRemainingTime ->
    # check_remaining squeue poll, train_validate_test.py:255-262)
    deadline = None
    if train_cfg.get("CheckRemainingTime", False):
        from .parallel.mesh import walltime_deadline
        deadline = walltime_deadline()

    # Training.ReduceLROnPlateau overrides the scheduler defaults (the
    # reference hard-codes factor 0.5 / patience 5, train_validate_test.py:
    # 191-195; exposing them matters for loss surfaces whose val plateaus
    # early, e.g. energy-force training)
    plateau = None
    if "ReduceLROnPlateau" in train_cfg:
        from .train.trainer import ReduceLROnPlateau
        pcfg = train_cfg["ReduceLROnPlateau"] or {}
        plateau = ReduceLROnPlateau(
            factor=float(pcfg.get("factor", 0.5)),
            patience=int(pcfg.get("patience", 5)),
            min_lr=float(pcfg.get("min_lr", 1e-6)))

    final_resume: dict = {}
    # SIGTERM (the SLURM/TPU preemption signal) -> one final synchronous
    # save at the next step boundary + clean exit. Installed HERE,
    # adjacent to the try whose finally restores it — installing earlier
    # would leave the flag-only handler live forever if anything between
    # raised first. The telemetry session starts here for the same
    # reason: start_session installs process-global state that the
    # finally below is responsible for unwinding.
    from .telemetry import start_session
    telemetry = start_session(tel_cfg, os.path.join("./logs", log_name))
    try:
        # NOTHING may run between start_session and this try outside it:
        # the session installs a process-global registry/recorder whose
        # uninstall is this try's finally — even the setup below raising
        # must not leak them into a later run in this process
        if telemetry is not None:
            # the MFU gauge halves the bf16 peak for f32 compute, so the
            # session must know the step's resolved precision policy
            from .train.precision import resolve_precision
            telemetry.compute_dtype = resolve_precision(
                getattr(mcfg, "dtype", None))
            if pipeline_stages > 1:
                # pipelined runs: the trainer reports the schedule's
                # closed-form bubble fraction as a gauge + per-stage idle
                # spans each epoch (docs/pipeline.md, docs/observability.md)
                from .parallel.pipeline import (bubble_fraction,
                                                train_bubble_fraction,
                                                train_step_ticks)
                telemetry.pipeline_info = {
                    "stages": pipeline_stages,
                    "microbatches": microbatches,
                    "data_shards": pipe_data_shards,
                    "schedule": pipe_schedule,
                    "remat": pipe_remat or "off",
                    "bubble_frac": bubble_fraction(pipeline_stages,
                                                   microbatches),
                    "train_bubble_frac": train_bubble_fraction(
                        pipeline_stages, microbatches, pipe_schedule),
                    "train_ticks": train_step_ticks(
                        pipeline_stages, microbatches, pipe_schedule),
                }
            log(f"telemetry: on -> {telemetry.out_dir}")
        if preempt_fn is not None:
            from .train.trainer import install_sigterm_handler
            install_sigterm_handler()
        state, history = train_validate_test(
            train_step, eval_step, state, train_loader, val_loader,
            test_loader, plateau=plateau,
            num_epochs=int(train_cfg["num_epoch"]), log_name=log_name,
            patience=int(train_cfg.get("patience", 10)),
            use_early_stopping=bool(train_cfg.get("EarlyStopping", False)),
            checkpoint_warmup=int(train_cfg.get("checkpoint_warmup", 0)),
            checkpoint_fn=ckpt_fn, verbosity=verbosity, tracer=tr.get(),
            place_fn=place_fn, profiler=profiler, walltime_deadline=deadline,
            multi_train_step=multi_step, steps_per_call=steps_per_call,
            place_group_fn=place_group_fn, multi_eval_step=multi_eval,
            keep_best=bool(train_cfg.get("keep_best", True)),
            start_epoch=start_epoch, resume=resume_trainer,
            checkpoint_every_n_epochs=ckpt_every,
            periodic_checkpoint_fn=periodic_fn, preempt_save_fn=preempt_fn,
            initial_best_state=best_state0, initial_best_val=best_val0,
            resume_meta_out=final_resume, telemetry=telemetry)
    finally:
        # the flag-only SIGTERM handler must not outlive the epoch loop:
        # after training, the previous disposition (usually terminate) is
        # the right response to a preemption signal
        if preempt_fn is not None:
            from .train.trainer import restore_sigterm_handler
            restore_sigterm_handler()
        # telemetry artifacts are written on EVERY exit path — a
        # preempted or crashed run's partial timeline is exactly the one
        # worth reading (finalize is idempotent and restores the process
        # registry/recorder)
        if telemetry is not None:
            paths = telemetry.finalize()
            if paths:
                log(f"telemetry artifacts: {paths['jsonl']} "
                    f"{paths['chrome_trace']}")

    from .train.trainer import preemption_requested
    if preemption_requested():
        # the trainer already wrote the resume point; the "run complete"
        # final save below would overwrite LATEST with next_epoch =
        # num_epoch and destroy resumability. Exit promptly — the SIGTERM
        # grace window is short.
        tr.print_timers(os.path.join("./logs", log_name))
        return state, history, model, config
    if train_cfg.get("Checkpoint", False):
        # final save via the same drain-then-save closure the periodic
        # path uses (an in-flight async best-val save can share the final
        # state's step dir). Its metadata marks the run COMPLETE
        # (next_epoch = num_epoch): a later Training.continue trains only
        # if num_epoch was raised, instead of silently replaying from
        # epoch 0 — and carries the full trainer counters so that
        # continuation resumes the scheduler/early-stop/best-val state.
        _sync_checkpoint(state, final_resume or None)

    if visualizer is not None:
        # final test-set predictions -> parity/global/error plots + history
        # (reference: train_validate_test.py:264-311, rank-0 only — here the
        # single-controller program is already rank-0-equivalent)
        trues, preds = run_prediction(config, datasets=datasets, state=state,
                                      model=model)
        visualizer.create_plot_global(trues, preds, output_names=out_names)
        visualizer.create_scatter_plots(trues, preds, output_names=out_names)
        visualizer.create_error_histograms(trues, preds,
                                           output_names=out_names)
        for ih, head in enumerate(mcfg.heads):
            if head.output_dim > 1:
                visualizer.create_parity_plot_vector(
                    trues[ih].reshape(-1, head.output_dim),
                    preds[ih].reshape(-1, head.output_dim),
                    name=out_names[ih])
        visualizer.plot_history(history)
    tr.print_timers(os.path.join("./logs", log_name))
    print_peak_memory(verbosity)
    return state, history, model, config
