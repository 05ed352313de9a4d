"""End-to-end training tests with accuracy thresholds — the analogue of the
reference's tests/test_graphs.py:139-195 (per-model RMSE thresholds on the
deterministic BCC dataset). Fast subset here; the full 13-model sweep runs
in test_graphs_full.py (marked slow)."""
import numpy as np
import pytest

from hydragnn_tpu.run_training import run_training
from hydragnn_tpu.run_prediction import run_prediction
from hydragnn_tpu.preprocess.load_data import split_dataset

from tests.deterministic_data import deterministic_graph_dataset
from tests.utils import make_config


def _train_and_rmse(model_type, num_epochs=30, heads=("graph",), **arch):
    samples = deterministic_graph_dataset(num_configs=160, heads=heads)
    splits = split_dataset(samples, 0.7)
    cfg = make_config(model_type, heads=heads, **arch)
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = num_epochs
    cfg["NeuralNetwork"]["Training"]["EarlyStopping"] = False
    cfg["Verbosity"] = {"level": 0}
    state, history, model, completed = run_training(cfg, datasets=splits,
                                                    num_shards=1)
    trues, preds = run_prediction(completed, datasets=splits, state=state,
                                  model=model)
    rmse = [float(np.sqrt(np.mean((t - p) ** 2))) for t, p in zip(trues, preds)]
    return rmse, history


def test_train_gin_graph_head():
    """GIN single graph head converges below threshold
    (reference threshold 0.25 at tests/test_graphs.py:146, 100-epoch budget)."""
    rmse, history = _train_and_rmse("GIN", num_epochs=100)
    assert history["train_loss"][-1] < history["train_loss"][0]
    assert rmse[0] < 0.25, f"GIN RMSE {rmse[0]} above threshold"


def test_train_pna_multihead():
    """PNA with graph+node heads (reference: 0.20/0.20 thresholds)."""
    rmse, _ = _train_and_rmse("PNA", num_epochs=60, heads=("graph", "node"))
    assert rmse[0] < 0.3 and rmse[1] < 0.3, f"PNA RMSE {rmse}"


def test_train_bfloat16_compute():
    """Architecture.dtype="bfloat16" selects the mixed-precision compute
    path: model compute in bf16 (MXU-native), params/losses/batch-stats in
    f32. Must still converge on the deterministic dataset."""
    rmse, history = _train_and_rmse("PNA", num_epochs=60, dtype="bfloat16")
    assert history["train_loss"][-1] < history["train_loss"][0]
    assert rmse[0] < 0.35, f"bf16 PNA RMSE {rmse[0]} above threshold"
    assert all(np.isfinite(v) for v in history["train_loss"])


def test_spmd_matches_single_device():
    """8-way shard_map DP training must track single-device training."""
    samples = deterministic_graph_dataset(num_configs=64)
    splits = split_dataset(samples, 0.7)
    cfg = make_config("GIN")
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 3
    cfg["NeuralNetwork"]["Training"]["EarlyStopping"] = False
    _, h1, _, _ = run_training(cfg, datasets=splits, num_shards=1)
    cfg2 = make_config("GIN")
    cfg2["NeuralNetwork"]["Training"]["num_epoch"] = 3
    cfg2["NeuralNetwork"]["Training"]["EarlyStopping"] = False
    _, h8, _, _ = run_training(cfg2, datasets=splits, num_shards=8)
    # not bitwise equal (batch-stat sync differs) but same scale of descent
    assert h8["train_loss"][-1] < h8["train_loss"][0]
    assert abs(h1["train_loss"][-1] - h8["train_loss"][-1]) < 0.5


def test_zero_opt_matches_replicated():
    """ZeRO-style sharded optimizer state must produce the same training
    trajectory as the replicated optimizer (reference:
    ZeroRedundancyOptimizer is numerically identical to the wrapped
    optimizer, utils/optimizer/optimizer.py:43-113)."""
    samples = deterministic_graph_dataset(num_configs=64)
    splits = split_dataset(samples, 0.7)

    def run(zero):
        cfg = make_config("GIN")
        tr = cfg["NeuralNetwork"]["Training"]
        tr["num_epoch"] = 3
        tr["EarlyStopping"] = False
        tr["Optimizer"]["use_zero_redundancy"] = zero
        # threshold 0 so even this tiny model's opt-state leaves really
        # shard over the mesh (the default 2**14 would replicate them all
        # and make the comparison vacuous)
        tr["Optimizer"]["zero_min_shard_size"] = 0
        state, hist, _, _ = run_training(cfg, datasets=splits, num_shards=8)
        return state, hist

    s0, h0 = run(False)
    s1, h1 = run(True)
    np.testing.assert_allclose(h0["train_loss"], h1["train_loss"],
                               rtol=1e-4, atol=1e-5)
    import jax
    leaves0 = jax.tree_util.tree_leaves(s0.params)
    leaves1 = jax.tree_util.tree_leaves(s1.params)
    for a, b in zip(leaves0, leaves1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_freeze_conv_layers():
    """freeze_conv_layers keeps conv + feature-norm params fixed while
    heads train (reference: Base.py:139-143 transfer-learning freeze)."""
    import jax
    samples = deterministic_graph_dataset(num_configs=48)
    splits = split_dataset(samples, 0.7)
    cfg = make_config("GIN")
    cfg["NeuralNetwork"]["Architecture"]["freeze_conv_layers"] = True
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 3
    cfg["NeuralNetwork"]["Training"]["EarlyStopping"] = False
    cfg["NeuralNetwork"]["Training"]["keep_best"] = False
    state, hist, model, completed = run_training(cfg, datasets=splits,
                                                 num_shards=1)
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.graphs.batch import collate
    init_vars = init_params(create_model(build_model_config(completed)),
                            collate(samples[:4]))
    for key in state.params:
        a = jax.tree_util.tree_leaves(state.params[key])
        b = jax.tree_util.tree_leaves(init_vars["params"][key])
        same = all(np.allclose(np.asarray(x), np.asarray(y))
                   for x, y in zip(a, b))
        if key.startswith(("conv_", "feature_norm_")):
            assert same, f"{key} changed despite freeze"
        elif key.startswith("head_") or key == "graph_shared":
            assert not same, f"{key} did not train"


def test_initial_bias_applied():
    """initial_bias sets every head's final Dense bias (Base.py:145-150)."""
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.graphs.batch import collate
    samples = deterministic_graph_dataset(num_configs=8,
                                          heads=("graph", "node"))
    cfg = make_config("GIN", heads=("graph", "node"))
    cfg["NeuralNetwork"]["Architecture"]["initial_bias"] = 2.5
    cfg = update_config(cfg, samples)
    model = create_model(build_model_config(cfg))
    v = init_params(model, collate(samples[:4]))
    p = v["params"]
    assert np.allclose(np.asarray(p["head_0"]["dense_2"]["bias"]), 2.5)
    assert np.allclose(np.asarray(p["head_1"]["MLP_0"]["dense_2"]["bias"]),
                       2.5)
    # non-final biases untouched
    assert not np.allclose(np.asarray(p["head_0"]["dense_0"]["bias"]), 2.5)


def test_env_flag_max_num_batch_and_valtest(monkeypatch):
    """HYDRAGNN_MAX_NUM_BATCH caps batches/epoch; HYDRAGNN_VALTEST=0 skips
    the eval passes (reference: train_validate_test.py:39-49,177)."""
    samples = deterministic_graph_dataset(num_configs=64)
    splits = split_dataset(samples, 0.7)
    cfg = make_config("GIN")
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 2
    cfg["NeuralNetwork"]["Training"]["EarlyStopping"] = False
    monkeypatch.setenv("HYDRAGNN_MAX_NUM_BATCH", "1")
    monkeypatch.setenv("HYDRAGNN_VALTEST", "0")
    state, hist, _, _ = run_training(cfg, datasets=splits, num_shards=1)
    assert len(hist["train_loss"]) == 2
    assert all(np.isnan(v) for v in hist["val_loss"])


def test_freeze_conv_leaves_conv_node_head_trainable():
    """freeze_conv_layers must not freeze conv-type NODE HEADS — only the
    encoder stack (reference Base.py:139-143 freezes graph_convs +
    feature_layers; head convs stay trainable)."""
    import jax
    samples = deterministic_graph_dataset(num_configs=48, heads=("node",))
    splits = split_dataset(samples, 0.7)
    cfg = make_config("GIN", heads=("node",))
    cfg["NeuralNetwork"]["Architecture"]["output_heads"]["node"]["type"] = \
        "conv"
    cfg["NeuralNetwork"]["Architecture"]["freeze_conv_layers"] = True
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 3
    cfg["NeuralNetwork"]["Training"]["EarlyStopping"] = False
    state, hist, model, completed = run_training(cfg, datasets=splits,
                                                 num_shards=1)
    from hydragnn_tpu.config import build_model_config
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.graphs.batch import collate
    init_vars = init_params(create_model(build_model_config(completed)),
                            collate(samples[:4]))
    ncl = completed["NeuralNetwork"]["Architecture"]["num_conv_layers"]
    trained_any_head_conv = False
    for key in state.params:
        a = jax.tree_util.tree_leaves(state.params[key])
        b = jax.tree_util.tree_leaves(init_vars["params"][key])
        same = all(np.allclose(np.asarray(x), np.asarray(y))
                   for x, y in zip(a, b))
        if key.startswith("conv_"):
            idx = int(key.split("_")[-1])
            if idx < ncl:
                assert same, f"encoder {key} changed despite freeze"
            else:
                trained_any_head_conv = trained_any_head_conv or not same
    assert trained_any_head_conv, "conv node head was frozen too"


def test_neighbor_format_wired_through_loaders(monkeypatch):
    """PNA-family training defaults to the dense neighbor-list layout with
    one K pinned across splits (single compiled shape);
    HYDRAGNN_NEIGHBOR_FORMAT=0 opts out."""
    from hydragnn_tpu.preprocess.load_data import create_dataloaders

    samples = deterministic_graph_dataset(num_configs=24)
    tr, va, te = samples[:16], samples[16:20], samples[20:]
    loaders = create_dataloaders(tr, va, te, batch_size=8,
                                 neighbor_format=True)
    ks = {ld.neighbor_k for ld in loaders}
    assert len(ks) == 1 and None not in ks
    batch = next(iter(loaders[0]))
    assert batch.nbr is not None and batch.nbr.shape[1] == ks.pop()

    cfg = make_config("PNA", heads=("graph",))
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 2
    state, history, _, _ = run_training(cfg, datasets=(tr, va, te),
                                        num_shards=1)
    assert all(np.isfinite(v) for v in history["train_loss"])

    monkeypatch.setenv("HYDRAGNN_NEIGHBOR_FORMAT", "0")
    loaders_off = create_dataloaders(tr, va, te, batch_size=8)
    assert next(iter(loaders_off[0])).nbr is None


def test_walltime_guard_stops_training(monkeypatch):
    """Training.CheckRemainingTime + an already-expired deadline stops after
    the first epoch (reference: check_remaining, distributed.py:331-356)."""
    import time
    monkeypatch.setenv("HYDRAGNN_WALLTIME_DEADLINE", str(time.time() - 1))
    samples = deterministic_graph_dataset(num_configs=16)
    tr, va, te = samples[:12], samples[12:14], samples[14:]
    cfg = make_config("GIN", heads=("graph",))
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 50
    cfg["NeuralNetwork"]["Training"]["CheckRemainingTime"] = True
    _, history, _, _ = run_training(cfg, datasets=(tr, va, te), num_shards=1)
    assert len(history["train_loss"]) == 1


def test_timedelta_parse():
    from hydragnn_tpu.parallel.mesh import _timedelta_parse
    assert _timedelta_parse("1:02:03") == 3723
    assert _timedelta_parse("2-00:00:10") == 2 * 86400 + 10
    assert _timedelta_parse("05:30") == 330


def test_env_flag_trace_level_and_ddstore(monkeypatch):
    """Host-stall accounting records dataload_wait/step_dispatch spans on
    every run (utils/profiling.HostStallMonitor — no trace-level opt-in
    needed); HYDRAGNN_USE_ddstore serves training batches from the C++
    DDStore (reference env-flag layer, SURVEY.md §5.6)."""
    monkeypatch.setenv("HYDRAGNN_USE_ddstore", "1")
    from hydragnn_tpu.utils import profiling as tr

    samples = deterministic_graph_dataset(num_configs=16)
    trs, va, te = samples[:12], samples[12:14], samples[14:]
    cfg = make_config("GIN", heads=("graph",))
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 2
    _, history, _, _ = run_training(cfg, datasets=(trs, va, te), num_shards=1)
    assert len(history["train_loss"]) == 2
    assert all(np.isfinite(v) for v in history["train_loss"])
    times = tr.get().times
    assert "dataload_wait" in times and "train_step" in times
    assert "step_dispatch" in times


def test_conv_checkpointing_equivalent():
    """Training.conv_checkpointing remats each conv (reference: activation
    checkpointing, Base.py:299-301): identical params, outputs, and grads —
    purely a memory/FLOPs trade."""
    import jax
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.graphs.batch import collate
    from hydragnn_tpu.models.create import create_model, init_params

    samples = deterministic_graph_dataset(num_configs=8)
    cfg = make_config("GIN", heads=("graph",))
    cfg = update_config(cfg, samples)
    import copy
    cfg_ckpt = copy.deepcopy(cfg)
    cfg_ckpt["NeuralNetwork"]["Training"]["conv_checkpointing"] = True

    batch = collate(samples[:4])
    m0 = create_model(build_model_config(cfg))
    m1 = create_model(build_model_config(cfg_ckpt))
    v0 = init_params(m0, batch)
    v1 = init_params(m1, batch)
    assert jax.tree_util.tree_structure(v0) == jax.tree_util.tree_structure(v1)

    o0, _ = m0.apply(v0, batch, train=False)
    o1, _ = m1.apply(v0, batch, train=False)  # same params on both
    for a, b in zip(o0, o1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    def loss(m, v):
        out, _ = m.apply(v, batch, train=False)
        return sum(jnp.sum(o ** 2) for o in out)

    import jax.numpy as jnp
    g0 = jax.grad(lambda v: loss(m0, v))(v0)
    g1 = jax.grad(lambda v: loss(m1, v))(v0)
    for a, b in zip(jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# slow lane since PR 21 (tier-1 budget): 15 s; test_steps_per_call_through_run_training keeps the path in tier-1
@pytest.mark.slow
def test_steps_per_call_multi_step_equivalence():
    """make_multi_train_step: one scanned dispatch over S stacked batches is
    bit-identical to S sequential single-step calls (dispatch-latency
    amortization the reference's per-batch loop can't express)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.graphs.batch import collate
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.train_step import (TrainState, make_train_step,
                                               make_multi_train_step)
    from tests.deterministic_data import deterministic_graph_dataset
    from tests.utils import make_config

    samples = deterministic_graph_dataset(num_configs=12)
    cfg = make_config("PNA", heads=("graph",))
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    model = create_model(mcfg)
    kw = dict(n_node=96, n_edge=640, n_graph=5)
    batches = [collate(samples[i:i + 4], **kw) for i in (0, 4, 8)]
    variables = init_params(model, batches[0])
    tx = select_optimizer(cfg["NeuralNetwork"]["Training"])
    state = TrainState.create(variables, tx)

    single = make_train_step(model, mcfg, tx, donate=False)
    s_loop, loop_losses = state, []
    for b in batches:
        s_loop, m = single(s_loop, b)
        loop_losses.append(float(m["loss"]))

    multi = make_multi_train_step(model, mcfg, tx, donate=False)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)
    s_scan, m_scan = multi(state, stacked)
    np.testing.assert_allclose(np.asarray(m_scan["loss"]), loop_losses,
                               rtol=1e-6)

    # metrics-only scanned eval matches per-batch eval
    from hydragnn_tpu.train.train_step import (make_eval_step,
                                               make_multi_eval_step)
    estep = make_eval_step(model, mcfg)
    eval_losses = [float(estep(s_scan, b)[0]["loss"]) for b in batches]
    meval = make_multi_eval_step(model, mcfg)
    np.testing.assert_allclose(np.asarray(meval(s_scan, stacked)["loss"]),
                               eval_losses, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(s_loop.params),
                    jax.tree_util.tree_leaves(s_scan.params)):
        # the scan body and the standalone step are compiled separately;
        # XLA may fuse them differently on TPU, so allow last-ulp drift
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_steps_per_call_through_run_training(monkeypatch):
    """Training.steps_per_call drives the grouped trainer path end-to-end,
    including the non-divisible remainder group, and HYDRAGNN_MAX_NUM_BATCH
    still caps the exact number of optimizer steps."""
    import numpy as np
    from hydragnn_tpu.run_training import run_training
    from tests.deterministic_data import deterministic_graph_dataset
    from tests.utils import make_config

    samples = deterministic_graph_dataset(num_configs=28)
    cfg = make_config("SAGE", heads=("graph",))
    tr_cfg = cfg["NeuralNetwork"]["Training"]
    tr_cfg["num_epoch"] = 2
    tr_cfg["batch_size"] = 4
    tr_cfg["steps_per_call"] = 2  # 5 train batches -> 2 groups + remainder
    # step-count assertions need the FINAL state, not the best-val snapshot
    # (which epoch wins validation is jax-version-dependent numerics)
    tr_cfg["keep_best"] = False
    datasets = (samples[:20], samples[20:24], samples[24:])
    state, history, _, _ = run_training(cfg, datasets=datasets, num_shards=1)
    assert len(history["train_loss"]) == 2
    assert all(np.isfinite(v) for v in history["train_loss"])
    assert int(state.step) == 10  # 5 batches x 2 epochs

    # the cap must bound optimizer steps exactly even mid-group
    monkeypatch.setenv("HYDRAGNN_MAX_NUM_BATCH", "3")
    tr_cfg["num_epoch"] = 1
    state, _, _, _ = run_training(cfg, datasets=datasets, num_shards=1)
    assert int(state.step) == 3


# slow lane since PR 21 (tier-1 budget): 13 s
@pytest.mark.slow
def test_spmd_steps_per_call_equivalence():
    """SPMD multi-step: one scanned dispatch over [S, D, ...] stacks matches
    S sequential SPMD steps, and Training.steps_per_call works end-to-end
    with num_shards=8 (remainder group included)."""
    import jax
    import numpy as np
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.datasets.loader import GraphDataLoader, _stack_batches
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.parallel.mesh import (make_mesh, shard_batch,
                                            shard_stacked_batch)
    from hydragnn_tpu.parallel.spmd import (make_spmd_multi_train_step,
                                            make_spmd_train_step)
    from hydragnn_tpu.run_training import run_training
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.train_step import TrainState
    from tests.deterministic_data import deterministic_graph_dataset
    from tests.utils import make_config

    ndev = 8
    samples = deterministic_graph_dataset(num_configs=48)
    cfg = make_config("SAGE", heads=("graph",))
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    model = create_model(mcfg)
    loader = GraphDataLoader(samples, batch_size=2 * ndev, num_shards=ndev,
                             shuffle=False)
    batches = list(loader)[:3]
    init_b = jax.tree_util.tree_map(
        lambda a: None if a is None else a[0], batches[0])
    import jax.numpy as jnp
    variables = init_params(model, init_b)
    tx = select_optimizer(cfg["NeuralNetwork"]["Training"])
    # both steps donate their input state; give each run its own buffers
    fresh = lambda: TrainState.create(
        jax.tree_util.tree_map(jnp.array, variables), tx)
    mesh = make_mesh((("data", ndev),))

    single = make_spmd_train_step(model, mcfg, tx, mesh)
    s_loop = fresh()
    loop_losses = []
    for b in batches:
        s_loop, m = single(s_loop, shard_batch(b, mesh))
        loop_losses.append(float(m["loss"]))

    multi = make_spmd_multi_train_step(model, mcfg, tx, mesh)
    stacked = shard_stacked_batch(_stack_batches(batches), mesh)
    s_scan, m_scan = multi(fresh(), stacked)
    np.testing.assert_allclose(np.asarray(m_scan["loss"]), loop_losses,
                               rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(s_loop.params),
                    jax.tree_util.tree_leaves(s_scan.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)

    # end-to-end: grouped SPMD training through run_training
    t = cfg["NeuralNetwork"]["Training"]
    t["num_epoch"] = 2
    t["batch_size"] = 2 * ndev
    t["steps_per_call"] = 2
    _, history, _, _ = run_training(
        cfg, datasets=(samples[:40], samples[40:44], samples[44:]),
        num_shards=ndev)
    assert len(history["train_loss"]) == 2
    assert all(np.isfinite(v) for v in history["train_loss"])


def test_lr_reduction_does_not_recompile_the_spmd_step():
    """ReduceLROnPlateau rewrites the injected LR leaf between steps. On
    the SPMD path that leaf comes back from the step replicated over the
    mesh; a fresh single-device scalar in its place changed the step's
    input shardings and cost one full train-step recompile at the first
    reduction (seen on the four-chip TPU host, PR 21)."""
    import jax
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.datasets.loader import GraphDataLoader
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.parallel.mesh import make_mesh, shard_batch
    from hydragnn_tpu.parallel.spmd import make_spmd_train_step
    from hydragnn_tpu.train.optimizer import (get_learning_rate,
                                              select_optimizer,
                                              set_learning_rate)
    from hydragnn_tpu.train.train_step import TrainState
    from hydragnn_tpu.utils.profiling import jit_cache_size

    ndev = 8
    samples = deterministic_graph_dataset(num_configs=16)
    cfg = update_config(make_config("SAGE", heads=("graph",)), samples)
    mcfg = build_model_config(cfg)
    model = create_model(mcfg)
    loader = GraphDataLoader(samples, batch_size=2 * ndev, num_shards=ndev,
                             shuffle=False)
    batch = next(iter(loader))
    init_b = jax.tree_util.tree_map(
        lambda a: None if a is None else a[0], batch)
    tx = select_optimizer(cfg["NeuralNetwork"]["Training"])
    state = TrainState.create(init_params(model, init_b), tx)
    mesh = make_mesh((("data", ndev),))
    step = make_spmd_train_step(model, mcfg, tx, mesh)
    for _ in range(2):  # host-resident init state, then its own output
        state, _ = step(state, shard_batch(batch, mesh))
    warm = jit_cache_size(step)
    for lr in (2.5e-3, 1.25e-3):
        set_learning_rate(state.opt_state, lr)
        state, _ = step(state, shard_batch(batch, mesh))
        assert get_learning_rate(state.opt_state) == pytest.approx(lr)
    assert jit_cache_size(step) == warm


def test_per_task_val_test_history():
    """val/test per-task losses recorded every epoch (reference:
    task_loss_val/test, train_validate_test.py:93-96)."""
    samples = deterministic_graph_dataset(num_configs=32,
                                          heads=("graph", "node"))
    splits = split_dataset(samples, 0.7)
    cfg = make_config("GIN", heads=("graph", "node"))
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 2
    _, history, _, _ = run_training(cfg, datasets=splits, num_shards=1)
    for key in ("task_0", "task_1", "val_task_0", "val_task_1",
                "test_task_0", "test_task_1"):
        assert key in history and len(history[key]) == 2, key
        assert all(np.isfinite(v) for v in history[key]), key
    # the NaN/overflow watchdog reports per epoch next to input_bound_frac
    # (train_step._nonfinite_watchdog); a healthy fp32 run counts zero
    assert history["nonfinite_steps"] == [0.0, 0.0]


# slow lane since PR 21 (tier-1 budget): 13 s
@pytest.mark.slow
def test_gradient_accumulation_matches_large_batch():
    """gradient_accumulation_steps=2 with batch B/2 must match one step at
    batch B (equal-size micro-batches -> mean of means == combined grad);
    the LR plateau schedule must still see the injected hyperparams through
    the MultiSteps wrapper (reference: DeepSpeed
    gradient_accumulation_steps, config_utils.py:326-330)."""
    import jax
    import jax.numpy as jnp
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.graphs.batch import collate
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.train.optimizer import (select_optimizer,
                                              supports_lr_schedule,
                                              get_learning_rate)
    from hydragnn_tpu.train.train_step import TrainState, make_train_step

    samples = deterministic_graph_dataset(num_configs=8)
    # EGNN (equivariant): identity feature layers, no BatchNorm — batch
    # statistics would otherwise legitimately differ between one big batch
    # and two micro-batches (true for the reference's DeepSpeed
    # accumulation as well)
    cfg = make_config("EGNN", equivariance=True)
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    model = create_model(mcfg)
    kw = dict(n_node=80, n_edge=560, n_graph=5)
    big = collate(samples[:8], n_node=160, n_edge=1120, n_graph=9)
    micro = [collate(samples[:4], **kw), collate(samples[4:], **kw)]
    variables = init_params(model, micro[0])
    fresh_vars = lambda: jax.tree_util.tree_map(jnp.array, variables)

    tcfg = cfg["NeuralNetwork"]["Training"]
    tx_big = select_optimizer(tcfg)
    s_big = TrainState.create({"params": fresh_vars()["params"]}, tx_big)
    step_big = make_train_step(model, mcfg, tx_big, donate=False)
    s_big, _ = step_big(s_big, big)

    tcfg["gradient_accumulation_steps"] = 2
    tx_acc = select_optimizer(tcfg)
    s_acc = TrainState.create({"params": fresh_vars()["params"]}, tx_acc)
    assert supports_lr_schedule(s_acc.opt_state)
    assert get_learning_rate(s_acc.opt_state) > 0
    step_acc = make_train_step(model, mcfg, tx_acc, donate=False)
    s_acc, _ = step_acc(s_acc, micro[0])
    # first micro step only accumulates: params unchanged
    for a, b in zip(jax.tree_util.tree_leaves(variables["params"]),
                    jax.tree_util.tree_leaves(s_acc.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s_acc, _ = step_acc(s_acc, micro[1])

    for a, b in zip(jax.tree_util.tree_leaves(s_big.params),
                    jax.tree_util.tree_leaves(s_acc.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_spmd_bfloat16_training():
    """Architecture.dtype="bfloat16" must drive mixed precision on the SPMD
    path too (model compute bf16, params/losses f32) and converge."""
    import jax
    samples = deterministic_graph_dataset(num_configs=64)
    splits = split_dataset(samples, 0.7)
    cfg = make_config("PNA", dtype="bfloat16")
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 4
    cfg["NeuralNetwork"]["Training"]["EarlyStopping"] = False
    state, h, _, _ = run_training(cfg, datasets=splits, num_shards=8)
    assert h["train_loss"][-1] < h["train_loss"][0]
    assert all(np.isfinite(v) for v in h["train_loss"])
    assert all(np.isfinite(v) for v in h["val_loss"])
    # master params stayed f32
    for leaf in jax.tree_util.tree_leaves(state.params):
        assert leaf.dtype == np.float32, leaf.dtype


# slow lane since PR 21 (tier-1 budget): 17 s
@pytest.mark.slow
def test_force_loss_weight_auto_matches_reference_balancing():
    """Training.force_loss_weight "auto" reproduces the reference's
    magnitude balancing (Base.energy_force_loss force_loss_weight,
    Base.py:400-404): force term scaled by mean|E|/mean|F| of the true
    labels, so the weighted total differs from the 1.0/1.0 default by
    exactly that factor on the force term."""
    import jax
    import numpy as np

    from examples.LennardJones.lj_data import generate_lj_dataset
    from hydragnn_tpu.graphs.batch import collate
    from hydragnn_tpu.train.loss import energy_force_loss
    from tests.utils import prepare

    samples = generate_lj_dataset(num_configs=6)
    cfg, mcfg, _ = prepare("SchNet", samples, heads=("node",),
                           equivariance=True)
    batch = collate(samples[:4])
    from hydragnn_tpu.models.create import create_model, init_params
    model = create_model(mcfg)
    variables = init_params(model, batch)

    def apply_fn(v, b, train=False):
        outputs, _ = model.apply(v, b, train=train)
        return (outputs, None), None

    tot_auto, aux = energy_force_loss(apply_fn, variables, mcfg, batch,
                                      "mse", 1.0, "auto")
    tot_unit, aux_u = energy_force_loss(apply_fn, variables, mcfg, batch,
                                        "mse", 1.0, 1.0)
    gm = np.asarray(batch.graph_mask)[:, None]
    nm = np.asarray(batch.node_mask)[:, None]
    e_mean = (np.abs(np.asarray(batch.energy)) * gm).sum() / gm.sum()
    f_mean = (np.abs(np.asarray(batch.forces)) * nm).sum() / (
        nm.sum() * 3)
    fw = e_mean / (f_mean + 1e-8)
    e_l = float(aux["energy_loss"])
    f_l = float(aux["force_loss"])
    np.testing.assert_allclose(float(tot_auto), e_l + fw * f_l, rtol=1e-5)
    np.testing.assert_allclose(float(tot_unit), e_l + f_l, rtol=1e-6)


# ------------------------------------------- one train step owed on the device

class _TrainRig:
    """A tiny GIN (graph + node heads), its jitted steps and a shuffled
    loader of 5 batches an epoch, built once for the tests below: each run
    takes a fresh state, so every run starts from the same weights."""

    def __init__(self):
        import jax
        from hydragnn_tpu.config import build_model_config, update_config
        from hydragnn_tpu.datasets.loader import GraphDataLoader
        from hydragnn_tpu.models.create import create_model, init_params
        from hydragnn_tpu.train.optimizer import select_optimizer
        from hydragnn_tpu.train.train_step import (make_eval_step,
                                                   make_multi_train_step,
                                                   make_train_step)
        heads = ("graph", "node")
        samples = deterministic_graph_dataset(num_configs=20, heads=heads)
        cfg = update_config(make_config("GIN", heads=heads), samples)
        mcfg = build_model_config(cfg)
        model = create_model(mcfg)
        self.loader = GraphDataLoader(samples, batch_size=4, shuffle=True,
                                      seed=0)
        self.variables = init_params(model, next(iter(self.loader)))
        self.tx = select_optimizer(cfg["NeuralNetwork"]["Training"])
        self.train_step = make_train_step(model, mcfg, self.tx, donate=False)
        self.multi_train_step = make_multi_train_step(model, mcfg, self.tx,
                                                      donate=False)
        self.eval_step = make_eval_step(model, mcfg)
        self.place = lambda b: jax.tree_util.tree_map(
            lambda a: None if a is None else jax.device_put(a), b)

    def state(self):
        import jax
        import jax.numpy as jnp
        from hydragnn_tpu.train.train_step import TrainState
        return TrainState.create(
            jax.tree_util.tree_map(jnp.array, self.variables), self.tx)

    def run(self, tmp_path, train_step, name, **kw):
        from hydragnn_tpu.train import trainer
        trainer.clear_preemption()
        try:
            return trainer.train_validate_test(
                train_step, self.eval_step, self.state(), self.loader, None,
                None, num_epochs=2, log_name=name, log_dir=str(tmp_path),
                use_early_stopping=False, keep_best=False,
                place_fn=self.place, **kw)
        finally:
            trainer.clear_preemption()


@pytest.fixture(scope="module")
def train_rig():
    return _TrainRig()


_OWED_ORDERS = [
    # steady state: step k+1 is dispatched before step k's metrics are
    # fetched; the epoch's end fetches the step still owed
    ("single", "d0 d1 f0 d2 f1 d3 f2 d4 f3 f4 "
               "d5 d6 f5 d7 f6 d8 f7 d9 f8 f9"),
    # HYDRAGNN_MAX_NUM_BATCH=3 ends each pass after its third step
    ("single_cap3", "d0 d1 f0 d2 f1 f2 d3 d4 f3 d5 f4 f5"),
    # steps_per_call 2: two scanned groups and the remainder's single step
    ("group", "g0 g2 f0 d4 f2 f4 g5 g7 f5 d9 f7 f9"),
    # ... and a cap inside the second group: its first step alone
    ("group_cap3", "g0 d2 f0 f2 g3 d5 f3 f5"),
    # a preemption asked for during step 6: the pass stops at the next
    # boundary and fetches the owed step before the save
    ("preempt", "d0 d1 f0 d2 f1 d3 f2 d4 f3 f4 d5 d6 f5 f6 save"),
]


@pytest.mark.parametrize("case, expected", _OWED_ORDERS,
                         ids=[case for case, _ in _OWED_ORDERS])
def test_train_pass_keeps_one_step_owed(train_rig, tmp_path, monkeypatch,
                                        case, expected):
    """The train pass dispatches step k+1 before it fetches step k's
    metrics, in all three branches (single steps, full `steps_per_call`
    groups, a remainder's single steps), and every exit from the pass (its
    end, a `max_num_batch` cap, a preemption) fetches the step still owed.
    `d<k>` / `g<k>`: a single step / a group dispatched, starting at step
    k; `f<k>`: the metrics of the dispatch that started at k fetched."""
    import jax
    from hydragnn_tpu.train import trainer
    monkeypatch.setenv("HYDRAGNN_DISABLE_TB", "1")
    if case.endswith("cap3"):
        monkeypatch.setenv("HYDRAGNN_MAX_NUM_BATCH", "3")
    events, tags, keep = [], {}, []
    taken = [0]

    def tagging(step, kind, size):
        def call(state, batch):
            state, metrics = step(state, batch)
            events.append(f"{kind}{taken[0]}")
            tags[id(metrics)] = f"f{taken[0]}"
            keep.append(metrics)  # no id is reused while the run lasts
            taken[0] += size
            if case == "preempt" and taken[0] == 7:
                trainer.request_preemption()
            return state, metrics
        return call

    real_get = jax.device_get

    def get(x):
        if id(x) in tags:
            events.append(tags[id(x)])
        return real_get(x)

    monkeypatch.setattr(trainer.jax, "device_get", get)
    kw = {}
    if case.startswith("group"):
        kw = dict(multi_train_step=tagging(train_rig.multi_train_step, "g",
                                           2),
                  steps_per_call=2, place_group_fn=train_rig.place)
    if case == "preempt":
        kw["preempt_save_fn"] = lambda s, meta: events.append("save")
    _, hist = train_rig.run(tmp_path, tagging(train_rig.train_step, "d", 1),
                            case, **kw)
    assert " ".join(events) == expected
    assert len(hist["train_loss"]) == (1 if case == "preempt" else 2)


class _Running:
    """A metric that reads as still running on the device."""

    def __init__(self, value):
        self.value = value

    def is_ready(self):
        return False

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.value, dtype=dtype)


def test_owed_step_leaves_the_results_unchanged(train_rig, tmp_path,
                                                monkeypatch):
    """Keeping a step owed moves only when the host reads the metrics: the
    final state (bit for bit), the per-epoch losses, the per-task sums and
    `nonfinite_steps` equal those of a run whose step blocks on its own
    output before it returns. `host_bound_steps` counts the fetches that
    found their step finished: every one in the blocking run (5 steps, 5
    fetches an epoch), none where every fetch has to wait."""
    import jax
    monkeypatch.setenv("HYDRAGNN_DISABLE_TB", "1")
    step = train_rig.train_step

    def blocking(state, batch):
        return jax.block_until_ready(step(state, batch))

    def running(state, batch):
        state, metrics = step(state, batch)
        return state, {k: _Running(v) for k, v in metrics.items()}

    state, hist = train_rig.run(tmp_path, step, "owed")
    state_synced, hist_synced = train_rig.run(tmp_path, blocking, "synced")
    _, hist_running = train_rig.run(tmp_path, running, "running")
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(state_synced)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    keys = [k for k in hist if k == "train_loss" or k == "nonfinite_steps"
            or k.startswith("task_")]
    assert {"task_0", "task_1"} <= set(keys)
    for k in keys:
        assert hist[k] == hist_synced[k] == hist_running[k], k
    assert hist_synced["host_bound_steps"] == [5, 5]
    assert hist_running["host_bound_steps"] == [0, 0]
