"""Config-reachable pipeline parallelism (`Training.pipeline_stages`).

The pipelined schedules must be pure execution strategies: pipelined
forward == sequential forward on the same params, 1f1b == gpipe modulo
window-boundary gradient reassociation, and a JSON config alone turns the
path on (VERDICT r1 item 4; docs/pipeline.md)."""
import copy
import os

import jax
import numpy as np
import pytest

from hydragnn_tpu.run_training import run_training

from tests.deterministic_data import deterministic_graph_dataset
from tests.utils import make_config


def _splits(n=48, heads=("graph",)):
    samples = deterministic_graph_dataset(num_configs=n, heads=heads)
    k = int(n * 2 / 3)
    return samples[:k], samples[k:k + n // 6], samples[k + n // 6:]


def _cfg(stages, model_type="GIN", num_conv_layers=4, heads=("graph",)):
    cfg = make_config(model_type, heads=heads,
                      num_conv_layers=num_conv_layers)
    cfg["NeuralNetwork"]["Training"]["pipeline_stages"] = stages
    cfg["NeuralNetwork"]["Training"]["pipeline_norm"] = "layernorm"
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 3
    return cfg


def test_pipeline_config_trains():
    state, history, model, completed = run_training(
        _cfg(2), datasets=_splits())
    assert all(np.isfinite(v) for v in history["train_loss"])
    assert history["train_loss"][-1] < history["train_loss"][0]


def test_pipeline_forward_matches_sequential():
    """Pipelined and sequential execution of the SAME params agree."""
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.graphs.batch import collate
    from hydragnn_tpu.datasets.loader import _stack_batches
    from hydragnn_tpu.parallel.mesh import make_mesh
    from hydragnn_tpu.parallel.pipeline_trainer import (
        init_pipeline_params, make_pipeline_forward)

    samples = deterministic_graph_dataset(num_configs=16)
    cfg = make_config("GIN", num_conv_layers=4)
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    micro = [collate(samples[i:i + 4], n_node=128, n_edge=2048, n_graph=5)
             for i in range(0, 16, 4)]
    stacked = _stack_batches(micro)
    params = init_pipeline_params(jax.random.PRNGKey(0), mcfg, micro[0])

    mesh = make_mesh((("pipe", 2),))
    fwd_pipe = make_pipeline_forward(mcfg, mesh, 2, pipelined=True)
    fwd_seq = make_pipeline_forward(mcfg, mesh, 2, pipelined=False)
    out_p, _ = fwd_pipe(params, stacked)
    out_s, _ = fwd_seq(params, stacked)
    for a, b in zip(out_p, out_s):
        # upgraded from rtol=1e-4: identical per-microbatch op sequence
        # means the two execution strategies are BITWISE-equal
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pipeline_node_head_trains():
    state, history, _, _ = run_training(
        _cfg(2, heads=("node",)), datasets=_splits(heads=("node",)))
    assert all(np.isfinite(v) for v in history["train_loss"])


def test_pipeline_validation_errors():
    with pytest.raises(ValueError, match="pipeline stages"):
        run_training(_cfg(3, num_conv_layers=4), datasets=_splits())
    with pytest.raises(ValueError, match="supports model_type"):
        run_training(_cfg(2, model_type="GAT"), datasets=_splits())


def test_pipeline_norm_optin_required():
    """The LayerNorm divergence is a config-time error without the
    explicit Training.pipeline_norm acknowledgement (r3 verdict Next #8)
    — not a mid-train NOTICE."""
    cfg = _cfg(2)
    del cfg["NeuralNetwork"]["Training"]["pipeline_norm"]
    with pytest.raises(ValueError, match="pipeline_norm"):
        run_training(cfg, datasets=_splits())
    cfg["NeuralNetwork"]["Training"]["pipeline_norm"] = "batchnorm"
    with pytest.raises(ValueError, match="pipeline_norm"):
        run_training(cfg, datasets=_splits())


def test_pipeline_equivariance_rejected():
    """Non-SchNet equivariant models have no pos-threading path through
    the pipelined block — config-time error, not a silently different
    architecture. (SchNet equivariance is supported: pos rides the
    carried activation — test_pipeline_ef_*.)"""
    cfg = _cfg(2, model_type="EGNN")
    cfg["NeuralNetwork"]["Architecture"]["equivariance"] = True
    with pytest.raises(ValueError, match="pipeline_stages"):
        run_training(cfg, datasets=_splits())


@pytest.mark.slow
def test_pipeline_schnet_config_trains():
    """SchNet (the EF flagship) pipelines: its CFConv needs per-batch
    edge lengths, threaded via PIPELINE_CONV_CARGS. Assert on val loss
    over a few epochs — the 3-epoch train series is too noisy for a
    strict first-vs-last comparison. Slow lane (PR 8 tier-1 rebalance:
    the 6-epoch train rides the nightly mfu-bench job; fast-lane SchNet
    pipeline coverage lives in
    test_eval_sequential_forward_matches_pipelined_train_forward)."""
    cfg = _cfg(2, model_type="SchNet")
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 6
    state, history, _, _ = run_training(cfg, datasets=_splits())
    assert all(np.isfinite(v) for v in history["train_loss"])
    assert history["val_loss"][-1] < history["val_loss"][0]


def test_pipeline_freeze_conv():
    """freeze_conv_layers freezes the pipelined conv stack (heads/embed
    keep training) — including under AdamW weight decay, which moves
    params even at zero gradient if updates aren't masked."""
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.graphs.batch import collate
    from hydragnn_tpu.datasets.loader import _stack_batches
    from hydragnn_tpu.parallel.mesh import make_mesh
    from hydragnn_tpu.parallel.pipeline_trainer import (
        init_pipeline_params, make_pipeline_train_step)
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.train_step import TrainState

    samples = deterministic_graph_dataset(num_configs=16)
    cfg = make_config("GIN", num_conv_layers=4)
    cfg["NeuralNetwork"]["Architecture"]["freeze_conv_layers"] = True
    train_cfg = cfg["NeuralNetwork"]["Training"]
    train_cfg["Optimizer"] = {"type": "AdamW", "learning_rate": 1e-2}
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    assert mcfg.freeze_conv

    micro = [collate(samples[i:i + 4], n_node=128, n_edge=2048, n_graph=5)
             for i in range(0, 16, 4)]
    stacked = _stack_batches(micro)
    params = init_pipeline_params(jax.random.PRNGKey(0), mcfg, micro[0])
    tx = select_optimizer(train_cfg)
    state = TrainState.create({"params": params}, tx)
    mesh = make_mesh((("pipe", 2),))
    step = make_pipeline_train_step(mcfg, mesh, 2, tx)
    for _ in range(3):
        state, metrics = step(state, stacked)
    assert np.isfinite(float(np.asarray(metrics["loss"])))
    conv0 = jax.tree_util.tree_leaves(params["convs"])
    conv1 = jax.tree_util.tree_leaves(state.params["convs"])
    for a, b in zip(conv0, conv1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    head0 = np.concatenate([np.ravel(l) for l in
                            jax.tree_util.tree_leaves(params["heads"])])
    head1 = np.concatenate([np.ravel(l) for l in
                            jax.tree_util.tree_leaves(
                                state.params["heads"])])
    assert not np.allclose(head0, head1)


def test_pipeline_pna_forward_matches_sequential():
    """The flagship conv (PNA) pipelines: pipelined == sequential on the
    same params (VERDICT r2 Next #6)."""
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.graphs.batch import collate
    from hydragnn_tpu.datasets.loader import _stack_batches
    from hydragnn_tpu.parallel.mesh import make_mesh
    from hydragnn_tpu.parallel.pipeline_trainer import (
        init_pipeline_params, make_pipeline_forward)

    samples = deterministic_graph_dataset(num_configs=16)
    cfg = make_config("PNA", num_conv_layers=4)
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    micro = [collate(samples[i:i + 4], n_node=128, n_edge=2048, n_graph=5)
             for i in range(0, 16, 4)]
    stacked = _stack_batches(micro)
    params = init_pipeline_params(jax.random.PRNGKey(0), mcfg, micro[0])

    mesh = make_mesh((("pipe", 2),))
    fwd_pipe = make_pipeline_forward(mcfg, mesh, 2, pipelined=True)
    fwd_seq = make_pipeline_forward(mcfg, mesh, 2, pipelined=False)
    out_p, _ = fwd_pipe(params, stacked)
    out_s, _ = fwd_seq(params, stacked)
    for a, b in zip(out_p, out_s):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_pipeline_pna_config_trains():
    state, history, _, _ = run_training(
        _cfg(2, model_type="PNA"), datasets=_splits())
    assert all(np.isfinite(v) for v in history["train_loss"])
    assert history["train_loss"][-1] < history["train_loss"][0]


def test_pipeline_bf16_trains():
    """Architecture.dtype=bfloat16 through the pipelined path: bf16
    compute, f32 masters (the main path's mixed-precision policy)."""
    cfg = _cfg(2)
    cfg["NeuralNetwork"]["Architecture"]["dtype"] = "bfloat16"
    state, history, _, _ = run_training(cfg, datasets=_splits())
    assert all(np.isfinite(v) for v in history["train_loss"])
    # masters stay f32
    leaves = jax.tree_util.tree_leaves(state.params)
    assert all(l.dtype == np.float32 for l in leaves
               if np.issubdtype(l.dtype, np.floating))


def _ef_cfg(stages, epochs=4):
    """SchNet equivariant energy-force config on the pipelined path (the
    flagship EF workload; r4 verdict Next #7)."""
    cfg = make_config("SchNet", heads=("node",), equivariance=True,
                      num_conv_layers=4)
    arch = cfg["NeuralNetwork"]["Architecture"]
    arch["radius"] = 2.0
    arch["max_neighbours"] = 64
    voi = cfg["NeuralNetwork"]["Variables_of_interest"]
    voi["type"] = ["node"]
    voi["output_names"] = ["node_energy"]
    voi["output_index"] = [0]
    voi["output_dim"] = [1]
    tr = cfg["NeuralNetwork"]["Training"]
    tr["pipeline_stages"] = stages
    tr["pipeline_norm"] = "layernorm"
    tr["num_epoch"] = epochs
    tr["compute_grad_energy"] = True
    tr["task_weights"] = [1.0]
    return cfg


def _lj_splits(n=24):
    from examples.LennardJones.lj_data import generate_lj_dataset
    samples = generate_lj_dataset(num_configs=n)
    k = int(n * 2 / 3)
    return samples[:k], samples[k:k + n // 6], samples[k + n // 6:]


@pytest.mark.slow
def test_pipeline_ef_matches_sequential():
    """Energy-force losses computed through the GPipe schedule equal the
    sequential-scan losses on the same params — the force grad (d/dpos)
    and its params-grad both differentiate through ppermute cleanly."""
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.graphs.batch import collate
    from hydragnn_tpu.datasets.loader import _stack_batches
    from hydragnn_tpu.parallel.mesh import make_mesh
    from hydragnn_tpu.parallel.pipeline_trainer import (
        _ef_losses, init_pipeline_params, make_pipeline_forward)

    tr, va, te = _lj_splits()
    samples = tr[:16]
    cfg = _ef_cfg(2)
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    micro = [collate(samples[i:i + 4], n_node=128, n_edge=4096, n_graph=5)
             for i in range(0, 16, 4)]
    stacked = _stack_batches(micro)
    params = init_pipeline_params(jax.random.PRNGKey(0), mcfg, micro[0])

    mesh = make_mesh((("pipe", 2),))
    fwd_pipe = make_pipeline_forward(mcfg, mesh, 2, pipelined=True)
    fwd_seq = make_pipeline_forward(mcfg, mesh, 2, pipelined=False)
    tot_p, e_p, f_p = _ef_losses(mcfg, "mse", fwd_pipe, params, stacked,
                                 1.0, 1.0)
    tot_s, e_s, f_s = _ef_losses(mcfg, "mse", fwd_seq, params, stacked,
                                 1.0, 1.0)
    np.testing.assert_allclose(np.asarray(tot_p), np.asarray(tot_s),
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(f_p), np.asarray(f_s),
                               rtol=2e-4, atol=1e-6)


@pytest.mark.slow
def test_pipeline_ef_config_trains():
    """Training.pipeline_stages + compute_grad_energy from a JSON config:
    the equivariant SchNet EF flagship trains on the pipelined path."""
    cfg = _ef_cfg(2, epochs=5)
    state, history, _, _ = run_training(cfg, datasets=_lj_splits())
    assert all(np.isfinite(v) for v in history["train_loss"])
    assert history["train_loss"][-1] < history["train_loss"][0]


# ---- PR 8: 1F1B schedule / remat / knobs / pipe x data (docs/pipeline.md)


def _trainer_fixture(model_type="GIN", num_conv_layers=4, micro=4,
                     n_graphs=16):
    """Shared scaffolding: stacked microbatches + initialized params for
    driving the step factories directly (much cheaper than run_training)."""
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.graphs.batch import collate
    from hydragnn_tpu.datasets.loader import _stack_batches
    from hydragnn_tpu.parallel.pipeline_trainer import init_pipeline_params

    samples = deterministic_graph_dataset(num_configs=n_graphs)
    cfg = make_config(model_type, num_conv_layers=num_conv_layers)
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    per = n_graphs // micro
    micro_b = [collate(samples[i:i + per], n_node=128, n_edge=2048,
                       n_graph=per + 1)
               for i in range(0, n_graphs, per)]
    stacked = _stack_batches(micro_b)
    params = init_pipeline_params(jax.random.PRNGKey(0), mcfg, micro_b[0])
    tx = _sgd()
    return cfg, mcfg, stacked, params, tx


def _sgd():
    import optax
    return optax.sgd(1e-2)


def _state(params, tx):
    from hydragnn_tpu.train.train_step import TrainState
    return TrainState.create({"params": params}, tx)


def test_pipeline_knob_resolution(monkeypatch, caplog):
    """resolve_pipeline (utils/envflags): env over config over defaults,
    STRICT parsing — a typo value warns and falls back instead of taking
    effect."""
    import logging
    from hydragnn_tpu.utils.envflags import resolve_pipeline

    for var in ("HYDRAGNN_PIPE_MICROBATCHES", "HYDRAGNN_PIPE_SCHEDULE",
                "HYDRAGNN_PIPE_REMAT"):
        monkeypatch.delenv(var, raising=False)
    # defaults: microbatches = stages, 1f1b, remat off, data shards 1
    assert resolve_pipeline({}, 4) == (4, "1f1b", None, 1)
    # config layer
    cfg = {"pipeline_microbatches": 8, "pipeline_schedule": "gpipe",
           "pipeline_remat": "dots", "pipeline_data_shards": 2}
    assert resolve_pipeline(cfg, 4) == (8, "gpipe", "dots", 2)
    assert resolve_pipeline({"pipeline_remat": True}, 4)[2] == "full"
    # env wins
    monkeypatch.setenv("HYDRAGNN_PIPE_MICROBATCHES", "16")
    monkeypatch.setenv("HYDRAGNN_PIPE_SCHEDULE", "1f1b")
    monkeypatch.setenv("HYDRAGNN_PIPE_REMAT", "1")
    assert resolve_pipeline(cfg, 4) == (16, "1f1b", "full", 2)
    # typos warn and fall back to the layer below
    caplog.clear()
    monkeypatch.setenv("HYDRAGNN_PIPE_SCHEDULE", "1f1b_typo")
    monkeypatch.setenv("HYDRAGNN_PIPE_REMAT", "ture")
    monkeypatch.setenv("HYDRAGNN_PIPE_MICROBATCHES", "eight")
    with caplog.at_level(logging.WARNING, logger="hydragnn_tpu"):
        micro, sched, remat, _ = resolve_pipeline(cfg, 4)
    assert (micro, sched, remat) == (8, "gpipe", "dots")
    assert sum(1 for r in caplog.records if "is not" in r.message) == 3
    # config-layer typo for remat also warns -> off
    caplog.clear()
    for var in ("HYDRAGNN_PIPE_MICROBATCHES", "HYDRAGNN_PIPE_SCHEDULE",
                "HYDRAGNN_PIPE_REMAT"):
        monkeypatch.delenv(var, raising=False)
    with caplog.at_level(logging.WARNING, logger="hydragnn_tpu"):
        assert resolve_pipeline({"pipeline_remat": "dotz"}, 4)[2] is None
    assert any("pipeline_remat" in r.message for r in caplog.records)
    # backward compat: a non-windowable M under the DEFAULTED 1f1b
    # schedule falls back to gpipe with a warning (a pre-PR-8 config
    # must not start failing from a changed default); an EXPLICIT 1f1b
    # request keeps the strict config-time error instead
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="hydragnn_tpu"):
        assert resolve_pipeline(
            {"pipeline_microbatches": 6}, 4)[1] == "gpipe"
    assert any("falling back to gpipe" in r.message
               for r in caplog.records)
    assert resolve_pipeline(
        {"pipeline_microbatches": 6, "pipeline_schedule": "1f1b"},
        4)[1] == "1f1b"
    # a TYPO'd env schedule does not count as an explicit choice: it
    # warns, falls back to the default, and the compat fallback still
    # applies — warn-and-fall-back must never become a hard error
    monkeypatch.setenv("HYDRAGNN_PIPE_SCHEDULE", "gpip")
    with caplog.at_level(logging.WARNING, logger="hydragnn_tpu"):
        assert resolve_pipeline(
            {"pipeline_microbatches": 6}, 4)[1] == "gpipe"
    monkeypatch.delenv("HYDRAGNN_PIPE_SCHEDULE")
    # a null/empty config value is NOT an explicit choice either — the
    # compat fallback applies exactly as if the key were absent
    for empty in (None, "", "  "):
        assert resolve_pipeline(
            {"pipeline_microbatches": 6, "pipeline_schedule": empty},
            4)[1] == "gpipe"


def test_1f1b_window_divisibility_actionable_error():
    """Direct step-factory callers (bench knobs, tests) bypass
    run_training's config-time validation — the window split must still
    raise the actionable message, not an opaque reshape error."""
    import types
    from hydragnn_tpu.parallel.pipeline_trainer import _windowed_grads
    fake = types.SimpleNamespace(x=np.zeros((6, 2), np.float32))
    with pytest.raises(ValueError, match="multiple of the stage count"):
        _windowed_grads(params={}, stacked=fake, micro_fn=None,
                        num_stages=4, data_shards=1)


# slow lane since PR 21 (tier-1 budget): 13 s; schedule/remat equivalence stays pinned at module level in tests/test_pipeline.py
@pytest.mark.slow
def test_pipeline_schedule_and_remat_equivalence_trainer_level():
    """1F1B vs GPipe vs 1F1B+remat on the real LayerNorm conv stack,
    driven as one test so the three compiled steps share the fixture
    (tier-1 budget): first-step metrics BITWISE across all three
    (identical per-micro forwards, identical metric reduction over the
    restacked flat axis); the remat 3-step trajectory is BITWISE vs
    un-remat'd 1f1b (jax.checkpoint is a pure memory/recompute trade);
    gpipe-vs-1f1b params agree to float tolerance (gradient sums
    reassociate at window boundaries — exact-data bitwise is pinned in
    test_pipeline.py)."""
    from hydragnn_tpu.parallel.mesh import make_mesh
    from hydragnn_tpu.parallel.pipeline_trainer import (
        make_pipeline_train_step)

    cfg, mcfg, stacked, params, tx = _trainer_fixture()
    mesh = make_mesh((("pipe", 2),))
    step_g = make_pipeline_train_step(mcfg, mesh, 2, tx, schedule="gpipe")
    step_f = make_pipeline_train_step(mcfg, mesh, 2, tx, schedule="1f1b")
    step_r = make_pipeline_train_step(mcfg, mesh, 2, tx, schedule="1f1b",
                                      remat=True, remat_policy="full")
    sg, mg = step_g(_state(params, tx), stacked)
    sf, mf = step_f(_state(params, tx), stacked)
    sr, mr = step_r(_state(params, tx), stacked)
    for k in mg:
        np.testing.assert_array_equal(np.asarray(mg[k]), np.asarray(mf[k]),
                                      err_msg=f"metric {k}")
        np.testing.assert_array_equal(np.asarray(mf[k]), np.asarray(mr[k]),
                                      err_msg=f"metric {k} (remat)")
    for _ in range(2):
        sg, _ = step_g(sg, stacked)
        sf, _ = step_f(sf, stacked)
        sr, _ = step_r(sr, stacked)
    for a, b, c in zip(jax.tree_util.tree_leaves(sg.params),
                       jax.tree_util.tree_leaves(sf.params),
                       jax.tree_util.tree_leaves(sr.params)):
        # remat: bitwise across the whole trajectory
        np.testing.assert_array_equal(np.asarray(b), np.asarray(c))
        # schedules: float tolerance (window-boundary reassociation)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-6, atol=1e-7)


# slow lane since PR 21 (tier-1 budget): 14 s
@pytest.mark.slow
def test_eval_sequential_forward_matches_pipelined_train_forward():
    """PINNED BITWISE: eval/prediction's sequential forward produces the
    exact arrays the pipelined train forward produces on the same params
    — a checkpoint trained through the pipeline evaluates identically on
    the sequential path. SchNet exercises the PIPELINE_PRECOMPUTE
    edge-length stash, the path most likely to drift between the two
    forwards; GIN's pin rides test_pipeline_forward_matches_sequential
    (also upgraded to array_equal)."""
    from hydragnn_tpu.parallel.mesh import make_mesh
    from hydragnn_tpu.parallel.pipeline_trainer import (
        make_pipeline_forward)

    cfg, mcfg, stacked, params, tx = _trainer_fixture(model_type="SchNet")
    mesh = make_mesh((("pipe", 2),))
    out_p, _ = make_pipeline_forward(mcfg, mesh, 2, pipelined=True)(
        params, stacked)
    out_s, _ = make_pipeline_forward(mcfg, mesh, 2, pipelined=False)(
        params, stacked)
    for a, b in zip(out_p, out_s):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pipeline_data_shards_parity():
    """pipe x data composition: the same 4 microbatches trained as 2
    data replicas x 2 microbatches (D=2 on a (pipe, data) mesh) produce
    the same loss BITWISE (identical per-micro forwards, same flat
    reduction) and the same updated params to float tolerance as the
    pipe-only run — with and without ZeRO opt-state sharding."""
    from hydragnn_tpu.parallel.mesh import make_mesh
    from hydragnn_tpu.parallel.pipeline_trainer import (
        make_pipeline_train_step, place_pipeline_batch)

    cfg, mcfg, stacked, params, tx = _trainer_fixture(micro=4)
    mesh1 = make_mesh((("pipe", 2),))
    step1 = make_pipeline_train_step(mcfg, mesh1, 2, tx, schedule="1f1b")
    s1, m1 = step1(_state(params, tx), stacked)

    mesh2 = make_mesh((("pipe", 2), ("data", 2)))
    placed = place_pipeline_batch(stacked, mesh2, data_shards=2)
    # zero_opt=True is the stronger claim (sharded opt state must not
    # change the update values); the zero=False leg adds a compile for
    # a strictly weaker assertion — tier-1 budget
    step2 = make_pipeline_train_step(mcfg, mesh2, 2, tx,
                                     schedule="1f1b", data_shards=2,
                                     zero_opt=True)
    s2, m2 = step2(_state(params, tx), placed)
    np.testing.assert_array_equal(np.asarray(m1["loss"]),
                                  np.asarray(m2["loss"]))
    for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                    jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-6, atol=1e-7)


@pytest.mark.slow
def test_pipeline_data_shards_config_trains():
    """Training.pipeline_data_shards from a JSON config: the pipe x data
    mesh trains end-to-end (loader stacks D x M microbatches)."""
    cfg = _cfg(2)
    tr = cfg["NeuralNetwork"]["Training"]
    tr["pipeline_data_shards"] = 2
    tr["Optimizer"] = {"type": "AdamW", "learning_rate": 1e-2,
                       "use_zero_redundancy": True}
    state, history, _, _ = run_training(cfg, datasets=_splits())
    assert all(np.isfinite(v) for v in history["train_loss"])


def test_pipeline_validation_new_errors():
    """The new schedule/data-shard validations raise actionable
    ValueErrors at config time (never bare asserts)."""
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.parallel.pipeline_trainer import (
        validate_pipeline_config)

    samples = deterministic_graph_dataset(num_configs=8)
    cfg = make_config("GIN", num_conv_layers=8)
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    # 1f1b needs M a multiple of S (or M <= S)
    with pytest.raises(ValueError, match="multiple of pipeline_stages"):
        validate_pipeline_config(mcfg, 4, batch_size=24, microbatches=6,
                                 schedule="1f1b")
    # ... but gpipe accepts the same M
    validate_pipeline_config(mcfg, 4, batch_size=24, microbatches=6,
                             schedule="gpipe")
    # and M <= S is one window — fine on either schedule
    validate_pipeline_config(mcfg, 4, batch_size=24, microbatches=3,
                             schedule="1f1b")
    with pytest.raises(ValueError, match="exceeds device count"):
        validate_pipeline_config(mcfg, 4, batch_size=32, microbatches=4,
                                 data_shards=4)
    with pytest.raises(ValueError, match="data shards"):
        validate_pipeline_config(mcfg, 2, batch_size=12, microbatches=4,
                                 data_shards=2)
    with pytest.raises(ValueError, match="pipeline_schedule"):
        validate_pipeline_config(mcfg, 2, batch_size=16, microbatches=4,
                                 schedule="interleaved")
    # microbatches=0 hits the >= 2 ValueError, not a ZeroDivisionError
    # from the batch-divisibility modulo (HYDRAGNN_PIPE_MICROBATCHES=0
    # reaches here as an explicit value — the `or`-fallback is config-only)
    for bad_m in (0, 1):
        with pytest.raises(ValueError, match="must be >= 2"):
            validate_pipeline_config(mcfg, 2, batch_size=16,
                                     microbatches=bad_m)


def test_pipeline_telemetry_bubble_metrics(tmp_path):
    """Satellite: pipelined runs report through the PR 7 telemetry layer
    — the closed-form bubble_frac gauge, pipeline fields in the epoch
    JSONL (data bucket: deterministic) land in the run artifacts every
    epoch, not just under BENCH_MFU. (The formula-made `pipe.stage_idle`
    spans went in PR 26: a span recorder holds measurements.)"""
    import json as _json
    cfg = _cfg(2)
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 1  # one epoch pins
    # the whole reporting path; more only costs tier-1 budget
    tel_dir = str(tmp_path / "tel")
    cfg["NeuralNetwork"]["Training"]["Telemetry"] = {
        "enabled": True, "dir": tel_dir}
    state, history, _, _ = run_training(cfg, datasets=_splits())
    events = [_json.loads(l) for l in
              open(tel_dir + "/telemetry.jsonl")]
    epochs = [e for e in events if e["kind"] == "epoch"]
    assert len(epochs) == 1
    for e in epochs:
        assert e["data"]["pipeline_schedule"] == "1f1b"
        assert e["data"]["pipeline_stages"] == 2
        assert 0 < e["data"]["pipeline_bubble_frac"] < 1
        assert 0 < e["data"]["pipeline_train_bubble_frac"] < 1
        # NO per-step MFU numerator on pipelined runs: the shard_map
        # step's cost analysis is per-partition (and counts remat
        # recompute), so the gauge is skipped with a log line instead of
        # reporting a ~S-fold-understated number (BENCH_MFU probes the
        # sequential step for the honest numerator)
        assert "achieved_flops_per_s" not in e["timing"]
    assert "achieved_flops_per_s" not in history
    prom = open(tel_dir + "/metrics.prom").read()
    assert "hydragnn_pipeline_bubble_frac" in prom
    assert "hydragnn_pipeline_train_bubble_frac" in prom
    assert "hydragnn_train_achieved_flops_per_s" not in prom


@pytest.mark.slow
def test_bench_mfu_smoke(tmp_path):
    """Slow lane (nightly mfu-bench): the BENCH_MFU mode emits its JSON
    artifact with the acceptance invariants — measured bubble within the
    adjudication band of (S-1)/(M+S-1), >= 2x lower peak-live-activation
    bytes for 1F1B+remat vs GPipe at (S=4, M=8), and the deep stack
    trains under a stage budget GPipe-without-remat exceeds. (The
    repo-root BENCH_MFU.json is the full 32-layer capture the nightly
    job regenerates — the smoke writes to a scratch path.)"""
    import json as _json
    import subprocess
    import sys
    out_path = str(tmp_path / "BENCH_MFU.json")
    env = dict(os.environ, BENCH_MFU="1",
               JAX_PLATFORMS="cpu", BENCH_MFU_LAYERS="16",
               BENCH_MFU_STEPS="2", BENCH_MFU_OUT=out_path)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "..", "bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    out = _json.loads(r.stdout.strip().splitlines()[-1])
    assert out["mode"] == "mfu"
    assert os.path.exists(out_path)  # the nightly's uploaded artifact
    v = out["variants"]
    for name in ("sequential", "gpipe", "gpipe_remat", "1f1b",
                 "1f1b_remat"):
        assert v[name]["graphs_per_s"] > 0
        assert v[name]["achieved_flops_per_s"] > 0
    # the deep-stack memory acceptance: >= 2x, budget separates the two
    deep = out["deep_stack"]
    assert deep["activation_bytes_ratio"] >= 2.0, deep
    assert deep["gpipe_exceeds_budget"] and deep["onef1b_remat_fits_budget"]
    assert deep["trains"]["finite"]
    assert deep["trains"]["loss_after"] < deep["trains"]["loss_first_step"]
    # measured bubble against the closed form (factor-of-two band — CPU
    # wall clocks; the artifact records both numbers for inspection)
    assert out["bubble"]["within_tolerance"], out["bubble"]
    # losses across variants agree (same params, same data): sequential
    # vs gpipe bitwise, 1f1b to float tolerance (window reassociation)
    l0 = v["sequential"]["loss_first_step"]
    assert v["gpipe"]["loss_first_step"] == l0
    assert abs(v["1f1b"]["loss_first_step"] - l0) <= 1e-6 * abs(l0) + 1e-9


@pytest.mark.slow
def test_deep_stack_example_config_trains():
    """The shipped deep-stack demonstration config (32-layer
    SchNet-invariant, 1f1b + remat over 4 stages) parses and trains —
    the configuration whose GPipe-without-remat activation footprint
    exceeds the stage budget (BENCH_MFU.json adjudicates the memory
    claim; this pins the config itself end-to-end)."""
    import json as _json
    path = os.path.join(os.path.dirname(__file__), "..", "examples",
                        "deep_stack", "deep_stack_32l.json")
    cfg = _json.load(open(path))
    tr = cfg["NeuralNetwork"]["Training"]
    assert tr["pipeline_schedule"] == "1f1b" and tr["pipeline_remat"]
    tr["num_epoch"] = 1  # smoke: one epoch of the real shape
    state, history, _, _ = run_training(cfg, datasets=_splits())
    assert all(np.isfinite(v) for v in history["train_loss"])
