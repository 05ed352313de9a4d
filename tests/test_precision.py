"""Mixed-precision policy layer (docs/mixed_precision.md):
resolver precedence + strict parsing, f32 segment accumulation, the
NaN/overflow watchdog, and the reduced-precision serving parity bound.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.deterministic_data import deterministic_graph_dataset
from tests.utils import prepare


def test_resolve_precision_precedence(monkeypatch):
    """override > HYDRAGNN_PRECISION > Architecture.dtype > float32, with
    aliases canonicalized."""
    from hydragnn_tpu.train.precision import resolve_precision
    monkeypatch.delenv("HYDRAGNN_PRECISION", raising=False)
    assert resolve_precision() == "float32"
    assert resolve_precision("bf16") == "bfloat16"
    assert resolve_precision("bfloat16", "f32") == "float32"
    monkeypatch.setenv("HYDRAGNN_PRECISION", "bf16")
    assert resolve_precision() == "bfloat16"
    assert resolve_precision("float32") == "bfloat16"      # env over cfg
    assert resolve_precision(None, "fp32") == "float32"    # override wins


def test_resolve_precision_strict_typo(monkeypatch):
    """A typo value warns and falls through instead of taking effect."""
    from hydragnn_tpu.train.precision import resolve_precision
    monkeypatch.setenv("HYDRAGNN_PRECISION", "bfloat")
    assert resolve_precision() == "float32"
    assert resolve_precision("bfloat16") == "bfloat16"     # cfg still heard
    # a typo'd override falls through to the (valid) env value
    monkeypatch.setenv("HYDRAGNN_PRECISION", "bf16")
    assert resolve_precision(None, "bf17") == "bfloat16"


def test_serving_precision_knob(monkeypatch):
    """Serving.precision block key + HYDRAGNN_SERVE_PRECISION env with
    strict parsing; unset inherits (None)."""
    from hydragnn_tpu.serving.config import resolve_serving
    monkeypatch.delenv("HYDRAGNN_SERVE_PRECISION", raising=False)
    assert resolve_serving({}).precision is None
    assert resolve_serving(
        {"Serving": {"precision": "bf16"}}).precision == "bfloat16"
    monkeypatch.setenv("HYDRAGNN_SERVE_PRECISION", "float32")
    assert resolve_serving(
        {"Serving": {"precision": "bf16"}}).precision == "float32"
    monkeypatch.setenv("HYDRAGNN_SERVE_PRECISION", "bf166")  # typo: warn,
    assert resolve_serving(                                  # keep config
        {"Serving": {"precision": "bf16"}}).precision == "bfloat16"


def test_segment_sum_bf16_accumulates_f32():
    """The policy's numeric point: a long bf16 segment sum accumulated
    pairwise in bf16 drifts; ops/segment.segment_sum accumulates f32 and
    stores back bf16, so the result is the f32 sum rounded ONCE."""
    from hydragnn_tpu.ops import segment as seg
    rng = np.random.RandomState(0)
    e, f = 4096, 4
    data32 = rng.rand(e, f).astype(np.float32)
    data16 = jnp.asarray(data32).astype(jnp.bfloat16)
    ids = jnp.zeros((e,), jnp.int32)            # ONE segment: worst case
    out = seg.segment_sum(data16, ids, 1)
    assert out.dtype == jnp.bfloat16
    want = jnp.sum(data16.astype(jnp.float32), axis=0).astype(jnp.bfloat16)
    assert np.array_equal(np.asarray(out[0], np.float32),
                          np.asarray(want, np.float32))
    # and it is strictly better than native bf16 accumulation would be:
    # the f32-accumulated result matches the f64 truth to bf16 round-off
    truth = data32.astype(np.float64).sum(axis=0)
    rel = np.abs(np.asarray(out[0], np.float64) - truth) / truth
    assert rel.max() < 2 ** -8, rel.max()


def test_nonfinite_watchdog_step_metric():
    """train_step emits nonfinite_steps per step: 0 on a healthy batch,
    1 when the loss/grads go non-finite (here: a NaN input feature)."""
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.train_step import TrainState, make_train_step

    samples = deterministic_graph_dataset(num_configs=8)
    cfg, mcfg, batch = prepare("GIN", samples)
    model = create_model(mcfg)
    tx = select_optimizer({"Optimizer": {"type": "AdamW",
                                         "learning_rate": 1e-3}})
    step = make_train_step(model, mcfg, tx, donate=False)
    state = TrainState.create(init_params(model, batch), tx)
    state, metrics = step(state, batch)
    assert float(metrics["nonfinite_steps"]) == 0.0
    bad = batch.replace(x=batch.x.at[0, 0].set(jnp.nan))
    _, metrics = step(state, bad)
    assert float(metrics["nonfinite_steps"]) == 1.0


def test_bf16_forward_within_serving_bound():
    """The documented reduced-precision bound
    (serving/engine.SERVE_REDUCED_RTOL/ATOL) holds for the bf16 forward
    vs the fp32 forward on an identical batch — the light tier-1 version
    of the engine-level adjudication below."""
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.serving.engine import (SERVE_REDUCED_ATOL,
                                             SERVE_REDUCED_RTOL)
    from hydragnn_tpu.train.train_step import make_forward_fn

    samples = deterministic_graph_dataset(num_configs=8)
    cfg, mcfg, batch = prepare("PNA", samples)
    model = create_model(mcfg)
    variables = init_params(model, batch)
    out32, _ = make_forward_fn(model, mcfg, "float32")(variables, batch)
    out16, _ = make_forward_fn(model, mcfg, "bfloat16")(variables, batch)
    for a, b in zip(out32, out16):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        bound = SERVE_REDUCED_ATOL + SERVE_REDUCED_RTOL * np.abs(a)
        assert (np.abs(b - a) <= bound).all(), float(
            (np.abs(b - a) - bound).max())


@pytest.mark.slow
def test_bf16_engine_within_bound_and_carries_parity():
    """Engine-level adjudication (acceptance contract): a bf16 engine's
    outputs sit inside the documented tolerance bound vs the fp32 engine
    on IDENTICAL buckets; bf16 futures carry the bound, fp32 futures
    advertise bitwise; and batched-vs-single parity stays BITWISE within
    the bf16 engine (same compiled program)."""
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.serving.engine import (SERVE_REDUCED_ATOL,
                                             SERVE_REDUCED_RTOL,
                                             InferenceEngine)

    samples = deterministic_graph_dataset(num_configs=12)
    cfg, mcfg, batch = prepare("GIN", samples)
    model = create_model(mcfg)
    variables = init_params(model, batch)
    engines = {}
    try:
        for dtype in ("float32", "bfloat16"):
            engines[dtype] = InferenceEngine(
                model, variables, mcfg, reference_samples=samples,
                max_batch_size=4, max_wait_ms=1.0, num_buckets=1,
                compute_dtype=dtype)
        futs32 = [engines["float32"].submit(s) for s in samples[:8]]
        futs16 = [engines["bfloat16"].submit(s) for s in samples[:8]]
        res32 = [f.result(timeout=300) for f in futs32]
        res16 = [f.result(timeout=300) for f in futs16]
        assert all(f.parity == "bitwise" and f.parity_rtol == 0.0
                   for f in futs32)
        assert all(f.parity == "tolerance"
                   and f.parity_rtol == SERVE_REDUCED_RTOL
                   and f.parity_atol == SERVE_REDUCED_ATOL
                   for f in futs16)
        for r32, r16, f16 in zip(res32, res16, futs16):
            for a, b in zip(r32, r16):
                a = np.asarray(a, np.float32)
                b = np.asarray(b, np.float32)
                bound = f16.parity_atol + f16.parity_rtol * np.abs(a)
                assert (np.abs(b - a) <= bound).all()
        # same-bucket batched-vs-single parity stays bitwise at bf16
        for i, f16 in enumerate(futs16):
            single = engines["bfloat16"].forward_single(samples[i],
                                                        bucket=f16.bucket)
            for a, b in zip(res16[i], single):
                assert np.array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        assert engines["bfloat16"].stats()["parity"] == "tolerance"
    finally:
        for eng in engines.values():
            eng.shutdown()


def test_bf16_training_smoke_finite():
    """Two bf16 optimizer steps on the deterministic dataset: loss stays
    finite, the watchdog counts zero, and params remain f32 masters."""
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.train_step import TrainState, make_train_step

    samples = deterministic_graph_dataset(num_configs=8)
    cfg, mcfg, batch = prepare("GIN", samples)
    model = create_model(mcfg)
    tx = select_optimizer({"Optimizer": {"type": "AdamW",
                                         "learning_rate": 1e-3}})
    step = make_train_step(model, mcfg, tx, donate=False,
                           compute_dtype="bfloat16")
    state = TrainState.create(init_params(model, batch), tx)
    for _ in range(2):
        state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert float(metrics["nonfinite_steps"]) == 0.0
    for leaf in jax.tree_util.tree_leaves(state.params):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.float32  # f32 master copies


@pytest.mark.slow
def test_bench_kernels_smoke(tmp_path):
    """Slow-lane BENCH_KERNELS smoke (the nightly kernel-bench job): the
    mode must emit its JSON with the fp32/bf16 grid, the int8 forward rows,
    and the bf16 and int8 serving legs inside their documented tolerance
    bounds."""
    import json
    import os
    import subprocess
    import sys

    out_path = tmp_path / "BENCH_KERNELS.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_KERNELS="1",
               BENCH_KERNELS_OUT=str(out_path),
               BENCH_KERNELS_BATCH="4", BENCH_KERNELS_NODES="24",
               BENCH_KERNELS_DEG="6", BENCH_KERNELS_HIDDEN="32",
               BENCH_KERNELS_STEPS="2")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, os.path.join(repo, "bench.py")],
                       env=env, capture_output=True, text=True,
                       timeout=1500, cwd=repo)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(out_path.read_text())
    points = {(p["model"], p["dtype"]): p for p in out["grid"]}
    assert len(points) == 4
    for m in ("SchNet", "PNA"):
        assert points[(m, "float32")]["fwd_max_abs_diff_vs_fp32"] == 0.0
        assert all(points[(m, dt)]["graphs_per_s"] > 0
                   for dt in ("float32", "bfloat16"))
    assert {row["model"] for row in out["int8_forward"]} == {"SchNet", "PNA"}
    assert out["serving"]["bf16_within_bound"] is True
    assert out["serving"]["int8_within_bound"] is True
    assert out["serving"]["fp32_parity"] == "bitwise"
    assert out["serving"]["bf16_parity"] == "tolerance"
