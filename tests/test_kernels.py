"""Pallas kernel tests (interpret mode — CPU backend).

Mirrors the reference's reliance on torch_scatter correctness (the segment
ops underpin every conv); the TPU-path kernel must agree with XLA's
segment_sum bit-for-bit-ish in fwd and bwd.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.kernels.segment_pallas import segment_sum_pallas


@pytest.mark.parametrize("e,f,n", [(700, 24, 130), (64, 8, 5), (2048, 128, 512)])
def test_segment_sum_pallas_forward(e, f, n):
    rng = np.random.RandomState(0)
    data = jnp.asarray(rng.randn(e, f).astype(np.float32))
    ids = jnp.asarray(rng.randint(0, n, e).astype(np.int32))
    ref = jax.ops.segment_sum(data, ids, n)
    out = segment_sum_pallas(data, ids, n, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_segment_sum_pallas_grad():
    rng = np.random.RandomState(1)
    e, f, n = 300, 16, 40
    data = jnp.asarray(rng.randn(e, f).astype(np.float32))
    ids = jnp.asarray(rng.randint(0, n, e).astype(np.int32))
    w = jnp.asarray(rng.randn(n, f).astype(np.float32))
    gp = jax.grad(lambda d: jnp.sum(segment_sum_pallas(d, ids, n, True) * w))(data)
    gr = jax.grad(lambda d: jnp.sum(jax.ops.segment_sum(d, ids, n) * w))(data)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               rtol=2e-5, atol=2e-5)


def test_segment_sum_pallas_empty_segments():
    # segments with no edges must be exactly zero
    data = jnp.ones((8, 4), jnp.float32)
    ids = jnp.asarray([0, 0, 3, 3, 3, 7, 7, 7], jnp.int32)
    out = np.asarray(segment_sum_pallas(data, ids, 9, True))
    assert out[1].sum() == 0 and out[8].sum() == 0
    assert out[0].sum() == 8 and out[3].sum() == 12


def test_pna_aggregate_fused_matches_separate():
    """Fused PNA aggregation must equal the separate segment ops."""
    import numpy as np
    import jax.numpy as jnp
    from hydragnn_tpu.ops import segment as seg
    rng = np.random.RandomState(0)
    E, N, F = 200, 40, 16
    data = jnp.asarray(rng.randn(E, F).astype(np.float32))
    ids = jnp.asarray(rng.randint(0, N, E).astype(np.int32))
    mask = jnp.asarray(rng.rand(E) > 0.2)
    mean, mn, mx, sd, deg = seg.pna_aggregate(data, ids, N, mask)
    np.testing.assert_allclose(
        np.asarray(mean),
        np.asarray(seg.segment_mean(data, ids, N, mask)), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(mn), np.asarray(seg.segment_min(data, ids, N, mask)),
        atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(mx), np.asarray(seg.segment_max(data, ids, N, mask)),
        atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(sd), np.asarray(seg.segment_std(data, ids, N, mask)),
        atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(deg), np.asarray(seg.degree(ids, N, mask)), atol=1e-6)


def test_fused_neighbor_aggregate_matches_reference():
    """kernels/nbr_pallas.py == proj_i[:,None,:] + proj_j[nbr] followed by
    ops/segment.neighbor_aggregate — values and gradients (the backward
    is the remat'd XLA path, but it must differentiate the same math)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.kernels.nbr_pallas import fused_neighbor_aggregate
    from hydragnn_tpu.ops import segment as seg

    rng = np.random.RandomState(0)
    n, k, f = 136, 9, 32   # NOT a block multiple: exercises the row pad
    pi = jnp.asarray(rng.randn(n, f).astype(np.float32))
    pj = jnp.asarray(rng.randn(n, f).astype(np.float32))
    nbr = jnp.asarray(rng.randint(0, n, (n, k)).astype(np.int32))
    mask = jnp.asarray(rng.rand(n, k) > 0.3)

    got = fused_neighbor_aggregate(pi, pj, nbr, mask, 64, True)
    h = pi[:, None, :] + pj[nbr]
    want = seg.neighbor_aggregate(h, mask)
    for g, w, name in zip(got, want, ("mean", "min", "max", "std", "deg")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5, err_msg=name)

    def loss_fused(pi, pj):
        mean, mn, mx, sd, deg = fused_neighbor_aggregate(
            pi, pj, nbr, mask, 64, True)
        return jnp.sum(mean * mn + mx * sd) + jnp.sum(deg * 0.1)

    def loss_ref(pi, pj):
        mean, mn, mx, sd, deg = seg.neighbor_aggregate(
            pi[:, None, :] + pj[nbr], mask)
        return jnp.sum(mean * mn + mx * sd) + jnp.sum(deg * 0.1)

    g_f = jax.grad(loss_fused, argnums=(0, 1))(pi, pj)
    g_r = jax.grad(loss_ref, argnums=(0, 1))(pi, pj)
    for gf, gr in zip(g_f, g_r):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-4, atol=1e-5)


def _int_valued(rng, shape, lo=-3, hi=4, dtype=np.float32):
    """Integer-valued float data: every partial sum is exactly
    representable (fp32 AND bf16 at these magnitudes), so ANY summation
    order gives the same bits — the bit-level indexing/masking contract
    that stays pinnable across the MXU reformulation (an MXU/matmul
    reduction contracts whole tiles at once, so random-float sums can
    differ from the sequential scatter in the last ulp — see the
    kernels/fused_mp_pallas.py numerical-contract docstring)."""
    return jnp.asarray(rng.randint(lo, hi, shape)).astype(dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_segment_sum_pallas_bitwise_across_dtypes(dtype):
    """Parity-suite pin: interpret-mode BITWISE equality vs
    jax.ops.segment_sum on exactly-representable data, across dtypes and
    ragged/padded segment ids — including ids that only hit a strict
    prefix of the segments (the collate padding shape) and an id stream
    that is unsorted with empty segments interleaved."""
    rng = np.random.RandomState(3)
    e, f, n = 530, 16, 96                   # e NOT a tile multiple
    data = _int_valued(rng, (e, f), dtype=dtype)
    # ragged/padded ids: unsorted, empty segments, a padding tail all
    # pointing at the last segment (the collate convention)
    ids = rng.randint(0, n - 7, e).astype(np.int32)
    ids[-40:] = n - 1
    ids = jnp.asarray(ids)
    ref = jax.ops.segment_sum(data, ids, n)
    out = segment_sum_pallas(data, ids, n, True)
    assert out.dtype == ref.dtype
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(ref, np.float32)), dtype


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_segment_sum_pallas_vjp_bitwise(dtype):
    """The VJP is a gather (grad_out[segment_ids]) on both paths —
    bitwise for ANY data, random floats included."""
    rng = np.random.RandomState(4)
    e, f, n = 300, 8, 40
    data = jnp.asarray(rng.randn(e, f).astype(np.float32)).astype(dtype)
    ids = jnp.asarray(rng.randint(0, n, e).astype(np.int32))
    w = jnp.asarray(rng.randn(n, f).astype(np.float32)).astype(dtype)

    def loss(fn, d):
        return jnp.sum((fn(d) * w).astype(jnp.float32))

    gp = jax.grad(lambda d: loss(
        lambda x: segment_sum_pallas(x, ids, n, True), d))(data)
    gr = jax.grad(lambda d: loss(
        lambda x: jax.ops.segment_sum(x, ids, n), d))(data)
    assert np.array_equal(np.asarray(gp, np.float32),
                          np.asarray(gr, np.float32))


def _edge_problem(rng, n, e, f):
    send = jnp.asarray(rng.randint(0, n, e).astype(np.int32))
    recv = jnp.asarray(rng.randint(0, n, e).astype(np.int32))
    mask = jnp.asarray(rng.rand(e) > 0.25)
    return send, recv, mask


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_filter_scatter_bitwise_exact_data(dtype):
    """kernels/fused_mp_pallas.fused_filter_scatter == the unfused
    segment_sum(h[send] * w, recv) BITWISE on exactly-representable data
    (fwd), and the backward is bitwise for ANY data (remat through the
    unfused formulation)."""
    from hydragnn_tpu.kernels.fused_mp_pallas import fused_filter_scatter
    from hydragnn_tpu.ops import segment as seg

    rng = np.random.RandomState(0)
    n, e, f = 150, 700, 16                  # neither axis a tile multiple
    send, recv, mask = _edge_problem(rng, n, e, f)
    h = _int_valued(rng, (n, f), -2, 3, dtype)
    w = _int_valued(rng, (e, f), -2, 3, dtype)
    out = fused_filter_scatter(h, w, send, recv, mask, n, True)
    ref = seg.segment_sum(h[send] * w, recv, n, mask)
    assert out.dtype == ref.dtype
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(ref, np.float32))

    # backward: random-float primals — the remat'd VJP must still be
    # bitwise against the unfused path
    hf = jnp.asarray(rng.randn(n, f).astype(np.float32)).astype(dtype)
    wf = jnp.asarray(rng.randn(e, f).astype(np.float32)).astype(dtype)
    g = jnp.asarray(rng.randn(n, f).astype(np.float32))

    def loss(fn, a, b):
        return jnp.sum(fn(a, b).astype(jnp.float32) * g)

    gf = jax.grad(lambda a, b: loss(
        lambda x, y: fused_filter_scatter(x, y, send, recv, mask, n, True),
        a, b), argnums=(0, 1))(hf, wf)
    gr = jax.grad(lambda a, b: loss(
        lambda x, y: seg.segment_sum(x[send] * y, recv, n, mask),
        a, b), argnums=(0, 1))(hf, wf)
    for a, b in zip(gf, gr):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))


def test_fused_filter_scatter_random_float_close():
    """Random fp32 forwards agree to the last ulp (the MXU tile
    contraction reorders the sum — documented contract)."""
    from hydragnn_tpu.kernels.fused_mp_pallas import fused_filter_scatter
    from hydragnn_tpu.ops import segment as seg

    rng = np.random.RandomState(1)
    n, e, f = 130, 640, 24
    send, recv, mask = _edge_problem(rng, n, e, f)
    h = jnp.asarray(rng.randn(n, f).astype(np.float32))
    w = jnp.asarray(rng.randn(e, f).astype(np.float32))
    out = fused_filter_scatter(h, w, send, recv, mask, n, True)
    ref = seg.segment_sum(h[send] * w, recv, n, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


# bfloat16 runs 29 s in interpret mode (the per-edge min/max loop is
# unrolled): slow lane since PR 21 (tier-1 budget); float32 stays
@pytest.mark.parametrize("dtype", [
    "float32", pytest.param("bfloat16", marks=pytest.mark.slow)])
def test_fused_pna_edge_aggregate_bitwise_exact_data(dtype):
    """fused_pna_edge_aggregate == pna_aggregate(proj_i[recv] +
    proj_j[send]) BITWISE on exactly-representable data for all five
    statistics, forward AND composite-loss backward (the epilogue is the
    SHARED ops/segment.pna_stats_epilogue subgraph, so cotangent
    accumulation through the mean/std interdependence is identical)."""
    from hydragnn_tpu.kernels.fused_mp_pallas import fused_pna_edge_aggregate
    from hydragnn_tpu.ops import segment as seg

    rng = np.random.RandomState(0)
    n, e, f = 150, 700, 16
    send, recv, mask = _edge_problem(rng, n, e, f)
    pi = _int_valued(rng, (n, f), -2, 3, dtype)
    pj = _int_valued(rng, (n, f), -2, 3, dtype)
    got = fused_pna_edge_aggregate(pi, pj, send, recv, mask, n, 1e-5, True)
    want = seg.pna_aggregate(pi[recv] + pj[send], recv, n, mask)
    for a, b, name in zip(got, want, ("mean", "min", "max", "std", "deg")):
        assert a.dtype == b.dtype, name
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32)), (dtype, name)

    # composite loss touching every statistic: gradients bitwise too
    def loss(fn, a, b):
        mean, mn, mx, sd, deg = fn(a, b)
        return (jnp.sum((mean * mn + mx * sd).astype(jnp.float32))
                + 0.1 * jnp.sum(deg.astype(jnp.float32)))

    gf = jax.grad(lambda a, b: loss(
        lambda x, y: fused_pna_edge_aggregate(x, y, send, recv, mask, n,
                                              1e-5, True), a, b),
        argnums=(0, 1))(pi, pj)
    gr = jax.grad(lambda a, b: loss(
        lambda x, y: seg.pna_aggregate(x[recv] + y[send], recv, n, mask),
        a, b), argnums=(0, 1))(pi, pj)
    for a, b in zip(gf, gr):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32)), dtype


def test_fused_pna_edge_aggregate_random_float_close():
    from hydragnn_tpu.kernels.fused_mp_pallas import fused_pna_edge_aggregate
    from hydragnn_tpu.ops import segment as seg

    rng = np.random.RandomState(2)
    n, e, f = 130, 640, 24
    send, recv, mask = _edge_problem(rng, n, e, f)
    pi = jnp.asarray(rng.randn(n, f).astype(np.float32))
    pj = jnp.asarray(rng.randn(n, f).astype(np.float32))
    got = fused_pna_edge_aggregate(pi, pj, send, recv, mask, n, 1e-5, True)
    want = seg.pna_aggregate(pi[recv] + pj[send], recv, n, mask)
    for a, b, name in zip(got, want, ("mean", "min", "max", "std", "deg")):
        # std amplifies the last-ulp sum difference through the
        # sq/cnt - mean^2 cancellation when var is near zero — wider
        # relative tolerance there, tight everywhere else
        rtol = 5e-3 if name == "std" else 2e-5
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=2e-5, err_msg=name)


# slow lane since PR 21 (tier-1 budget): 17 s in interpret mode; the kernels' parity tests stay in tier-1
@pytest.mark.slow
def test_fused_mp_flag_routes_models(monkeypatch):
    """HYDRAGNN_FUSED_MP=1 routes the SchNet and PNA edge-list branches
    through the fused kernels; outputs match the default path. Strict
    parsing: a typo value warns and stays OFF."""
    from tests.deterministic_data import deterministic_graph_dataset
    from tests.utils import prepare
    from hydragnn_tpu.kernels import fused_mp_pallas as kfm
    from hydragnn_tpu.models.create import create_model, init_params

    samples = deterministic_graph_dataset(num_configs=8)
    monkeypatch.setattr(kfm, "_RESOLVED_FLAG", None)
    monkeypatch.setenv("HYDRAGNN_FUSED_MP", "ture")  # the classic typo
    assert kfm.resolve_fused_mp_flag(refresh=True) is False
    for model_type in ("SchNet", "PNA"):
        cfg, mcfg, batch = prepare(model_type, samples)
        model = create_model(mcfg)
        variables = init_params(model, batch)
        monkeypatch.delenv("HYDRAGNN_FUSED_MP", raising=False)
        assert kfm.resolve_fused_mp_flag(refresh=True) is False
        out_default, _ = model.apply(variables, batch, train=False)
        monkeypatch.setenv("HYDRAGNN_FUSED_MP", "1")
        assert kfm.resolve_fused_mp_flag(refresh=True) is True
        out_fused, _ = model.apply(variables, batch, train=False)
        for a, b in zip(out_default, out_fused):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5,
                                       err_msg=model_type)


@pytest.mark.slow
def test_bench_kernels_smoke(tmp_path):
    """Slow-lane BENCH_KERNELS smoke (the nightly kernel-bench job): the
    mode must emit its JSON with the fused/bf16 grid, fp32 fused parity
    at zero forward diff, and the bf16 serving leg inside the documented
    tolerance bound."""
    import json
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
    points = {(p["model"], p["fused"], p["dtype"]): p for p in out["grid"]}
    assert len(points) == 8
    for m in ("SchNet", "PNA"):
        # random-float weights: fused fp32 agrees to the last ulp (the
        # bitwise contract is pinned on exact data by the tier-1 parity
        # suite above; see the fused_mp_pallas numerical-contract note)
        assert points[(m, True, "float32")][
            "fwd_max_abs_diff_vs_unfused_fp32"] < 1e-5
        assert all(points[(m, fz, dt)]["graphs_per_s"] > 0
                   for fz in (False, True)
                   for dt in ("float32", "bfloat16"))
    assert out["serving"]["bf16_within_bound"] is True
    assert out["serving"]["fp32_parity"] == "bitwise"
    assert out["serving"]["bf16_parity"] == "tolerance"


def test_fused_neighbor_aggregate_in_pna(monkeypatch):
    """HYDRAGNN_PALLAS_NBR=1 routes PNA's dense branch through the fused
    kernel; forward outputs match the default path."""
    import numpy as np
    import jax

    from tests.deterministic_data import deterministic_graph_dataset
    from tests.utils import prepare
    from hydragnn_tpu.models.create import create_model, init_params

    samples = deterministic_graph_dataset(num_configs=8)
    cfg, mcfg, batch = prepare("PNA", samples)
    from hydragnn_tpu.graphs.batch import with_neighbor_format
    batch = with_neighbor_format(batch, k=12)
    model = create_model(mcfg)
    variables = init_params(model, batch)
    # the flag is pinned at resolve time, not read per-trace — refresh it
    # around each env change exactly like a step factory would, and let
    # monkeypatch restore the pre-test pin at teardown
    from hydragnn_tpu.kernels import nbr_pallas as knp
    monkeypatch.setattr(knp, "_RESOLVED_FLAG", None)
    monkeypatch.delenv("HYDRAGNN_PALLAS_NBR", raising=False)
    assert knp.resolve_nbr_pallas_flag(refresh=True) is False
    out_default, _ = model.apply(variables, batch, train=False)

    monkeypatch.setenv("HYDRAGNN_PALLAS_NBR", "1")
    assert knp.resolve_nbr_pallas_flag(refresh=True) is True
    out_fused, _ = model.apply(variables, batch, train=False)
    for a, b in zip(out_default, out_fused):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flag_on_but_kernel_not_taken_is_logged(monkeypatch, caplog):
    """A user-set kernel flag must never be a silent no-op: when it is on
    and the gate sends the conv to the XLA path anyway, the log names the
    kernel, the shape and the reason — once per distinct case."""
    import logging

    from hydragnn_tpu import kernels
    from hydragnn_tpu.kernels import fused_mp_pallas as kfm
    from hydragnn_tpu.kernels import nbr_pallas as knp
    kernels.kernel_not_taken.cache_clear()
    monkeypatch.setattr(knp, "_RESOLVED_FLAG", True)
    monkeypatch.setattr(kfm, "_RESOLVED_FLAG", True)
    big = (16384, 128)          # 8 MB of f32: over the 4 MB VMEM bound
    with caplog.at_level(logging.WARNING, logger="hydragnn_tpu"):
        assert knp.nbr_pallas_enabled((2048, 128), jnp.float32)
        assert kfm.fused_mp_enabled((2048, 128), jnp.float32)
        assert caplog.text == ""                 # taken: nothing to say
        assert not knp.nbr_pallas_enabled(big, jnp.float32)
        assert not knp.nbr_pallas_enabled(big, jnp.float32)   # 3 layers,
        assert not knp.nbr_pallas_enabled(big, jnp.float32)   # one line
        assert not knp.nbr_pallas_enabled((2048, 128), jnp.float32,
                                          edge_terms=True)    # PNAPlus
        assert not kfm.fused_mp_enabled(big, jnp.float32)
        assert not kfm.fused_mp_enabled((2048, 128), jnp.float32,
                                        has_edge_mask=False)
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 4, lines
    assert "HYDRAGNN_PALLAS_NBR" in lines[0] and "nbr_pallas" in lines[0]
    assert "(16384, 128)" in lines[0] and "VMEM bound" in lines[0]
    assert "edge_dim/rbf" in lines[1]
    assert "HYDRAGNN_FUSED_MP" in lines[2] and "fused_mp_pallas" in lines[2]
    assert "no edge mask" in lines[3]
    # flag off: the gate is silent whatever the shape
    monkeypatch.setattr(knp, "_RESOLVED_FLAG", False)
    caplog.clear()
    assert not knp.nbr_pallas_enabled(big, jnp.float32)
    assert caplog.text == ""
    kernels.kernel_not_taken.cache_clear()
