"""int8 PTQ serving tier (hydragnn_tpu/quant/,
docs/mixed_precision.md "int8", docs/serving.md "Tiered
fleets").

Contract under test:
* calibration determinism is BITWISE: two runs over the same set return
  identical scale tensors and digest, and any sharding of the set
  (merge_calibrations) reproduces the single-pass result bitwise — the
  worker-count pin that makes the scales a compile-store identity,
* padding rows are EXCLUDED from calibration (a zero-degree padding row
  through PNA's attenuation scaler carries ~1e3-magnitude garbage that
  would poison the scales and quantize every real row to zero), and
  silent channels inherit the layer's LARGEST channel scale (an
  arbitrary sentinel would dominate the folded-weight absmax),
* the int8 forward sits inside the documented 2^-3 tolerance bound vs
  fp32 on real rows; the engine echoes the bound + tier on futures and
  keeps same-bucket batched-vs-single BITWISE,
* int8 is serving-only: the train-side step/forward factories reject it
  and the config-side dtype fallback warns-and-f32,
* CompileStore.fingerprint keyed on (precision mode, calibration
  digest) never collides across modes — both tiers of a mixed fleet
  warm-restart from one store with zero fresh compiles,
* head-wise distillation is deterministic and never worse than the
  teacher-initialized student (best-iterate contract),
* TierPolicy priority/quota routing: high-priority requests land on the
  accurate tier, low on the fast tier, over-quota priority traffic is
  downgraded (counted), and a dead preferred tier falls back cross-tier
  (counted) — zero lost futures,
* the HYDRAGNN_QUANT_CALIB_SAMPLES / HYDRAGNN_FLEET_TIER_* knobs parse
  strictly (typo warns and falls back).
"""
import numpy as np
import pytest

import jax

from hydragnn_tpu.config import build_model_config, update_config
from hydragnn_tpu.graphs.batch import GraphSample, collate
from hydragnn_tpu.models.create import create_model, init_params
from hydragnn_tpu.quant import (CalibrationScales, calibrate,
                                distill_heads, int8_dense,
                                make_quantized_forward,
                                merge_calibrations, scales_digest)
from hydragnn_tpu.serving.engine import (SERVE_INT8_ATOL, SERVE_INT8_RTOL,
                                         InferenceEngine)
from hydragnn_tpu.serving.fleet import ReplicaRouter, TierPolicy
from hydragnn_tpu.train.train_step import make_forward_fn
from hydragnn_tpu.utils.devices import CompileStore

from tests.deterministic_data import deterministic_graph_dataset
from tests.utils import make_config, prepare


@pytest.fixture(scope="module")
def quantset():
    """Tiny PNA + deterministic samples — PNA because its attenuation
    scaler is the padding-garbage worst case the calibration masking
    exists for."""
    samples = deterministic_graph_dataset(num_configs=12)
    cfg, mcfg, batch = prepare("PNA", samples)
    model = create_model(mcfg)
    variables = init_params(model, batch)
    return samples, mcfg, model, variables, batch


def _scales_equal(a, b):
    return (sorted(a.scales) == sorted(b.scales)
            and all(np.array_equal(a.scales[k], b.scales[k])
                    for k in a.scales)
            and a.digest == b.digest)


# --------------------------------------------------------- calibration


def test_calibration_bitwise_deterministic(quantset):
    samples, mcfg, model, variables, _ = quantset
    c1 = calibrate(model, variables, mcfg, samples, num_samples=8)
    c2 = calibrate(model, variables, mcfg, samples, num_samples=8)
    assert _scales_equal(c1, c2)
    assert c1.num_samples == 8
    # amax tensors too — they are the merge currency
    assert all(np.array_equal(c1.amax[k], c2.amax[k]) for k in c1.amax)


def test_calibration_worker_count_pinned(quantset):
    """The shard-merge reproduces the single-pass scales BITWISE for any
    worker count — np.maximum is commutative/associative and real-row
    activations are independent of each shard's padding shape."""
    samples, mcfg, model, variables, _ = quantset
    whole = calibrate(model, variables, mcfg, samples)
    two = merge_calibrations([
        calibrate(model, variables, mcfg, samples[:6]),
        calibrate(model, variables, mcfg, samples[6:])])
    three = merge_calibrations([
        calibrate(model, variables, mcfg, samples[:4]),
        calibrate(model, variables, mcfg, samples[4:8]),
        calibrate(model, variables, mcfg, samples[8:])])
    assert _scales_equal(whole, two)
    assert _scales_equal(whole, three)
    assert two.num_samples == three.num_samples == len(samples)


def test_merge_rejects_shape_mismatch():
    a = CalibrationScales.from_amax(
        {"conv_0/lin": np.ones(4, np.float32)}, 1)
    b = CalibrationScales.from_amax(
        {"conv_0/lin": np.ones(8, np.float32)}, 1)
    with pytest.raises(ValueError, match="shape"):
        merge_calibrations([a, b])
    with pytest.raises(ValueError):
        merge_calibrations([])


def test_silent_channels_inherit_layer_max_scale():
    """A channel that never fired must NOT get an arbitrary sentinel:
    the activation scales fold into the weight rows before weight
    quantization, so a 1.0 sentinel next to ~0.01 real scales would
    dominate the per-output-channel weight absmax and crush every
    CALIBRATED row's quantized weights to zero (the conv_1 exact-zero
    regression)."""
    c = CalibrationScales.from_amax(
        {"conv_0/lin": np.array([1.27, 0.0, 2.54], np.float32)}, 4)
    s = c.scales["conv_0/lin"]
    assert s[0] == np.float32(1.27 / 127)
    assert s[2] == np.float32(2.54 / 127)
    assert s[1] == s[2]          # silent -> the layer's LARGEST scale
    # all-silent layer: 1.0 is the only choice left
    c = CalibrationScales.from_amax(
        {"conv_0/lin": np.zeros(3, np.float32)}, 1)
    assert (c.scales["conv_0/lin"] == 1.0).all()


def test_calibration_shape_keeps_axes_distinct():
    """The interceptor tells node- from edge-aligned activations by
    leading dim, so the two padding lengths must never coincide."""
    from hydragnn_tpu.quant.calibrate import _calibration_shape
    rng = np.random.RandomState(0)
    s = GraphSample(x=rng.rand(7, 1).astype(np.float32),
                    pos=rng.rand(7, 3).astype(np.float32),
                    senders=np.arange(7, dtype=np.int32),
                    receivers=np.roll(np.arange(7, dtype=np.int32), 1))
    n_node, n_edge, _ = _calibration_shape([s])
    assert n_node == 8 and n_edge == 16   # collision bumped away


def test_digest_tracks_scales():
    s1 = {"conv_0/lin": np.array([0.01, 0.02], np.float32)}
    s2 = {"conv_0/lin": np.array([0.01, 0.03], np.float32)}
    assert scales_digest(s1) == scales_digest(dict(s1))
    assert scales_digest(s1) != scales_digest(s2)


# ------------------------------------------------------------ PTQ math


def test_int8_dense_close_to_f32_and_validates():
    rng = np.random.RandomState(0)
    x = rng.randn(16, 8).astype(np.float32)
    w = rng.randn(8, 4).astype(np.float32)
    b = rng.randn(4).astype(np.float32)
    s_x = (np.abs(x).max(axis=0) / 127).astype(np.float32)
    y = np.asarray(int8_dense(x, w, b, s_x), np.float32)
    ref = x @ w + b
    # two rounding sites (activation grid, folded-weight grid): ~2^-7
    # relative per site on a single matmul
    assert np.abs(y - ref).max() <= 2 ** -5 * np.abs(ref).max() + 2 ** -5
    with pytest.raises(ValueError):
        int8_dense(x, w, b, s_x[:4])      # scale/input-channel mismatch


def test_int8_forward_within_serving_bound(quantset):
    """The documented int8 bound (SERVE_INT8_RTOL/ATOL = 2^-3) holds
    for the quantized forward vs the fp32 forward on real rows — the
    light tier-1 version of the engine adjudication below."""
    samples, mcfg, model, variables, batch = quantset
    calib = calibrate(model, variables, mcfg, samples, num_samples=8)
    out32, _ = make_forward_fn(model, mcfg, "float32")(
        variables, batch, train=False)
    out8, _ = make_quantized_forward(model, mcfg, calib)(
        variables, batch, train=False)
    for ih, head in enumerate(mcfg.heads):
        mask = np.asarray(batch.node_mask if head.head_type == "node"
                          else batch.graph_mask, bool)
        a = np.asarray(out32[ih], np.float32)[mask]
        b = np.asarray(out8[ih], np.float32)[mask]
        bound = SERVE_INT8_ATOL + SERVE_INT8_RTOL * np.abs(a)
        assert (np.abs(b - a) <= bound).all(), float(
            (np.abs(b - a) - bound).max())


def test_int8_training_rejected(quantset, monkeypatch):
    """int8 is serving-only: train-side factories raise with an
    actionable message; the config-side dtype fallback warns-and-f32."""
    from hydragnn_tpu.train.precision import (canonical_or_f32,
                                              resolve_precision)
    from hydragnn_tpu.train.train_step import make_train_step
    _, mcfg, model, _, _ = quantset
    with pytest.raises(ValueError, match="serving-only"):
        make_forward_fn(model, mcfg, compute_dtype="int8")
    import optax
    with pytest.raises(ValueError, match="serving-only"):
        make_train_step(model, mcfg, optax.sgd(1e-3),
                        compute_dtype="int8")
    monkeypatch.delenv("HYDRAGNN_PRECISION", raising=False)
    assert canonical_or_f32("int8") == "float32"
    assert resolve_precision(cfg_dtype="int8") == "float32"


# ------------------------------------------------- knobs + store keys


def test_quant_calib_samples_knob(monkeypatch):
    from hydragnn_tpu.serving.config import resolve_serving
    monkeypatch.delenv("HYDRAGNN_QUANT_CALIB_SAMPLES", raising=False)
    assert resolve_serving({}).quant_calib_samples == 32
    cfg = {"Serving": {"quant_calib_samples": 8}}
    assert resolve_serving(cfg).quant_calib_samples == 8
    monkeypatch.setenv("HYDRAGNN_QUANT_CALIB_SAMPLES", "4")
    assert resolve_serving(cfg).quant_calib_samples == 4   # env wins
    monkeypatch.setenv("HYDRAGNN_QUANT_CALIB_SAMPLES", "four")  # typo:
    assert resolve_serving(cfg).quant_calib_samples == 8   # warn, keep cfg


def test_serve_precision_accepts_int8(monkeypatch):
    from hydragnn_tpu.serving.config import resolve_serving
    monkeypatch.delenv("HYDRAGNN_SERVE_PRECISION", raising=False)
    assert resolve_serving(
        {"Serving": {"precision": "int8"}}).precision == "int8"
    monkeypatch.setenv("HYDRAGNN_SERVE_PRECISION", "i8")
    assert resolve_serving({}).precision == "int8"


def test_fleet_tier_knobs(monkeypatch):
    from hydragnn_tpu.serving.config import resolve_fleet
    for k in ("HYDRAGNN_FLEET_TIER_PRIORITY_MIN",
              "HYDRAGNN_FLEET_TIER_QUOTA", "HYDRAGNN_FLEET_TIER_FAST",
              "HYDRAGNN_FLEET_TIER_ACCURATE"):
        monkeypatch.delenv(k, raising=False)
    base = resolve_fleet({})
    assert (base.tier_priority_min, base.tier_quota) == (0, 0.0)
    assert (base.tier_fast, base.tier_accurate) == ("int8", "float32")
    cfg = {"Serving": {"fleet": {"tier_priority_min": 2,
                                 "tier_quota": 0.25,
                                 "tier_fast": "bf16-student",
                                 "tier_accurate": "f32-teacher"}}}
    fc = resolve_fleet(cfg)
    assert (fc.tier_priority_min, fc.tier_quota) == (2, 0.25)
    assert (fc.tier_fast, fc.tier_accurate) == ("bf16-student",
                                                "f32-teacher")
    monkeypatch.setenv("HYDRAGNN_FLEET_TIER_PRIORITY_MIN", "5")
    monkeypatch.setenv("HYDRAGNN_FLEET_TIER_QUOTA", "0.5")
    fc = resolve_fleet(cfg)
    assert (fc.tier_priority_min, fc.tier_quota) == (5, 0.5)  # env wins
    monkeypatch.setenv("HYDRAGNN_FLEET_TIER_PRIORITY_MIN", "five")
    monkeypatch.setenv("HYDRAGNN_FLEET_TIER_QUOTA", "half")   # typos:
    fc = resolve_fleet(cfg)
    assert (fc.tier_priority_min, fc.tier_quota) == (2, 0.25)  # keep cfg


def test_store_fingerprint_no_cross_mode_collision(tmp_path):
    """int8 and fp32 programs for the SAME bucket must never collide in
    one shared store — the key folds the precision mode AND the
    calibration digest (two different calibrations = two different
    compiled programs: the scales are trace-time constants)."""
    store = CompileStore(str(tmp_path))
    keys = {
        store.fingerprint("bucket", 64, precision=None),
        store.fingerprint("bucket", 64, precision=("float32", None)),
        store.fingerprint("bucket", 64, precision=("bfloat16", None)),
        store.fingerprint("bucket", 64, precision=("int8", "digest-a")),
        store.fingerprint("bucket", 64, precision=("int8", "digest-b")),
    }
    assert len(keys) == 5
    # and identical inputs agree — the warm-restart identity
    assert (store.fingerprint("bucket", 64,
                              precision=("int8", "digest-a"))
            == store.fingerprint("bucket", 64,
                                 precision=("int8", "digest-a")))


# -------------------------------------------------------- distillation


def test_distill_deterministic_and_never_worse(quantset):
    samples, mcfg, model, variables, _ = quantset
    calib = calibrate(model, variables, mcfg, samples, num_samples=6)
    s1, r1 = distill_heads(model, variables, mcfg, calib, samples,
                           steps=4, num_samples=6)
    s2, r2 = distill_heads(model, variables, mcfg, calib, samples,
                           steps=4, num_samples=6)
    assert r1 == r2
    for leaf1, leaf2 in zip(jax.tree_util.tree_leaves(s1["params"]),
                            jax.tree_util.tree_leaves(s2["params"])):
        assert np.array_equal(np.asarray(leaf1), np.asarray(leaf2))
    # best-iterate: the student is never worse than no distillation
    assert sum(r1["head_mse_vs_teacher_post"]) <= sum(
        r1["head_mse_vs_teacher_pre"])
    # the encoder is bitwise the teacher's — only heads moved
    from hydragnn_tpu.quant.calibrate import encoder_param_key
    num_conv = int(mcfg.num_conv_layers)
    for key, sub in variables["params"].items():
        if encoder_param_key(key, num_conv):
            for a, b in zip(jax.tree_util.tree_leaves(sub),
                            jax.tree_util.tree_leaves(
                                s1["params"][key])):
                assert np.array_equal(np.asarray(a), np.asarray(b))
    assert r1["trained_param_keys"]
    assert isinstance(r1["improved"], bool)


# ------------------------------------------------------- tier routing


def test_tier_policy_validation():
    TierPolicy()                                    # defaults valid
    with pytest.raises(ValueError, match="quota"):
        TierPolicy(quota=1.5)
    with pytest.raises(ValueError, match="one-tier"):
        TierPolicy(fast="int8", accurate="int8")


def test_tier_routing_priority_quota_fallback():
    """Priority >= priority_min lands on the accurate tier, lower on
    the fast tier; over-quota priority traffic downgrades (counted);
    killing the accurate tier falls back cross-tier (counted) with the
    request still served — zero lost futures."""
    samples = deterministic_graph_dataset(num_configs=8)
    cfg = make_config("GIN")
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    model = create_model(mcfg)
    variables = init_params(model, collate(samples[:4]))

    def factory(idx):
        return InferenceEngine(
            model, variables, mcfg, reference_samples=samples,
            max_batch_size=2, max_wait_ms=1.0, num_buckets=1,
            tier="cheap" if idx == 0 else "exact")

    policy = TierPolicy(fast="cheap", accurate="exact", priority_min=1)
    router = ReplicaRouter(factory, 2, tier_policy=policy)
    try:
        lo = router.submit(samples[0], priority=0)
        lo.result(timeout=300)
        hi = router.submit(samples[1], priority=3)
        hi.result(timeout=300)
        assert lo.tier == "cheap" and lo.replica == 0
        assert hi.tier == "exact" and hi.replica == 1
        st = router.stats()
        assert st["tier_dispatches"] == {"cheap": 1, "exact": 1}
        assert st["tier_fallbacks"] == 0
        assert st["tier_downgrades"] == 0
        # cross-tier fallback: the accurate tier dies, priority traffic
        # still resolves — on the fast tier, counted
        router.kill_replica(1)
        fb = router.submit(samples[2], priority=5)
        fb.result(timeout=300)
        assert fb.tier == "cheap"
        assert router.stats()["tier_fallbacks"] >= 1
    finally:
        router.shutdown()

    # quota: sequential priority submits alternate accurate/fast once
    # the accurate share would exceed 50%
    policy = TierPolicy(fast="cheap", accurate="exact", priority_min=1,
                        quota=0.5)
    router = ReplicaRouter(factory, 2, tier_policy=policy)
    try:
        tiers = []
        for i in range(4):
            fut = router.submit(samples[i], priority=9)
            fut.result(timeout=300)
            tiers.append(fut.tier)
        assert tiers == ["exact", "cheap", "cheap", "exact"]
        assert router.stats()["tier_downgrades"] == 2
    finally:
        router.shutdown()


# ------------------------------------------------ engine-level (slow)


@pytest.mark.slow
def test_int8_engine_bound_breadcrumbs_and_bitwise_batching(quantset):
    """Engine-level acceptance: int8 futures carry the documented bound
    + tier; outputs sit inside it vs the fp32 engine on identical
    buckets; same-bucket batched-vs-single stays BITWISE within the
    int8 engine (same compiled program); health/stats echo the tier."""
    samples, mcfg, model, variables, _ = quantset
    engines = {}
    try:
        for dtype in ("float32", "int8"):
            engines[dtype] = InferenceEngine(
                model, variables, mcfg, reference_samples=samples,
                max_batch_size=4, max_wait_ms=1.0, num_buckets=1,
                compute_dtype=dtype)
        futs32 = [engines["float32"].submit(s) for s in samples[:8]]
        futs8 = [engines["int8"].submit(s) for s in samples[:8]]
        res32 = [f.result(timeout=300) for f in futs32]
        res8 = [f.result(timeout=300) for f in futs8]
        assert all(f.parity == "tolerance"
                   and f.parity_rtol == SERVE_INT8_RTOL
                   and f.parity_atol == SERVE_INT8_ATOL
                   and f.tier == "int8" for f in futs8)
        for r32, r8 in zip(res32, res8):
            for a, b in zip(r32, r8):
                a = np.asarray(a, np.float32)
                b = np.asarray(b, np.float32)
                bound = SERVE_INT8_ATOL + SERVE_INT8_RTOL * np.abs(a)
                assert (np.abs(b - a) <= bound).all()
        for i, f8 in enumerate(futs8):
            single = engines["int8"].forward_single(samples[i],
                                                    bucket=f8.bucket)
            for a, b in zip(res8[i], single):
                assert np.array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        assert engines["int8"].stats()["tier"] == "int8"
        assert engines["int8"].health()["tier"] == "int8"
        assert engines["float32"].health()["tier"] == "float32"
    finally:
        for eng in engines.values():
            eng.shutdown()


@pytest.mark.slow
def test_compile_store_warms_per_mode_no_collision(quantset, tmp_path):
    """One shared store, both precision modes: a second engine of the
    SAME mode+calibration warms with 0 fresh compiles, while a
    DIFFERENT mode (or a different calibration digest) never hits the
    other's entries."""
    samples, mcfg, model, variables, _ = quantset
    store = CompileStore(str(tmp_path))
    calib = calibrate(model, variables, mcfg, samples, num_samples=6)
    other = calibrate(model, variables, mcfg, samples[:3],
                      num_samples=3)
    assert calib.digest != other.digest

    def eng(**kw):
        return InferenceEngine(
            model, variables, mcfg, reference_samples=samples,
            max_batch_size=4, max_wait_ms=1.0, num_buckets=1,
            compile_store=store, **kw)

    e1 = eng(compute_dtype="int8", quant_calibration=calib)
    e1.warmup()
    st1 = e1.stats()
    e1.shutdown()
    assert st1["compile_fresh"] > 0        # cold store pays the compile

    e2 = eng(compute_dtype="int8", quant_calibration=calib)
    e2.warmup()
    st2 = e2.stats()
    e2.shutdown()
    assert st2["compile_fresh"] == 0       # warm restart, same identity
    assert st2["compile_store_hits"] > 0

    e3 = eng(compute_dtype="float32")
    e3.warmup()
    st3 = e3.stats()
    e3.shutdown()
    assert st3["compile_fresh"] > 0        # fp32 never hits int8 keys

    e4 = eng(compute_dtype="int8", quant_calibration=other)
    e4.warmup()
    st4 = e4.stats()
    e4.shutdown()
    assert st4["compile_fresh"] > 0        # different digest = new key
