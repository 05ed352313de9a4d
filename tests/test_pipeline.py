"""Pipeline (layer) parallelism: the pipelined schedules over a ``pipe``
mesh axis must reproduce the sequential stack exactly (technique from the
retrieved GNNPipe paper, PAPERS.md; no reference analogue — SURVEY.md §2.6
lists pipeline parallelism as absent upstream).

Bitwise contracts (docs/pipeline.md): the pipelined FORWARD is bitwise
vs the sequential stack on any data (identical per-microbatch op
sequence); remat on/off is bitwise on any data (jax.checkpoint recomputes
the same ops); the 1F1B windowed backward is bitwise vs GPipe and the
sequential stack on EXACTLY-REPRESENTABLE data (gradient sums reassociate
only at window boundaries — the PR 6 precedent: random-float cross-path
bitwise is unattainable where reduction order changes, so exactness pins
the structure and allclose pins the floats)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from hydragnn_tpu.ops import segment as seg
from hydragnn_tpu.parallel.mesh import make_mesh
from hydragnn_tpu.parallel.pipeline import (bubble_fraction,
                                            check_stage_divisibility,
                                            forward_ticks,
                                            make_pipeline_apply,
                                            stack_stage_params,
                                            train_bubble_fraction,
                                            train_step_ticks)

N, E, F = 24, 96, 8
L = 8          # conv layers
S = 4          # pipeline stages
M = 6          # microbatches


def _layer_fn(params, x, structure):
    send, recv, mask = structure
    agg = seg.segment_sum(x[send], recv, x.shape[0], mask)
    return jax.nn.relu((x + agg) @ params["w"] + params["b"])


def _random_problem(seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(M, N, F).astype(np.float32))
    send = jnp.asarray(rng.randint(0, N, (M, E)).astype(np.int32))
    recv = jnp.asarray(rng.randint(0, N, (M, E)).astype(np.int32))
    mask = jnp.asarray(rng.rand(M, E) < 0.9)
    params = [{"w": jnp.asarray(rng.randn(F, F).astype(np.float32) * 0.2),
               "b": jnp.asarray(rng.randn(F).astype(np.float32) * 0.01)}
              for _ in range(L)]
    return x, (send, recv, mask), params


def _sequential(params, x_micro, structure):
    outs = []
    for m in range(M):
        h = x_micro[m]
        st = jax.tree_util.tree_map(lambda a: a[m], structure)
        for p in params:
            h = _layer_fn(p, h, st)
        outs.append(h)
    return jnp.stack(outs)


def test_pipeline_matches_sequential():
    x, structure, params = _random_problem()
    expect = _sequential(params, x, structure)

    mesh = make_mesh((("pipe", S),), devices=jax.devices()[:S])
    apply_fn = make_pipeline_apply(mesh, _layer_fn, L)
    stacked = stack_stage_params(params, S)
    got = apply_fn(stacked, x, structure)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


# slow lane since PR 21 (tier-1 budget): 19 s; gradient equivalence stays pinned by the exact-data 1f1b/gpipe/sequential test
@pytest.mark.slow
def test_pipeline_differentiable():
    x, structure, params = _random_problem(1)
    mesh = make_mesh((("pipe", S),), devices=jax.devices()[:S])
    apply_fn = make_pipeline_apply(mesh, _layer_fn, L)
    stacked = stack_stage_params(params, S)

    def loss_pipe(sp):
        return jnp.sum(apply_fn(sp, x, structure) ** 2)

    def loss_seq(ps):
        return jnp.sum(_sequential(ps, x, structure) ** 2)

    g_pipe = jax.grad(loss_pipe)(stacked)
    g_seq = jax.grad(loss_seq)(params)
    g_seq_stacked = stack_stage_params(g_seq, S)
    for a, b in zip(jax.tree_util.tree_leaves(g_pipe),
                    jax.tree_util.tree_leaves(g_seq_stacked)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_stack_stage_params_shape():
    _, _, params = _random_problem(2)
    stacked = stack_stage_params(params, S)
    assert stacked["w"].shape == (S, L // S, F, F)
    # a ValueError with an actionable message, never a bare assert
    # (asserts vanish under python -O)
    with pytest.raises(ValueError, match="pipeline stages"):
        stack_stage_params(params, 3)


def test_stage_divisibility_raises_value_error():
    with pytest.raises(ValueError, match="divisor"):
        check_stage_divisibility(10, 4)
    with pytest.raises(ValueError, match="pipeline_stages must be >= 1"):
        check_stage_divisibility(8, 0)
    assert check_stage_divisibility(8, 4) == 2
    mesh = make_mesh((("pipe", S),), devices=jax.devices()[:S])
    with pytest.raises(ValueError, match="pipeline stages"):
        make_pipeline_apply(mesh, _layer_fn, 7)


def test_schedule_accounting_closed_forms():
    """Bubble math (docs/pipeline.md): one pass is M + S - 1 ticks with
    (S-1)/(M+S-1) bubble; gpipe doubles it; the windowed 1f1b pays one
    fill/drain pair per window of W = min(S, M)."""
    assert forward_ticks(4, 8) == 11
    assert abs(bubble_fraction(4, 8) - 3 / 11) < 1e-12
    assert train_step_ticks(4, 8, "gpipe") == 22
    assert train_step_ticks(4, 8, "1f1b") == 2 * 2 * 7  # 2 windows of 4
    assert abs(train_bubble_fraction(4, 8, "gpipe") - (1 - 16 / 22)) < 1e-12
    assert abs(train_bubble_fraction(4, 8, "1f1b") - (1 - 16 / 28)) < 1e-12
    # M <= S: a single window, same tick count as gpipe
    assert train_step_ticks(4, 4, "1f1b") == train_step_ticks(4, 4, "gpipe")
    with pytest.raises(ValueError, match="schedule"):
        train_step_ticks(4, 8, "interleaved")


def test_pipeline_forward_bitwise_and_remat():
    """Banked-output pipelined forward == sequential stack BITWISE on
    random floats (identical per-microbatch op sequence — the banked
    last-stage slice replaces the seed's psum broadcast, which was also
    value-exact but shipped a full zero tensor per stage); remat on is
    bitwise vs remat off (jax.checkpoint recomputes the same ops).

    The contract stays BITWISE, between COMPILED programs: the reference
    is the jitted sequential stack. Decided in PR 21: under jax 0.9.0 the
    op-by-op (eager) stack and its own jitted form differ by one ulp
    (7.6e-06 at |h| ~ 100, 32% of elements) because XLA:CPU fuses
    (x + agg) @ w + b inside one program and cannot across eager
    dispatches — a property of the compiler, not of the schedule. The
    pipeline matches the jitted stack exactly (measured 0.0 max diff), and
    that is what a user runs: every training path is jitted."""
    x, structure, params = _random_problem(3)
    expect = jax.jit(_sequential)(params, x, structure)
    mesh = make_mesh((("pipe", S),), devices=jax.devices()[:S])
    stacked = stack_stage_params(params, S)
    got = make_pipeline_apply(mesh, _layer_fn, L)(stacked, x, structure)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))
    got_remat = make_pipeline_apply(mesh, _layer_fn, L, remat=True)(
        stacked, x, structure)
    np.testing.assert_array_equal(np.asarray(got_remat), np.asarray(got))
    got_dots = make_pipeline_apply(mesh, _layer_fn, L, remat=True,
                                   remat_policy="dots")(stacked, x,
                                                        structure)
    np.testing.assert_array_equal(np.asarray(got_dots), np.asarray(got))


def test_remat_grads_bitwise_any_data():
    """Gradients through the remat'd schedule equal the un-remat'd ones
    BITWISE on random floats — rematerialization must be a pure memory/
    recompute trade, never a numeric knob."""
    x, structure, params = _random_problem(4)
    mesh = make_mesh((("pipe", S),), devices=jax.devices()[:S])
    stacked = stack_stage_params(params, S)
    apply_plain = make_pipeline_apply(mesh, _layer_fn, L)
    apply_remat = make_pipeline_apply(mesh, _layer_fn, L, remat=True)

    def loss(apply_fn):
        return lambda sp: jnp.sum(apply_fn(sp, x, structure) ** 2)

    g0 = jax.jit(jax.grad(loss(apply_plain)))(stacked)
    g1 = jax.jit(jax.grad(loss(apply_remat)))(stacked)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- schedule equivalence on exactly-representable data ----------------
# integer-valued inputs, quarter-integer weights, permutation receivers
# (in-degree exactly 1) keep every intermediate value and every gradient
# product exactly representable in f32, so reassociating sums across
# window boundaries cannot round — bitwise equality then pins the
# SCHEDULE structure (the PR 6 exact-data contract)

ME = 8   # microbatches
SE = 4   # stages


def _exact_problem(seed=0, layers=4, n=16, f=8):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randint(-1, 2, (ME, n, f)).astype(np.float32))
    send = jnp.asarray(
        np.stack([rng.permutation(n) for _ in range(ME)]).astype(np.int32))
    recv = jnp.asarray(
        np.stack([rng.permutation(n) for _ in range(ME)]).astype(np.int32))
    mask = jnp.asarray(np.ones((ME, n), bool))
    params = [
        {"w": jnp.asarray(
            (rng.randint(-1, 2, (f, f)) * 0.25).astype(np.float32)),
         "b": jnp.asarray(
             (rng.randint(-1, 2, (f,)) * 0.25).astype(np.float32))}
        for _ in range(layers)]
    return x, (send, recv, mask), params


def _windowed_grads_of(apply_fn, x, structure, window):
    """The 1f1b backward organization at this test's level: scan windows,
    each differentiating sum(window losses)/M, f32 accumulation."""
    M = x.shape[0]
    nw = M // window
    xw = x.reshape((nw, window) + x.shape[1:])
    stw = jax.tree_util.tree_map(
        lambda a: a.reshape((nw, window) + a.shape[1:]), structure)

    def step(params):
        def body(gsum, win):
            xb, stb = win

            def wloss(p):
                return jnp.sum(apply_fn(p, xb, stb) ** 2) / M
            g = jax.grad(wloss)(params)
            return jax.tree_util.tree_map(jnp.add, gsum, g), None
        g0 = jax.tree_util.tree_map(jnp.zeros_like, params)
        return lax.scan(body, g0, (xw, stw))[0]
    return step


def test_1f1b_grads_bitwise_vs_gpipe_and_sequential_exact_data():
    """1F1B windowed forward/backward == GPipe == the sequential stack
    BITWISE (values AND gradients) on exactly-representable data, with
    and without remat."""
    x, structure, params = _exact_problem()
    mesh = make_mesh((("pipe", SE),), devices=jax.devices()[:SE])
    stacked = stack_stage_params(params, SE)
    apply_fn = make_pipeline_apply(mesh, _layer_fn, 4)
    apply_remat = make_pipeline_apply(mesh, _layer_fn, 4, remat=True)

    def seq(params_list):
        outs = []
        for m in range(ME):
            h = x[m]
            st = jax.tree_util.tree_map(lambda a: a[m], structure)
            for p in params_list:
                h = _layer_fn(p, h, st)
            outs.append(h)
        return jnp.stack(outs)

    # forward: all three bitwise
    y_seq = seq(params)
    y_pipe = apply_fn(stacked, x, structure)
    np.testing.assert_array_equal(np.asarray(y_pipe), np.asarray(y_seq))

    # gradients: gpipe (one backward through the full scan) vs 1f1b
    # (windowed, W = S) vs sequential — bitwise on exact data
    def gpipe_loss(sp):
        return jnp.sum(apply_fn(sp, x, structure) ** 2) / ME

    g_gpipe = jax.jit(jax.grad(gpipe_loss))(stacked)
    g_seq = jax.grad(
        lambda ps: jnp.sum(seq(ps) ** 2) / ME)(params)
    g_seq = stack_stage_params(g_seq, SE)
    g_1f1b = jax.jit(_windowed_grads_of(apply_fn, x, structure, SE))(
        stacked)
    g_1f1b_r = jax.jit(_windowed_grads_of(apply_remat, x, structure, SE))(
        stacked)

    for name, g in (("gpipe", g_gpipe), ("1f1b", g_1f1b),
                    ("1f1b_remat", g_1f1b_r)):
        for a, b in zip(jax.tree_util.tree_leaves(g),
                        jax.tree_util.tree_leaves(g_seq)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{name} grads diverge from sequential")
    # the data must actually exercise the stack (all-zero grads would
    # vacuously pass)
    assert any(float(np.abs(np.asarray(l)).max()) > 0
               for l in jax.tree_util.tree_leaves(g_seq))
