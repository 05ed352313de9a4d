"""`ops/segment.edge_gather` under its hand-written derivative: a batch that
carries `edge_slot` gets the values, the gradient and the gradient of the
gradient of plain `ev[nbr_edge]`, for ANY cotangent (padding slots NOT
masked), and no lowered backward pass scatters."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hydragnn_tpu.graphs import collate
from hydragnn_tpu.graphs.batch import with_neighbor_format
from hydragnn_tpu.ops import segment as seg
from tests.test_graph_core import _rand_sample

F = 5


def _batch(case):
    rng = np.random.RandomState(7)
    samples = [_rand_sample(rng, n) for n in (9, 14, 6)]
    if case == "padded":
        b = collate(samples)
        assert not bool(b.edge_mask[-1])
    else:  # edge E - 1 is real AND every padding slot points at it
        tot_e = sum(s.num_edges for s in samples)
        b = collate(samples, n_node=40, n_edge=tot_e, n_graph=4)
        assert bool(b.edge_mask[-1])
    b = with_neighbor_format(b)
    assert not bool(jnp.all(b.nbr_mask))
    return b


def _inputs(b, seed=0):
    rng = np.random.RandomState(seed)
    ev = jnp.asarray(rng.randn(b.num_edges, F).astype(np.float32))
    scale = jnp.asarray(rng.randn(b.num_edges).astype(np.float32))
    w = jnp.asarray(rng.randn(*b.nbr_edge.shape, F).astype(np.float32))
    return ev, scale, w


def _energy(ev, scale, w, b):
    # nonlinear on both sides of the gather, weights on EVERY slot: the
    # cotangent that reaches the gather is dense, padding slots included
    return jnp.sum(jnp.sin(seg.edge_gather(ev * scale[:, None], b)) * w)


def _loss(ev, scale, w, b):
    """Energy plus the square of a 'force' (d energy / d scale): its
    gradient differentiates the gather's backward pass once more."""
    force = jax.grad(_energy, argnums=1)(ev, scale, w, b)
    return _energy(ev, scale, w, b) + jnp.sum(force ** 2)


def _close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= 1e-5 * max(np.max(np.abs(want)), 1.0)


@pytest.mark.parametrize("case", ["padded", "last_edge_real"])
def test_value_grad_and_grad_of_grad_equal_plain_indexing(case):
    b = _batch(case)
    plain = b.replace(edge_slot=None)
    ev, scale, w = _inputs(b)
    np.testing.assert_array_equal(np.asarray(seg.edge_gather(ev, b)),
                                  np.asarray(ev[b.nbr_edge]))
    np.testing.assert_array_equal(np.asarray(seg.edge_gather(ev, plain)),
                                  np.asarray(ev[b.nbr_edge]))
    for fn in (_energy, _loss):
        got = jax.grad(fn, argnums=(0, 1))(ev, scale, w, b)
        want = jax.grad(fn, argnums=(0, 1))(ev, scale, w, plain)
        for g, t in zip(got, want):
            assert float(jnp.max(jnp.abs(t))) > 0.1
            _close(g, t)
    # the raw VJP, a cotangent that is nowhere zero; any trailing shape
    rng = np.random.RandomState(5)
    for tail in ((F,), (), (2, 3)):
        v = jnp.asarray(rng.randn(b.num_edges, *tail).astype(np.float32))
        ct = jnp.asarray(
            rng.randn(*b.nbr_edge.shape, *tail).astype(np.float32))
        _, vjp = jax.vjp(lambda v: seg.edge_gather(v, b), v)
        _, vjp_plain = jax.vjp(lambda v: v[b.nbr_edge], v)
        _close(vjp(ct)[0], vjp_plain(ct)[0])


@pytest.mark.parametrize("wrap", ["checkpoint", "vmap", "jit"])
def test_grad_of_grad_under_transformations(wrap):
    b = _batch("last_edge_real")
    plain = b.replace(edge_slot=None)
    ev, scale, w = _inputs(b, seed=1)
    if wrap == "vmap":  # a leading shard axis over the batch too
        stack = lambda *trees: jax.tree_util.tree_map(
            lambda *a: jnp.stack(a), *trees)
        fn = lambda e, s, w_, b_: jnp.sum(jax.vmap(_loss)(e, s, w_, b_))
        args = stack((ev, scale, w), _inputs(b, seed=2))
        got = jax.grad(fn, argnums=(0, 1))(*args, stack(b, b))
        want = jax.grad(fn, argnums=(0, 1))(*args, stack(plain, plain))
    else:
        outer = jax.checkpoint if wrap == "checkpoint" else jax.jit
        energy = outer(_energy)

        def fn(e, s, w_, b_):
            force = jax.grad(energy, argnums=1)(e, s, w_, b_)
            return energy(e, s, w_, b_) + jnp.sum(force ** 2)
        got = jax.grad(fn, argnums=(0, 1))(ev, scale, w, b)
        want = jax.grad(_loss, argnums=(0, 1))(ev, scale, w, plain)
    for g, t in zip(got, want):
        _close(g, t)


def _lowered(b):
    ev, scale, w = _inputs(b)
    return jax.jit(jax.grad(_loss, argnums=(0, 1))).lower(
        ev, scale, w, b).as_text(debug_info=True)


def test_lowered_grad_of_grad_has_no_scatter_and_keeps_the_scope():
    b = _batch("padded")
    text = _lowered(b)
    assert "stablehlo.scatter" not in text
    assert "stablehlo.scatter" in _lowered(b.replace(edge_slot=None))
    # every gather of the program is edge_gather's (the test indexes
    # nowhere else): forward, its transpose, and the transpose's transpose
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    gathers = [locs[m] for m in re.findall(
        r'"?stablehlo\.gather"?\(.*loc\((#loc\d+)\)', text)]
    assert len(gathers) >= 4
    assert all("edge_gather" in name for name in gathers), gathers
    assert any("transpose" in name for name in gathers)
