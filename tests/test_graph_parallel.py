"""Graph parallelism (edge-sharded + ring message passing) on the 8-device
CPU mesh — the framework's sequence/context-parallel analogue (SURVEY.md
§5.7). Both modes must reproduce the single-device segment-sum aggregation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from hydragnn_tpu.parallel.graph_parallel import (
    build_ring_buckets, edge_sharded_aggregate, make_edge_sharded_layer,
    make_ring_layer, partition_nodes, shard_edge_arrays, shard_node_array)

D = 8


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.asarray(jax.devices()[:D]), ("graph",))


def random_graph(n_nodes=200, n_edges=3000, f=16, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n_nodes, f).astype(np.float32)
    send = rng.randint(0, n_nodes, n_edges).astype(np.int32)
    recv = rng.randint(0, n_nodes, n_edges).astype(np.int32)
    return x, send, recv


def sum_message(xi, xj, ea):
    # asymmetric so sender/receiver mix-ups are caught
    return xj * 2.0 + xi * 0.5


def reference_aggregate(x, send, recv):
    m = sum_message(x[recv], x[send], None)
    return jax.ops.segment_sum(m, recv, x.shape[0])


def test_edge_sharded_matches_reference(mesh):
    x, send, recv = random_graph()
    ref = reference_aggregate(x, send, recv)
    mask, send_s, recv_s = shard_edge_arrays(D, send, recv)
    layer = make_edge_sharded_layer(mesh, sum_message, x.shape[0])
    out = layer(jnp.asarray(x), jnp.asarray(send_s), jnp.asarray(recv_s),
                jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_matches_reference(mesh):
    x, send, recv = random_graph(n_nodes=208)  # divisible and padded cases
    ref = reference_aggregate(x, send, recv)
    buckets = build_ring_buckets(send, recv, x.shape[0], D)
    x_sh = shard_node_array(jnp.asarray(x), D)
    layer = make_ring_layer(mesh, sum_message)
    out = layer(x_sh, jnp.asarray(buckets.send_local),
                jnp.asarray(buckets.recv_local), jnp.asarray(buckets.mask))
    flat = np.asarray(out).reshape(-1, x.shape[1])[:x.shape[0]]
    np.testing.assert_allclose(flat, np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_ring_with_uneven_nodes(mesh):
    # N not divisible by D: last block zero-padded, results must still match
    x, send, recv = random_graph(n_nodes=203, n_edges=2000, seed=1)
    ref = reference_aggregate(x, send, recv)
    block = partition_nodes(x.shape[0], D)
    assert block * D > x.shape[0]
    buckets = build_ring_buckets(send, recv, x.shape[0], D)
    x_sh = shard_node_array(jnp.asarray(x), D)
    layer = make_ring_layer(mesh, sum_message)
    out = layer(x_sh, jnp.asarray(buckets.send_local),
                jnp.asarray(buckets.recv_local), jnp.asarray(buckets.mask))
    flat = np.asarray(out).reshape(-1, x.shape[1])[:x.shape[0]]
    np.testing.assert_allclose(flat, np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_ring_bucket_invariants():
    _, send, recv = random_graph(n_nodes=64, n_edges=500, seed=2)
    b = build_ring_buckets(send, recv, 64, D)
    # every real edge appears exactly once
    assert int(b.mask.sum()) == 500
    ids = b.edge_id[b.mask]
    assert sorted(ids.tolist()) == list(range(500))
    # bucket [d, k] receivers lie in block d, senders in block (d - k) % D
    for d in range(D):
        for k in range(D):
            m = b.mask[d, k]
            if not m.any():
                continue
            sel = b.edge_id[d, k][m]
            assert np.all(recv[sel] // b.block == d)
            assert np.all(send[sel] // b.block == (d - k) % D)
            # local indices consistent with global ones
            assert np.all(b.recv_local[d, k][m] == recv[sel] % b.block)
            assert np.all(b.send_local[d, k][m] == send[sel] % b.block)


def test_edge_sharded_inside_shard_map_composes(mesh):
    """edge_sharded_aggregate is usable as a building block inside a larger
    shard_map (e.g. a full conv layer with pre/post MLPs)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    x, send, recv = random_graph(n_nodes=100, n_edges=1000, seed=3)
    w = np.random.RandomState(4).randn(16, 16).astype(np.float32) * 0.1
    mask, send_s, recv_s = shard_edge_arrays(D, send, recv)

    def per_device(x, w, send, recv, m):
        agg = edge_sharded_aggregate(sum_message, x, send[0], recv[0], m[0],
                                     x.shape[0])
        return jnp.tanh(agg @ w)

    fn = jax.jit(shard_map(
        per_device, mesh=mesh,
        in_specs=(P(), P(), P("graph"), P("graph"), P("graph")),
        out_specs=P()))
    out = fn(jnp.asarray(x), jnp.asarray(w), jnp.asarray(send_s),
             jnp.asarray(recv_s), jnp.asarray(mask))
    ref = jnp.tanh(reference_aggregate(x, send, recv) @ w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
