"""The aggregations of `ops/segment.py` and `PNAConv` on the two batch
layouts: the edge list (masked segment scatter) and the dense neighbour
tables (masked K-axis reduction). A conv takes its path from what the batch
carries and from nothing else, so the two must agree.

On small-integer data every partial sum is representable, in float32 AND in
bfloat16, so any order of summation gives the same bits: the edge list, the
dense tables and a float64 numpy sum must then agree bit for bit, which
holds indexing and masking (masked edges, a node without in-edges, the
padding node) to the bit on both layouts, forward and pulled back."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hydragnn_tpu.graphs.batch import GraphBatch, with_neighbor_format
from hydragnn_tpu.ops import segment as seg

N, K, F = 12, 8, 6
# in-degrees are powers of two, so a mean of integers is representable
# too; node 10 is real and has no in-edge, node 11 is the padding node
IN_DEGREE = (8, 4, 4, 2, 2, 2, 1, 1, 1, 1, 0, 0)
DTYPES = ["float32", "bfloat16"]


def _batches(edge_slot=True):
    """(edge-list batch, the same batch with the dense tables). Beside the
    real edges: six switched off by `edge_mask` that name real receivers,
    and collate's padding edges (node N - 1, mask False), all shuffled."""
    rng = np.random.RandomState(5)
    recv = np.repeat(np.arange(N), IN_DEGREE)
    real = np.ones(recv.size, bool)
    recv = np.concatenate([recv, rng.randint(0, 10, 6), np.full(6, N - 1)])
    real = np.concatenate([real, np.zeros(12, bool)])
    order = rng.permutation(recv.size)
    if edge_slot:               # collate's rule: the last edge is padding
        order = np.concatenate([order[order != recv.size - 1],
                                [recv.size - 1]])
    recv, real = recv[order].astype(np.int32), real[order]
    send = rng.randint(0, N - 1, recv.size).astype(np.int32)
    node_mask = np.arange(N) < N - 1
    if not edge_slot:           # a full, real last node: no inverse table
        recv = np.concatenate([recv, np.full(K, N - 1)]).astype(np.int32)
        send = np.concatenate([send, np.arange(K)]).astype(np.int32)
        real = np.concatenate([real, np.ones(K, bool)])
        node_mask = np.ones(N, bool)
    edges = GraphBatch(
        x=np.zeros((N, 1), np.float32), pos=np.zeros((N, 3), np.float32),
        senders=send, receivers=recv, node_graph=np.zeros(N, np.int32),
        node_mask=node_mask, edge_mask=real, graph_mask=np.ones(1, bool))
    dense = with_neighbor_format(edges, k=K)
    assert (dense.edge_slot is not None) == edge_slot
    return edges, dense


def _ints(seed, shape, dtype, lo=-3, hi=4):
    return jnp.asarray(
        np.random.RandomState(seed).randint(lo, hi, shape)).astype(dtype)


def _f64(a):
    return np.asarray(a.astype(jnp.float32), np.float64)


def _numpy_sum(edge_values, batch):
    """sum over the real in-edges of every node, in float64."""
    out = np.zeros((N,) + edge_values.shape[1:])
    real = np.asarray(batch.edge_mask)
    np.add.at(out, np.asarray(batch.receivers)[real], edge_values[real])
    return out


def _same_bits(got, want, what):
    assert got.dtype == want.dtype, what
    assert np.array_equal(_f64(got), _f64(want)), what


def _segment_sum(ev, h, b):
    """`segment_sum` itself, and `neighbor_sum` over the gathered slots."""
    if b.nbr_edge is None:
        return seg.segment_sum(ev, b.receivers, N, b.edge_mask)
    return seg.neighbor_sum(ev[b.nbr_edge], b.nbr_mask)


def _pna(ev, h, b):
    if b.nbr_edge is None:
        return seg.pna_aggregate(ev, b.receivers, N, b.edge_mask)
    return seg.neighbor_aggregate(seg.edge_gather(ev, b), b.nbr_mask)


OPS = {
    "segment_sum": _segment_sum,
    "edge_aggregate_sum": lambda ev, h, b: seg.edge_aggregate_sum(ev, b),
    "edge_aggregate_mean": lambda ev, h, b: seg.edge_aggregate_mean(ev, b),
    "filter_weighted_aggregate":
        lambda ev, h, b: seg.filter_weighted_aggregate(h, ev, b),
    "pna_aggregate": _pna,
}


def _numpy_reference(op, ev, h, batch):
    """float64; for PNA (mean, min, max, None, degree): the standard
    deviation takes a square root and is compared across layouts only."""
    ev, h = _f64(ev), _f64(h)
    cnt = np.asarray(IN_DEGREE, np.float64)[:, None]
    if op == "filter_weighted_aggregate":
        return _numpy_sum(h[np.asarray(batch.senders)] * ev, batch)
    total = _numpy_sum(ev, batch)
    if op in ("segment_sum", "edge_aggregate_sum"):
        return total
    if op == "edge_aggregate_mean":
        return total / np.maximum(cnt, 1.0)
    real, recv = np.asarray(batch.edge_mask), np.asarray(batch.receivers)
    rows = [ev[real & (recv == i)] for i in range(N)]
    return (total / np.maximum(cnt, 1.0),
            np.stack([r.min(0) if len(r) else np.zeros(F) for r in rows]),
            np.stack([r.max(0) if len(r) else np.zeros(F) for r in rows]),
            None, cnt[:, 0])


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("op", sorted(OPS))
def test_exact_data_gives_the_same_bits_on_both_layouts(op, dtype):
    edges, dense = _batches()
    ev = _ints(1, (edges.num_edges, F), dtype)
    h = _ints(2, (N, F), dtype, -2, 3)
    got_e, got_d = OPS[op](ev, h, edges), OPS[op](ev, h, dense)
    want = _numpy_reference(op, ev, h, edges)
    if op != "pna_aggregate":
        got_e, got_d, want = (got_e,), (got_d,), (want,)
    for a, b, ref, name in zip(got_e, got_d, want,
                               ("mean", "min", "max", "std", "degree")):
        if op == "edge_aggregate_mean":
            # the scatter layout divides by a float32 count, the dense
            # one by a count of the data's dtype: the values are the same
            assert a.dtype == jnp.float32 and b.dtype == jnp.dtype(dtype)
            a = a.astype(b.dtype)
        _same_bits(a, b, (op, name))
        assert a.dtype == jnp.dtype(dtype) or name == "degree", (op, name)
        if ref is not None:
            assert np.array_equal(_f64(a), ref), (op, name)
    # a node without in-edges and the padding node read exactly zero,
    # whatever the masked and the padding edges carry
    first = got_d[0]
    assert not np.any(_f64(first)[[10, 11]])


def _scatters(fn, *args):
    return "scatter" in str(jax.make_jaxpr(fn)(*args))


def test_the_batch_alone_chooses_the_path():
    """Tables on the batch: K-axis reductions, no scatter. No tables: the
    segment scatter. Nothing else is consulted."""
    edges, dense = _batches()
    ev = _ints(1, (edges.num_edges, F), "float32")
    h = _ints(2, (N, F), "float32")
    for op in ("edge_aggregate_sum", "edge_aggregate_mean",
               "filter_weighted_aggregate"):
        assert _scatters(lambda v, b=edges: OPS[op](v, h, b), ev), op
        assert not _scatters(lambda v, b=dense: OPS[op](v, h, b), ev), op


# ------------------------------------------------- the pulled-back cotangent

def _pullback(op, ev, h, batch, ct):
    out, vjp = jax.vjp(lambda v, x: OPS[op](v, x, batch), ev, h)
    return vjp(ct.astype(out.dtype))


VJP_CASES = [("edge_aggregate_sum", True), ("edge_aggregate_sum", False),
             ("edge_aggregate_mean", True),
             ("filter_weighted_aggregate", True)]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize(
    "op,edge_slot", VJP_CASES,
    ids=[op + ("" if slot else "-no_edge_slot") for op, slot in VJP_CASES])
def test_pulled_back_cotangent_is_the_same_bits_on_both_layouts(
        op, edge_slot, dtype):
    """`jax.vjp` of the sum-type aggregations: the cotangent of the edge
    values (and of the node values under the filter) from the scatter, from
    the dense tables (under `edge_gather`'s hand-written transpose where the
    batch carries `edge_slot`, under jax's scatter-add where it does not)
    and from float64 numpy, on exact data."""
    edges, dense = _batches(edge_slot)
    ev = _ints(1, (edges.num_edges, F), dtype)
    h = _ints(2, (N, F), dtype, -2, 3)
    ct = _ints(3, (N, F), dtype, -2, 3)
    d_ev_e, d_h_e = _pullback(op, ev, h, edges, ct)
    d_ev_d, d_h_d = _pullback(op, ev, h, dense, ct)
    _same_bits(d_ev_e, d_ev_d, "d edge values")
    _same_bits(d_h_e, d_h_d, "d node values")

    real = np.asarray(edges.edge_mask)[:, None]
    recv, send = np.asarray(edges.receivers), np.asarray(edges.senders)
    at_edge = _f64(ct)[recv] * real
    if op == "edge_aggregate_mean":
        deg = np.bincount(recv[real[:, 0]], minlength=N)
        at_edge = at_edge / np.maximum(deg, 1.0)[recv][:, None]
    want_h = np.zeros((N, F))
    if op == "filter_weighted_aggregate":
        np.add.at(want_h, send, at_edge * _f64(ev))
        at_edge = at_edge * _f64(h)[send]
    assert np.array_equal(_f64(d_ev_d), at_edge)
    assert np.array_equal(_f64(d_h_d), want_h)


# ------------------------------------------------ the accumulation policy

@pytest.mark.parametrize("layout", ["segment_sum", "neighbor_sum"])
def test_bfloat16_sums_accumulate_in_float32(layout):
    """300 ones: a bfloat16 running sum stalls at 256 (256 + 1 rounds back
    to 256); `_accum_f32` sums in float32 and stores bfloat16 once."""
    ones = jnp.ones((300, 2), jnp.bfloat16)
    if layout == "segment_sum":
        out = seg.segment_sum(ones, jnp.zeros(300, jnp.int32), 1)
    else:
        out = seg.neighbor_sum(ones[None], jnp.ones((1, 300), bool))
    assert out.dtype == jnp.bfloat16 and out.shape == (1, 2)
    assert np.array_equal(_f64(out), np.full((1, 2), 300.0))
    stalled = jnp.bfloat16(256) + jnp.bfloat16(1)
    assert float(stalled) == 256.0


# ------------------------------------------------------ PNAConv end to end

@pytest.mark.parametrize("model_type", ["PNA", "PNAPlus"])
def test_pnaconv_gradients_agree_across_layouts(model_type):
    """The two branches of `PNAConv.__call__`, without per-edge terms (PNA)
    and with them threaded through `edge_terms` (PNAPlus, `rbf`): the
    gradient of a scalar loss with respect to every parameter and to the
    positions. In float64, so that agreement means the same indexing and
    masking and not a tolerance wide enough for float32's sqrt(var + eps)
    at var ~ 0, which amplifies the last bit of a sum a hundredfold."""
    from hydragnn_tpu.models.create import create_model, init_params
    from tests.deterministic_data import deterministic_graph_dataset
    from tests.utils import prepare

    _, mcfg, batch = prepare(model_type,
                             deterministic_graph_dataset(num_configs=8))
    assert batch.nbr is None and not bool(jnp.all(batch.edge_mask))
    model = create_model(mcfg)
    variables = init_params(model, batch)

    def loss(params, pos, b):
        outs, _ = model.apply({**variables, "params": params},
                              b.replace(pos=pos), train=False)
        return sum(jnp.sum(jnp.sin(o)) for o in outs)

    def widen(a):
        a = jnp.asarray(a)
        return a.astype(jnp.float64) if jnp.issubdtype(
            a.dtype, jnp.floating) else a

    with jax.enable_x64(True):
        variables, batch = jax.tree_util.tree_map(widen, (variables, batch))
        grad = jax.jit(jax.grad(loss, argnums=(0, 1)))
        g_edges = grad(variables["params"], batch.pos, batch)
        g_dense = grad(variables["params"], batch.pos,
                       with_neighbor_format(batch))
    flat_e = jax.tree_util.tree_flatten_with_path(g_edges)[0]
    flat_d = jax.tree_util.tree_leaves(g_dense)
    for (path, a), b in zip(flat_e, flat_d):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == np.float64
        np.testing.assert_allclose(a, b, rtol=1e-9, atol=1e-12,
                                   err_msg=jax.tree_util.keystr(path))
    params, pos = g_edges
    assert np.abs(params["conv_0"]["pre_j"]["kernel"]).max() > 1e-3
    if model_type == "PNAPlus":
        assert np.abs(params["conv_1"]["rbf_proj"]["kernel"]).max() > 1e-3
        assert np.abs(pos).max() > 1e-3
    else:   # plain PNA reads no geometry
        assert not np.any(pos)
