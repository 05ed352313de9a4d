"""Unit tests for the graph core: segment ops, batching, radius graphs."""
import numpy as np
import pytest

import jax.numpy as jnp

from hydragnn_tpu.graphs import (BucketSpec, GraphSample, collate,
                                 radius_graph, radius_graph_pbc)
from hydragnn_tpu.ops import segment as seg


def _rand_sample(rng, n, f=4):
    pos = rng.rand(n, 3).astype(np.float32) * 3
    send, recv = radius_graph(pos, 1.2)
    return GraphSample(x=rng.rand(n, f).astype(np.float32), pos=pos,
                       senders=send, receivers=recv,
                       y_graph=rng.rand(2).astype(np.float32),
                       y_node=rng.rand(n, 1).astype(np.float32))


class TestSegmentOps:
    def test_sum_mean_match_numpy(self):
        rng = np.random.RandomState(0)
        data = rng.rand(20, 5).astype(np.float32)
        ids = rng.randint(0, 4, 20)
        mask = rng.rand(20) > 0.3
        out = seg.segment_sum(jnp.asarray(data), jnp.asarray(ids), 4,
                              jnp.asarray(mask))
        for k in range(4):
            expect = data[(ids == k) & mask].sum(axis=0)
            np.testing.assert_allclose(np.asarray(out[k]), expect, rtol=1e-5)
        mean = seg.segment_mean(jnp.asarray(data), jnp.asarray(ids), 4,
                                jnp.asarray(mask))
        for k in range(4):
            sel = data[(ids == k) & mask]
            expect = sel.mean(axis=0) if len(sel) else np.zeros(5)
            np.testing.assert_allclose(np.asarray(mean[k]), expect, rtol=1e-5)

    def test_min_max_empty_segments(self):
        data = jnp.asarray([[1.0], [5.0]])
        ids = jnp.asarray([0, 0])
        mx = seg.segment_max(data, ids, 3)
        mn = seg.segment_min(data, ids, 3)
        assert float(mx[0, 0]) == 5.0 and float(mn[0, 0]) == 1.0
        # empty segments clamp to 0, not +-inf
        assert float(mx[2, 0]) == 0.0 and float(mn[2, 0]) == 0.0

    def test_sorted_indices_hint_matches_unhinted(self):
        """The graph pools pass indices_are_sorted=True (node_graph is
        nondecreasing by collate construction); the hinted lowering must
        agree with the unhinted scatter-add on real padded batches —
        including masked padding nodes at the tail id."""
        rng = np.random.RandomState(3)
        samples = [_rand_sample(rng, n) for n in (3, 7, 5, 9)]
        batch = collate(samples, n_node=32, n_edge=256, n_graph=6)
        for hinted, ref in (
            (seg.global_sum_pool(batch.x, batch.node_graph, 6,
                                 batch.node_mask),
             seg.segment_sum(batch.x, batch.node_graph, 6,
                             batch.node_mask)),
            (seg.global_mean_pool(batch.x, batch.node_graph, 6,
                                  batch.node_mask),
             seg.segment_mean(batch.x, batch.node_graph, 6,
                              batch.node_mask)),
            (seg.segment_count(batch.node_graph, 6, batch.node_mask,
                               indices_are_sorted=True),
             seg.segment_count(batch.node_graph, 6, batch.node_mask)),
        ):
            np.testing.assert_allclose(np.asarray(hinted), np.asarray(ref),
                                       rtol=1e-6, atol=1e-7)

    def test_softmax_normalizes(self):
        logits = jnp.asarray([0.5, 1.5, -0.2, 3.0])
        ids = jnp.asarray([0, 0, 1, 1])
        mask = jnp.asarray([True, True, True, False])
        sm = seg.segment_softmax(logits, ids, 2, mask)
        np.testing.assert_allclose(float(sm[0] + sm[1]), 1.0, rtol=1e-5)
        np.testing.assert_allclose(float(sm[2]), 1.0, rtol=1e-5)
        assert float(sm[3]) == 0.0


class TestCollate:
    def test_masks_and_offsets(self):
        rng = np.random.RandomState(1)
        samples = [_rand_sample(rng, n) for n in (5, 8, 3)]
        batch = collate(samples, n_node=32, n_edge=256, n_graph=4)
        assert batch.x.shape == (32, 4)
        assert int(batch.count_real_nodes()) == 16
        assert int(batch.count_real_graphs()) == 3
        # padding edges self-loop on padding node
        em = np.asarray(batch.edge_mask)
        assert np.all(np.asarray(batch.senders)[~em] == 31)
        # node_graph of padding nodes is the padding graph
        nm = np.asarray(batch.node_mask)
        assert np.all(np.asarray(batch.node_graph)[~nm] == 3)
        # per-graph y preserved
        np.testing.assert_allclose(np.asarray(batch.y_graph)[1], samples[1].y_graph)

    def test_overflow_raises(self):
        rng = np.random.RandomState(2)
        samples = [_rand_sample(rng, 10)]
        with pytest.raises(ValueError):
            collate(samples, n_node=10, n_edge=500, n_graph=2)

    def test_bucketing_bounded(self):
        b = BucketSpec(multiple=64)
        sizes = {b.bucket(n) for n in range(1, 4096)}
        assert len(sizes) < 16
        for n in range(1, 4096):
            assert b.bucket(n) >= n


class TestRadiusGraph:
    def test_bcc_neighbor_count(self):
        # 3x3x3 BCC supercell, open boundaries: center atoms have 8 nbrs
        from tests.deterministic_data import bcc_positions
        pos = bcc_positions(3, 3, 3)
        send, recv = radius_graph(pos, 1.0)
        deg = np.bincount(recv, minlength=len(pos))
        # the most-interior center atom sees all 8 corner neighbors
        assert deg.max() >= 8
        # symmetry: edge set is symmetric
        edges = set(zip(send.tolist(), recv.tolist()))
        assert all((r, s) in edges for s, r in edges)

    def test_pbc_bcc_exact_counts(self):
        # reference analogue: tests/test_periodic_boundary_conditions.py —
        # exact neighbor counts. 1x1x1 BCC cell with PBC, cutoff just above
        # sqrt(3)/2: every atom has exactly 8 first-shell neighbors.
        pos = np.asarray([[0, 0, 0], [0.5, 0.5, 0.5]], np.float64)
        cell = np.eye(3)
        send, recv, shifts = radius_graph_pbc(pos, cell, r=0.9)
        deg = np.bincount(recv, minlength=2)
        assert deg[0] == 8 and deg[1] == 8
        # displacement lengths all equal sqrt(3)/2
        disp = pos[send] + shifts - pos[recv]
        d = np.linalg.norm(disp, axis=1)
        np.testing.assert_allclose(d, np.sqrt(3) / 2, rtol=1e-6)

    def test_cell_list_matches_bruteforce(self):
        rng = np.random.RandomState(3)
        pos = rng.rand(600, 3) * 5  # triggers the cell-list path
        s1, r1 = radius_graph(pos, 0.8)
        # brute force
        d2 = np.sum((pos[:, None] - pos[None, :]) ** 2, axis=-1)
        adj = d2 <= 0.64
        np.fill_diagonal(adj, False)
        r2, s2 = np.nonzero(adj)
        assert set(zip(s1.tolist(), r1.tolist())) == set(zip(s2.tolist(), r2.tolist()))


def test_descriptor_transforms():
    """Spherical + PointPair descriptors append edge columns and are
    rotation-equivariant/invariant as appropriate."""
    import numpy as np
    from hydragnn_tpu.preprocess.transforms import (point_pair_features,
                                                    spherical_coordinates)
    rng = np.random.RandomState(0)
    pos = rng.rand(10, 3).astype(np.float32) * 4
    send = np.repeat(np.arange(10), 3)
    recv = (send + rng.randint(1, 10, 30)) % 10
    vec = pos[send] - pos[recv]
    sph = spherical_coordinates(vec)
    assert sph.shape == (30, 3)
    np.testing.assert_allclose(sph[:, 0], np.linalg.norm(vec, axis=1),
                               rtol=1e-5)
    assert np.all(sph[:, 1] >= 0) and np.all(sph[:, 1] <= 2 * np.pi)
    ppf = point_pair_features(pos, vec, send, recv)
    assert ppf.shape == (30, 4)
    # PPF is rotation invariant (normals from the centroid co-rotate)
    theta = 0.7
    R = np.array([[np.cos(theta), -np.sin(theta), 0],
                  [np.sin(theta), np.cos(theta), 0],
                  [0, 0, 1]], np.float32)
    pos_r = pos @ R.T
    vec_r = pos_r[send] - pos_r[recv]
    ppf_r = point_pair_features(pos_r, vec_r, send, recv)
    np.testing.assert_allclose(ppf, ppf_r, atol=1e-4)


def test_build_graph_sample_with_descriptors():
    import numpy as np
    from hydragnn_tpu.preprocess.transforms import build_graph_sample
    rng = np.random.RandomState(1)
    nf = rng.rand(12, 2).astype(np.float32)
    pos = rng.rand(12, 3).astype(np.float32) * 3
    cfg = {
        "Dataset": {
            "node_features": {"dim": [1, 1], "column_index": [0, 1]},
            "graph_features": {"dim": [], "column_index": []},
            "Descriptors": ["SphericalCoordinates", "PointPairFeatures"],
        },
        "NeuralNetwork": {
            "Architecture": {"radius": 2.5, "max_neighbours": 10,
                             "edge_features": ["lengths"]},
            "Variables_of_interest": {
                "input_node_features": [0],
                "type": ["node"], "output_index": [1]},
        },
    }
    s = build_graph_sample(nf, pos, cfg)
    # 1 length + 3 spherical + 4 ppf columns
    assert s.edge_attr.shape[1] == 8


def test_neighbor_format_tables():
    """with_neighbor_format builds receiver-major fixed-degree tables that
    cover every real edge exactly once."""
    import numpy as np
    from hydragnn_tpu.graphs.batch import build_neighbor_tables

    rng = np.random.RandomState(0)
    n_node, n_edge = 33, 200
    send = rng.randint(0, n_node - 1, n_edge).astype(np.int32)
    recv = rng.randint(0, n_node - 1, n_edge).astype(np.int32)
    mask = rng.rand(n_edge) < 0.9
    nbr, nbr_edge, nbr_mask, _ = build_neighbor_tables(
        send, recv, mask, n_node, n_edge)
    assert int(nbr_mask.sum()) == int(mask.sum())
    covered = sorted(nbr_edge[nbr_mask].tolist())
    assert covered == sorted(np.nonzero(mask)[0].tolist())
    rows, slots = np.nonzero(nbr_mask)
    assert np.all(recv[nbr_edge[rows, slots]] == rows)
    assert np.all(send[nbr_edge[rows, slots]] == nbr[rows, slots])


def _slot_case(case):
    """A numpy batch (or a stack of two) with neighbour tables, by case:
    what `edge_slot` has to invert (ops/segment.edge_gather)."""
    from hydragnn_tpu.graphs.batch import with_neighbor_format

    rng = np.random.RandomState(3)
    samples = [_rand_sample(rng, n) for n in (9, 14, 6)]
    tot_e = sum(s.num_edges for s in samples)
    if case == "padded":
        return with_neighbor_format(collate(samples, np_out=True))
    if case == "last_edge_real":  # collate allows tot_e == n_edge
        b = with_neighbor_format(collate(
            samples, n_node=40, n_edge=tot_e, n_graph=4, np_out=True))
        assert bool(b.edge_mask[-1])
        return b
    from hydragnn_tpu.models.create import (build_model_config, create_model,
                                            init_params)
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.serving.engine import InferenceEngine
    from tests.utils import make_config

    cfg = update_config(make_config("PNA", heads=("graph", "node")), samples)
    mcfg = build_model_config(cfg)
    model = create_model(mcfg)
    eng = InferenceEngine(model, init_params(model, collate(samples)), mcfg,
                          reference_samples=samples, max_batch_size=4,
                          neighbor_format=True, num_shards=1)
    try:
        bucket = eng.buckets[-1]
        empty = eng._empty_shard(bucket)
        if case == "empty_shard":
            return empty
        assert case == "two_shards"
        return eng._stack_shards(
            [eng._collate_bucket(samples[:2], bucket), None], bucket)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("case", ["padded", "last_edge_real", "empty_shard",
                                  "two_shards"])
def test_edge_slot_inverts_the_neighbor_table(case):
    """Every real edge sits in exactly one slot of the dense table, and
    `edge_slot` names it: flat slot receiver * K + rank. A padding edge
    reads 0 and is masked."""
    b = _slot_case(case)
    assert b.edge_slot is not None and b.edge_slot.dtype == np.int32
    shards = ([b] if b.nbr_edge.ndim == 2 else
              [type(b)(**{k: None if v is None else v[i]
                          for k, v in vars(b).items()})
               for i in range(b.nbr_edge.shape[0])])
    assert len(shards) == (2 if case == "two_shards" else 1)
    for sh in shards:
        assert sh.edge_slot.shape == sh.edge_mask.shape
        real = np.nonzero(sh.edge_mask)[0]
        k = sh.nbr_edge.shape[1]
        slots = sh.edge_slot[real]
        assert np.all(sh.nbr_edge.reshape(-1)[slots] == real)
        assert np.all(sh.nbr_mask.reshape(-1)[slots])
        assert np.all(slots // k == sh.receivers[real])
        assert len(set(slots.tolist())) == real.size == int(sh.nbr_mask.sum())
        assert np.all(sh.edge_slot[~sh.edge_mask] == 0)
    if case == "empty_shard":
        assert not b.edge_mask.any() and not b.nbr_mask.any()
        assert np.all(b.nbr_edge == b.num_edges - 1)
        assert np.all(b.nbr == b.num_nodes - 1)
    if case == "two_shards":
        assert shards[0].edge_mask.any() and not shards[1].edge_mask.any()


def test_no_edge_slot_when_the_last_slot_is_real():
    """`edge_gather`'s fast path reads the padding value off the last slot;
    a hand-built batch whose last node is real and full carries no
    `edge_slot` and so indexes plainly. `build_neighbor_tables`, the one
    producer, holds the rule for every caller."""
    from hydragnn_tpu.graphs.batch import (GraphBatch, build_neighbor_tables,
                                           with_neighbor_format)

    n, k = 4, 8
    recv = np.repeat(np.arange(n), k).astype(np.int32)
    send = np.tile(np.arange(k) % n, n).astype(np.int32)
    full = GraphBatch(
        x=np.zeros((n, 1), np.float32), pos=np.zeros((n, 3), np.float32),
        senders=send, receivers=recv, node_graph=np.zeros(n, np.int32),
        node_mask=np.ones(n, bool), edge_mask=np.ones(n * k, bool),
        graph_mask=np.ones(1, bool))
    assert build_neighbor_tables(send, recv, full.edge_mask, n, n * k,
                                 k=k)[3] is None
    b = with_neighbor_format(full, k=k)
    assert b.nbr_mask.all() and b.edge_slot is None
    ev = jnp.arange(n * k * 2, dtype=jnp.float32).reshape(n * k, 2)
    assert np.array_equal(seg.edge_gather(ev, b), ev[b.nbr_edge])
    one_less = full.replace(edge_mask=np.arange(n * k) < n * k - 1)
    assert with_neighbor_format(one_less, k=k).edge_slot is not None


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


def test_neighbor_aggregate_matches_segment():
    import numpy as np
    import jax.numpy as jnp
    from hydragnn_tpu.graphs.batch import build_neighbor_tables
    from hydragnn_tpu.ops import segment as seg

    rng = np.random.RandomState(1)
    n_node, n_edge, f = 20, 120, 8
    send = rng.randint(0, n_node - 1, n_edge).astype(np.int32)
    recv = rng.randint(0, n_node - 1, n_edge).astype(np.int32)
    mask = rng.rand(n_edge) < 0.8
    h = rng.randn(n_edge, f).astype(np.float32)
    ref = seg.pna_aggregate(jnp.asarray(h), jnp.asarray(recv), n_node,
                            jnp.asarray(mask))
    nbr, nbr_edge, nbr_mask, _ = build_neighbor_tables(
        send, recv, mask, n_node, n_edge)
    hk = jnp.asarray(h)[jnp.asarray(nbr_edge)]
    out = seg.neighbor_aggregate(hk, jnp.asarray(nbr_mask))
    for a, b, name in zip(ref, out, ["mean", "min", "max", "std", "deg"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=name)


@pytest.mark.parametrize(
    "model_type", ["GIN", "SAGE", "GAT", "MFC", "CGCNN", "PNA",
                   "PNAPlus", "SchNet", "EGNN", "PAINN", "PNAEq",
                   "DimeNet", "MACE"])
def test_forward_matches_across_layouts(model_type):
    """Every stack must produce identical outputs from the edge-list and
    dense neighbor-list layouts (same parameters)."""
    import numpy as np
    from hydragnn_tpu.graphs.batch import with_neighbor_format
    from hydragnn_tpu.models.create import create_model, init_params
    from tests.deterministic_data import deterministic_graph_dataset
    from tests.utils import prepare

    samples = deterministic_graph_dataset(num_configs=8)
    cfg, mcfg, batch = prepare(model_type, samples)
    if model_type == "DimeNet":
        from hydragnn_tpu.graphs.triplets import add_triplets, triplet_budget
        batch = add_triplets(batch, triplet_budget(samples[:8], 8))
    model = create_model(mcfg)
    variables = init_params(model, batch)
    out_edges, _ = model.apply(variables, batch, train=False)
    out_nbr, _ = model.apply(variables, with_neighbor_format(batch),
                             train=False)
    for a, b in zip(out_edges, out_nbr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_neighbor_softmax_grad_finite_with_empty_rows():
    """Gradient through neighbor_softmax must stay finite when a node has
    zero real neighbors (the where-around-exp NaN trap)."""
    import jax
    logits = jnp.asarray(np.random.RandomState(0).randn(4, 5).astype(np.float32))
    mask = jnp.asarray(np.array([[1, 1, 0, 0, 0],
                                 [0, 0, 0, 0, 0],   # empty row
                                 [1, 1, 1, 1, 1],
                                 [1, 0, 0, 0, 0]], bool))

    def f(lg):
        return jnp.sum(seg.neighbor_softmax(lg, mask) ** 2)

    g = jax.grad(f)(logits)
    assert np.all(np.isfinite(np.asarray(g)))
    a = seg.neighbor_softmax(logits, mask)
    np.testing.assert_allclose(np.asarray(a[1]), 0.0)
    np.testing.assert_allclose(np.asarray(a.sum(1)[0]), 1.0, rtol=1e-5)
