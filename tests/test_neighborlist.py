"""Verlet-skin incremental neighbor lists
(hydragnn_tpu/graphs/neighborlist.py, docs/serving.md raw-structure
section).

Contract under test — the PR 5 total order, made incremental:
* every ``update()`` emits edges BITWISE-identical to a fresh
  ``radius_graph``/``radius_graph_pbc`` build at the same positions
  (open + PBC, capped + uncapped, across the n=512↔513 dense/cell-list
  straddle), while actually reusing the candidate cache between rebuilds;
* no pair within the cutoff is ever missed between rebuilds (brute-force
  O(N²) oracle, independent of both implementations);
* the rebuild trigger fires exactly past the skin/2 displacement bound,
  on any cell change, and on every step at skin 0;
* the candidate-layout cap (`_CandidateCap`) selects exactly the
  documented (d², sender[, shift-id]) smallest-k, ties included.

The slow lane runs the BENCH_MD subprocess smoke: the closed-loop MD
bench must hold its cross-mode bitwise adjudications and a speedup floor
on a CI-sized trajectory.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from hydragnn_tpu.graphs.neighborlist import NeighborList, _CandidateCap
from hydragnn_tpu.graphs.radius import (_cap_neighbours, radius_graph,
                                        radius_graph_pbc)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _walk(rng, pos, scale):
    return pos + rng.randn(*pos.shape) * scale


# ------------------------------------------------- incremental == fresh --

@pytest.mark.parametrize("n,cap", [(40, None), (40, 6), (500, 6),
                                   (513, 6), (530, None)])
def test_open_incremental_matches_fresh_bitwise(n, cap):
    """Every step's edges equal a fresh radius_graph build bit for bit —
    including across the dense/cell-list straddle — with real reuse."""
    rng = np.random.RandomState(n)
    pos = rng.rand(n, 3) * (n ** (1 / 3.0))
    nl = NeighborList(0.6, 0.2, max_neighbours=cap)
    for step in range(20):
        pos = _walk(rng, pos, 0.01)
        send, recv, shifts, _ = nl.update(pos)
        f_send, f_recv = radius_graph(pos, 0.6, max_neighbours=cap)
        assert shifts is None
        np.testing.assert_array_equal(send, f_send)
        np.testing.assert_array_equal(recv, f_recv)
        assert send.dtype == np.int32
    assert 0 < nl.rebuilds < nl.updates, "no candidate reuse happened"
    assert nl.rebuild_fraction == nl.rebuilds / nl.updates


@pytest.mark.parametrize("nd,box,r,cap", [
    (2, 2.0, 1.9, None),   # tiny cell: self-images are neighbors
    (2, 2.0, 1.9, 8),      # ... with the shift-id cap tie-break live
    (5, 6.0, 2.0, 8),
    (5, 6.0, 2.0, None),
])
def test_pbc_incremental_matches_fresh_bitwise(nd, box, r, cap):
    """PBC: senders/receivers AND the float32 cartesian shift vectors
    equal the fresh build's, across rebuild boundaries."""
    rng = np.random.RandomState(nd)
    n = nd ** 3
    cell = np.eye(3) * box
    grid = np.stack(np.meshgrid(*[np.arange(nd)] * 3, indexing="ij"),
                    axis=-1).reshape(-1, 3) * (box / nd)
    pos = grid + rng.rand(n, 3) * 0.03
    nl = NeighborList(r, 0.3, max_neighbours=cap, pbc=(True, True, True))
    for step in range(20):
        pos = _walk(rng, pos, 0.008)
        send, recv, shifts, _ = nl.update(pos, cell=cell)
        f_send, f_recv, f_shifts = radius_graph_pbc(pos, cell, r,
                                                    max_neighbours=cap)
        np.testing.assert_array_equal(send, f_send)
        np.testing.assert_array_equal(recv, f_recv)
        np.testing.assert_array_equal(shifts, f_shifts)
    assert 0 < nl.rebuilds < nl.updates, "no candidate reuse happened"


def test_no_edge_missed_between_rebuilds_bruteforce():
    """Independent O(N²) oracle: between rebuilds no within-cutoff pair
    is ever dropped and no beyond-cutoff pair ever emitted."""
    rng = np.random.RandomState(3)
    n, r = 120, 0.7
    pos = rng.rand(n, 3) * 3.0
    nl = NeighborList(r, 0.25)
    for step in range(30):
        pos = _walk(rng, pos, 0.012)
        send, recv, _, _ = nl.update(pos)
        d2 = np.sum((pos[:, None] - pos[None, :]) ** 2, axis=-1)
        adj = d2 <= r * r
        np.fill_diagonal(adj, False)
        o_recv, o_send = np.nonzero(adj)
        assert (set(zip(send.tolist(), recv.tolist()))
                == set(zip(o_send.tolist(), o_recv.tolist()))), step
    assert nl.rebuilds < nl.updates


# ------------------------------------------------------ rebuild trigger --

def test_rebuild_triggers_exactly_at_skin_half():
    """Displacement of exactly skin/2 reuses the cache; one epsilon past
    it rebuilds — the bound is strict, matching the coverage argument
    (two atoms at skin/2 apiece close at most skin)."""
    rng = np.random.RandomState(0)
    skin = 0.25                            # skin/2 = 0.125, a power of two
    pos = rng.rand(60, 3) * 3.0
    pos[7, 0] = 1.0                        # exact binary coordinate, so
    # the +0.125 displacement below is computed without rounding
    nl = NeighborList(0.8, skin)
    nl.update(pos)
    assert nl.rebuilds == 1

    at_bound = pos.copy()
    at_bound[7, 0] += skin / 2            # exactly at the bound
    nl.update(at_bound)
    assert nl.rebuilds == 1, "rebuild at exactly skin/2 — bound not strict"

    past_bound = pos.copy()
    past_bound[7, 0] += skin / 2 + 1e-9   # just past it
    nl.update(past_bound)
    assert nl.rebuilds == 2, "no rebuild just past skin/2"
    # displacement is measured against the NEW reference after a rebuild
    nl.update(past_bound)
    assert nl.rebuilds == 2


def test_cell_change_forces_rebuild():
    """Any lattice change — including a pure volume change — invalidates
    the image enumeration and must rebuild, even with zero atom motion
    relative to the fractional frame."""
    rng = np.random.RandomState(1)
    cell = np.eye(3) * 4.0
    pos = rng.rand(40, 3) * 4.0
    nl = NeighborList(1.0, 0.3, pbc=(True, True, True))
    nl.update(pos, cell=cell)
    nl.update(pos, cell=cell)
    assert nl.rebuilds == 1
    scaled = cell * 1.0005
    send, recv, shifts, rebuilt = nl.update(pos, cell=scaled)
    assert rebuilt and nl.rebuilds == 2
    f_send, f_recv, f_shifts = radius_graph_pbc(pos, scaled, 1.0)
    np.testing.assert_array_equal(send, f_send)
    np.testing.assert_array_equal(shifts, f_shifts)


def test_zero_skin_rebuilds_every_step():
    rng = np.random.RandomState(2)
    pos = rng.rand(50, 3) * 2.0
    nl = NeighborList(0.7, 0.0)
    for step in range(5):
        pos = _walk(rng, pos, 1e-6)
        *_, rebuilt = nl.update(pos)
        assert rebuilt
    assert nl.rebuilds == nl.updates == 5
    assert nl.rebuild_fraction == 1.0


def test_atom_count_change_and_empty():
    nl = NeighborList(1.0, 0.3)
    send, recv, shifts, rebuilt = nl.update(np.zeros((0, 3)))
    assert rebuilt and len(send) == 0 and shifts is None
    rng = np.random.RandomState(4)
    pos = rng.rand(30, 3)
    *_, rebuilt = nl.update(pos)
    assert rebuilt  # 0 -> 30 atoms
    *_, rebuilt = nl.update(np.concatenate([pos, rng.rand(1, 3)]))
    assert rebuilt  # 30 -> 31 atoms


def test_validation_errors():
    with pytest.raises(ValueError, match="cutoff"):
        NeighborList(0.0, 0.1)
    with pytest.raises(ValueError, match="skin"):
        NeighborList(1.0, -0.1)
    with pytest.raises(ValueError, match="cell"):
        NeighborList(1.0, 0.1, pbc=(True, True, True)).update(
            np.zeros((3, 3)))
    with pytest.raises(ValueError, match="open-boundary"):
        NeighborList(1.0, 0.1).update(np.zeros((3, 3)), cell=np.eye(3))


# -------------------------------------------------- candidate-layout cap --

def test_candidate_cap_matches_generic_cap_with_ties():
    """`_CandidateCap.keep` == the documented `_cap_neighbours` order on
    heavy-tie inputs, with out-of-cutoff candidates masked to +inf."""
    rng = np.random.RandomState(5)
    for trial in range(50):
        nseg = rng.randint(1, 20)
        recv = np.concatenate([np.full(rng.randint(1, 25), s)
                               for s in range(nseg)])
        n_edges = len(recv)
        send = np.concatenate(
            [np.sort(rng.choice(500, size=int((recv == s).sum()),
                                replace=False)) for s in range(nseg)])
        d2 = rng.choice([0.25, 1.0, 2.25, rng.rand()], size=n_edges)
        ok = rng.rand(n_edges) < 0.8
        k = int(rng.randint(1, 6))
        got = _CandidateCap(recv, k).keep(d2, ok)
        # reference: compress first, cap with the generic total order
        ref_keep = _cap_neighbours(d2[ok], recv[ok], k, send[ok])
        full_ref = np.zeros(n_edges, bool)
        full_ref[np.flatnonzero(ok)[ref_keep]] = True
        np.testing.assert_array_equal(got, full_ref, err_msg=str(trial))


def test_candidate_cap_skewed_degrees_fallback():
    """One huge segment beside thousands of singletons: the dense matrix
    would waste > _CAP_DENSE_WASTE x the edges, so the lexsort fallback
    fires — and must select identically (incl. all-filtered inputs)."""
    rng = np.random.RandomState(6)
    recv = np.concatenate([np.zeros(40000, np.int64),
                           np.arange(1, 20001, dtype=np.int64)])
    n_edges = len(recv)
    send = np.concatenate([np.arange(40000), np.zeros(20000)])
    d2 = rng.rand(n_edges)
    ok = rng.rand(n_edges) < 0.7
    cap = _CandidateCap(recv, 5)
    assert cap.mat is None and not cap.keep_all  # fallback branch live
    got = cap.keep(d2, ok)
    ref_keep = _cap_neighbours(d2[ok], recv[ok], 5, send[ok])
    full_ref = np.zeros(n_edges, bool)
    full_ref[np.flatnonzero(ok)[ref_keep]] = True
    np.testing.assert_array_equal(got, full_ref)
    assert not cap.keep(d2, np.zeros(n_edges, bool)).any()


# --------------------------------------------------- BENCH_MD slow smoke --

@pytest.mark.slow
def test_bench_md_smoke():
    """CI-sized BENCH_MD subprocess: the three neighbor strategies must
    traverse bitwise-identical trajectories, the incremental edges must
    equal fresh builds at every recorded step, the prebuilt-submit
    bitwise parity must hold, and the Verlet skin must show a real
    speedup (the committed BENCH_MD.json quotes the full-size numbers —
    CI boxes only guard a conservative floor)."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu", BENCH_MD="1",
               BENCH_MD_ATOMS="512", BENCH_MD_STEPS="25",
               BENCH_MD_RADIUS="4.0", BENCH_MD_CAP="12",
               BENCH_MD_HIDDEN="4", BENCH_MD_DT="0.004",
               BENCH_MD_TEMP="0.3")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True,
                       timeout=900, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["trajectories_bitwise_equal_across_modes"], out
    assert out["incremental_edges_bitwise_equal_vs_fresh"], out
    assert out["prebuilt_submit_bitwise_parity"], out
    assert out["rebuild_fraction"] < 0.5, out
    assert out["speedup_incremental_vs_rebuild"] >= 1.5, out
    assert out["compile_count_after_warmup"] == 1, out


def test_cap_zero_keeps_nothing_everywhere():
    """max_neighbours=0 must drop every edge in ALL cap implementations
    (the legacy rank < 0 semantics): generic lexsort, canonical dense,
    skew fallback, and the candidate-layout cap."""
    rng = np.random.RandomState(7)
    recv = np.sort(rng.randint(0, 20, 300))
    send = np.arange(300)
    d2 = rng.rand(300)
    assert not _cap_neighbours(d2, recv, 0, send).any()
    assert not _cap_neighbours(d2, recv, 0, send,
                               canonical_order=True).any()
    assert not _CandidateCap(recv, 0).keep(d2,
                                           np.ones(300, bool)).any()
    s, r = radius_graph(rng.rand(30, 3), 0.8, max_neighbours=0)
    assert len(s) == 0 and len(r) == 0
