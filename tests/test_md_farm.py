"""Massively-batched on-device MD farm (hydragnn_tpu/md/,
docs/serving.md "MD farm").

Contracts under test:
* the grid integrator (md/integrator.py) computes IDENTICAL values in
  numpy and in compiled jax — under jit, vmap, and scan — because every
  operation is exact or single-rounded on exact operands (the
  association-proof design its docstring documents);
* the batched compiled re-filter (md/farm.make_batched_refilter) emits
  BITWISE the per-trajectory `NeighborList` keep decisions — open + PBC,
  capped + uncapped, cap-tie lattices, heterogeneous rebuild times
  across the batch — on the same stacked candidate layout the farm packs
  (`pack_candidates`);
* end to end (slow lane): every `TrajectoryFarm` trajectory equals the
  PR 10 single-session `run_md` loop bitwise from identical initial
  conditions, including the 1-trajectory degenerate farm, and the
  BENCH_MD_FARM subprocess smoke holds its scaling floor + adjudication
  flags on a CI-sized run.

Everything jax-side runs under ``jax.enable_x64`` — the
farm's own execution convention (its f64 grid state needs it, and the
session reference must trace under the same dtype semantics).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from hydragnn_tpu.graphs.neighborlist import NeighborList
from hydragnn_tpu.md import integrator as mdi
from hydragnn_tpu.md.farm import make_batched_refilter, pack_candidates

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _x64():
    import jax
    return jax.enable_x64(True)


# ------------------------------------------------------------ integrator --

def test_integrator_matches_numpy_bitwise_under_jit_vmap_scan():
    """drift/kick/accel_term: numpy and compiled jax must agree BITWISE
    — standalone, vmapped over trajectories, and inside a scan — for
    grid-state inputs. This is the association-proof property the
    whole farm-vs-session contract stands on."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    T, n = 3, 40
    dt = 0.004
    pos, vd = mdi.init_state(rng.randn(T, n, 3) * 2.0,
                             rng.randn(T, n, 3), dt)
    s_hi, s_lo = mdi.force_scale_split(dt, force_scale=1.7, mass=0.9)
    forces = rng.randn(T, n, 3).astype(np.float32) * 50.0
    ad2 = mdi.accel_term(forces, s_hi, s_lo)
    ad2_new = mdi.accel_term(-2.5 * forces, s_hi, s_lo)

    np_drift = mdi.drift(pos, vd, ad2)
    np_kick = mdi.kick(vd, ad2, ad2_new)
    with _x64():
        j_drift = np.asarray(jax.jit(
            lambda p, v, a: mdi.drift(p, v, a, xp=jnp))(pos, vd, ad2))
        j_kick = np.asarray(jax.jit(
            lambda v, a, b: mdi.kick(v, a, b, xp=jnp))(vd, ad2, ad2_new))
        j_acc = np.asarray(jax.jit(
            lambda f: mdi.accel_term(f, s_hi, s_lo, xp=jnp))(forces))
        np.testing.assert_array_equal(np_drift, j_drift)
        np.testing.assert_array_equal(np_kick, j_kick)
        np.testing.assert_array_equal(ad2, j_acc)

        # vmap over the trajectory axis + a 4-step scan, against the
        # straight numpy loop
        def body(carry, f):
            p, v, a = carry
            p2 = mdi.drift(p, v, a, xp=jnp)
            a2 = mdi.accel_term(f, s_hi, s_lo, xp=jnp)
            v2 = mdi.kick(v, a, a2, xp=jnp)
            return (p2, v2, a2), p2

        def scan_all(p, v, a, fs):
            return jax.lax.scan(body, (p, v, a), fs)

        fs = (rng.randn(4, T, n, 3) * 30.0).astype(np.float32)
        (jp, jv, ja), traj = jax.jit(scan_all)(pos, vd, ad2, fs)
        hp, hv, ha = pos, vd, ad2
        for k in range(4):
            hp = mdi.drift(hp, hv, ha)
            ha2 = mdi.accel_term(fs[k], s_hi, s_lo)
            hv = mdi.kick(hv, ha, ha2)
            ha = ha2
            np.testing.assert_array_equal(hp, np.asarray(traj[k]))
        np.testing.assert_array_equal(hp, np.asarray(jp))
        np.testing.assert_array_equal(hv, np.asarray(jv))
        np.testing.assert_array_equal(ha, np.asarray(ja))


def test_integrator_grid_and_validation():
    """Grid states are fixed points of their quantizers; the split scale
    halves recombine exactly; out-of-budget systems are rejected with
    actionable errors."""
    rng = np.random.RandomState(1)
    pos, vd = mdi.init_state(rng.randn(10, 3), rng.randn(10, 3), 0.004)
    np.testing.assert_array_equal(pos, mdi.quantize_pos(pos))
    np.testing.assert_array_equal(vd, mdi.quantize_vel(vd))
    cell = mdi.quantize_cell(np.eye(3) * 4.0 + rng.rand(3, 3) * 0.01)
    np.testing.assert_array_equal(cell, mdi.quantize_pos(cell))
    s_hi, s_lo = mdi.force_scale_split(0.004, 1.3, 0.7)
    s2 = (1.3 / 0.7) * 0.004 * 0.004 * 2.0 ** mdi.VEL_BITS
    assert s_hi + s_lo == s2  # Veltkamp split is exact
    with pytest.raises(ValueError, match="coordinate magnitude"):
        mdi.validate_ranges(1e7, 2.0)
    with pytest.raises(ValueError, match="exact-d"):
        mdi.validate_ranges(10.0, 100.0)
    mdi.validate_ranges(10.0, 5.3)  # the BENCH_MD shape passes


def test_rebuild_fraction_zero_updates_guard():
    """`rebuild_fraction` with zero updates returns 0.0 and never raises
    — on the NeighborList itself and on a fresh StructureSession (the
    serving gauge reads the same guarded engine counters)."""
    from hydragnn_tpu.serving.engine import StructureSession
    nl = NeighborList(1.0, 0.3)
    assert nl.rebuild_fraction == 0.0
    assert StructureSession(nl).rebuild_fraction == 0.0


# ----------------------------------------------------- batched re-filter --

def _walk_on_grid(rng, pos, scale):
    return mdi.quantize_pos(pos + rng.randn(*pos.shape) * scale)


@pytest.mark.parametrize("pbc,cap", [(False, None), (False, 5),
                                     (True, None), (True, 6)])
def test_batched_refilter_matches_neighborlist_oracle(pbc, cap):
    """The compiled batched re-filter's keep decisions — and the edges
    they induce — equal per-trajectory `NeighborList.update` emissions
    BITWISE at every step, across heterogeneous rebuild times (each
    trajectory walks at its own temperature, so rebuilds interleave),
    with the 1-trajectory degenerate case as trajectory 0's own
    sub-history."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(3 if pbc else 4)
    T, n, r, skin = 3, 40, 1.1, 0.3
    cell = mdi.quantize_cell(np.eye(3) * 3.5) if pbc else None
    pos = np.stack([
        mdi.quantize_pos(rng.rand(n, 3) * 3.0) for _ in range(T)])
    nls = [NeighborList(r, skin, max_neighbours=cap,
                        pbc=(True, True, True) if pbc else None)
           for _ in range(T)]
    c_cap, w_cap = 4096, 64
    scales = [0.004, 0.012, 0.03]  # heterogeneous rebuild cadences

    with _x64():
        refilter = jax.jit(make_batched_refilter(n, r, cap, w_cap))
        packed = [None] * T
        for step in range(12):
            edges_ref = []
            for t in range(T):
                if step:
                    pos[t] = _walk_on_grid(rng, pos[t], scales[t])
                send, recv, shifts, rebuilt = nls[t].update(
                    pos[t], cell=cell)
                edges_ref.append((send, recv, shifts))
                if rebuilt or packed[t] is None:
                    packed[t] = pack_candidates(
                        nls[t], c_cap, w_cap, n, pbc=pbc,
                        capped=cap is not None)
            caches = {k: jnp.stack([jnp.asarray(p[k]) for p in packed])
                      for k in packed[0]}
            keep = np.asarray(refilter(
                jnp.asarray(pos), caches["send"], caches["recv"],
                caches["valid"], caches["seg_start"], caches["off"]))
            for t in range(T):
                kept = keep[t]
                send, recv, shifts = edges_ref[t]
                np.testing.assert_array_equal(
                    packed[t]["send"][kept].astype(np.int32), send,
                    err_msg=f"step {step} traj {t}")
                np.testing.assert_array_equal(
                    packed[t]["recv"][kept].astype(np.int32), recv)
                if pbc:
                    np.testing.assert_array_equal(
                        packed[t]["shift"][kept], shifts)
        assert any(nl.rebuilds > 1 for nl in nls), "no rebuild exercised"
        assert any(nl.rebuilds < nl.updates for nl in nls), \
            "no candidate reuse exercised"


def test_batched_refilter_cap_tie_lattice():
    """Perfect-lattice grid positions: every neighbor shell ties exactly
    in d², so the cap's (d², input order) tie-break is live — the
    compiled selection must reproduce the host's tie winners bitwise."""
    import jax
    import jax.numpy as jnp

    nd, spacing, r, cap = 4, 1.0, 1.05, 3  # 6 tied first-shell nbrs, keep 3
    grid = np.stack(np.meshgrid(*[np.arange(nd)] * 3, indexing="ij"),
                    axis=-1).reshape(-1, 3) * spacing
    pos = mdi.quantize_pos(grid.astype(np.float64))
    n = pos.shape[0]
    nl = NeighborList(r, 0.25, max_neighbours=cap)
    send, recv, _, _ = nl.update(pos)
    packed = pack_candidates(nl, 1024, 32, n, pbc=False, capped=True)
    with _x64():
        refilter = jax.jit(make_batched_refilter(n, r, cap, 32))
        keep = np.asarray(refilter(
            jnp.asarray(pos)[None],
            jnp.asarray(packed["send"])[None],
            jnp.asarray(packed["recv"])[None],
            jnp.asarray(packed["valid"])[None],
            jnp.asarray(packed["seg_start"])[None],
            jnp.asarray(packed["off"])[None]))[0]
    np.testing.assert_array_equal(packed["send"][keep].astype(np.int32),
                                  send)
    np.testing.assert_array_equal(packed["recv"][keep].astype(np.int32),
                                  recv)
    # interior atoms really had to drop tied shell members
    assert len(send) < 6 * n


# ----------------------------------------------------- end-to-end (slow) --

def _farm_fixture(pbc, cap, hidden=4, apd=3, radius=1.2, lattice=1.0,
                  skin=0.3):
    from examples.md_loop.md_loop import (init_lattice, lj_md_config,
                                          md_buckets)
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.graphs.batch import collate
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.preprocess.transforms import build_graph_sample
    from hydragnn_tpu.serving.engine import InferenceEngine

    cfg = lj_md_config(radius=radius, max_neighbours=cap,
                       hidden_dim=hidden, num_conv_layers=1,
                       num_gaussians=8)
    cfg["NeuralNetwork"]["Architecture"][
        "periodic_boundary_conditions"] = pbc
    pos0, cell = init_lattice(apd, lattice, jitter=0.05, seed=1)
    if not pbc:
        cell = None
    n = pos0.shape[0]
    nf = np.ones((n, 1), np.float32)
    frame0 = build_graph_sample(nf, pos0, cfg, cell=cell,
                                with_targets=False)
    ucfg = update_config(cfg, [frame0])
    mcfg = build_model_config(ucfg)
    model = create_model(mcfg)
    variables = init_params(model, collate([frame0]))
    engine = InferenceEngine(
        model, variables, mcfg,
        buckets=md_buckets(n, max(frame0.num_edges, 1)),
        proto_sample=frame0, max_batch_size=1, max_wait_ms=0.0,
        structure_config=ucfg, md_skin=skin, ef_forward=True)
    engine.warmup()
    return engine, ucfg, n, nf, cell


@pytest.mark.slow
@pytest.mark.parametrize("pbc,cap", [(True, 6), (False, None)])
def test_farm_bitwise_vs_single_session(pbc, cap):
    """End to end: every farm trajectory — hot and cold walkers rebuild
    at different times, swaps landing mid-run — equals the PR 10
    single-session `run_md` incremental loop bitwise (positions,
    velocities, first/last energies), and the 1-trajectory farm equals
    its T=3 sibling (width independence)."""
    from examples.md_loop.md_loop import (init_lattice,
                                          maxwell_velocities, run_md)
    with _x64():
        engine, ucfg, n, nf, cell = _farm_fixture(pbc, cap)
        try:
            T, S, dt, skin = 3, 24, 0.004, 0.3
            pos_t = np.stack([init_lattice(3, 1.0, jitter=0.05,
                                           seed=100 + t)[0]
                              for t in range(T)])
            vel_t = np.stack([maxwell_velocities(n, 0.3 * (t + 1),
                                                 seed=200 + t)
                              for t in range(T)])
            farm = engine.trajectory_farm(dt=dt, skin=skin,
                                          steps_per_dispatch=5)
            res = farm.run(pos_t, vel_t, S, node_features=nf, cell=cell)
            assert res["rebuild_swaps"] > 0, "no mid-run swap exercised"
            for t in range(T):
                seq = run_md(engine, ucfg, pos_t[t], vel_t[t], cell, nf,
                             steps=S, dt=dt, mode="incremental",
                             skin=skin)
                np.testing.assert_array_equal(res["final_pos"][t],
                                              seq["final_pos"])
                np.testing.assert_array_equal(res["final_vel"][t],
                                              seq["final_vel"])
                # the scalar energy READOUT may reassociate in the last
                # ulp at large batch widths (farm.py docstring); the
                # trajectory is exact, the readout near-exact
                assert np.isclose(float(res["energy_first"][t]),
                                  seq["energy_first"], rtol=1e-9)
                assert np.isclose(float(res["energy_last"][t]),
                                  seq["energy_last"], rtol=1e-9)
            farm1 = engine.trajectory_farm(dt=dt, skin=skin,
                                           steps_per_dispatch=5)
            res1 = farm1.run(pos_t[:1], vel_t[:1], S, node_features=nf,
                             cell=cell)
            np.testing.assert_array_equal(res1["final_pos"][0],
                                          res["final_pos"][0])
            np.testing.assert_array_equal(res1["final_vel"][0],
                                          res["final_vel"][0])
        finally:
            engine.shutdown()


@pytest.mark.slow
def test_farm_telemetry_and_validation():
    """Farm counters land in the telemetry registry (deterministic
    `data` bucket in the JSONL event), and the farm rejects
    out-of-contract inputs with actionable errors."""
    from examples.md_loop.md_loop import init_lattice, maxwell_velocities
    from hydragnn_tpu.telemetry.registry import (MetricsRegistry,
                                                 set_registry)
    with _x64():
        engine, ucfg, n, nf, cell = _farm_fixture(True, 6)
        try:
            reg = MetricsRegistry()
            prev = set_registry(reg)
            try:
                farm = engine.trajectory_farm(dt=0.004, skin=0.3)
                pos_t = init_lattice(3, 1.0, jitter=0.05, seed=7)[0][None]
                vel_t = maxwell_velocities(n, 0.3, seed=8)[None]
                res = farm.run(pos_t, vel_t, 6, node_features=nf,
                               cell=cell)
            finally:
                set_registry(prev)
            snap = reg.snapshot()
            assert snap["md.farm_steps_total"]["values"][()] == 6.0
            assert "md.farm_steps_per_dispatch" in snap
            evts = [e for e in reg.events if e["name"] == "farm_run"]
            assert len(evts) == 1
            assert evts[0]["data"]["steps"] == 6
            assert evts[0]["data"]["trajectories"] == 1
            assert "wall_s" in evts[0]["timing"]

            with pytest.raises(ValueError, match=r"\[T, n_atoms, 3\]"):
                farm.run(pos_t[0], vel_t[0], 4, node_features=nf,
                         cell=cell)
            with pytest.raises(ValueError, match="steps must be"):
                farm.run(pos_t, vel_t, 0, node_features=nf, cell=cell)
            with pytest.raises(ValueError, match="cell"):
                farm.run(pos_t, vel_t, 4, node_features=nf)
        finally:
            engine.shutdown()


@pytest.mark.slow
def test_trajectory_farm_requires_single_bucket_and_ef():
    with _x64():
        engine, *_ = _farm_fixture(True, 6)
        try:
            engine.ef_forward = False
            with pytest.raises(ValueError, match="ef_forward"):
                engine.trajectory_farm(dt=0.004)
            engine.ef_forward = True
            buckets = engine.buckets
            engine.buckets = buckets + buckets  # multi-bucket ladder
            try:
                with pytest.raises(ValueError, match="single-bucket"):
                    engine.trajectory_farm(dt=0.004)
            finally:
                engine.buckets = buckets
            # config-block knob reaches the farm (the documented
            # env-over-config precedence; env unset here)
            engine._structure_cfg.setdefault("Serving", {})["md_farm"] = {
                "steps_per_dispatch": 3}
            farm = engine.trajectory_farm(dt=0.004)
            assert farm.steps_per_dispatch == 3
        finally:
            engine.ef_forward = True
            engine.shutdown()


@pytest.mark.slow
def test_bench_md_farm_smoke():
    """CI-sized BENCH_MD_FARM subprocess: the farm-vs-session and
    cross-width bitwise adjudications must hold and aggregate steps/s
    must scale with trajectory count (conservative floor — the
    committed BENCH_MD_FARM.json quotes the full 1/64/1024 numbers)."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               BENCH_MD_FARM="1", BENCH_MD_FARM_ATOMS="8",
               BENCH_MD_FARM_STEPS="32", BENCH_MD_FARM_TRAJ="1,16",
               BENCH_MD_FARM_CHECK_TRAJ="2")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True,
                       timeout=900, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["farm_vs_session_bitwise"], out
    assert out["farm_vs_session_energy_within_tol"], out
    assert out["cross_width_bitwise"], out
    assert out["farm_vs_session_trajectories_checked"] >= 3, out
    assert out["aggregate_scaling_vs_first"]["16"] >= 2.0, out
    assert out["trajectories"]["16"]["rebuild_fraction"] < 0.5, out
