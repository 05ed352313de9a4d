"""Active-learning MD farm (hydragnn_tpu/md/active.py,
docs/active_learning.md).

Contracts under test:
* the `EnsembleScorer` validates its spec up front, and its
  perturbation multipliers are a pure function of (seed, members, eps)
  — member 0 exactly 1.0, twin constructions bitwise;
* the deterministic harvest rule: the device's rising-edge decisions
  equal a host-side replay of the SAME rule over the emitted
  (unc, adv) traces, the tau = ±inf straddle cases land exactly where
  the contract says, and twin farm runs harvest BITWISE-identical
  pools (positions, steps, uncertainties, content digests);
* the scored dispatch is compile-pinned: the first run on a shape
  compiles exactly once, every subsequent run adds ZERO compiles, and
  hot-swapping variables through `swap_variables` adds none either;
* the `CandidatePool` dedups by content address (same grid state ->
  same shard, re-adds are hits, `manifest_digest` stable) and
  round-trips oracle labels;
* (slow) the BENCH_ACTIVE subprocess smoke holds its adjudication
  flags at CI scale.

Everything jax-side runs under ``jax.enable_x64`` (the
farm's execution convention).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from hydragnn_tpu.md.active import (CandidatePool, EnsembleScorer,
                                    structure_key)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _x64():
    import jax
    return jax.enable_x64(True)


def _replay_harvest(unc, adv, step, tau):
    """Host-side replay of the farm's rising-edge harvest rule over one
    trajectory's per-step traces — the independent oracle the device
    decisions are pinned against."""
    out, was_above = [], False
    for u, a, s in zip(unc, adv, step):
        if not a:
            continue
        above = bool(u >= tau)
        if above and not was_above:
            out.append((int(s), np.float32(u)))
        was_above = above
    return out


# ------------------------------------------------------------ fast lane --

def _tiny_model(seed=1):
    """(model, mcfg, variables, ucfg, pos0, nf, cell) — the LJ MD shape
    without an engine (no serving threads, fast-lane friendly)."""
    from examples.md_loop.md_loop import init_lattice, lj_md_config
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.graphs.batch import collate
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.preprocess.transforms import build_graph_sample

    cfg = lj_md_config(radius=1.2, max_neighbours=6, hidden_dim=4,
                       num_conv_layers=1, num_gaussians=8)
    pos0, cell = init_lattice(2, 1.0, jitter=0.05, seed=seed)
    nf = np.ones((pos0.shape[0], 1), np.float32)
    frame0 = build_graph_sample(nf, pos0, cfg, cell=cell,
                                with_targets=False)
    ucfg = update_config(cfg, [frame0])
    mcfg = build_model_config(ucfg)
    model = create_model(mcfg)
    variables = init_params(model, collate([frame0]))
    return model, mcfg, variables, ucfg, pos0, nf, cell


def test_scorer_validation_and_multiplier_determinism():
    model, mcfg, variables, ucfg, pos0, nf, cell = _tiny_model()

    with pytest.raises(ValueError, match=">= 2 members"):
        EnsembleScorer(model, mcfg, variables, members=1)
    with pytest.raises(ValueError, match="eps must be"):
        EnsembleScorer(model, mcfg, variables, eps=0.0)
    with pytest.raises(ValueError, match="harvest_cap"):
        EnsembleScorer(model, mcfg, variables, harvest_cap=0)
    # a head layout the ensemble cannot replay fails at CONSTRUCTION
    bad = {"params": {"head_0": {"weird": {}}},
           "batch_stats": {}}
    with pytest.raises(ValueError, match="node-MLP"):
        EnsembleScorer(model, mcfg, bad)

    a = EnsembleScorer(model, mcfg, variables, members=4, eps=0.03,
                       seed=11)
    b = EnsembleScorer(model, mcfg, variables, members=4, eps=0.03,
                       seed=11)
    c = EnsembleScorer(model, mcfg, variables, members=4, eps=0.03,
                       seed=12)
    diff_seen = False
    for lname, leaf in a._mults.items():
        for pname, m in leaf.items():
            # member 0 is the UNPERTURBED head
            np.testing.assert_array_equal(m[0], np.ones_like(m[0]))
            # twin constructions are bitwise; a different seed is not
            np.testing.assert_array_equal(m, b._mults[lname][pname])
            if not np.array_equal(m, c._mults[lname][pname]):
                diff_seen = True
    assert diff_seen
    assert a.spec() == {"members": 4, "eps": 0.03, "tau": 0.1,
                        "harvest_cap": 16, "seed": 11}


def test_scorer_from_config_resolution(monkeypatch, caplog):
    """`EnsembleScorer.from_config` sizes the ensemble from the
    `Serving.md_active` block overridden by the strict-parsed
    HYDRAGNN_MD_ACTIVE_* env knobs; a typo'd env value warns and keeps
    the layer below."""
    model, mcfg, variables, _, _, _, _ = _tiny_model()
    for k in list(os.environ):
        if k.startswith("HYDRAGNN_MD_ACTIVE_"):
            monkeypatch.delenv(k)

    s = EnsembleScorer.from_config(model, mcfg, variables)
    assert s.spec() == {"members": 4, "eps": 0.02, "tau": 0.1,
                        "harvest_cap": 16, "seed": 0}

    cfg_block = {"Serving": {"md_active": {"members": 3, "tau": 0.25}}}
    s = EnsembleScorer.from_config(model, mcfg, variables, cfg_block)
    assert s.members == 3 and s.tau == 0.25 and s.eps == 0.02

    monkeypatch.setenv("HYDRAGNN_MD_ACTIVE_TAU", "0.5")
    monkeypatch.setenv("HYDRAGNN_MD_ACTIVE_EPS", "not-a-float")
    with caplog.at_level("WARNING", logger="hydragnn_tpu"):
        s = EnsembleScorer.from_config(model, mcfg, variables, cfg_block)
    assert "HYDRAGNN_MD_ACTIVE_EPS" in caplog.text
    assert s.tau == 0.5      # env beats the config block
    assert s.eps == 0.02     # typo warns, keeps the layer below
    assert s.members == 3    # config block beats the dataclass default


def test_candidate_pool_dedup_and_labels(tmp_path):
    _, _, _, ucfg, pos0, nf, cell = _tiny_model(seed=3)
    n = pos0.shape[0]

    # the content key is a pure function of the exact grid-state bytes
    k1 = structure_key(pos0, nf, cell)
    assert k1 == structure_key(pos0.copy(), nf.copy(), cell.copy())
    assert k1 != structure_key(pos0 + 1e-9, nf, cell)
    assert structure_key(pos0, nf, None) != k1

    pool = CandidatePool(str(tmp_path / "pool"), ucfg)
    key, added = pool.add(pos0, nf, cell, unc=0.5, step=7, traj=0)
    assert added and key == k1 and len(pool) == 1
    # same structure again — from any "trajectory" — is a dedup hit
    _, added = pool.add(pos0, nf, cell, unc=0.9, step=30, traj=5)
    assert not added and pool.dedup_hits == 1 and len(pool) == 1
    d1 = pool.manifest_digest()
    pos2 = pos0.copy()
    pos2[0, 0] += 0.25
    k2, added = pool.add(pos2, nf, cell, unc=0.7, step=9, traj=1)
    assert added and len(pool) == 2
    assert pool.manifest_digest() != d1
    assert pool.keys() == sorted([k1, k2])

    # label round-trip through the content-addressed shard
    samples, metas = pool.load()
    assert all(not m.get("labeled") for m in metas)
    forces = np.zeros((n, 3), np.float32)
    pool.label(k1, -3.25, forces)
    samples, metas = pool.load(labeled_only=True)
    assert len(samples) == 1
    assert float(samples[0].energy[0]) == -3.25
    np.testing.assert_array_equal(samples[0].forces, forces)
    # exact grid positions ride in the meta for oracle labeling
    labeled_meta = [m for m in pool.load()[1] if m.get("labeled")][0]
    np.testing.assert_array_equal(np.asarray(labeled_meta["pos64"]),
                                  pos0)


# ---------------------------------------------------- end-to-end (slow) --

def _scored_fixture(tau, members=3, eps=0.05, harvest_cap=4, seed=0):
    from tests.test_md_farm import _farm_fixture
    engine, ucfg, n, nf, cell = _farm_fixture(True, 6)
    scorer = EnsembleScorer(engine._model, engine.mcfg,
                            engine._variables, members=members, eps=eps,
                            tau=tau, harvest_cap=harvest_cap, seed=seed)
    farm = engine.trajectory_farm(dt=0.004, skin=0.3,
                                  steps_per_dispatch=5, scorer=scorer)
    return engine, farm, ucfg, n, nf, cell


def _ics(n, T):
    from examples.md_loop.md_loop import init_lattice, maxwell_velocities
    pos_t = np.stack([init_lattice(3, 1.0, jitter=0.05, seed=100 + t)[0]
                      for t in range(T)])
    vel_t = np.stack([maxwell_velocities(n, 0.3 * (t + 1), seed=200 + t)
                      for t in range(T)])
    return pos_t, vel_t


@pytest.mark.slow
def test_harvest_rule_device_matches_host_replay():
    """The device's harvest decisions — slots, steps, uncertainties —
    equal a host-side replay of the rising-edge rule over the emitted
    traces, and the ±inf straddle cases land exactly: tau=-inf harvests
    ONE structure per trajectory (the first advanced step is the only
    rising edge), tau=+inf harvests none while scoring identically."""
    with _x64():
        engine, farm, ucfg, n, nf, cell = _scored_fixture(tau=0.0)
        try:
            T, S = 2, 12
            pos_t, vel_t = _ics(n, T)
            res = farm.run(pos_t, vel_t, S, node_features=nf, cell=cell)
            h = res["harvest"]
            for t in range(T):
                expect = _replay_harvest(res["unc_trace"][:, t],
                                         res["adv_trace"][:, t],
                                         res["step_trace"][:, t],
                                         h["tau"])
                assert int(h["count"][t]) == len(expect)
                for s, (step, unc) in enumerate(
                        expect[:int(h["filled"][t])]):
                    assert int(h["step"][t, s]) == step
                    assert h["unc"][t, s] == unc  # f32 bitwise
            assert h["dropped"] == int(
                np.maximum(h["count"] - farm.scorer.harvest_cap,
                           0).sum())

            # tau = -inf: unc >= tau always -> exactly one rising edge,
            # at each trajectory's FIRST advanced step
            lo = EnsembleScorer(engine._model, engine.mcfg,
                                engine._variables, members=3, eps=0.05,
                                tau=float("-inf"), harvest_cap=4)
            farm_lo = engine.trajectory_farm(dt=0.004, skin=0.3,
                                             steps_per_dispatch=5,
                                             scorer=lo)
            res_lo = farm_lo.run(pos_t, vel_t, S, node_features=nf,
                                 cell=cell)
            h_lo = res_lo["harvest"]
            np.testing.assert_array_equal(h_lo["count"], np.ones(T))
            adv = res_lo["adv_trace"]
            for t in range(T):
                first_row = int(np.flatnonzero(adv[:, t])[0])
                assert (int(h_lo["step"][t, 0])
                        == int(res_lo["step_trace"][first_row, t]))

            # tau = +inf: never above -> zero harvests, same trajectory
            hi = EnsembleScorer(engine._model, engine.mcfg,
                                engine._variables, members=3, eps=0.05,
                                tau=float("inf"), harvest_cap=4)
            farm_hi = engine.trajectory_farm(dt=0.004, skin=0.3,
                                             steps_per_dispatch=5,
                                             scorer=hi)
            res_hi = farm_hi.run(pos_t, vel_t, S, node_features=nf,
                                 cell=cell)
            assert int(res_hi["harvest"]["count"].sum()) == 0
            # the threshold gates HARVEST only, never the dynamics
            np.testing.assert_array_equal(res_lo["final_pos"],
                                          res_hi["final_pos"])
            np.testing.assert_array_equal(res_lo["final_pos"],
                                          res["final_pos"])
        finally:
            engine.shutdown()


@pytest.mark.slow
def test_twin_runs_harvest_bitwise_pools(tmp_path):
    """Two independently constructed scored farms, identical initial
    conditions: harvest buffers bitwise (pos f64, unc f32, steps), twin
    `CandidatePool`s content-identical (`manifest_digest`), and the
    scored farm's trajectories bitwise the UNSCORED farm's (scoring
    never perturbs the dynamics)."""
    with _x64():
        engine, farm_a, ucfg, n, nf, cell = _scored_fixture(tau=0.0)
        try:
            T, S = 2, 12
            pos_t, vel_t = _ics(n, T)
            scorer_b = EnsembleScorer(engine._model, engine.mcfg,
                                      engine._variables, members=3,
                                      eps=0.05, tau=0.0, harvest_cap=4)
            farm_b = engine.trajectory_farm(dt=0.004, skin=0.3,
                                            steps_per_dispatch=5,
                                            scorer=scorer_b)
            ra = farm_a.run(pos_t, vel_t, S, node_features=nf, cell=cell)
            rb = farm_b.run(pos_t, vel_t, S, node_features=nf, cell=cell)
            for key in ("pos", "step", "unc", "count"):
                np.testing.assert_array_equal(ra["harvest"][key],
                                              rb["harvest"][key])
            pools = []
            for tag, r in (("a", ra), ("b", rb)):
                pool = CandidatePool(str(tmp_path / tag), ucfg)
                h = r["harvest"]
                for t in range(T):
                    for s in range(int(h["filled"][t])):
                        pool.add(h["pos"][t, s], nf, cell,
                                 unc=float(h["unc"][t, s]),
                                 step=int(h["step"][t, s]), traj=t)
                pools.append(pool)
            assert len(pools[0]) > 0
            assert pools[0].keys() == pools[1].keys()
            assert (pools[0].manifest_digest()
                    == pools[1].manifest_digest())

            farm_plain = engine.trajectory_farm(dt=0.004, skin=0.3,
                                                steps_per_dispatch=5)
            rp = farm_plain.run(pos_t, vel_t, S, node_features=nf,
                                cell=cell)
            np.testing.assert_array_equal(rp["final_pos"],
                                          ra["final_pos"])
            np.testing.assert_array_equal(rp["final_vel"],
                                          ra["final_vel"])
            assert rp["harvest"] is None and rp["unc_trace"] is None
        finally:
            engine.shutdown()


@pytest.mark.slow
def test_scored_dispatch_zero_added_compiles_and_hot_swap():
    """Compile pinning: the scored program compiles ONCE per shape;
    repeat runs and `swap_variables` hot-swaps add zero. The swap
    contract rejects shape-incompatible trees, serves the swapped
    variables on the very next run, and keeps the scorer live
    (uncertainty changes with the head, same ensemble geometry).
    Telemetry: `md.harvest_total` / `md.uncertainty` land in the
    registry."""
    import jax
    from hydragnn_tpu.telemetry.registry import (MetricsRegistry,
                                                 set_registry)
    with _x64():
        engine, farm, ucfg, n, nf, cell = _scored_fixture(tau=0.0)
        try:
            T, S = 2, 10
            pos_t, vel_t = _ics(n, T)
            reg = MetricsRegistry()
            prev = set_registry(reg)
            try:
                r1 = farm.run(pos_t, vel_t, S, node_features=nf,
                              cell=cell)
                assert r1["fresh_compiles_run"] == 1
                assert r1["dispatches"] > 1  # one compile, many uses
                r2 = farm.run(pos_t, vel_t, S, node_features=nf,
                              cell=cell)
                assert r2["fresh_compiles_run"] == 0

                # hot-swap: perturbed params, same tree -> accepted,
                # zero compiles, different energies, scorer still live
                vv = farm._variables
                pert = jax.tree_util.tree_map(lambda p: p * 1.5,
                                              vv["params"])
                old = farm.swap_variables(
                    {"params": pert,
                     "batch_stats": vv["batch_stats"]}, "v-test")
                assert farm.version == "v-test" and old == "farm-init"
                r3 = farm.run(pos_t, vel_t, S, node_features=nf,
                              cell=cell)
                assert r3["fresh_compiles_run"] == 0
                assert not np.array_equal(r3["energy_last"],
                                          r2["energy_last"])
                assert r3["max_uncertainty"] != r2["max_uncertainty"]

                with pytest.raises(ValueError, match="swap rejected"):
                    farm.swap_variables(
                        {"params": jax.tree_util.tree_map(
                            lambda p: np.zeros(np.shape(p) + (2,),
                                               np.float32),
                            vv["params"]),
                         "batch_stats": vv["batch_stats"]}, "bad")
            finally:
                set_registry(prev)
            snap = reg.snapshot()
            total = sum(float(r["harvest"]["filled"].sum())
                        for r in (r1, r2, r3))
            assert snap["md.harvest_total"]["values"][()] == total
            assert snap["md.uncertainty"]["values"][()] == pytest.approx(
                r3["max_uncertainty"])
        finally:
            engine.shutdown()


@pytest.mark.slow
def test_bench_active_smoke(tmp_path):
    """CI-sized BENCH_ACTIVE subprocess: throughput floor vs the
    unscored farm, zero added compiles, twin-run pool equality, and
    error-vs-oracle strictly decreasing across harvest rounds."""
    out_path = str(tmp_path / "BENCH_ACTIVE.json")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               BENCH_ACTIVE="1", BENCH_ACTIVE_TRAJ="4",
               BENCH_ACTIVE_TP_TRAJ="4",
               BENCH_ACTIVE_STEPS="16", BENCH_ACTIVE_ROUNDS="2",
               # the scoring cost is per-op, so the ratio only reaches
               # its honest value at real farm widths (bench docstring)
               # — the CI-sized smoke checks mechanics, the committed
               # BENCH_ACTIVE.json pins the 0.9 floor at width 256
               BENCH_ACTIVE_MIN_RATIO="0.5",
               BENCH_ACTIVE_OUT=out_path)
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True,
                       timeout=900, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["throughput_ratio_ok"], out
    assert out["zero_added_compiles"], out
    assert out["twin_pools_bitwise"], out
    assert out["error_strictly_decreasing"], out
    assert os.path.exists(out_path)
