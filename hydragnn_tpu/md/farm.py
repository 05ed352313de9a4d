"""Massively-batched on-device MD: a trajectory farm that vmaps the
velocity-Verlet update + Verlet-skin cutoff re-filter over a
``[T, n_atoms, 3]`` trajectory batch and runs K MD steps device-resident
per dispatch (ROADMAP item 3, FlashSchNet; docs/serving.md "MD farm").

The PR 10 serving loop closes one trajectory at a time: every step
round-trips positions through the host, re-filters the candidate cache
in numpy, and serves ONE structure per compiled forward. For
screening/sampling workloads — thousands of independent trajectories of
near-identical systems — the fixed per-step cost (engine queue, collate,
unpad, dispatch latency) dominates. The farm amortizes it twice over:

* **batch over trajectories** — one compiled program evaluates the model
  forward (and forces = -dE/dpos) for all T trajectories per step, via
  ``jax.vmap`` of exactly the per-structure EF forward the serving
  engine compiles (same `make_forward_fn` + `energy_forces_from_node_head`
  composition, same single-structure bucket layout);
* **batch over steps** — a ``lax.scan`` runs ``steps_per_dispatch``
  whole MD steps per dispatch, positions never leaving the device in
  between. The host's only jobs are the two things that genuinely need
  it: adjudicating per-trajectory skin-bound violations and swapping
  rebuilt candidate caches in and out of the stacked batch (the PR 5
  cell-list construction stays host-side and bitwise).

The per-step re-filter is the PR 10 fixed-layout candidate cache lifted
into a jax-traced batched form: per-trajectory candidate arrays padded
to one static capacity (+inf masking), the ``max_neighbours`` cap
evaluated in the dense ``[n_atoms, max_degree]`` layout with exactly the
``radius._dense_select`` selection rule (strict/equal-quota under the
documented (d², input order) total order — see its docstring; the mirror
is adjudicated in tests/test_md_farm.py).

Bitwise contract. Each farm trajectory is BITWISE-equal to the PR 10
single-session loop (`examples/md_loop.run_md` mode="incremental") from
identical initial conditions: same positions, same velocities, same
edges, same rebuild decisions, at every step, for any trajectory count
and any ``steps_per_dispatch``. Three mechanisms carry it:

* integration, displacement checks, and re-filter d² run on the
  md/integrator.py binary grid, where every operation is exact in f64 —
  host numpy and XLA-compiled code cannot disagree no matter how the
  compiler contracts or reassociates (the integrator docstring documents
  why nothing weaker survives XLA CPU codegen);
* rebuilds run on the host through the SAME `NeighborList` the serving
  session uses, and the farm asserts the device's violation verdict
  against the host's (`update` must report ``rebuilt=True``) — a grid
  budget violation fails loudly instead of silently forking paths;
* the model forward is the engine's own EF forward vmapped over the
  stacked batch; per-trajectory outputs equal the single-structure
  program's bitwise (pinned empirically by tests/test_md_farm.py and
  re-adjudicated end-to-end by bench.py BENCH_MD_FARM).

One measured carve-out: the scalar ENERGY readout (the masked
segment-sum pooling of node energies) may differ from the session's in
the last ulp at large batch widths — XLA's codegen reassociates the
batched reduction (observed at T=64; T<=8 was bitwise). The trajectory
itself is immune: a sum's backward is a cotangent broadcast, so the
forces that drive the integrator carry no reduction at all. BENCH_MD_FARM
adjudicates positions/velocities bitwise and energies to 1e-9 relative.

Everything jax-side runs under ``jax.enable_x64`` (the
integrator state is f64); for the farm-vs-session adjudication the
reference engine must be compiled under x64 too (BENCH_MD_FARM and the
tests do), since the trace-time constant dtypes of the model change
with the flag.

One farm per (system shape, model); not thread-safe.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from ..graphs.neighborlist import NeighborList
from ..graphs.radius import _segment_layout
from ..telemetry import spans as _spans
from ..telemetry.registry import get_registry
from . import integrator as mdi

_CAND_MULTIPLE = 64  # static candidate-capacity rounding (recompile-free
# across rebuilds; the packing headroom rides on top)
_DEG_MULTIPLE = 8


def _roundup(x: int, m: int) -> int:
    return ((int(x) + m - 1) // m) * m


def make_batched_refilter(n_atoms: int, r: float,
                          max_neighbours: Optional[int], w_cap: int):
    """Batched candidate re-filter: ``fn(pos [T,n,3], send, recv, valid,
    seg_start [T,C], off [T,C,3]) -> keep [T,C]`` — the jax mirror of
    `NeighborList._emit`'s keep decision (cutoff filter + the
    `radius._dense_select` cap rule) on the candidate layout.

    Exactness contract: with positions and ghost offsets on the
    md/integrator.py grid, every d² is exact in f64, so the keep mask —
    cap tie-breaks included — equals the host's bitwise (adjudicated in
    tests/test_md_farm.py against per-trajectory NeighborList updates).
    Padding candidates carry ``valid=False`` (+inf distance) and their
    ``seg_start`` points at themselves; padding ``recv`` is ``n_atoms``
    (the trash row of the dense matrix)."""
    import jax
    import jax.numpy as jnp

    r2 = float(r) * float(r)  # the host compares d2 <= self.r * self.r
    k = None if max_neighbours is None else int(max_neighbours)

    def one(pos, send, recv, valid, seg_start, off):
        g = (pos[send] + off) - pos[recv]  # exact on the grid
        d2 = (g[:, 0] * g[:, 0] + g[:, 1] * g[:, 1]) + g[:, 2] * g[:, 2]
        ok = valid & (d2 <= r2)
        if k is None or k >= w_cap:
            return ok  # no receiver can exceed the cap (host keep_all)
        if k <= 0:
            return jnp.zeros_like(ok)  # the legacy rank < 0 result
        cand = jnp.arange(send.shape[0], dtype=jnp.int32)
        idx = cand - seg_start
        d2m = jnp.where(ok, d2, jnp.inf)
        # padding candidates are dropped from the scatter (their rows
        # start +inf-filled anyway), which leaves every landing index
        # unique — XLA CPU's scatter loop skips duplicate handling
        row = jnp.where(valid, recv, n_atoms + 1)
        mat = jnp.full((n_atoms + 1, w_cap), jnp.inf,
                       d2.dtype).at[row, idx].set(
                           d2m, mode="drop", unique_indices=True)
        kth = jnp.sort(mat, axis=1)[:, k - 1]
        kth_e = kth[recv]
        strict = d2m < kth_e
        scount = jnp.zeros(n_atoms + 1, jnp.int32).at[recv].add(
            strict.astype(jnp.int32))
        quota = k - scount[recv]
        eq = d2m == kth_e
        run = jnp.cumsum(eq.astype(jnp.int32))
        base = run[seg_start] - eq[seg_start].astype(jnp.int32)
        eq_rank = run - base
        return (strict | (eq & (eq_rank <= quota))) & ok

    return jax.vmap(one)


def pack_candidates(nl: NeighborList, c_cap: int, w_cap: int,
                    n_atoms: int, *, pbc: bool,
                    capped: bool) -> Dict[str, np.ndarray]:
    """One trajectory's candidate cache in the stacked static layout
    the batched re-filter consumes: +inf-masked padding (``valid``
    False), self-pointing padding ``seg_start``, trash-row padding
    receivers (``n_atoms``), per-candidate float64 ghost offsets and
    float32 cartesian shifts (PBC). Raises with an actionable message
    when the cache outgrew the static capacities."""
    cs, cr, off, shift32, ref = nl.export_candidates()
    c = len(cs)
    if c > c_cap:
        raise ValueError(
            f"trajectory candidate count {c} exceeds the farm's static "
            f"capacity {c_cap} — raise cand_headroom "
            "(HYDRAGNN_MD_FARM_CAND_HEADROOM) or rebuild the farm")
    out = {
        "send": np.zeros(c_cap, np.int32),
        "recv": np.full(c_cap, n_atoms, np.int32),
        "valid": np.zeros(c_cap, bool),
        "seg_start": np.arange(c_cap, dtype=np.int32),
        "off": np.zeros((c_cap, 3), np.float64),
        "ref": np.asarray(ref, np.float64),
    }
    if pbc:
        out["shift"] = np.zeros((c_cap, 3), np.float32)
    if c:
        seg_id, starts, idx = _segment_layout(cr)
        width = int(idx.max()) + 1
        if capped and width > w_cap:
            raise ValueError(
                f"trajectory candidate max degree {width} exceeds the "
                f"farm's static degree capacity {w_cap} — raise "
                "cand_headroom (HYDRAGNN_MD_FARM_CAND_HEADROOM) or "
                "rebuild the farm")
        out["send"][:c] = cs
        out["recv"][:c] = cr
        out["valid"][:c] = True
        out["seg_start"][:c] = starts[seg_id]
        if pbc:
            out["off"][:c] = off
            out["shift"][:c] = shift32
    return out


class TrajectoryFarm:
    """Device-resident trajectory batch over one model + one system
    shape. Build via ``InferenceEngine.trajectory_farm`` (shares the
    engine's model/variables/precision/bucket so the adjudication
    reference is the same compiled quantity) or directly.

    ``run(pos0 [T,n,3], vel0 [T,n,3], steps, node_features=..., cell=...)``
    integrates every trajectory ``steps`` velocity-Verlet steps and
    returns final state + farm statistics. Initial conditions are
    snapped to the integrator grid exactly as `run_md` snaps its own.

    With ``scorer`` (an `md.active.EnsembleScorer`) the SAME jitted
    dispatch additionally scores each structure's ensemble uncertainty
    and applies the deterministic harvest rule: a trajectory harvests
    the structure at which its uncertainty RISES through ``scorer.tau``
    (``cross = advanced & (unc >= tau) & ~was_above`` — a pure function
    of grid state, so twin runs harvest bitwise-identical pools) into
    per-trajectory device buffers (``scorer.harvest_cap`` slots, part of
    the donated scan carry), drained once per run into
    ``result["harvest"]``. Without a scorer the program is byte-for-byte
    the PR 11 farm — every bitwise contract above is untouched.
    """

    def __init__(self, model, variables, mcfg, structure_config, *,
                 bucket, dt: float, skin: float = 0.3, mass: float = 1.0,
                 force_scale: float = 1.0, steps_per_dispatch: int = 8,
                 cand_headroom: float = 0.5,
                 compute_dtype: Optional[str] = None, scorer=None):
        from ..train.loss import energy_forces_from_node_head
        from ..train.train_step import make_forward_fn

        ds = structure_config["Dataset"]
        arch = structure_config["NeuralNetwork"]["Architecture"]
        if ds.get("rotational_invariance", False):
            raise ValueError(
                "trajectory farms need Dataset.rotational_invariance off "
                "— the incremental neighbor list tracks displacements in "
                "the raw frame (the structure_session contract)")
        if arch.get("edge_features") or ds.get("Descriptors"):
            raise ValueError(
                "trajectory farms do not support edge_features/"
                "Descriptors configs — per-edge geometric features would "
                "have to be rebuilt on-device every step; serve these "
                "through the per-step submit_structure path instead")
        if mcfg.heads[0].head_type != "node":
            raise ValueError(
                "trajectory farms serve energy+forces from a node-level "
                "energy head (the energy_force_loss convention); got a "
                f"{mcfg.heads[0].head_type!r} head 0")
        self._cfg = structure_config
        self.pbc = bool(arch.get("periodic_boundary_conditions", False))
        self.radius = float(arch.get("radius") or 5.0)
        mn = arch.get("max_neighbours")
        self.max_neighbours = None if mn is None else int(mn)
        self.skin = float(skin)
        if not np.isfinite(self.skin) or self.skin < 0.0:
            raise ValueError(f"farm skin must be finite >= 0, got {skin}")
        self.dt = float(dt)
        if not self.dt > 0.0:
            raise ValueError(f"farm dt must be > 0, got {dt}")
        self.mass = float(mass)
        self.force_scale = float(force_scale)
        self.steps_per_dispatch = int(steps_per_dispatch)
        if self.steps_per_dispatch < 1:
            raise ValueError("steps_per_dispatch must be >= 1, got "
                             f"{steps_per_dispatch}")
        self.cand_headroom = float(cand_headroom)
        if self.cand_headroom < 0.0:
            raise ValueError("cand_headroom must be >= 0, got "
                             f"{cand_headroom}")
        self.bucket = bucket
        self._model = model
        self.mcfg = mcfg
        self.compute_dtype = compute_dtype
        self._variables = {"params": variables["params"],
                           "batch_stats": variables.get("batch_stats", {})}
        self.scorer = scorer
        if scorer is not None:
            # the scored forward replaces the EF forward INSIDE the same
            # vmapped/scanned program: one conv stack, M perturbed head
            # replays on its sown final embedding, f32 std — see
            # md/active.py for the math and docs/active_learning.md for
            # the contract
            self._head_forward = scorer.make_head_forward()
        else:
            forward = make_forward_fn(model, mcfg, compute_dtype)

            def head_forward(variables, batch):
                # identical composition to the engine's ef_forward path:
                # the served quantity IS the trained quantity, and the
                # vmapped farm forward stays the same expression the
                # session serves
                def apply_fn(v, b, train):
                    return forward(v, b, train=train), None

                graph_e, forces, _ = energy_forces_from_node_head(
                    apply_fn, variables, batch, train=False)
                return graph_e, forces

            self._head_forward = head_forward
        # compiled K-step dispatch executables, keyed by the shape
        # tuple that determines every aval — repeat run() calls on the
        # same farm are compile-free (the engine's warmup-once
        # convention)
        self._exec_cache: Dict = {}
        self.fresh_compiles = 0  # lifetime exec-cache misses (the
        # BENCH_ACTIVE zero-added-compiles pin reads the per-run delta)
        self._jswap = None
        self._jresume = None
        self.version = "farm-init"

    def swap_variables(self, variables, version: str) -> str:
        """Hot-swap the farm's model variables (the PR 12-13 engine
        contract, mirrored): the replacement tree must match the current
        one leaf-for-leaf in shape and dtype — the compiled dispatch
        takes variables as a runtime argument, so a shape-compatible
        swap costs ZERO recompiles and the next dispatch serves the new
        model. Returns the previous version tag."""
        import jax
        new = {"params": variables["params"],
               "batch_stats": variables.get("batch_stats", {})}

        def _check(old_leaf, new_leaf):
            o, nl = np.shape(old_leaf), np.shape(new_leaf)
            od = np.asarray(old_leaf).dtype
            nd = np.asarray(new_leaf).dtype
            if o != nl or od != nd:
                raise ValueError(
                    f"swap rejected: leaf {nl}/{nd} != current {o}/{od} "
                    "— farms only hot-swap shape/dtype-compatible "
                    "variables (rebuild the farm for a new architecture)")
            return new_leaf

        jax.tree_util.tree_map(_check, self._variables, new)
        old_version = self.version
        self._variables = new
        self.version = str(version)
        return old_version

    # ------------------------------------------------------------- packing

    def _pack_traj(self, nl: NeighborList, c_cap: int, w_cap: int,
                   n: int) -> Dict[str, np.ndarray]:
        return pack_candidates(nl, c_cap, w_cap, n, pbc=self.pbc,
                               capped=self.max_neighbours is not None)

    # ------------------------------------------------------------ dispatch

    def _build_dispatch(self, n: int, w_cap: int, s_hi: float,
                        s_lo: float):
        import jax
        import jax.numpy as jnp

        K = self.steps_per_dispatch
        n_node = self.bucket.n_node
        e_cap = self.bucket.n_edge
        bound2 = (0.5 * self.skin) ** 2  # NeighborList._needs_rebuild's
        # exact expression — same float, same strict > comparison
        refilter = make_batched_refilter(n, self.radius,
                                         self.max_neighbours, w_cap)
        head_forward = self._head_forward
        scored = self.scorer is not None
        if scored:
            tau = float(self.scorer.tau)      # trace constants — part of
            H = int(self.scorer.harvest_cap)  # the compiled program, like
            # every other farm knob (a new threshold is a new farm)

        def one_compact(pos, keep, send, recv, shift):
            # `shift` is None on the open-boundary trace (no cartesian
            # image shifts exist) — the branch below is trace-time
            # ONE stream-compaction scatter (candidate ids into edge
            # slots; kept ranks are unique, drops discard the rest),
            # then cheap gathers — scatters are serial per update on
            # XLA CPU, so this is 1x C updates instead of 3x
            cnt = jnp.sum(keep.astype(jnp.int32))
            rank = jnp.cumsum(keep.astype(jnp.int32)) - 1
            slot = jnp.where(keep, rank, e_cap)
            c_pad = send.shape[0]  # sentinel: the padding-edge values
            cidx = jnp.full(e_cap, c_pad, jnp.int32).at[slot].set(
                jnp.arange(send.shape[0], dtype=jnp.int32), mode="drop",
                unique_indices=True)
            send_ext = jnp.concatenate(
                [send, jnp.full(1, n_node - 1, jnp.int32)])
            recv_ext = jnp.concatenate(
                [recv, jnp.full(1, n_node - 1, jnp.int32)])
            senders = send_ext[cidx]
            receivers = recv_ext[cidx]
            eshift = None
            if shift is not None:
                shift_ext = jnp.concatenate(
                    [shift, jnp.zeros((1, 3), jnp.float32)])
                eshift = shift_ext[cidx]
            emask = jnp.arange(e_cap, dtype=jnp.int32) < cnt
            posf = jnp.zeros((n_node, 3), jnp.float32).at[:n].set(
                pos.astype(jnp.float32))
            return senders, receivers, eshift, emask, posf, cnt

        compact = jax.vmap(one_compact)

        def one_forward(variables, b_template, posf, senders, receivers,
                        eshift, emask):
            b = b_template.replace(
                pos=posf, senders=senders, receivers=receivers,
                edge_shifts=eshift, edge_mask=emask)
            return head_forward(variables, b)

        vfwd = jax.vmap(one_forward, in_axes=(None, None, 0, 0, 0, 0, 0))

        def body(st, caches, variables, steps_target, b_template):
            act = (~st["frozen"]) & (st["steps_done"] < steps_target)
            do_drift = act & st["has_acc"] & (~st["skip_drift"])
            drifted = mdi.drift(st["pos"], st["vd"], st["ad2"], xp=jnp)
            p_new = jnp.where(do_drift[:, None, None], drifted, st["pos"])
            d = p_new - caches["ref"]
            disp2 = (d[..., 0] * d[..., 0] + d[..., 1] * d[..., 1]
                     ) + d[..., 2] * d[..., 2]
            viol = act & (jnp.max(disp2, axis=1) > bound2)
            keep = refilter(p_new, caches["send"], caches["recv"],
                            caches["valid"], caches["seg_start"],
                            caches["off"])
            senders, receivers, eshift, emask, posf, cnt = compact(
                p_new, keep, caches["send"], caches["recv"],
                caches.get("shift"))
            over = act & (~viol) & (cnt > e_cap)
            adv = act & (~viol) & (~over)
            if scored:
                graph_e, forces, unc = vfwd(variables, b_template, posf,
                                            senders, receivers, eshift,
                                            emask)
            else:
                graph_e, forces = vfwd(variables, b_template, posf,
                                       senders, receivers, eshift, emask)
            acc_new = mdi.accel_term(forces[:, :n, :], s_hi, s_lo, xp=jnp)
            vd_new = mdi.kick(st["vd"], st["ad2"], acc_new, xp=jnp)
            m3 = adv[:, None, None]
            # full-precision energies (the session loop records python
            # floats of whatever the forward emits)
            e = graph_e[:, 0, 0].astype(jnp.float64)
            first = adv & (~st["has_acc"])
            stepped = adv & st["has_acc"]
            new = {
                "pos": p_new,
                "vd": jnp.where(stepped[:, None, None], vd_new, st["vd"]),
                "ad2": jnp.where(m3, acc_new, st["ad2"]),
                "steps_done": st["steps_done"] + stepped.astype(jnp.int32),
                "has_acc": st["has_acc"] | adv,
                "skip_drift": st["skip_drift"] & (~adv),
                "frozen": st["frozen"] | viol | over,
                "overflow": st["overflow"] | over,
                "coord_ok": st["coord_ok"]
                & (jnp.max(jnp.abs(p_new)) <= mdi.COORD_LIMIT),
                "energy_first": jnp.where(first, e, st["energy_first"]),
                "energy_last": jnp.where(adv, e, st["energy_last"]),
            }
            if not scored:
                return new, None
            # deterministic harvest (docs/active_learning.md): the rule
            # is a pure function of (adv, unc, previous level state) —
            # booleans and an f32 std of exact-input energies — so twin
            # runs make identical decisions at every step. Rising-edge:
            # harvest the structure at which unc CROSSES tau upward,
            # not every structure sitting above it.
            above = unc >= tau
            cross = adv & above & (~st["unc_above"])
            slot = st["harvest_count"]  # next free buffer slot (or >= H:
            # pool full, crossing counted but structure dropped)
            write = cross & (slot < H)
            slot_w = jnp.where(write, slot, H)  # H = out of bounds,
            rows = jnp.arange(slot.shape[0])    # dropped by mode="drop"
            step_val = new["steps_done"]
            new.update({
                "unc_above": jnp.where(adv, above, st["unc_above"]),
                "harvest_count": slot + cross.astype(jnp.int32),
                "harvest_pos": st["harvest_pos"].at[rows, slot_w].set(
                    p_new, mode="drop", unique_indices=True),
                "harvest_step": st["harvest_step"].at[rows, slot_w].set(
                    step_val, mode="drop", unique_indices=True),
                "harvest_unc": st["harvest_unc"].at[rows, slot_w].set(
                    unc, mode="drop", unique_indices=True),
                "unc_max": jnp.maximum(
                    st["unc_max"],
                    jnp.max(jnp.where(adv, unc,
                                      jnp.float32(-jnp.inf)))),
            })
            # per-step traces for host-side adjudication (the
            # threshold-straddle tests recompute the harvest rule from
            # these and pin equality) — small [T] rows, stacked by scan
            ys = {"unc": unc, "adv": adv, "steps_done": step_val}
            return new, ys

        def dispatch(state, caches, variables, steps_target, b_template):
            def scan_body(st, _):
                return body(st, caches, variables, steps_target,
                            b_template)

            out, ys = jax.lax.scan(scan_body, state, None, length=K)
            if scored:
                return out, ys
            return out

        return jax.jit(dispatch, donate_argnums=(0,))

    # ----------------------------------------------------------------- run

    def run(self, pos0, vel0, steps: int, *, node_features,
            cell=None) -> Dict:
        """Integrate T trajectories ``steps`` velocity-Verlet steps.

        ``pos0``/``vel0``: [T, n_atoms, 3]; ``node_features``: [n_atoms,
        F] in the dataset layout, shared across trajectories (the
        near-identical-systems screening shape); ``cell``: [3, 3],
        required under PBC, shared across trajectories. Returns final
        positions/velocities, per-trajectory first/last energies, and the
        farm statistics BENCH_MD_FARM reports."""
        import jax
        import jax.numpy as jnp

        from ..graphs.batch import collate
        from ..preprocess.transforms import build_graph_sample

        pos0 = np.asarray(pos0, np.float64)
        vel0 = np.asarray(vel0, np.float64)
        if pos0.ndim != 3 or pos0.shape[-1] != 3 or pos0.shape != vel0.shape:
            raise ValueError(
                "farm run needs pos0/vel0 of shape [T, n_atoms, 3]; got "
                f"{pos0.shape} / {vel0.shape}")
        T, n, _ = pos0.shape
        steps = int(steps)
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        if self.pbc and cell is None:
            raise ValueError("periodic farm needs a [3, 3] cell")
        if n + 1 > self.bucket.n_node:
            raise ValueError(
                f"{n} atoms exceed the farm bucket's node capacity "
                f"{self.bucket.n_node - 1}")
        node_features = np.asarray(node_features, np.float32)

        # grid state — the same snapping run_md applies, so identical
        # initial conditions land on identical grid points
        pos, vd = mdi.init_state(pos0, vel0, self.dt)
        cellq = mdi.quantize_cell(cell) if self.pbc else None
        rc = self.radius + self.skin
        mdi.validate_ranges(float(np.abs(pos).max(initial=0.0)), rc)
        s_hi, s_lo = mdi.force_scale_split(self.dt, self.force_scale,
                                           self.mass)

        # host neighbor lists: one per trajectory, the serving session's
        # own class — initial build is rebuild #1, exactly as a session's
        # first update
        nls: List[NeighborList] = [
            NeighborList(self.radius, self.skin,
                         max_neighbours=self.max_neighbours,
                         pbc=(True, True, True) if self.pbc else None)
            for _ in range(T)]
        counts, widths = [], []
        edges0 = None
        for t in range(T):
            send, recv, _sh, rebuilt = nls[t].update(
                pos[t], cell=cellq if self.pbc else None)
            if t == 0:
                edges0 = (send, recv, _sh)
            cs, cr, *_ = nls[t].export_candidates()
            counts.append(len(cs))
            if len(cr):
                widths.append(int(_segment_layout(cr)[2].max()) + 1)
        c_cap = _roundup(max(max(counts), 1) * (1.0 + self.cand_headroom),
                         _CAND_MULTIPLE)
        w_cap = _roundup(max(max(widths) if widths else 1, 1)
                         * (1.0 + self.cand_headroom), _DEG_MULTIPLE)

        # batch constants from the engine's own collate conventions
        sample0 = build_graph_sample(node_features, pos[0], self._cfg,
                                     cell=cellq, edges=edges0,
                                     with_targets=False)
        if sample0.edge_attr is not None:
            raise ValueError("farm configs must not produce edge_attr")
        b0 = collate([sample0], n_node=self.bucket.n_node,
                     n_edge=self.bucket.n_edge,
                     n_graph=self.bucket.n_graph, np_out=True)
        b0 = b0.replace(y_graph=None, y_node=None, energy=None, forces=None)

        reg = get_registry()
        swaps = 0
        dispatches = 0
        scored = self.scorer is not None
        fresh_compiles_before = self.fresh_compiles
        traces: List[Dict[str, np.ndarray]] = []
        with jax.enable_x64(True):
            b_template = jax.tree_util.tree_map(jnp.asarray, b0)
            packed = [self._pack_traj(nls[t], c_cap, w_cap, n)
                      for t in range(T)]
            caches = {key: jnp.stack([jnp.asarray(p[key]) for p in packed])
                      for key in packed[0]}
            state = {
                "pos": jnp.asarray(pos), "vd": jnp.asarray(vd),
                "ad2": jnp.zeros((T, n, 3), jnp.float64),
                "steps_done": jnp.zeros(T, jnp.int32),
                "has_acc": jnp.zeros(T, bool),
                "skip_drift": jnp.zeros(T, bool),
                "frozen": jnp.zeros(T, bool),
                "overflow": jnp.zeros(T, bool),
                "coord_ok": jnp.asarray(True),
                "energy_first": jnp.zeros(T, jnp.float64),
                "energy_last": jnp.zeros(T, jnp.float64),
            }
            if scored:
                H = int(self.scorer.harvest_cap)
                state.update({
                    "unc_above": jnp.zeros(T, bool),
                    "harvest_count": jnp.zeros(T, jnp.int32),
                    "harvest_pos": jnp.zeros((T, H, n, 3), jnp.float64),
                    "harvest_step": jnp.full((T, H), -1, jnp.int32),
                    "harvest_unc": jnp.zeros((T, H), jnp.float32),
                    "unc_max": jnp.asarray(-jnp.inf, jnp.float32),
                })
            steps_target = jnp.asarray(steps, jnp.int32)
            if self._jswap is None:
                def swap_one(caches, t, new):
                    return {key: buf.at[t].set(new[key])
                            for key, buf in caches.items()}

                def resume_one(state, t):
                    return dict(
                        state,
                        frozen=state["frozen"].at[t].set(False),
                        skip_drift=state["skip_drift"].at[t].set(True))

                self._jswap = jax.jit(swap_one, donate_argnums=(0,))
                self._jresume = jax.jit(resume_one, donate_argnums=(0,))
            jswap, jresume = self._jswap, self._jresume

            # compile outside the timed loop (the engine's warmup()
            # convention), cached per shape key so repeat run() calls on
            # the same farm are compile-free — b_template/variables are
            # arguments, not baked constants, so the cache stays valid
            # across runs with different features/cells of one shape
            exec_key = (T, n, c_cap, w_cap)
            compiled = self._exec_cache.get(exec_key)
            if compiled is None:
                dispatch = self._build_dispatch(n, w_cap, s_hi, s_lo)
                compiled = dispatch.lower(state, caches, self._variables,
                                          steps_target,
                                          b_template).compile()
                self._exec_cache[exec_key] = compiled
                self.fresh_compiles += 1

            t_start = time.perf_counter()
            last_done = -1
            while True:
                t0 = _spans.now()
                if scored:
                    state, ys = compiled(state, caches, self._variables,
                                         steps_target, b_template)
                    traces.append({key: np.asarray(val)
                                   for key, val in ys.items()})
                else:
                    state = compiled(state, caches, self._variables,
                                     steps_target, b_template)
                dispatches += 1
                frozen = np.asarray(state["frozen"])
                done = int(np.asarray(state["steps_done"]).sum())
                if bool(np.asarray(state["overflow"]).any()):
                    bad = int(np.asarray(state["overflow"]).sum())
                    raise ValueError(
                        f"{bad} trajectorie(s) exceeded the bucket edge "
                        f"capacity {self.bucket.n_edge} mid-run — rebuild "
                        "the farm with a roomier bucket (the engine "
                        "rejects such requests the same way)")
                if not bool(np.asarray(state["coord_ok"])):
                    raise ValueError(
                        "trajectory coordinates exceeded the grid "
                        f"integrator's exact range ({mdi.COORD_LIMIT}) — "
                        "the bitwise contract cannot be kept; recenter "
                        "or shrink the system (docs/serving.md)")
                rec = _spans.current_recorder()
                if rec is not None:
                    rec.add("md.farm_dispatch", t0, _spans.now() - t0,
                            "md", {"frozen": int(frozen.sum()),
                                   "steps_done": done})
                if done >= steps * T:
                    break
                idx = np.flatnonzero(frozen)
                if idx.size == 0 and done == last_done:
                    raise RuntimeError(
                        "farm made no progress in a dispatch with no "
                        "frozen trajectories — internal scheduling bug")
                last_done = done
                for t in idx:
                    p_t = np.asarray(state["pos"][int(t)])
                    _s, _r, _sh, rebuilt = nls[int(t)].update(
                        p_t, cell=cellq if self.pbc else None)
                    if not rebuilt:
                        raise RuntimeError(
                            "device flagged a skin-bound violation the "
                            "host NeighborList does not see — the grid "
                            "exactness contract is broken (report this)")
                    new = {key: jnp.asarray(val) for key, val in
                           self._pack_traj(nls[int(t)], c_cap, w_cap,
                                           n).items()}
                    caches = jswap(caches, int(t), new)
                    state = jresume(state, int(t))
                    swaps += 1
            wall = time.perf_counter() - t_start
            final_pos = np.asarray(state["pos"])
            final_vd = np.asarray(state["vd"])
            e_first = np.asarray(state["energy_first"])
            e_last = np.asarray(state["energy_last"])
            harvest = None
            max_unc = None
            if scored:
                h_cnt = np.asarray(state["harvest_count"])
                filled = np.minimum(h_cnt, self.scorer.harvest_cap)
                harvest = {
                    "pos": np.asarray(state["harvest_pos"]),
                    "step": np.asarray(state["harvest_step"]),
                    "unc": np.asarray(state["harvest_unc"]),
                    "count": h_cnt,
                    "filled": filled,
                    "dropped": int(np.maximum(
                        h_cnt - self.scorer.harvest_cap, 0).sum()),
                    "tau": float(self.scorer.tau),
                }
                um = float(np.asarray(state["unc_max"]))
                max_unc = um if np.isfinite(um) else None

        total_steps = steps * T
        reg.counter_inc("md.farm_steps_total", float(total_steps),
                        help="MD steps completed by trajectory farms")
        reg.counter_inc("md.farm_rebuild_swaps_total", float(swaps),
                        help="candidate-cache rebuild swaps performed by "
                             "trajectory farms")
        reg.counter_inc("md.farm_dispatches_total", float(dispatches),
                        help="device dispatches issued by trajectory "
                             "farms")
        reg.gauge_set("md.farm_steps_per_dispatch",
                      total_steps / dispatches if dispatches else 0.0,
                      help="completed steps per device dispatch "
                           "(aggregate over trajectories) of the last "
                           "farm run")
        if scored:
            reg.counter_inc(
                "md.harvest_total", float(harvest["filled"].sum()),
                help="structures harvested into candidate pools by "
                     "scored trajectory farms")
            reg.gauge_set(
                "md.uncertainty",
                max_unc if max_unc is not None else 0.0,
                help="maximum ensemble uncertainty observed over the "
                     "last scored farm run (model energy units)")
        reg.log_event(
            "md", "farm_run",
            data={"trajectories": T, "atoms": n, "steps": steps,
                  "rebuild_swaps": swaps, "dispatches": dispatches,
                  "steps_per_dispatch": self.steps_per_dispatch,
                  "cand_capacity": c_cap,
                  "harvested": (int(harvest["filled"].sum())
                                if scored else None)},
            timing={"wall_s": wall,
                    "aggregate_steps_per_s": (total_steps / wall
                                              if wall > 0 else None)})
        return {
            "trajectories": T,
            "atoms": n,
            "steps": steps,
            "final_pos": final_pos,
            "final_vel": final_vd / self.dt,
            "energy_first": e_first,
            "energy_last": e_last,
            "wall_s": round(wall, 4),
            "aggregate_steps_per_s": (round(total_steps / wall, 3)
                                      if wall > 0 else None),
            "per_traj_steps_per_s": (round(steps / wall, 3)
                                     if wall > 0 else None),
            "dispatches": dispatches,
            "steps_per_dispatch": self.steps_per_dispatch,
            "steps_per_dispatch_effective": (
                round(total_steps / (dispatches * T), 3)
                if dispatches else None),
            "rebuild_swaps": swaps,
            "rebuild_fraction": round(swaps / total_steps, 4),
            "per_traj_rebuilds": [nl.rebuilds - 1 for nl in nls],
            "cand_capacity": c_cap,
            "max_degree_capacity": w_cap,
            "fresh_compiles_run": self.fresh_compiles
            - fresh_compiles_before,
            "harvest": harvest,
            "max_uncertainty": max_unc,
            "unc_trace": (np.concatenate([tr["unc"] for tr in traces])
                          if traces else None),
            "adv_trace": (np.concatenate([tr["adv"] for tr in traces])
                          if traces else None),
            "step_trace": (np.concatenate([tr["steps_done"]
                                           for tr in traces])
                           if traces else None),
        }
