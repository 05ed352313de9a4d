"""Active-learning MD farm: device-fused uncertainty scoring, the
deterministic harvest contract, and the self-retraining hot-swap loop
(ROADMAP item 5, FlashSchNet; docs/active_learning.md).

The PR 11 trajectory farm only *consumes* a model. This module closes
the loop — MD that explores, flags its own uncertain regions, and
repairs its potential — in three pieces:

* **`EnsembleScorer`** — a cheap last-layer ensemble evaluated per
  structure INSIDE the farm's K-step device-resident dispatch, as part
  of the same jitted program. The conv stack runs once (its final node
  embedding is captured through the existing ``encoder_h{i}`` sow
  points, base.py); M perturbed copies of the head-0 energy MLP re-read
  that embedding, and the uncertainty is the f32 standard deviation of
  the M masked-pooled graph energies. Member 0 is the UNPERTURBED head;
  members m >= 1 scale each head weight by ``1 + eps * delta`` with
  delta drawn once, deterministically, from the scorer seed — the
  multipliers are runtime constants, so a hot-swapped model is scored
  by the SAME ensemble geometry without recompiling. Cost: M tiny
  [n, hidden] matmul chains on an embedding already resident on device
  — no extra forward, no extra H2D/D2H round-trip, zero added compiles
  per dispatch (BENCH_ACTIVE pins throughput >= 0.9x unscored).

* **deterministic harvest** (the farm side lives in md/farm.py): a
  trajectory harvests a structure exactly when its uncertainty RISES
  through ``tau`` — ``cross = advanced & (unc >= tau) & ~was_above`` —
  a pure function of trajectory state on the exact binary integrator
  grid, so two identical farm runs harvest bitwise-identical pools.
  The rising-edge rule (not level-triggered) means a trajectory
  wandering in an uncertain region harvests its ENTRY structure once
  instead of flooding the pool with near-duplicates every step.

* **`CandidatePool`** — harvested structures dumped through the PR 5
  content-addressed preproc-cache shard format, keyed by a sha256 over
  the exact grid-state bytes (positions, features, cell): the same
  structure harvested twice — same run, twin run, or a later round —
  lands on the same key, so the pool dedups by construction and its
  ``manifest_digest()`` adjudicates twin-run bitwise equality.

* **`ActiveLearner`** — the self-retraining loop: run the farm, label
  the fresh harvest with an oracle, fine-tune from the BEST variables
  under a `TrialSupervisor` (PR 14 — the fine-tune job is a supervised
  trial with heartbeat/retry/deadline), and hot-swap the improved model
  into the engine and farm via the PR 12-13 swap contract
  (``swap_variables``: shape-checked, recompile-free).

Everything here follows the traced-env rule: knobs resolve through
`serving.config.resolve_active` (HYDRAGNN_MD_ACTIVE_*) at construction,
never by env reads in traced code.
"""
from __future__ import annotations

import hashlib
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..preprocess.cache import _shard_dir, load_shard, save_shard

__all__ = ["EnsembleScorer", "CandidatePool", "ActiveLearner",
           "finetune_on_pool", "oracle_error"]


# ------------------------------------------------------------- scorer --

def _head_mlp_params(params: Dict) -> Dict:
    """The dense-layer dict of head 0's shared node MLP
    (``params["head_0"]["MLP_0"]["dense_i"]``), validated actionably —
    the ensemble re-applies exactly these layers to the captured final
    embedding, so any other head layout cannot be scored."""
    head = params.get("head_0")
    if not isinstance(head, dict) or "MLP_0" not in head:
        raise ValueError(
            "active-learning scoring needs head 0 to be a shared node-MLP "
            "energy head (node_arch='mlp', the energy_force_loss "
            f"convention); got head_0 params with keys "
            f"{sorted(head) if isinstance(head, dict) else type(head)}")
    mlp = head["MLP_0"]
    denses = sorted((k for k in mlp if k.startswith("dense_")),
                    key=lambda k: int(k.split("_")[1]))
    if not denses or any(f"dense_{i}" != k for i, k in enumerate(denses)):
        raise ValueError(
            f"head_0/MLP_0 has unexpected layer keys {sorted(mlp)} — "
            "expected dense_0..dense_{L-1}")
    return {k: mlp[k] for k in denses}


class EnsembleScorer:
    """Device-fused last-layer-ensemble uncertainty head (module
    docstring). Attach to a farm via
    ``engine.trajectory_farm(..., scorer=scorer)`` — the farm's
    per-structure forward then returns ``(graph_e, forces, unc)`` from
    ONE jitted program.

    ``tau`` and ``harvest_cap`` ride on the scorer: they parameterize
    the farm's harvest rule (threshold + per-trajectory buffer slots).
    """

    def __init__(self, model, mcfg, variables, *, members: int = 4,
                 eps: float = 0.02, tau: float = 0.1,
                 harvest_cap: int = 16, seed: int = 0,
                 compute_dtype: Optional[str] = None):
        if int(members) < 2:
            raise ValueError(
                f"ensemble needs >= 2 members (got {members}) — a "
                "1-member ensemble has zero variance everywhere")
        if not float(eps) > 0.0:
            raise ValueError(f"perturbation eps must be > 0, got {eps}")
        if int(harvest_cap) < 1:
            raise ValueError(
                f"harvest_cap must be >= 1, got {harvest_cap}")
        if mcfg.heads[0].head_type != "node":
            raise ValueError(
                "active-learning scoring serves energy from a node-level "
                f"head 0; got a {mcfg.heads[0].head_type!r} head")
        self.model = model
        self.mcfg = mcfg
        self.members = int(members)
        self.eps = float(eps)
        self.tau = float(tau)
        self.harvest_cap = int(harvest_cap)
        self.seed = int(seed)
        self.compute_dtype = compute_dtype
        # validate the head layout NOW (construction-time failure beats a
        # trace-time KeyError) and derive the layer count the traced
        # ensemble walk is specialized to
        self._num_dense = len(_head_mlp_params(variables["params"]))
        self._mults = self._make_multipliers(variables["params"])

    def _make_multipliers(self, params: Dict) -> Dict[str, Dict]:
        """Per-leaf multiplicative perturbations [M, *leaf.shape] f32:
        member 0 is exactly 1.0 (the true head), member m >= 1 draws
        ``1 + eps * N(0,1)`` from a RandomState seeded by (seed, layer
        index, leaf name) — a pure function of the scorer spec, so twin
        farms score identically and a hot-swap keeps the ensemble
        geometry."""
        mults: Dict[str, Dict] = {}
        for li, (lname, leaf) in enumerate(
                sorted(_head_mlp_params(params).items())):
            mults[lname] = {}
            for pname in sorted(leaf):
                shape = np.asarray(leaf[pname]).shape
                rs = np.random.RandomState(
                    [self.seed & 0x7FFFFFFF, li,
                     0 if pname == "kernel" else 1])
                delta = rs.randn(self.members - 1, *shape)
                m = np.concatenate(
                    [np.ones((1,) + shape, np.float64),
                     1.0 + self.eps * delta]).astype(np.float32)
                mults[lname][pname] = m
        return mults

    @classmethod
    def from_config(cls, model, mcfg, variables,
                    config: Optional[Dict] = None, *,
                    compute_dtype: Optional[str] = None
                    ) -> "EnsembleScorer":
        """Build from the resolved knob stack — the `Serving.md_active`
        config block overridden by the strict-parsed
        HYDRAGNN_MD_ACTIVE_* env knobs (serving/config.resolve_active),
        so deployments size the ensemble without code changes."""
        from ..serving.config import resolve_active
        knobs = resolve_active(config)
        return cls(model, mcfg, variables, members=knobs.members,
                   eps=knobs.eps, tau=knobs.tau,
                   harvest_cap=knobs.harvest_cap, seed=knobs.seed,
                   compute_dtype=compute_dtype)

    def spec(self) -> Dict[str, Any]:
        """The scorer's identity for artifacts/fingerprints."""
        return {"members": self.members, "eps": self.eps, "tau": self.tau,
                "harvest_cap": self.harvest_cap, "seed": self.seed}

    def make_head_forward(self) -> Callable:
        """``fn(variables, batch) -> (graph_e, forces, unc)`` — the
        scored replacement for the farm's EF forward, same casting
        policy as `make_forward_fn` (mixed-precision compute, f32 in/
        out), with the final conv embedding captured through the
        ``encoder_h{L-1}`` sow point and the M-member head variance
        accumulated in f32."""
        import jax
        import jax.numpy as jnp

        from ..ops.activations import activation_function_selection
        from ..ops.segment import global_sum_pool
        from ..train.train_step import _cast_floats, _resolve_compute_dtype

        cdtype = _resolve_compute_dtype(self.mcfg, self.compute_dtype)
        mixed = cdtype != jnp.float32
        model = self.model
        act = activation_function_selection(self.mcfg.activation)
        h_name = f"encoder_h{self.mcfg.num_conv_layers - 1}"
        num_dense = self._num_dense
        mults = jax.tree_util.tree_map(jnp.asarray, self._mults)

        def member_energies(head_params, h, node_mask, node_graph):
            # [M] f32: each member's masked-pooled graph-0 energy. The
            # perturbed parameter stack is [M, ...] per leaf; the walk is
            # the MLP's own dense/act sequence (models/layers.MLP) with
            # activation between all but the last layer, accumulated f32.
            pert = jax.tree_util.tree_map(
                lambda p, m: p.astype(jnp.float32)[None] * m,
                head_params, mults)
            mask = (node_mask & (node_graph == 0)).astype(jnp.float32)

            def one_member(hp):
                x = h.astype(jnp.float32)
                for i in range(num_dense):
                    lp = hp[f"dense_{i}"]
                    x = x @ lp["kernel"]
                    if "bias" in lp:
                        x = x + lp["bias"]
                    if i < num_dense - 1:
                        x = act(x)
                return jnp.sum(x[:, 0] * mask)

            return jax.vmap(one_member)(pert)

        def head_forward(variables, batch):
            head_params = _head_mlp_params(variables["params"])

            def total_energy(pos):
                b = batch.replace(pos=pos)
                vv = _cast_floats(variables, cdtype) if mixed else variables
                bb = _cast_floats(b, cdtype) if mixed else b
                (outputs, _), muts = model.apply(
                    vv, bb, train=False, mutable=["intermediates"])
                if mixed:
                    outputs = _cast_floats(outputs, jnp.float32)
                node_e = outputs[0][:, :1]
                graph_e = global_sum_pool(node_e, b.node_graph,
                                          b.num_graphs, b.node_mask)
                h = muts["intermediates"][h_name][0]
                if mixed:
                    h = _cast_floats(h, jnp.float32)
                return (jnp.sum(jnp.where(batch.graph_mask[:, None],
                                          graph_e, 0.0)),
                        (graph_e, h))

            (_, (graph_e, h)), neg_forces = jax.value_and_grad(
                total_energy, has_aux=True)(batch.pos)
            e_m = member_energies(head_params, h, batch.node_mask,
                                  batch.node_graph)
            unc = jnp.std(e_m).astype(jnp.float32)
            return graph_e, -neg_forces, unc

        return head_forward


# -------------------------------------------------------- candidate pool --

def structure_key(pos: np.ndarray, node_features: np.ndarray,
                  cell: Optional[np.ndarray]) -> str:
    """Content address of one harvested structure: sha256 over the EXACT
    grid-state bytes. Positions are on the binary integrator grid, so
    bitwise-identical trajectories produce byte-identical keys — the
    twin-run pool-equality contract rides on this."""
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(pos, np.float64).tobytes())
    h.update(np.ascontiguousarray(node_features, np.float32).tobytes())
    if cell is not None:
        h.update(np.ascontiguousarray(cell, np.float64).tobytes())
    return h.hexdigest()[:32]


class CandidatePool:
    """Dedup'd pool of harvested candidate structures, one PR 5
    content-addressed preproc-cache shard per structure (atomic rename,
    sha256'd data.bin, concurrent-writer safe). The key is a pure
    function of the structure's grid state (`structure_key`), so re-adds
    of the same structure — within a run, across twin runs, or across
    harvest rounds — hit the same shard and the pool stays duplicate-
    free by construction."""

    def __init__(self, root: str, structure_config: Dict):
        self.root = str(root)
        self._cfg = structure_config
        self.added = 0
        self.dedup_hits = 0
        os.makedirs(self.root, exist_ok=True)

    def add(self, pos: np.ndarray, node_features: np.ndarray,
            cell: Optional[np.ndarray], *, unc: float, step: int,
            traj: int) -> Tuple[str, bool]:
        """Store one harvested structure; returns (key, newly_added).
        The graph sample is rebuilt through the standard
        `build_graph_sample` path (fresh edges from the grid positions)
        and the exact f64 grid positions ride along in the shard's
        meta so labeling/fine-tuning can reach them."""
        from ..preprocess.transforms import build_graph_sample
        pos = np.asarray(pos, np.float64)
        node_features = np.asarray(node_features, np.float32)
        key = structure_key(pos, node_features, cell)
        if os.path.isdir(_shard_dir(self.root, key)):
            self.dedup_hits += 1
            return key, False
        sample = build_graph_sample(node_features, pos, self._cfg,
                                    cell=cell, with_targets=False)
        save_shard(self.root, key, [sample],
                   extra_meta={"pos64": pos, "unc": float(unc),
                               "step": int(step), "traj": int(traj),
                               "labeled": 0})
        self.added += 1
        return key, True

    def label(self, key: str, energy: float, forces: np.ndarray) -> None:
        """Attach oracle labels to one candidate (idempotent rewrite of
        its shard — same key, content now carries energy/forces)."""
        samples, meta = load_shard(self.root, key)
        s = samples[0]
        kw = {f: getattr(s, f, None) for f in s.__slots__ if f != "extras"}
        kw["energy"] = np.asarray([energy], np.float32)
        kw["forces"] = np.asarray(forces, np.float32)
        s = type(s)(**kw)
        meta = dict(meta or {})
        meta["labeled"] = 1
        save_shard(self.root, key, [s], extra_meta=meta)

    def keys(self) -> List[str]:
        """Sorted content keys — THE pool iteration order (sorted, so
        fine-tune batches are independent of harvest arrival order)."""
        pref = "preproc-"
        return sorted(d[len(pref):] for d in os.listdir(self.root)
                      if d.startswith(pref))

    def __len__(self) -> int:
        return len(self.keys())

    def manifest_digest(self) -> str:
        """sha256 over (sorted keys, per-shard data sha256) — two pools
        are equal iff their digests are (the twin-run adjudication)."""
        import json
        h = hashlib.sha256()
        for key in self.keys():
            h.update(key.encode())
            with open(os.path.join(_shard_dir(self.root, key),
                                   "meta.json")) as f:
                h.update(json.load(f)["data_sha256"].encode())
        return h.hexdigest()

    def load(self, labeled_only: bool = False
             ) -> Tuple[List, List[Dict]]:
        """(samples, metas) in sorted-key order."""
        samples, metas = [], []
        for key in self.keys():
            ss, meta = load_shard(self.root, key)
            meta = meta or {}
            if labeled_only and not meta.get("labeled"):
                continue
            samples.append(ss[0])
            metas.append(meta)
        return samples, metas


# ------------------------------------------------------------ fine-tune --

def finetune_on_pool(model, mcfg, variables, samples: Sequence, *,
                     bucket, steps: int, lr: float, seed: int = 0,
                     compute_dtype: Optional[str] = None,
                     progress_cb: Optional[Callable[[int], None]] = None
                     ) -> Tuple[Dict, List[float]]:
    """Fine-tune the EF model on labeled pool samples: Adam on the
    energy+force loss (the trained quantity IS the served quantity —
    `energy_force_loss`), one sample per step on the farm's own bucket
    shape, visiting the pool in deterministically shuffled passes.
    Returns (new_variables, per-step losses)."""
    import jax
    import jax.numpy as jnp
    import optax

    from ..graphs.batch import collate
    from ..train.loss import energy_force_loss
    from ..train.train_step import make_forward_fn

    if not samples:
        raise ValueError("fine-tune needs a non-empty labeled pool")
    forward = make_forward_fn(model, mcfg, compute_dtype)

    def apply_fn(v, b, train):
        return forward(v, b, train=train), None

    batch_stats = variables.get("batch_stats", {})

    def loss_fn(params, batch):
        total, _ = energy_force_loss(
            apply_fn, {"params": params, "batch_stats": batch_stats},
            mcfg, batch, loss_name="mse", train=False)
        return total

    tx = optax.adam(float(lr))

    @jax.jit
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    batches = [collate([s], n_node=bucket.n_node, n_edge=bucket.n_edge,
                       n_graph=bucket.n_graph) for s in samples]
    params = variables["params"]
    opt_state = tx.init(params)
    rs = np.random.RandomState(int(seed) & 0x7FFFFFFF)
    order: List[int] = []
    losses: List[float] = []
    for it in range(int(steps)):
        if not order:
            order = list(rs.permutation(len(batches)))
        params, opt_state, loss = train_step(params, opt_state,
                                             batches[order.pop(0)])
        losses.append(float(loss))
        if progress_cb is not None:
            progress_cb(it + 1)
    del opt_state
    return {"params": params, "batch_stats": batch_stats}, losses


def oracle_error(engine, probe: Sequence, oracle_fn: Callable) -> float:
    """Mean |E_model - E_oracle| over probe structures (the BENCH_ACTIVE
    error-vs-oracle metric), served through the engine's own
    ``submit_structure`` EF path so the measured quantity is the served
    one."""
    errs = []
    for pos, nf, cell in probe:
        fut = engine.submit_structure(np.asarray(pos, np.float64),
                                      node_features=nf, cell=cell)
        res = fut.result()  # ef_forward responses are [energy, forces]
        e_model = float(np.asarray(res[0]).ravel()[0])
        e_true = float(oracle_fn(np.asarray(pos, np.float64), cell)[0])
        errs.append(abs(e_model - e_true))
    return float(np.mean(errs))


# ---------------------------------------------------------- active loop --

class _FinetuneHandle:
    """In-process `TrialHandle` for one fine-tune job: the trial body
    runs on a thread, progress is the optimizer-step counter (the
    supervisor's heartbeat token), and the result payload carries the
    fine-tuned variables. Process-grade isolation (hpo.process) is not
    needed here — the job shares the farm's devices by design."""

    def __init__(self, fn: Callable[[Callable[[int], None]],
                                    Dict[str, Any]]):
        import threading
        self._result: Optional[Dict[str, Any]] = None
        self._error: Optional[str] = None
        self._steps = 0
        self._lock = threading.Lock()

        def _run():
            try:
                res = fn(self._on_step)
                with self._lock:
                    self._result = res
            except Exception as exc:  # noqa: BLE001 — surfaced as a
                # nonzero exit so the supervisor retries/fails the trial
                with self._lock:
                    self._error = f"{type(exc).__name__}: {exc}"

        self._thread = threading.Thread(target=_run, daemon=True,
                                        name="active-finetune")
        self._thread.start()

    def _on_step(self, it: int) -> None:
        with self._lock:
            self._steps = it

    def poll(self) -> Optional[int]:
        if self._thread.is_alive():
            return None
        with self._lock:
            return 0 if self._result is not None else 1

    def kill(self) -> None:
        # a thread cannot be force-killed; the supervisor only calls this
        # on shutdown/deadline, where the daemon thread dies with the
        # process — mark the result void so a late finish is not consumed
        with self._lock:
            if self._thread.is_alive():
                self._error = "killed"

    def progress(self) -> Any:
        with self._lock:
            return self._steps

    def checkpoint_step(self) -> Optional[int]:
        with self._lock:
            return self._steps if self._steps else None

    def result(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            if self._error is not None:
                return None
            return self._result


class ActiveLearner:
    """The explore -> flag -> label -> retrain -> hot-swap loop over one
    engine + farm (module docstring; examples/active_learning).

    ``oracle_fn(pos, cell) -> (energy, forces)`` labels harvested
    structures (the ground-truth potential the farm's model is
    repairing). The fine-tune leg always starts from the BEST variables
    seen so far (best probe error), runs as a supervised `TrialSupervisor`
    trial, and on improvement hot-swaps engine + farm through the
    shape-checked `swap_variables` contract — the farm's compiled
    dispatch takes variables as a runtime argument, so the swap costs
    zero recompiles."""

    def __init__(self, engine, farm, pool: CandidatePool,
                 oracle_fn: Callable, *, probe: Sequence,
                 finetune_steps: int = 60, finetune_lr: float = 1e-3,
                 trial_deadline_s: float = 600.0, seed: int = 0):
        self.engine = engine
        self.farm = farm
        self.pool = pool
        self.oracle_fn = oracle_fn
        self.probe = list(probe)
        self.finetune_steps = int(finetune_steps)
        self.finetune_lr = float(finetune_lr)
        self.trial_deadline_s = float(trial_deadline_s)
        self.seed = int(seed)
        self.rounds: List[Dict[str, Any]] = []
        self.best_error = oracle_error(engine, self.probe, oracle_fn)
        self.best_variables = farm._variables
        self.swaps = 0
        # (final_pos, final_vel) of the last round's farm run — chain
        # these into the next round's initial conditions so every round
        # explores (and harvests from) fresh territory
        self.last_state: Optional[Tuple[np.ndarray, np.ndarray]] = None

    def harvest_from(self, result: Dict, node_features, cell) -> int:
        """Drain one farm run's harvest into the pool; returns the
        number of newly added (non-duplicate) structures."""
        h = result.get("harvest")
        if h is None:
            raise ValueError(
                "farm result carries no harvest — build the farm with a "
                "scorer (engine.trajectory_farm(..., scorer=...))")
        fresh = 0
        for t in range(h["pos"].shape[0]):
            for s in range(int(h["filled"][t])):
                _, added = self.pool.add(
                    h["pos"][t, s], node_features, cell,
                    unc=float(h["unc"][t, s]), step=int(h["step"][t, s]),
                    traj=t)
                fresh += int(added)
        return fresh

    def label_pool(self) -> int:
        """Oracle-label every unlabeled candidate; returns the count."""
        labeled = 0
        for key, meta in zip(self.pool.keys(),
                             self.pool.load()[1]):
            if meta.get("labeled"):
                continue
            pos = np.asarray(meta["pos64"], np.float64)
            cell = self._probe_cell()
            energy, forces = self.oracle_fn(pos, cell)
            self.pool.label(key, float(energy), forces)
            labeled += 1
        return labeled

    def _probe_cell(self):
        return self.probe[0][2] if self.probe else None

    def run_round(self, pos0, vel0, steps: int, *, node_features,
                  cell=None) -> Dict[str, Any]:
        """One active-learning round: farm -> harvest -> label ->
        supervised fine-tune from BEST -> hot-swap on improvement.
        Returns the round report (farm stats + error trajectory)."""
        from ..hpo.supervisor import TrialSpec, TrialSupervisor

        result = self.farm.run(pos0, vel0, steps,
                               node_features=node_features, cell=cell)
        self.last_state = (result["final_pos"], result["final_vel"])
        fresh = self.harvest_from(result, node_features, cell)
        labeled = self.label_pool()
        samples, _ = self.pool.load(labeled_only=True)
        round_idx = len(self.rounds)
        report: Dict[str, Any] = {
            "round": round_idx,
            "harvested_fresh": fresh,
            "labeled": labeled,
            "pool_size": len(self.pool),
            "error_before": self.best_error,
            "aggregate_steps_per_s": result["aggregate_steps_per_s"],
            "max_uncertainty": result["max_uncertainty"],
        }
        if not samples:
            # nothing to train on (threshold never crossed): the round
            # still reports, the model stands
            report.update(error_after=self.best_error, swapped=False,
                          trial_state="skipped")
            self.rounds.append(report)
            return report

        base_vars = self.best_variables
        bucket = self.farm.bucket
        model, mcfg = self.farm._model, self.farm.mcfg
        cdtype = self.farm.compute_dtype
        ft_steps, ft_lr = self.finetune_steps, self.finetune_lr
        ft_seed = self.seed + round_idx
        payload: Dict[str, Any] = {}

        def trial_body(progress_cb):
            new_vars, losses = finetune_on_pool(
                model, mcfg, base_vars, samples, bucket=bucket,
                steps=ft_steps, lr=ft_lr, seed=ft_seed,
                compute_dtype=cdtype, progress_cb=progress_cb)
            payload["variables"] = new_vars
            return {"objective": losses[-1], "loss_first": losses[0],
                    "loss_last": losses[-1]}

        def launch_fn(spec, attempt, resume, hang):
            return _FinetuneHandle(trial_body)

        sup = TrialSupervisor(
            launch_fn,
            [TrialSpec(trial_id=round_idx,
                       params={"steps": ft_steps, "lr": ft_lr,
                               "pool_size": len(samples)})],
            heartbeat_s=max(self.trial_deadline_s / 4.0, 5.0))
        recs = sup.run(deadline_s=self.trial_deadline_s)
        rec = recs[round_idx]
        report["trial_state"] = rec.state
        report["finetune_objective"] = rec.objective
        swapped = False
        if rec.state == "completed" and "variables" in payload:
            new_vars = payload["variables"]
            err = self._probe_error_with(new_vars)
            report["error_candidate"] = err
            if err < self.best_error:
                version = f"active-r{round_idx}"
                self.engine.swap_variables(new_vars, version)
                self.farm.swap_variables(new_vars, version)
                self.best_variables = self.farm._variables
                self.best_error = err
                self.swaps += 1
                swapped = True
        report["swapped"] = swapped
        report["error_after"] = self.best_error
        self.rounds.append(report)
        return report

    def _probe_error_with(self, variables) -> float:
        """Probe error under candidate variables: swap in, measure,
        swap back (the engine's swap is atomic and recompile-free, so
        the probe measures the real served path)."""
        old = self.engine.swap_variables(variables, "active-probe")
        try:
            return oracle_error(self.engine, self.probe, self.oracle_fn)
        finally:
            self.engine.swap_variables(self.best_variables, old)
