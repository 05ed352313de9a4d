"""Energy+force training through the loop users run.

The job composes what ``run_training`` composes on its default path
(`system.Training`) and hands it to ``train/trainer.train_validate_test``
— epoch loop, async loader, device prefetch, per-step loss fetch,
validation and test passes, best-state copy — with the step callable
wrapped by a counter. After the warm-up steps the counter syncs and opens
the window; it counts the optimizer steps and their REAL graphs, atoms and
edges (read on the host from each batch's masks as it is placed); when the
window's time has passed it syncs on the last step's output and closes the
window. The trainer takes `LATE_STEPS` more steps, outside the window, the
counter reads the loss of the trainer's first batches with the state each
of them returned (`loss_fell`) and then asks the trainer to stop
(``trainer.request_preemption``); after that the job compares the step
programs with the plain reference (`Checks`).

Not as a user has it: no TensorBoard writer (HYDRAGNN_DISABLE_TB, saves
importing torch in every run), no checkpoint, weights from ``--seed``.
"""
from __future__ import annotations

import functools
import os
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .. import say, system
from ..reference import common
from . import checks

WARMUP_STEPS = 2   # the trainer's first steps: both compiled variants of a
#                    data-parallel step (PERF.md, PR 21) and the prefetch
# `loss_fell` (`jobs/checks.LOSS_FELL`): the trainer's first batches, as
# many as hold this many structures, are read with the state that closed
# the window and with the states of this many further steps
PROBE_STRUCTURES = 32
LATE_STEPS = 4


class WindowedStep:
    """`train_step` with the window round it. Not jitted itself: the
    trainer's recompile counter skips it and reads the eval step."""

    def __init__(self, step, ctx, max_steps=None, keep=1, read=None):
        self.step, self.ctx, self.max_steps = step, ctx, max_steps
        self.calls = 0
        self.t0 = self.t1 = None
        # for `loss_fell`: the first `keep` batches the trainer feeds before
        # the window closes, as the loader collated them, and what
        # `read(state, batches)` says of them with the state the window's
        # last step returned and with each of the `LATE_STEPS` after it
        self.keep, self.read = keep, read
        self.first_batches, self.after = [], []
        self.losses: List = []
        self.nonfinite: List = []
        self.work = {"graphs": 0, "atoms": 0, "edges": 0, "steps": 0,
                     "node_slots": 0}
        self._placed: Dict[int, tuple] = {}

    def placing(self, place):
        """Wrap the placement: count a batch's real content on the host,
        keyed by the placed batch the step will receive; the host batch
        beside it until the trainer's first `keep` steps have taken theirs
        (`loss_fell` reads those batches again after the window, and
        nothing more lies on the device meanwhile)."""
        def placed(batch):
            counts = (int(np.sum(batch.graph_mask)),
                      int(np.sum(batch.node_mask)),
                      int(np.sum(batch.edge_mask)),
                      int(np.size(batch.node_mask)))
            out = place(batch)
            self._placed[id(out)] = (
                counts, batch if self.calls < self.keep else None)
            return out
        return placed

    def __call__(self, state, batch):
        counts, host_batch = self._placed.pop(id(batch), (None, None))
        if self.calls == WARMUP_STEPS and self.t0 is None:
            jax.block_until_ready(state)
            self.ctx.open_window()
            self.t0 = time.perf_counter()
        state, metrics = self.step(state, batch)
        if self.calls < self.keep and self.t1 is None:
            self.first_batches.append(host_batch)
        self.calls += 1
        if self.t1 is not None:
            self.late(state)
        elif self.t0 is not None:
            self.losses.append(metrics["loss"])
            self.nonfinite.append(metrics["nonfinite_steps"])
            self.work["steps"] += 1
            for key, n in zip(("graphs", "atoms", "edges", "node_slots"),
                              counts):
                self.work[key] += n
            out_of_time = (time.perf_counter() - self.t0
                           >= self.ctx.window_seconds())
            if out_of_time or self.work["steps"] == self.max_steps:
                jax.block_until_ready((state, metrics))
                self.t1 = time.perf_counter()
                self.ctx.close_window()
                self.late(state)
        return state, metrics

    def late(self, state):
        """Past the window's close: one more reading of the first batches,
        before the trainer's next step donates `state`; after the last of
        them the trainer is asked to stop."""
        from hydragnn_tpu.train import trainer
        self.after.append(self.read(state, self.first_batches))
        if len(self.after) > LATE_STEPS:
            trainer.request_preemption()


@jax.jit
def copy_state(state):
    """A copy of the train state in one program. The step donates its
    state, and both the check step and the trainer's first step must see
    the same kind of array (the trainer's would otherwise compile the step
    a third time)."""
    return jax.tree_util.tree_map(jnp.copy, state)


def loss_of(comp, state, batches) -> float:
    """What the train step says of the host batches `batches` with `state`,
    their mean: a step's loss precedes its update, and the update is thrown
    away here. Train mode: BatchNorm takes the batch's own statistics, so
    the reading hangs on the weights alone."""
    return float(np.mean([
        jax.device_get(comp.train_step(copy_state(state),
                                       comp.place(batch))[1]["loss"])
        for batch in batches]))


class Checks:
    """The step programs against the plain reference, on the check
    structures, with the same weights (`jobs/checks.py`).

    `as_run()` comes before the window and is its warm-up: it compiles and
    runs the train step and the eval step in the variants the trainer will
    call, and keeps what they gave. `judge()` comes after the window: the
    reference, and the same programs traced at highest matmul precision.

    Every shard of a data-parallel step gets `system.CHECK_STRUCTURES`
    structures (`per_shard`), small and large: the picks are evenly spaced
    by size and shard i holds picks[i::shards]. The batch keeps the
    loader's padded shape, so the warm-up compiles what the window runs."""

    def __init__(self, comp, doc, config, per_shard: int = None):
        self.comp, self.doc, self.config = comp, doc, config
        n = comp.num_shards
        testset = comp.loaders[2].dataset
        self.per_shard = per_shard or min(
            system.CHECK_STRUCTURES, comp.loaders[0].batch_size // n,
            len(testset) // n)
        self.chk = system.check_structures(testset, self.per_shard * n)
        self.placed = comp.place(comp.collate(self.chk))
        # the positions, in `chk`, of the structures each shard holds
        self.shards = [list(range(len(self.chk)))[i::n] for i in range(n)]
        self.where = (f"{len(self.chk)} structures on one chip" if n == 1
                      else f"mean of {n} shards x {self.per_shard} "
                      "structures")

    def _first_step(self):
        """(the state one train step on the check batch leaves, its
        metrics), under the matmul precision in force."""
        stepped, metrics = self.comp.train_step(copy_state(self.state),
                                                self.placed)
        return stepped, {k: float(metrics[k]) for k in (
            "loss", "energy_loss", "force_loss")}

    def _evaluate(self):
        """The eval step on the check batch with the weights `as_run` left,
        under the matmul precision in force."""
        return jax.device_get(self.comp.eval_step(self.stepped, self.placed))

    def as_run(self, state, warm: bool = True) -> None:
        self.state = state
        self.stepped, self.first = self._first_step()
        self.evaluated = self._evaluate()
        if warm:
            # the step on its own output: a data-parallel step compiles
            # once more for it (PERF.md, PR 21)
            self.comp.train_step(copy_state(self.stepped), self.placed)

    def at_highest(self):
        """(the first step's metrics, the eval step's output) of the same
        programs traced at highest matmul precision."""
        with jax.default_matmul_precision("highest"):
            _, first = self._first_step()
            return first, self._evaluate()

    def outputs(self, first: Dict, evaluated) -> Dict:
        """What a step pair gave, in the form `compare` judges: the train
        step's loss and its terms; the eval step's energies and forces
        (one chip) or its losses (the data-parallel step returns no more)."""
        out = {"train": first}
        if self.comp.num_shards == 1:
            _, (energy, forces) = evaluated
            out["energy"], out["forces"] = checks.unpad_ef(
                energy, forces, self.chk)
        else:
            out["eval"] = {k: float(evaluated[k])
                           for k in ("energy_loss", "force_loss")}
        return out

    def reference(self, fault: str = None, float64: bool = False) -> Dict:
        """What the plain reference says of the check batch, in the same
        form. Train mode: BatchNorm takes the statistics of the atoms one
        device sees, so each shard is evaluated alone and the losses are
        composed as the step composes them (ROADMAP A8). Eval mode: with
        the weights the as-run step left. `fault="edge_mask"` plants that
        negative control (`checks.CONTROLS`): the reference then stands in
        the program's place."""
        doc, config = self.doc, self.config
        chk = ([system.drop_edges(s) for s in self.chk]
               if fault == "edge_mask" else self.chk)
        variables = {"params": self.state.params,
                     "batch_stats": self.state.batch_stats}
        terms = []
        for members in self.shards:
            e, f, struct = system.reference_energy_forces(
                doc, config, variables, [chk[i] for i in members],
                train=True, float64=float64)
            e_loss, f_loss = common.mae_losses(e, f, struct)
            terms.append({"energy_loss": e_loss, "force_loss": f_loss,
                          "graphs": len(members)})
        train = checks.compose(terms)
        train["loss"] = train["energy_loss"] + train["force_loss"]
        variables = {"params": self.stepped.params,
                     "batch_stats": self.stepped.batch_stats}
        ref_e, ref_f, struct = system.reference_energy_forces(
            doc, config, variables, chk, train=False, float64=float64)
        eval_terms = checks.shard_terms(ref_e, ref_f, struct, self.shards)
        return {"train": train, "terms": terms, "energy": ref_e,
                "forces": ref_f, "eval_terms": eval_terms,
                "eval": checks.compose(eval_terms)}

    def compare(self, out: checks.Compared, suffix: str, tol: Dict,
                got: Dict, want: Dict) -> None:
        """A loss is judged by the entry of `tol` named beside it and
        printed where the table has none: at highest the scalars made of
        forces are recorded only (`jobs/checks.py` says why), as run every
        loss is held to the one loose `loss`."""
        where = self.where
        for name, term, key in (
                ("train_step_loss", "loss", "loss"),
                ("train_step_energy_loss", "energy_loss",
                 "train_energy_loss"),
                ("train_step_force_loss", "force_loss", "train_force_loss")):
            out.close(f"{name}_{suffix}", got["train"][term],
                      want["train"][term], tol, key, where)
        label = f"eval_step_{suffix}"
        if self.comp.num_shards == 1:
            out.arrays(label, got["energy"], got["forces"], want["energy"],
                       want["forces"], tol, where)
        else:
            # the data-parallel eval step returns losses only: hold them
            # to the reference's predictions composed the same way
            for key in ("energy_loss", "force_loss"):
                out.close(f"{label}_{key}", got["eval"][key],
                          want["eval"][key], tol,
                          key if tol is checks.HIGHEST_TOL else "loss", where)

    def judge(self) -> checks.Compared:
        out = checks.Compared(say)
        first, evaluated = self.at_highest()
        want = self.reference()
        checks.describe_shards(want["terms"], say, "train mode,")
        if self.comp.num_shards > 1:
            checks.describe_shards(want["eval_terms"], say, "eval mode,")
        self.compare(out, "at_highest", checks.HIGHEST_TOL,
                     self.outputs(first, evaluated), want)
        self.compare(out, "as_run", checks.AS_RUN_TOL,
                     self.outputs(self.first, self.evaluated), want)
        return out

    def control(self, fault: str) -> checks.Compared:
        """The at-highest comparisons with the faulty reference in the
        program's place: it has to FAIL one of them."""
        out = checks.Compared(say)
        self.compare(out, "at_highest", checks.HIGHEST_TOL,
                     self.reference(fault), self.reference())
        return out


def run(ctx) -> Dict:
    os.environ.setdefault("HYDRAGNN_DISABLE_TB", "1")
    from hydragnn_tpu.config import get_log_name_config
    from hydragnn_tpu.train import trainer
    from hydragnn_tpu.utils import profiling as tr
    cell = ctx.cell
    doc = system.apply_tiny(cell.config_doc) if ctx.tiny else cell.config_doc
    pool, valset, testset = system.load_pools(doc)
    trainset = [pool[i] for i in system.seeded_order(len(pool), ctx.seed)]
    pools = (trainset, valset, testset)
    batch_size = int(ctx.param("graphs_per_chip")) * cell.chips
    config = system.complete_config(doc, pools, batch_size,
                                    training=ctx.param("training"))
    say(f"pools loaded: {len(pool)} + {len(valset)} + {len(testset)} "
        "structures")
    comp = system.Training(config, pools, num_shards=cell.chips)
    loader = comp.loaders[0]
    say(f"layout: neighbor_format={comp.neighbor_format} K="
        f"{loader.neighbor_k} batch {batch_size} graphs over {cell.chips} "
        f"chip(s), padded to {loader.n_node} nodes x {loader.n_edge} edges "
        f"per chip; {len(loader)} steps an epoch")
    state = comp.initial_state(ctx.seed)
    say("weights initialised")
    against_reference = Checks(comp, doc, config)
    against_reference.as_run(state)
    say("step programs ready")

    tr.initialize(sync=False)
    trainer.clear_preemption()
    step = WindowedStep(comp.train_step, ctx,
                        max_steps=ctx.param("trace_steps")
                        if ctx.trace is not None else None,
                        keep=-(-PROBE_STRUCTURES // batch_size),
                        read=functools.partial(loss_of, comp))
    tcfg = comp.train_cfg
    try:
        trainer.train_validate_test(
            step, comp.eval_step, copy_state(state), *comp.loaders,
            num_epochs=int(tcfg["num_epoch"]),
            log_name="bench-" + get_log_name_config(config),
            patience=int(tcfg.get("patience", 10)),
            use_early_stopping=bool(tcfg.get("EarlyStopping", False)),
            verbosity=0, tracer=tr.get(), place_fn=step.placing(comp.place),
            keep_best=bool(tcfg.get("keep_best", True)))
    finally:
        trainer.clear_preemption()
    if step.t1 is None:
        raise RuntimeError("training ended before the window closed: "
                           "raise Training.num_epoch")

    seconds = step.t1 - step.t0
    work = step.work
    losses = np.asarray(jax.device_get(step.losses), np.float64)
    bad = int(np.sum(jax.device_get(step.nonfinite)))
    tenth = max(1, len(losses) // 10)
    first, last = losses[:tenth].mean(), losses[-tenth:].mean()
    say(f"{work['steps']} steps in {seconds:.3f} s: {work['graphs']} "
        f"graphs, {work['atoms'] / seconds:.1f} real atoms/s, "
        f"{work['edges'] / seconds:.1f} real edges/s; loss {first:.5f} "
        f"(first tenth) -> {last:.5f} (last tenth)")
    # `loss_fell`: the trainer's first batches (as many as hold 32
    # structures, of those it fed before the window closed) through the
    # train step again: with the state the window's last step returned and
    # with the states of the `LATE_STEPS` steps after it (`step.after`:
    # read as the trainer went on, past the close), the LEAST of them over
    # the reading with the fresh weights. The same structures and the same
    # compiled program on both sides of the window; everything after the
    # close and before the result line, so inside neither
    # `train_graphs_per_s` nor `setup_s`. A step that returns its state
    # unchanged, or moves only BatchNorm's running statistics, reads 1 at
    # every state (`jobs/checks.LOSS_FELL`)
    fresh = loss_of(comp, state, step.first_batches)
    ratio = float(np.min(step.after)) / fresh   # a NaN among them fails
    say(f"loss_fell: the trainer's first {len(step.first_batches)} "
        f"batch(es) read {fresh:.6f} with the fresh weights; with the state "
        f"the window's last step returned and the {len(step.after) - 1} "
        "after it "
        + ", ".join(f"{loss:.6f}" for loss in step.after)
        + f": least ratio {ratio:.4f} (limit {checks.LOSS_FELL:g})")
    results = against_reference.judge()
    results.flag("every_loss_finite", bool(np.isfinite(losses).all()
                                           and bad == 0))
    results.record("loss_fell", ratio, checks.LOSS_FELL)
    return {
        "end_to_end": {"train_graphs_per_s": work["graphs"] / seconds},
        "attempted": work["steps"], "failed": bad, "checks": results,
        "counters": {
            "pad_node_share": 1.0 - work["atoms"] / work["node_slots"]},
        "work": work, "arch": config["NeuralNetwork"]["Architecture"]}
