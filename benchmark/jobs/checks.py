"""The comparisons behind `correct`, shared by the jobs.

Two readings of every compared output:

* AT HIGHEST matmul precision. The same program code, traced once more
  under ``jax.default_matmul_precision("highest")``, against the plain
  reference, which multiplies the same way. The two differ by summation
  order only, so the tolerance is tight: a change to the model's
  mathematics — a wrong mask, a kernel that rounds, activations or sums
  kept in bfloat16 — fails it.
* AS RUN. On a TPU a float32 matmul at DEFAULT precision rounds its
  operands to bfloat16. Through 5-6 layers and a gradient with respect to
  positions that is far from the reference on seeded random weights
  (forces: 5-20% for PNAPlus, 40-60% for SchNet; an operand-rounding
  emulation of the reference on the CPU gives the same). So the measured
  program's own output is only held to loose bounds that catch garbage,
  not rounding, and its distance is printed in every run.

How a limit is set (PR 25; README.md, "Tolerance of `correct`"). A limit
judges ONE kind of number and lies between two readings of that number,
taken over seeds through the jobs' own `judge` (`calibrate tolerance`;
calibration.json keeps the distributions): at least 3 x above the widest
reading of the sound program (64 seeds a train cell and further sweeps on
fresh seeds; 24 a serving cell), and below the narrowest reading of every
negative control (`CONTROLS`) that separates from the sound program by 3 x
or more. Each control, at every seed, is 3 x or more over the limit of
some judged number. A number whose two readings leave no such room is
printed (`recorded`) and not judged: a limit above a control's reading
passes that control, and one inside the sound program's tail fails sound
runs, which is what refused PR 24. PR 22 held three different scalars to
one `loss` entry of 1e-4, read on one seed in eval mode. What the sweeps
found (TPU v5 lite, my chip runs, PR 25):

* ENERGIES are well conditioned: at highest the system and the reference
  agree to 3e-6 (arrays), 5e-7 (a data-parallel eval step's energy MAE)
  and 1.8e-5 (a train step's energy term) at every seed, and every
  control reads 6.5e-4 or more there. They are judged: `energy`,
  `energy_loss` at 1e-4, `train_energy_loss` at 2e-4.
* FORCES as arrays are judged too (`forces`, 2e-2): they read up to 4.7e-3
  on one chip (179 seeds) and 4.6e-3 from the engine, the controls 3.7e-2
  or more.
* SCALARS MADE OF FORCES are not judged at highest: a train step's total
  and its force term, a data-parallel eval step's force MAE. A gradient
  through ReLU, min and max takes another branch where two float32
  evaluations round a near-tie differently, a handful of atoms then carry
  the whole difference, and in train mode BatchNorm's batch statistics
  carry it into every atom's gradient. The float32 reference is as far
  from its own float64 evaluation as the system is (PERF.md section 6),
  so the cause lies in the number, and it has a tail: the train total
  read 7.6e-4 at one of 179 seeds on one chip, ten times the next (its
  force term 5.5e-3 apart, its energy term 5e-7), where bfloat16 reads
  3.8e-3; the data-parallel force MAE 3.5e-4 at one of 112, three times
  the next, where a masked edge in a hundred reads 1.1e-3; the
  data-parallel total 5.0e-5 at most, but a shard is the one-chip
  computation over again and that seed's step on one shard of four would
  read 1e-4 to 2e-4, where the masked edge reads 5.5e-4. No limit has
  3 x on both sides of those. The steady numbers beside them, the energy
  terms and the force arrays, are what a control fails; the scalars are
  printed at highest, and the total and a data-parallel eval step's
  losses stay judged as run.
* a data-parallel step's losses are means over each shard's own atoms, and
  in train mode BatchNorm takes each shard's own statistics: with two check
  structures a shard (~150 atoms) the same rounding read 3.6e-4. Every
  shard now holds `system.CHECK_STRUCTURES` structures, as the one-chip
  check always did.

Every number, judged or recorded, is printed with its value, its
reference, its limit and the shards it came from (`Compared`), a
data-parallel check prints each shard's reference terms, and every judged
number goes into the result line under `compared`: a failed run can be
read off the log.
"""
from __future__ import annotations

import sys
from typing import Dict, List, Sequence

import numpy as np

from .. import system

# at highest precision, relative: arrays by their L2 norm (`energy`,
# `forces`), scalars by the reference's value (`train_energy_loss`: the
# energy term of a train step's loss; `energy_loss`: a data-parallel eval
# step's energy MAE). A loss with no entry here is printed, not judged
HIGHEST_TOL = {"energy": 1e-4, "forces": 2e-2, "train_energy_loss": 2e-4,
               "energy_loss": 1e-4}
# as run: every loss is held to `loss`
AS_RUN_TOL = {"energy": 0.2, "forces": 1.0, "loss": 0.05}

# `loss_fell` of a train cell (`jobs/train.run`): the loss of the trainer's
# first batches (as many as hold 32 structures) from the train step itself
# (train mode), the LEAST of five states' readings over the reading with the
# fresh weights: the state the window's last step returned and the states
# of the four steps the trainer takes after it. All after the window's
# close. One state's reading swings 2-4 x from state to state at the
# published 1e-3 (period 2-3 steps, every batch together: PERF.md, PR 33):
# it read 0.37 at one seed where five steps earlier it read 0.07, too near
# the controls to be held against them. Set between the sound program's
# widest reading (0.19 from the traced steps on, 45 readings of 21 seeds in
# three cells; the fourth's one-state readings bound it at 0.15) and its two
# controls, which both read EXACTLY 1 at every state, with no
# spread to leave room for: a step that returns its state unchanged, and a
# learning rate of 0 (only BatchNorm's running statistics move, and train
# mode does not read them). `calibrate loss_fell`,
# calibration-loss-fell.json, README.md. Up to PR 32 the check compared the
# TRAINING loss of the window's first tenth of steps with its last tenth,
# each on other structures: a tenth of a traced window is one step, and a
# state left unchanged passed whenever the last step drew easier
# structures than the first. Same-structure readings that the sweeps ruled
# out: the check structures through the eval step (the running statistics
# lag the weights: 0.9 to 470 x the fresh loss after 8 steps); a baseline
# at the window's first step (the second trainer step is inside Adam's
# first overshoot, the loss there anything from a fifth of the fresh one to
# ten times it: 0.08 to 1.03 after 8 steps); one state alone (above)
LOSS_FELL = 0.9

# the negative controls `calibrate tolerance` reads beside the sound
# program, and tests/benchmark/test_bench_checks.py keeps failing: the
# program computing in bfloat16 (Architecture.dtype), and the reference
# with one edge in `system.DROPPED_EDGE` masked out in the program's place
CONTROLS = ("bfloat16", "edge_mask")


class Compared:
    """The numbers a run was judged on: name -> how far the system was,
    the limit, and whether that passes. `ok` is what `correct` is the
    conjunction of; `numbers` goes into the result line; `keys` names the
    entry of the tolerance table behind each limit; `recorded` holds the
    numbers that were printed and not judged."""

    def __init__(self, say):
        self.say = say
        self.ok: Dict[str, bool] = {}
        self.numbers: Dict[str, List[float]] = {}
        self.keys: Dict[str, str] = {}
        self.recorded: Dict[str, float] = {}

    def record(self, name: str, value: float, limit: float,
               key: str = None) -> bool:
        # a NaN is never within a limit
        self.ok[name] = bool(value <= limit)
        self.numbers[name] = [float(value), float(limit)]
        if key:
            self.keys[name] = key
        return self.ok[name]

    def flag(self, name: str, ok: bool) -> bool:
        """A check that is a yes or a no: 0 failures allowed."""
        return self.record(name, 0.0 if ok else 1.0, 0.0)

    def close(self, name: str, got: float, want: float, tol: Dict,
              key: str, where: str) -> bool:
        """A scalar against its reference, relative to the reference:
        judged by `tol[key]`, or printed and kept under `recorded` where
        the table has no such entry."""
        err = abs(got - want) / max(abs(want), 1e-30)
        limit = (f"tolerance {tol[key]:.0e}" if key in tol
                 else "recorded, not judged")
        self.say(f"{name} [{where}]: system {got:.6f}, reference "
                 f"{want:.6f}, relative difference {err:.3e} ({limit})")
        if key not in tol:
            self.recorded[name] = float(err)
            return True
        return self.record(name, err, tol[key], key)

    def arrays(self, label: str, energy, forces, ref_energy, ref_forces,
               tol: Dict, where: str) -> None:
        """Energies and forces against the reference's, by relative L2."""
        for key, got, want in (("energy", energy, ref_energy),
                               ("forces", forces, ref_forces)):
            err = system.relative_error(got, want)
            self.say(f"{label}_{key} [{where}]: relative L2 error vs the "
                     f"plain reference {err:.3e} (tolerance "
                     f"{tol[key]:.0e}) over {np.size(want)} values")
            self.record(f"{label}_{key}", err, tol[key], key)

    def report(self, stream=None) -> None:
        """Each number compared beside its limit, as the last lines on
        standard error."""
        stream = stream or sys.stderr
        for name, (value, limit) in self.numbers.items():
            print(f"compared {name}: {value:.6g} (limit {limit:.6g}) "
                  f"{'ok' if self.ok[name] else 'FAILED'}", file=stream,
                  flush=True)


def unpad_ef(energy, forces, samples: Sequence):
    """Padded (E [G+1, 1], F [N_pad, 3]) of one collated batch -> the real
    rows, in sample order (collate concatenates in order)."""
    atoms = sum(s.num_nodes for s in samples)
    return (np.asarray(energy)[:len(samples), 0],
            np.asarray(forces)[:atoms])


def shard_terms(ref_energy, ref_forces, struct, shards: Sequence[Sequence]
                ) -> List[Dict[str, float]]:
    """For each shard, the energy and force MAE over its own structures
    and atoms, and how many real graphs it holds. `shards` lists, for each
    shard, the positions of its samples in `struct`'s order."""
    sizes = np.bincount(struct["node_graph"])
    starts = np.concatenate([[0], np.cumsum(sizes)])
    out = []
    for members in shards:
        rows = np.concatenate([np.arange(starts[g], starts[g + 1])
                               for g in members])
        out.append({
            "energy_loss": float(np.mean(np.abs(
                ref_energy[members] - struct["energy"][members]))),
            "force_loss": float(np.mean(np.abs(
                ref_forces[rows] - struct["forces"][rows]))),
            "graphs": len(members)})
    return out


def compose(terms: Sequence[Dict[str, float]]) -> Dict[str, float]:
    """Per-shard losses as a data-parallel step composes them: the mean
    over the shards weighted by their real graphs
    (`parallel/spmd.make_spmd_eval_step`)."""
    w = np.asarray([t["graphs"] for t in terms], np.float64)
    w /= w.sum()
    return {key: float(np.dot(w, [t[key] for t in terms]))
            for key in ("energy_loss", "force_loss")}


def describe_shards(terms: Sequence[Dict[str, float]], say, label: str
                    ) -> None:
    """One line a shard: the reference's terms, so that a composed loss
    that fails can be traced to its shard and its term."""
    for i, t in enumerate(terms):
        say(f"{label} shard {i} of {len(terms)} ({t['graphs']} "
            f"structures): reference energy MAE {t['energy_loss']:.6f}, "
            f"force MAE {t['force_loss']:.6f}")
