"""The comparisons behind `correct`, shared by the jobs.

Two readings of every compared output (PERF.md section 2 has the numbers
they were set from, `calibrate tolerance` retakes them):

* AT HIGHEST matmul precision. The same program code, traced once more
  under ``jax.default_matmul_precision("highest")``, against the plain
  reference, which multiplies the same way. The two differ by summation
  order only, so the tolerance is tight: any change to the mathematics —
  a dropped term, a wrong mask, a kernel that rounds, activations or sums
  kept in bfloat16 — fails it.
* AS RUN. On a TPU a float32 matmul at DEFAULT precision rounds its
  operands to bfloat16. Through 5-6 layers and a gradient with respect to
  positions that is far from the reference on seeded random weights
  (forces: 5-20% for PNAPlus, 40-60% for SchNet; an operand-rounding
  emulation of the reference on the CPU gives the same). So the measured
  program's own output is only held to loose bounds that catch garbage,
  not rounding, and its distance is printed in every run.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

from .. import system

HIGHEST_TOL = {"energy": 1e-4, "forces": 2e-2, "loss": 1e-4}
AS_RUN_TOL = {"energy": 0.2, "forces": 1.0, "loss": 0.05}


def unpad_ef(energy, forces, samples: Sequence):
    """Padded (E [G+1, 1], F [N_pad, 3]) of one collated batch -> the real
    rows, in sample order (collate concatenates in order)."""
    atoms = sum(s.num_nodes for s in samples)
    return (np.asarray(energy)[:len(samples), 0],
            np.asarray(forces)[:atoms])


def against_reference(label: str, energy, forces, ref_energy, ref_forces,
                      say, tol: Dict) -> Dict[str, bool]:
    e_err = system.relative_error(energy, ref_energy)
    f_err = system.relative_error(forces, ref_forces)
    say(f"{label}: relative error vs the plain reference: energy "
        f"{e_err:.3e} (tolerance {tol['energy']:.0e}), forces "
        f"{f_err:.3e} (tolerance {tol['forces']:.0e})")
    return {f"{label}_energy": e_err <= tol["energy"],
            f"{label}_forces": f_err <= tol["forces"]}


def sharded_losses(ref_energy, ref_forces, struct, shards: Sequence[Sequence]
                   ) -> Dict[str, float]:
    """Energy and force MAE as a data-parallel step composes them: each
    shard's own means, then the mean over the shards weighted by their real
    graphs (`parallel/spmd.make_spmd_eval_step`). `shards` lists, for each
    shard, the positions of its samples in `struct`'s order."""
    sizes = np.bincount(struct["node_graph"])
    starts = np.concatenate([[0], np.cumsum(sizes)])
    e_terms, f_terms, weights = [], [], []
    for members in shards:
        rows = np.concatenate([np.arange(starts[g], starts[g + 1])
                               for g in members])
        e_terms.append(np.mean(np.abs(ref_energy[members]
                                      - struct["energy"][members])))
        f_terms.append(np.mean(np.abs(ref_forces[rows]
                                      - struct["forces"][rows])))
        weights.append(len(members))
    w = np.asarray(weights, np.float64) / np.sum(weights)
    return {"energy_loss": float(np.dot(w, e_terms)),
            "force_loss": float(np.dot(w, f_terms))}


def close(label: str, got: float, want: float, say, tol: float) -> bool:
    err = abs(got - want) / max(abs(want), 1e-30)
    say(f"{label}: system {got:.6f}, reference {want:.6f}, relative "
        f"difference {err:.3e} (tolerance {tol:.0e})")
    return err <= tol
