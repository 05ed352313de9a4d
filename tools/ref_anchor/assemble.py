"""Merge the per-model anchor runs (logs/anchor_ref.jsonl +
logs/anchor_tpu.jsonl) into ANCHOR_r{N}.json with ours-vs-reference MAE
ratios — the cross-framework evaluation of BASELINE.md's "<=5% MAE
regression" clause (round-3 verdict, Next #6).

Usage: python tools/ref_anchor/assemble.py [--round 4]
"""
import argparse
import json
import os

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))

# Models whose formulation deliberately diverges from the reference's
# (advisor r4): the ratio for these cells mixes framework parity with an
# architecture change, and the artifact must say so.
FORMULATION_DIVERGENCE = {
    "EGNN": ("ours uses sinc-RBF edge embedding + cosine cutoff envelope "
             "+ SiLU (models/egnn.py); the reference EGCLStack uses raw "
             "r^2 edge features + ReLU — this cell compares frameworks "
             "AND formulations, not formulation-identical models"),
}

# Per-row budget disclosures (the protocol requires identical budgets
# ACROSS SIDES, not across rows)
BUDGET_NOTES = {
    "MACE": ("60-epoch budget on BOTH sides (the other rows use 150): "
             "the reference side under the shims measures ~250 s/epoch "
             "on this one-core box (~10.5 h at 150 epochs, infeasible "
             "in-round); the comparison stays budget-matched"),
}


def load_jsonl(path):
    out = {}
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                rec = json.loads(line)
                out[rec["model"]] = rec  # last run per model wins
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--round", type=int,
                   default=int(os.environ.get("GRAFT_ROUND", "4")))
    p.add_argument("--base", default=None,
                   help="prior ANCHOR_r{N}.json whose rows seed this one "
                        "(new jsonl rows overlay per model)")
    p.add_argument("--ref-log", default=os.path.join(REPO, "logs",
                                                     "anchor_ref.jsonl"))
    p.add_argument("--tpu-log", default=os.path.join(REPO, "logs",
                                                     "anchor_tpu.jsonl"))
    args = p.parse_args()
    ref = load_jsonl(args.ref_log)
    tpu = load_jsonl(args.tpu_log)
    models = sorted(set(ref) | set(tpu))
    rows, evaluated = {}, 0
    for m in models:
        r, t = ref.get(m), tpu.get(m)
        row = {}
        if t:
            row.update(energy_mae=t["energy_mae"], force_mae=t["force_mae"],
                       energy_mae_rel=t["energy_mae_rel"],
                       force_mae_rel=t["force_mae_rel"],
                       train_secs=t["train_secs"],
                       num_epoch=t.get("budget", {}).get("num_epoch"))
        if r:
            row.update(reference_energy_mae=r["energy_mae"],
                       reference_force_mae=r["force_mae"],
                       reference_energy_mae_rel=r["energy_mae_rel"],
                       reference_force_mae_rel=r["force_mae_rel"],
                       reference_train_secs=r["train_secs"])
        if r and t:
            row["energy_ratio_ours_over_ref"] = round(
                t["energy_mae"] / max(r["energy_mae"], 1e-12), 4)
            row["force_ratio_ours_over_ref"] = round(
                t["force_mae"] / max(r["force_mae"], 1e-12), 4)
            row["parity_le_1.05"] = bool(
                row["energy_ratio_ours_over_ref"] <= 1.05
                and row["force_ratio_ours_over_ref"] <= 1.05)
            evaluated += 1
        if m in FORMULATION_DIVERGENCE:
            row["formulation_divergence"] = FORMULATION_DIVERGENCE[m]
        if m in BUDGET_NOTES:
            row["budget_note"] = BUDGET_NOTES[m]
        rows[m] = row
    if args.base and os.path.exists(args.base):
        with open(args.base) as f:
            base = json.load(f)
        merged = dict(base.get("models", {}))
        for m, row in rows.items():
            # field-level overlay: a one-sided rerun (e.g. ref landed,
            # tpu still pending) must not wipe the base row's other
            # side; recompute the ratios from the combined fields
            comb = {**merged.get(m, {}), **{k: v for k, v in row.items()
                                            if v is not None}}
            if "energy_mae" in comb and "reference_energy_mae" in comb:
                comb["energy_ratio_ours_over_ref"] = round(
                    comb["energy_mae"]
                    / max(comb["reference_energy_mae"], 1e-12), 4)
                comb["force_ratio_ours_over_ref"] = round(
                    comb["force_mae"]
                    / max(comb["reference_force_mae"], 1e-12), 4)
                comb["parity_le_1.05"] = bool(
                    comb["energy_ratio_ours_over_ref"] <= 1.05
                    and comb["force_ratio_ours_over_ref"] <= 1.05)
            merged[m] = comb
        rows = merged
        evaluated = sum(1 for r in rows.values()
                        if "energy_ratio_ours_over_ref" in r)
    any_rec = next(iter((ref or tpu).values()), None)
    budget = dict(any_rec["budget"]) if any_rec else {}
    budget["num_epoch"] = "per-row (see each model's num_epoch)"
    out = {
        "metric": "lj_anchor_cross_framework_mae",
        "round": args.round,
        "protocol": ("identical workload (our LJ generator, 64-atom 4^3 "
                     "PBC cells), identical budget and split on both "
                     "sides per row; the reference runs UNMODIFIED on the "
                     "tools/ref_anchor/shims dependency surface "
                     "(validated by SHIM_FIDELITY_r05.json: the "
                     "reference's own CI battery passes under the shims)"),
        "budget": budget,
        "models": rows,
        "models_evaluated": evaluated,
        "parity_claim": "ours <= 1.05x reference MAE (BASELINE.md)",
    }
    path = os.path.join(REPO, f"ANCHOR_r{args.round:02d}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
