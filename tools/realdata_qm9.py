"""Milestone-1 real-data evidence: QM9 ingest + train, or the attempt log.

On a host with egress this downloads the real GDB-9 archive and trains on
it. This container has ZERO egress (DNS resolution itself fails), so the
run does the next-best provable thing (round-2 verdict, Next #4):

  1. attempt the real downloads and record each exact failure;
  2. build a format-faithful gdb9.sdf / gdb9.sdf.csv pair — real V2000
     molfile blocks and the real PyG property-CSV schema — so the ingest
     exercises the REAL-data code path end to end:
     examples/qm9/download_dataset.py --from-file (resolve/extract) ->
     qm9_data._load_real_qm9 (SDF parser + pandas CSV, NOT the synthetic
     generator) -> GraphStore conversion -> run_training(GIN);
  3. write REALDATA_r{N}.json with the attempt log + run metrics.

Swap-in proof: point --datadir at a directory holding the real archive
and the identical pipeline trains on actual QM9.
"""
from __future__ import annotations

import datetime
import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request
import zipfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ROUND = int(os.environ.get("GRAFT_ROUND", "3"))
OUT = os.path.join(REPO, f"REALDATA_r{ROUND:02d}.json")
WORK = os.path.join(REPO, "examples", "qm9", "dataset", "qm9")

URLS = [
    # PyG QM9 raw_url (figshare mirror of GDB-9); reference delegates to
    # torch_geometric.datasets.QM9 (reference: examples/qm9/qm9.py:29-45)
    "https://deepchemdata.s3-us-west-1.amazonaws.com/datasets/"
    "molnet_publish/qm9.zip",
    "https://figshare.com/ndownloader/files/3195389",
]

N_MOLECULES = 2000


def now() -> str:
    return datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds")


def attempt_downloads() -> list:
    attempts = []
    for url in URLS:
        rec = {"ts": now(), "url": url}
        try:
            with urllib.request.urlopen(url, timeout=60) as r:
                rec["status"] = getattr(r, "status", "ok")
                rec["ok"] = True
        except (urllib.error.URLError, OSError, TimeoutError) as e:
            rec["ok"] = False
            rec["error"] = repr(e)
        attempts.append(rec)
    return attempts


def write_v2000_sdf(mols, sdf_path: str, csv_path: str) -> None:
    """gdb9.sdf + gdb9.sdf.csv in the exact layout the real files use:
    V2000 counts line, %10.4f coordinate columns, symbol at col 31, and
    the PyG property CSV header with g298 at its real position."""
    from hydragnn_tpu.utils.elements import SYMBOLS
    header = ("mol_id,A,B,C,mu,alpha,homo,lumo,gap,r2,zpve,u0,u298,"
              "h298,g298,cv")
    with open(sdf_path, "w") as sdf, open(csv_path, "w") as csv:
        csv.write(header + "\n")
        for i, (zs, pos, g) in enumerate(mols):
            n = len(zs)
            sdf.write(f"gdb_{i + 1}\n     local  3D\n\n")
            sdf.write(f"{n:3d}{0:3d}  0  0  0  0  0  0  0  0999 V2000\n")
            for z, (x, y, w) in zip(zs, pos):
                sym = SYMBOLS[int(z)]
                sdf.write(f"{x:10.4f}{y:10.4f}{w:10.4f} {sym:<3s}"
                          " 0  0  0  0  0  0  0  0  0  0  0  0\n")
            sdf.write("M  END\n$$$$\n")
            zero = ",".join("0"
                            for _ in range(11))
            csv.write(f"gdb_{i + 1},{zero},0,0,{g},0\n")


def main() -> None:
    import jax
    report = {"metric": "realdata_qm9_ingest_train", "round": ROUND,
              "backend": jax.default_backend(),
              "attempts": attempt_downloads()}
    egress = any(a.get("ok") for a in report["attempts"])
    report["egress"] = "available" if egress else "blocked"

    raw = os.path.join(WORK, "raw")
    os.makedirs(raw, exist_ok=True)
    if not egress:
        # format-faithful archive so --from-file drives the real-data path
        from examples.qm9.qm9_data import _synthetic_qm9
        mols = _synthetic_qm9(N_MOLECULES, seed=7)
        sdf_tmp = os.path.join(WORK, "gdb9.sdf")
        csv_tmp = os.path.join(WORK, "gdb9.sdf.csv")
        write_v2000_sdf(mols, sdf_tmp, csv_tmp)
        archive = os.path.join(WORK, "qm9_local.zip")
        with zipfile.ZipFile(archive, "w") as z:
            z.write(sdf_tmp, "gdb9.sdf")
            z.write(csv_tmp, "gdb9.sdf.csv")
        os.remove(sdf_tmp)
        os.remove(csv_tmp)
        report["archive"] = {"path": os.path.relpath(archive, REPO),
                             "molecules": N_MOLECULES,
                             "format": "V2000 SDF + PyG property CSV"}
        from_file = ["--from-file", archive]
    else:
        from_file = []

    # ingest via the example's own CLI (resolve -> extract -> parse ->
    # GraphStore); identical invocation a real-data user would run
    t0 = time.time()
    cmd = [sys.executable, "examples/qm9/download_dataset.py",
           "--datadir", raw, "--to-graphstore",
           "--limit", str(N_MOLECULES)] + from_file
    r = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True,
                       timeout=3600)
    report["ingest"] = {"cmd": " ".join(cmd[1:]), "rc": r.returncode,
                        "stdout": r.stdout.strip()[-500:],
                        "stderr": r.stderr.strip()[-500:] or None,
                        "seconds": round(time.time() - t0, 1)}
    if r.returncode != 0:
        _write(report)
        raise SystemExit("ingest failed")

    # train on the ingested data through the REAL-file parser
    from examples.qm9.qm9_data import _load_real_qm9, load_qm9
    assert _load_real_qm9(WORK, 10) is not None, \
        "real-file path not reachable after ingest"
    samples = load_qm9(WORK, num_samples=N_MOLECULES)
    report["parsed_samples"] = len(samples)

    from hydragnn_tpu.run_training import run_training
    from tests.utils import make_config
    cfg = make_config("GIN", heads=("graph",))
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 30
    cfg["NeuralNetwork"]["Training"]["batch_size"] = 32
    n = len(samples)
    tr, va, te = (samples[: int(0.8 * n)],
                  samples[int(0.8 * n): int(0.9 * n)],
                  samples[int(0.9 * n):])
    t0 = time.time()
    state, history, model, completed = run_training(
        cfg, datasets=(tr, va, te))
    walltime = time.time() - t0

    # test MAE in label units (free energy / atom)
    import numpy as np
    from hydragnn_tpu.run_prediction import run_prediction
    trues, preds = run_prediction(completed, datasets=(tr, va, te),
                                  state=state, model=model)
    mae = float(np.mean(np.abs(np.asarray(preds[0]).ravel()
                               - np.asarray(trues[0]).ravel())))
    label_std = float(np.std([s.y_graph[0] for s in te]))
    report["train"] = {
        "model": "GIN", "epochs": 30, "samples": n,
        "walltime_s": round(walltime, 1),
        "final_train_loss": round(float(history["train_loss"][-1]), 6),
        "final_val_loss": round(float(history["val_loss"][-1]), 6),
        "test_mae": round(mae, 6), "test_label_std": round(label_std, 6),
        "test_mae_over_std": round(mae / max(label_std, 1e-9), 4),
    }
    _write(report)
    print(json.dumps(report["train"]))


def _write(report: dict) -> None:
    with open(OUT, "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
