"""Profile one bench train step + compute its analytic roofline.

Two halves (r3 verdict, Next #2 — "name the actual bound"):

1. `--analytic` (runs anywhere): count the workload's matmul FLOPs and
   HBM-resident tensor traffic from the bench shape, print the
   compute-vs-bandwidth roofline and where the measured throughput sits.
2. On a live TPU: capture a `jax.profiler` trace of a few steps
   (`--trace-dir logs/profile_tpu`) for op-level attribution; the trace
   names the dominant op family (gather/dynamic-slice vs MXU convs vs
   elementwise) directly.

Usage:
    python tools/profile_step.py --analytic
    python tools/profile_step.py --trace-dir logs/profile_tpu  # on-chip
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def analytic(batch=32, nodes=80, deg=30, hidden=128, num_conv=3,
             gps_measured=4429.6, peak_flops=197e12 / 2,
             hbm_gbps=819.0):
    """Roofline for the OC20-like PNA EF workload (bench.py shapes)."""
    N = batch * nodes
    K = deg
    F = hidden
    # PNA dense-neighbor aggregation per conv layer (graphs/batch.py
    # neighbor format): gather [N,K,F], tower MLP on [x_i, x_j] (2F->F),
    # 4 aggregations, post MLP ((4+1)F -> F), plus node MLPs. Forward
    # matmul FLOPs (x2 for multiply-add):
    pre = N * K * (2 * F) * F * 2
    post = N * (5 * F) * F * 2
    node = N * F * F * 2 * 2
    fwd_layer = pre + post + node
    fwd = num_conv * fwd_layer
    # energy-force training: forward + grad-wrt-params backward (~2x fwd)
    # + force grad (second forward-mode-ish pass, ~2x fwd again)
    total_flops = fwd * 5
    # HBM traffic: the [N,K,F] gathered neighbor tensor is materialized
    # (gather output + pre-MLP input/output + backward counterparts);
    # count ~6 [N,K,F] tensors + ~10 [N,F] tensors per layer, f32
    bytes_nkf = N * K * F * 4
    bytes_nf = N * F * 4
    traffic = num_conv * (6 * bytes_nkf + 10 * bytes_nf) * 2  # fwd+bwd
    t_compute = total_flops / peak_flops
    t_hbm = traffic / (hbm_gbps * 1e9)
    steps_measured = gps_measured / batch
    t_measured = 1.0 / steps_measured
    out = {
        "shape": {"batch": batch, "nodes": nodes, "deg": deg,
                  "hidden": hidden, "num_conv": num_conv},
        "analytic_flops_per_step": total_flops,
        "analytic_hbm_bytes_per_step": traffic,
        "t_compute_roofline_us": round(t_compute * 1e6, 1),
        "t_hbm_roofline_us": round(t_hbm * 1e6, 1),
        "t_measured_us": round(t_measured * 1e6, 1),
        "bound": "hbm" if t_hbm > t_compute else "compute",
        "gap_vs_roofline": round(t_measured / max(t_hbm, t_compute), 1),
        "note": ("gap >> 1 means neither roofline explains the step "
                 "time — the residual is dispatch latency, unfused "
                 "gathers, or padding waste; the on-chip trace "
                 "attributes it"),
    }
    print(json.dumps(out, indent=1))
    return out


def trace(trace_dir: str, steps: int = 5):
    import jax
    import numpy as np
    import bench
    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "a device trace needs the TPU; JAX found "
                                   f"{jax.default_backend()!r}"}))
        return 1
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.graphs.batch import collate, with_neighbor_format
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.train_step import TrainState, make_train_step
    from tests.utils import make_config

    rng = np.random.RandomState(0)
    samples = bench.synth_samples(bench.BATCH_GRAPHS, rng)
    cfg = make_config("PNA", heads=("node",), hidden_dim=bench.HIDDEN,
                      num_conv_layers=bench.NUM_CONV, radius=6.0)
    cfg["NeuralNetwork"]["Training"]["compute_grad_energy"] = True
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    model = create_model(mcfg)
    n_node = bench.BATCH_GRAPHS * bench.NODES_PER_GRAPH + 8
    n_edge = bench.BATCH_GRAPHS * bench.NODES_PER_GRAPH * bench.DEG + 8
    batch = with_neighbor_format(collate(
        samples, n_node=n_node, n_edge=n_edge,
        n_graph=bench.BATCH_GRAPHS + 1))
    variables = init_params(model, batch)
    tx = select_optimizer(cfg["NeuralNetwork"]["Training"])
    state = TrainState.create(variables, tx)
    step = make_train_step(model, mcfg, tx, loss_name="mae",
                           compute_grad_energy=True, donate=False,
                           compute_dtype="float32")
    state, m = step(state, batch)          # compile
    float(np.asarray(m["loss"]).ravel()[-1])
    import jax.profiler
    jax.profiler.start_trace(trace_dir)
    for _ in range(steps):
        state, m = step(state, batch)
    float(np.asarray(m["loss"]).ravel()[-1])
    jax.profiler.stop_trace()
    print(json.dumps({"trace_dir": trace_dir, "steps": steps,
                      "backend": backend}))
    return 0


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--analytic", action="store_true")
    p.add_argument("--trace-dir", default=None)
    p.add_argument("--gps", type=float, default=4429.6,
                   help="measured graphs/s for the gap computation")
    args = p.parse_args()
    if args.analytic or not args.trace_dir:
        analytic(gps_measured=args.gps)
        return 0
    return trace(args.trace_dir)


if __name__ == "__main__":
    main()
