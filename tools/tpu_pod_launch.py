"""Multi-host TPU-pod launcher — the reference's Slurm job script
equivalent (reference: job-frontier-ogb-deepspeed.sh:43-44 `srun -N8
-n64 ... train_gap.py --adios --use_deepspeed`) for jax.distributed
pods.

Two launch modes:

  gcloud (default): one `gcloud compute tpus tpu-vm ssh --worker=all`
      fan-out; every worker runs the same command and
      jax.distributed.initialize() discovers coordinator/world from the
      TPU runtime metadata — no explicit rendezvous flags needed.
  hostfile (--hosts h1,h2,...): plain ssh per host with explicit
      HYDRAGNN_MASTER_ADDR / HYDRAGNN_MASTER_PORT / process ids, the
      path parallel/mesh.init_distributed reads (the reference's
      MASTER_ADDR convention, distributed.py:139-141).

Data layout: with --graphstore-root each process gets
HYDRAGNN_GS_SHARD_DIR=<root>/shard_<process_id> — write per-host
GraphStore shards there (examples/dataset_utils.to_graphstore), so no
host reads another host's bytes over DCN at step time.

`--dry-run` prints the full command plan without executing anything —
run it from any shell to review or copy/paste.
"""
from __future__ import annotations

import argparse
import shlex
import subprocess
import sys

# steps-per-call default follows the measured single-chip adjudication
# (BENCH_SWEEP_TPU.json: spc=1 wins decisively on-chip — the scan's
# stacked batch breaks XLA fusion and costs more than the dispatch it
# amortizes; bench.py's per-backend default table). A pod MAY differ
# (DCN dispatch amortization) but that is unmeasured — prefer the
# measured number over a guess and tune per pod with BENCH_SWEEP=1.
DEFAULT_STEPS_PER_CALL = 1


def build_worker_command(args, process_id=None, num_hosts=None):
    """The command every worker runs."""
    env = {
        "HYDRAGNN_NUM_WORKERS": str(args.prefetch_workers),
        "HYDRAGNN_STEPS_PER_CALL": str(args.steps_per_call),
    }
    if args.compile_cache:
        # jax's own variable; unset, every worker uses
        # <checkout>/.jax_cache (utils/devices.enable_compile_cache)
        env["JAX_COMPILATION_CACHE_DIR"] = args.compile_cache
    if args.graphstore_root:
        if process_id is None:
            # gcloud --worker=all runs one identical command everywhere;
            # the worker resolves shard_<jax.process_index()> at runtime
            env["HYDRAGNN_GS_SHARD_ROOT"] = args.graphstore_root
        else:
            env["HYDRAGNN_GS_SHARD_DIR"] = \
                f"{args.graphstore_root}/shard_{process_id}"
    if process_id is not None:  # hostfile mode: explicit rendezvous
        env["HYDRAGNN_MASTER_ADDR"] = args.hosts[0]
        env["HYDRAGNN_MASTER_PORT"] = str(args.port)
        env["SLURM_NPROCS"] = str(num_hosts)
        env["SLURM_PROCID"] = str(process_id)
    for kv in args.env:
        k, _, v = kv.partition("=")
        env[k] = v
    exports = " ".join(f"{k}={shlex.quote(v)}" for k, v in env.items())
    script = f"python -u {args.script} {args.script_args}".strip()
    return f"cd {args.repo_dir} && {exports} {script}"


def build_plan(args):
    """List of (description, argv-or-shell-string) launch steps."""
    plan = []
    if args.hosts:
        for pid, host in enumerate(args.hosts):
            inner = build_worker_command(args, process_id=pid,
                                         num_hosts=len(args.hosts))
            if args.local_spawn:
                # rehearsal mode: same per-host command plan, executed by
                # local shells instead of ssh (CI boxes without sshd —
                # the multi-process rendezvous is still real)
                plan.append((f"host {host} (process {pid}, local spawn)",
                             ["bash", "-c", inner]))
            else:
                plan.append((f"host {host} (process {pid})",
                             ["ssh", host, inner]))
    else:
        inner = build_worker_command(args)
        cmd = ["gcloud", "compute", "tpus", "tpu-vm", "ssh", args.tpu,
               "--worker=all", f"--command={inner}"]
        if args.zone:
            cmd.insert(5, f"--zone={args.zone}")
        if args.project:
            cmd.insert(5, f"--project={args.project}")
        plan.append((f"all workers of TPU pod {args.tpu}", cmd))
    return plan


def main(argv=None):
    p = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--script",
                   default="examples/multidataset/train.py")
    p.add_argument("--script-args", default="--ddstore",
                   help="args passed to the training script")
    p.add_argument("--repo-dir", default="~/hydragnn_tpu")
    # gcloud mode
    p.add_argument("--tpu", default="hydragnn-pod",
                   help="TPU pod name (gcloud mode)")
    p.add_argument("--zone", default=None)
    p.add_argument("--project", default=None)
    # hostfile mode
    p.add_argument("--hosts", default=None,
                   help="comma-separated host list -> plain-ssh mode "
                        "with explicit jax.distributed rendezvous")
    p.add_argument("--port", type=int, default=12355)
    # performance / data-layout knobs
    p.add_argument("--steps-per-call", type=int,
                   default=DEFAULT_STEPS_PER_CALL)
    p.add_argument("--prefetch-workers", type=int, default=2)
    p.add_argument("--compile-cache", default=None,
                   help="persistent XLA cache dir for every worker "
                        "(exported as JAX_COMPILATION_CACHE_DIR; default: "
                        "the checkout's .jax_cache)")
    p.add_argument("--graphstore-root", default=None,
                   help="root dir of per-host GraphStore shards "
                        "(shard_<pid> per process)")
    p.add_argument("--env", action="append", default=[],
                   metavar="KEY=VAL", help="extra env for every worker")
    p.add_argument("--local-spawn", action="store_true",
                   help="hostfile mode: run each per-host command in a "
                        "local shell instead of ssh (multi-process "
                        "rehearsal on one box)")
    p.add_argument("--dry-run", action="store_true",
                   help="print the command plan, execute nothing")
    args = p.parse_args(argv)
    args.hosts = args.hosts.split(",") if args.hosts else None

    plan = build_plan(args)
    for desc, cmd in plan:
        pretty = cmd if isinstance(cmd, str) else \
            " ".join(shlex.quote(c) if " " in c else c for c in cmd)
        print(f"# {desc}\n{pretty}")
    if args.dry_run:
        print(f"# dry run: {len(plan)} launch step(s), nothing executed")
        return 0
    rcs = []
    procs = [subprocess.Popen(cmd) for _, cmd in plan]
    for proc in procs:
        rcs.append(proc.wait())
    return max(rcs) if rcs else 0


if __name__ == "__main__":
    sys.exit(main())
