"""Device mesh + distributed runtime — the TPU-native comm backend.

Replaces the reference's distributed runtime
(reference: hydragnn/utils/distributed/distributed.py:86-188 — env-var
rendezvous, NCCL/Gloo process groups, DDP wrapping) with single-controller
JAX SPMD:

* `setup_ddp()` -> `init_distributed()` (jax.distributed.initialize; TPU
  metadata replaces the SLURM/LSF env parsing),
* process groups -> a `jax.sharding.Mesh` with named axes,
* DDP gradient allreduce -> pjit-inserted psum over the `data` axis (ICI),
* comm splits (multi-dataset groups, DDStore width) -> sub-axes of the mesh.

The default mesh is 1-D ("data",) over all devices. The GFM multi-dataset
mode (reference: examples/multidataset/train.py:188-328) uses a 2-D
("group", "data") mesh — see parallel/multidataset.py.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     timeout_s: Optional[float] = None) -> Tuple[int, int]:
    """Multi-host rendezvous (reference setup_ddp, distributed.py:119-188).

    On TPU pods jax.distributed.initialize discovers everything from the
    runtime metadata; env overrides mirror HYDRAGNN_MASTER_ADDR/PORT
    (reference: distributed.py:139-141). Returns (world_size, rank).

    ``timeout_s`` (default: HYDRAGNN_RENDEZVOUS_TIMEOUT_S, strict-parsed
    — docs/fault_tolerance.md) bounds the rendezvous: a peer rank that
    never arrives turns into an actionable RuntimeError naming this
    process, the expected world, and the coordinator, instead of wedging
    the job forever (the elastic supervisor relies on a bounded child
    startup so a half-spawned generation self-destructs).
    """
    # must not touch the XLA backend before jax.distributed.initialize
    # (jax.process_count() would initialise it), so probe the distributed
    # client state instead
    if not jax.distributed.is_initialized() and (
            coordinator or os.getenv("HYDRAGNN_MASTER_ADDR")):
        coord = coordinator or (
            os.environ["HYDRAGNN_MASTER_ADDR"] + ":" +
            os.environ.get("HYDRAGNN_MASTER_PORT", "12355"))
        nproc = num_processes or int(os.environ.get("SLURM_NPROCS", 1))
        pid = process_id or int(os.environ.get("SLURM_PROCID", 0))
        if timeout_s is None:
            from ..utils.envflags import resolve_rendezvous_timeout
            timeout_s = resolve_rendezvous_timeout()
        kwargs = {}
        if timeout_s:
            kwargs["initialization_timeout"] = max(int(timeout_s), 1)
        # NOTE: on some jaxlib paths the distributed client LOG(FATAL)s
        # the process on a coordination deadline before Python sees an
        # exception — the rank still dies within the bound (the
        # contract: never wedge an allocation on a missing peer), it
        # just skips the prettier message below
        try:
            jax.distributed.initialize(
                coordinator_address=coord, num_processes=nproc,
                process_id=pid, **kwargs)
        except Exception as exc:  # noqa: BLE001 — re-raise actionable
            msg = str(exc).lower()
            if timeout_s and ("deadline" in msg or "timed out" in msg):
                raise RuntimeError(
                    f"multi-process rendezvous timed out after "
                    f"{timeout_s:g}s: this is process {pid} of {nproc} "
                    f"(coordinator {coord}) — at least one rank in "
                    f"0..{nproc - 1} besides {pid} never reached the "
                    "coordinator (died before init, wrong address, or "
                    "still spawning). Restart the whole job — a partial "
                    "world cannot proceed (docs/fault_tolerance.md "
                    "'Elastic multi-process training')") from exc
            raise
    return jax.process_count(), jax.process_index()


def get_comm_size_and_rank() -> Tuple[int, int]:
    """reference: distributed.py:106-117."""
    return jax.process_count(), jax.process_index()


def make_mesh(axes: Sequence[Tuple[str, int]] = None,
              devices=None) -> Mesh:
    """Build a named device mesh. Default: all devices on one "data" axis."""
    devices = devices if devices is not None else jax.devices()
    if axes is None:
        axes = (("data", len(devices)),)
    names = tuple(n for n, _ in axes)
    sizes = tuple(s for _, s in axes)
    need = int(np.prod(sizes))
    if need > len(devices):
        raise ValueError(f"mesh {dict(axes)} needs {need} devices, "
                         f"have {len(devices)}")
    arr = np.asarray(devices[:need]).reshape(sizes)
    return Mesh(arr, names)


def resolve_num_shards(num_shards: Optional[int], batch_size: int,
                       use_spmd: Optional[bool] = None,
                       device_budget: Optional[int] = None) -> int:
    """Shared shard-count policy for run_training/run_prediction: default
    to all devices when more than one, fall back to single-program when the
    batch doesn't divide or the request exceeds the device count.
    `device_budget` caps the devices available to the data axis (a composed
    mesh reserves device_count/graph_shards for the graph axis).

    A fallback is never silent: an explicit request that cannot be met
    warns, and the implicit all-devices default that cannot be met says so
    in the startup log (devices seen, shards used, why) — otherwise a
    multi-chip host quietly trains on device 0 only."""
    ndev = device_budget if device_budget is not None else jax.device_count()
    explicit = num_shards is not None
    if num_shards is None:
        num_shards = ndev if (use_spmd or (use_spmd is None and ndev > 1)) \
            else 1
    num_shards = max(int(num_shards), 1)
    if num_shards > ndev or batch_size % num_shards != 0:
        reason = (f"exceeds device count {ndev}"
                  if num_shards > ndev else
                  f"does not divide batch_size {batch_size}")
        if explicit and num_shards > 1:
            import warnings
            warnings.warn(
                f"requested num_shards={num_shards} {reason}; "
                f"falling back to a single-device run", stacklevel=2)
        elif not explicit:
            import logging
            logging.getLogger("hydragnn_tpu").warning(
                "data parallelism off: %d devices seen, 1 shard used — "
                "the default num_shards=%d %s, so this run computes on "
                "device 0 only. Pass num_shards or pick a batch_size the "
                "device count divides.", ndev, num_shards, reason)
        return 1
    return num_shards


def data_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Sharding for batch arrays: leading dim split over the data axis."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(batch, mesh: Mesh, axis: str = "data",
                spec: Optional[P] = None):
    """Place a GraphBatch with every leading dim sharded over `axis`.

    All GraphBatch arrays lead with a padded N/E/G dim that is a multiple of
    the axis size by construction (the loader pads per-device shapes), so
    each device gets an equal contiguous shard — the DistributedSampler
    analogue (reference: preprocess/load_data.py:236-244) at array level.
    """
    sh = NamedSharding(mesh, spec if spec is not None else P(axis))
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, sh) if a is not None else None, batch)


def shard_stacked_batch(batch, mesh: Mesh, axis: str = "data"):
    """Place a steps-per-call stack of device-stacked batches ([S, D, ...]
    leaves): the scan axis S stays replicated, the device axis D shards
    over `axis` (see train.trainer steps_per_call grouping)."""
    return shard_batch(batch, mesh, axis, spec=P(None, axis))


def walltime_deadline(default: Optional[float] = None) -> Optional[float]:
    """Absolute stop deadline (epoch seconds) for the trainer's walltime
    guard (reference: check_remaining, distributed.py:331-356 — rank 0 shells
    out to `squeue -o %L` for the job's remaining time and broadcasts a stop
    flag). Sources, in order:

    * ``HYDRAGNN_WALLTIME_DEADLINE`` — absolute epoch seconds,
    * ``SLURM_JOB_END_TIME`` — absolute epoch seconds (set by SLURM),
    * ``squeue -h -j $SLURM_JOB_ID -o %L`` — remaining [d-]hh:mm:ss.

    Single-controller JAX runs one Python per host executing identical code,
    so every host derives the same deadline — no broadcast needed (the
    reference needs one because each rank polls at a different moment).
    """
    import time
    val = os.getenv("HYDRAGNN_WALLTIME_DEADLINE")
    if val:
        return float(val)
    val = os.getenv("SLURM_JOB_END_TIME")
    if val:
        return float(val)
    jobid = os.getenv("SLURM_JOB_ID")
    if jobid:
        import subprocess
        try:
            out = subprocess.run(
                ["squeue", "-h", "-j", jobid, "-o", "%L"],
                stdout=subprocess.PIPE, timeout=30).stdout.decode().strip()
            return time.time() + _timedelta_parse(out)
        except Exception:
            return default
    return default


def _timedelta_parse(timestr: str) -> float:
    """Parse SLURM's remaining-time format `[days-]hours:minutes:seconds`
    (reference: timedelta_parse used at distributed.py:344)."""
    days = 0.0
    if "-" in timestr:
        d, timestr = timestr.split("-", 1)
        days = float(d)
    parts = [float(p) for p in timestr.split(":")]
    while len(parts) < 3:
        parts.insert(0, 0.0)
    h, m, s = parts[-3:]
    return days * 86400 + h * 3600 + m * 60 + s


def param_sharding_zero(mesh: Mesh, params, axis: str = "data",
                        min_size: int = 2 ** 14):
    """ZeRO-style sharding spec for optimizer state pytrees: shard the
    leading dim of every large leaf over the data axis, replicate the rest
    (reference equivalents: ZeroRedundancyOptimizer utils/optimizer/
    optimizer.py:43-101 and DeepSpeed ZeRO run_training.py:136-149)."""
    def spec(leaf):
        if leaf.ndim >= 1 and leaf.size >= min_size and \
                leaf.shape[0] % mesh.shape[axis] == 0:
            return NamedSharding(mesh, P(axis))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map(spec, params)
