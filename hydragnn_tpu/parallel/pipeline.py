"""Pipeline (layer) parallelism for deep GNN conv stacks — a 1F1B-capable
schedule over a ``pipe`` mesh axis (optionally composed with a ``data``
axis for pipeline x data parallelism).

The reference has no pipeline parallelism (SURVEY.md §2.6: "NOT present");
the technique comes from the retrieved GNNPipe work (PAPERS.md: pipelined
model parallelism for deep GNNs). It matters when the conv stack is deep
enough that one chip can't hold all layer parameters + activations, or to
scale layer compute across chips without replicating every layer everywhere.

Layout:

* the stack's `num_layers` homogeneous conv layers are split into
  `S = mesh.shape[axis]` contiguous stages; stage parameters are stacked on
  a leading axis sharded over ``pipe`` (each device holds only its stage's
  layers),
* a batch is split into M microbatches; activations flow stage->stage with
  `ppermute` (one ICI hop per tick): `M + S - 1` ticks, stage s works on
  microbatch (t - s),
* graph structure (senders/receivers/masks) for ALL microbatches is
  replicated to every stage — index arrays are tiny next to features; only
  the node-feature activation rides the ring,
* with a ``data_axis``, each data shard runs its own pipe ring on its own
  microbatches ([D, M, ...] input); the schedule below is unchanged
  because `ppermute` pairs are relative to the ``pipe`` axis only.

Schedule details (docs/pipeline.md):

* **double-buffered carry** — the tick body carries the PREVIOUS tick's
  stage output and issues its `ppermute` hop at the top of the next tick,
  adjacent to the microbatch injection select. The hop and the producing
  stage's next compute have no data dependence, which is what lets XLA's
  async collective-permute (collective-permute-start/done + the latency
  hiding scheduler) overlap the ICI transfer with compute on TPU. Tick
  count is unchanged: M + S - 1.
* **banked outputs** — finished microbatches accumulate in the LAST
  stage's local buffer and are returned on a stage-sharded leading axis;
  the caller slices stage S-1. The seed implementation instead `psum`ed
  the full [M, ...] output tensor across the ring (every stage shipping
  a same-sized zero tensor through ICI) — one hop of pure waste.
* **activation rematerialization** (`remat=True`) — `stage_apply` is
  wrapped in `jax.checkpoint`, so the backward saves only each tick's
  stage INPUT (one [N, F] activation) instead of every intermediate
  inside the per-stage layer scan, and recomputes the stage forward
  during the backward pass. Numerically a no-op: the recomputed forward
  is the same op sequence, pinned BITWISE in tests/test_pipeline.py.
  `remat_policy` selects a `jax.checkpoint` save policy ("full" saves
  nothing, "dots" saves matmul outputs and recomputes the rest).

`pipeline_apply` is jit-able and differentiable (the schedule is a
`lax.scan`), so the same function serves training. Differentiating through
the whole M-microbatch scan at once is the GPipe regime (all forwards,
then all backwards — residuals for O(M) microbatches live at the backward
start); the 1F1B regime bounds that to O(S) by windowing the loss/grad
computation over S microbatches at a time (pipeline_trainer.py).
Equivalence to the sequential stack is tested in tests/test_pipeline.py.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

# pipeline schedules (docs/pipeline.md): the forward tick pattern is
# identical; they differ in how the train step's backward is organized
# (pipeline_trainer.make_pipeline_train_step)
PIPELINE_SCHEDULES = ("gpipe", "1f1b")

# jax.checkpoint save policies for `remat_policy` (None = the jax default
# of saving nothing, i.e. full rematerialization)
_REMAT_POLICIES = ("full", "dots")


def check_stage_divisibility(num_layers: int, num_stages: int) -> int:
    """Layers-per-stage, or a config-time `ValueError` with an actionable
    message. A bare `assert` here vanishes under `python -O` and the
    failure would resurface later as an opaque reshape error — the ONE
    divisibility check shared by stack_stage_params, make_pipeline_apply
    and pipeline_trainer.validate_pipeline_config so the message cannot
    drift."""
    num_stages = int(num_stages)
    if num_stages < 1:
        raise ValueError(
            f"pipeline_stages must be >= 1 (got {num_stages})")
    if num_layers % num_stages:
        raise ValueError(
            f"num_conv_layers={num_layers} does not split into "
            f"{num_stages} pipeline stages: set Training.pipeline_stages "
            f"to a divisor of the conv-layer count (remainder "
            f"{num_layers % num_stages})")
    return num_layers // num_stages


def resolve_remat_policy(name: Optional[str]):
    """Map a remat-policy name to a jax.checkpoint policy. `None`/"full"
    -> save nothing (full recompute); "dots" -> save matmul outputs
    (jax.checkpoint_policies.checkpoint_dots: cheaper backward, more
    saved bytes). Unknown names raise — the knob is already
    strict-parsed at the env layer (utils/envflags.resolve_pipeline), so
    reaching here with garbage is a programming error worth surfacing."""
    if name is None or name == "full":
        return None
    if name == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    raise ValueError(
        f"unknown pipeline remat policy {name!r} (use one of "
        f"{_REMAT_POLICIES})")


def stack_stage_params(per_layer_params, num_stages: int):
    """[L] pytrees -> pytree with leading [S, L/S] axes (stage-major), ready
    to shard over ``pipe``. L must divide evenly into S stages (raises
    `ValueError` otherwise — never a stripped-out assert)."""
    L = len(per_layer_params)
    per_stage = check_stage_divisibility(L, num_stages)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                     *per_layer_params)
    return jax.tree_util.tree_map(
        lambda a: a.reshape((num_stages, per_stage) + a.shape[1:]), stacked)


def forward_ticks(num_stages: int, microbatches: int) -> int:
    """Ticks one pipelined forward pass takes: M + S - 1."""
    return microbatches + num_stages - 1


def bubble_fraction(num_stages: int, microbatches: int) -> float:
    """Closed-form bubble fraction of one pipelined pass (forward OR
    backward): (S - 1) / (M + S - 1) — the fraction of stage-ticks spent
    on pipeline fill/drain rather than useful microbatch work. This is
    the figure BENCH_MFU's measured bubble is adjudicated against."""
    return (num_stages - 1) / forward_ticks(num_stages, microbatches)


def train_step_ticks(num_stages: int, microbatches: int,
                     schedule: str = "gpipe") -> int:
    """Closed-form stage-tick count of one train step (forward+backward).

    * gpipe: one M-microbatch forward + its mirror backward,
      2 * (M + S - 1) ticks, with O(M) microbatch activations live at
      the fwd->bwd turnaround.
    * 1f1b: ceil(M / S) windows of W = min(S, M) microbatches, each a
      forward + backward pass, 2 * (W + S - 1) ticks per window, with
      O(S) activations live. The window serialization costs
      (ceil(M/S) - 1) extra fill/drain pairs over the ideal interleaved
      1F1B (docs/pipeline.md has the accounting).
    """
    S, M = int(num_stages), int(microbatches)
    if schedule == "gpipe":
        return 2 * (M + S - 1)
    if schedule == "1f1b":
        W = min(S, M)
        windows = -(-M // W)
        return windows * 2 * (W + S - 1)
    raise ValueError(f"unknown pipeline schedule {schedule!r} "
                     f"(use one of {PIPELINE_SCHEDULES})")


def train_bubble_fraction(num_stages: int, microbatches: int,
                          schedule: str = "gpipe") -> float:
    """Closed-form bubble fraction of one full train step under
    `schedule`: 1 - useful_ticks / total_ticks with 2M useful ticks
    (every microbatch crosses every stage once forward, once backward)."""
    total = train_step_ticks(num_stages, microbatches, schedule)
    return 1.0 - (2 * int(microbatches)) / total


def make_pipeline_apply(mesh: Mesh, layer_fn: Callable, num_layers: int,
                        axis: str = "pipe",
                        data_axis: Optional[str] = None,
                        remat: bool = False,
                        remat_policy: Optional[str] = None):
    """Build `apply(stage_params, x_micro, structure) -> y_micro`.

    layer_fn(layer_params, x, structure) -> x' applies ONE conv layer;
    activations must keep one shape across layers (hidden_dim stacks).

    * stage_params: pytree with leading [S, L/S] axes (stack_stage_params),
      sharded over ``pipe``,
    * x_micro: [M, ...] microbatched node features (replicated), or
      [D, M, ...] with ``data_axis`` (leading dim sharded over it),
    * structure: pytree of [M, ...] (or [D, M, ...]) graph-structure
      arrays, sharded like x_micro.

    Returns [M, ...] (or [D, M, ...]) outputs after all `num_layers`
    layers, banked on the last stage (no full-tensor psum broadcast).
    With ``remat`` each tick's stage compute is wrapped in
    `jax.checkpoint` (bitwise-identical values/grads; backward saves
    only the stage input per tick).
    """
    S = mesh.shape[axis]
    check_stage_divisibility(num_layers, S)

    def stage_apply(params_1stage, x, structure_t):
        def body(h, layer_params):
            return layer_fn(layer_params, h, structure_t), None
        out, _ = lax.scan(body, x, params_1stage)
        return out

    if remat:
        stage_apply = jax.checkpoint(
            stage_apply, policy=resolve_remat_policy(remat_policy))

    def pipelined(stage_params, x_micro, structure):
        # inside shard_map: stage_params leads with the local [1, L/S, ...]
        my_params = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        if data_axis is not None:
            # local [1, M, ...] data slice — each data shard runs its own
            # ring on its own microbatches
            x_micro = x_micro[0]
            structure = jax.tree_util.tree_map(lambda a: a[0], structure)
        M = x_micro.shape[0]
        s_idx = lax.axis_index(axis)
        right = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            h_prev, outputs = carry
            # double-buffered carry: the hop for the activation produced
            # at tick t-1 is issued HERE, at the top of tick t, with no
            # data dependence on this tick's stage compute below — the
            # structure XLA's async collective-permute needs to overlap
            # the ICI transfer with compute (schedule unchanged: stage s
            # still consumes stage s-1's tick t-1 output at tick t)
            inflight = lax.ppermute(h_prev, axis, right)
            # stage 0 injects microbatch t (when valid), others take the
            # hopped activation from the previous stage
            mb = jnp.clip(t, 0, M - 1)
            h = jnp.where(s_idx == 0, x_micro[mb], inflight)
            # microbatch index this stage works on at tick t
            my_mb = jnp.clip(t - s_idx, 0, M - 1)
            structure_t = jax.tree_util.tree_map(
                lambda a: a[my_mb], structure)
            h_out = stage_apply(my_params, h, structure_t)
            valid = jnp.logical_and(t - s_idx >= 0, t - s_idx <= M - 1)
            # last stage banks finished microbatches in ITS local buffer
            is_last = s_idx == S - 1
            outputs = outputs.at[my_mb].set(
                jnp.where(jnp.logical_and(valid, is_last), h_out,
                          outputs[my_mb]))
            return (h_out, outputs), None

        h0 = jnp.zeros_like(x_micro[0])
        outputs0 = jnp.zeros_like(x_micro)
        (_, outputs), _ = lax.scan(tick, (h0, outputs0),
                                   jnp.arange(M + S - 1))
        # banked outputs: return each stage's buffer on a stage-sharded
        # leading axis; only stage S-1's slice is meaningful and the
        # caller takes it — replacing the seed's full-tensor psum
        # broadcast (every stage all-reducing an [M, ...] tensor of
        # zeros through ICI)
        out = outputs[None]
        if data_axis is not None:
            out = out[:, None]
        return out

    if data_axis is None:
        in_specs = (P(axis), P(), P())
        out_specs = P(axis)
    else:
        in_specs = (P(axis), P(data_axis), P(data_axis))
        out_specs = P(axis, data_axis)
    mapped = shard_map(pipelined, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)

    def apply(stage_params, x_micro, structure):
        return mapped(stage_params, x_micro, structure)[S - 1]

    return apply
