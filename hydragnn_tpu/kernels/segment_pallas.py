"""Pallas TPU kernel: segment-sum as tiled one-hot MXU matmuls.

The message-passing hot loop (reference: hydragnn/models/EGCLStack.py:225-245
scatter_add; torch_scatter C++/CUDA kernels) needs an [E, F] -> [N, F]
scatter-reduction. XLA lowers `jax.ops.segment_sum` to a scatter, which the
TPU executes as a serialized sorted update — the VPU/MXU sit idle. This
kernel instead expresses the reduction as dense matmuls on the MXU:

    out[n_block] = sum_e onehot(ids_tile, n_block)^T @ data_tile

with a 2-D grid (node blocks x edge tiles). The one-hot is built in-register
from a broadcasted iota, so HBM traffic is just data (once per node block)
and the accumulator; all the "scatter" work rides the 128x128 systolic array.

Backward of segment_sum is a gather (`grad_out[segment_ids]`), which XLA
handles well natively — so the custom VJP uses a plain take.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tile sizes: edges per grid step x nodes per output block.
# VMEM at fp32: onehot 512x512 (1 MB) + data 512xF + acc 512xF — comfortably
# under the ~16 MB/core budget for F <= 1024.
TILE_E = 512
TILE_N = 512


def _seg_kernel(ids_ref, data_ref, out_ref, acc_ref):
    n_blk = pl.program_id(0)
    e_idx = pl.program_id(1)
    n_last = pl.num_programs(1) - 1

    @pl.when(e_idx == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ids = ids_ref[0, :]                                   # [TILE_E] int32
    local = ids - n_blk * TILE_N
    cols = jax.lax.broadcasted_iota(jnp.int32, (TILE_E, TILE_N), 1)
    onehot = (local[:, None] == cols).astype(data_ref.dtype)
    # [TILE_N, TILE_E] @ [TILE_E, F] on the MXU. HIGHEST: at the default
    # precision the MXU rounds f32 operands to bf16 — the one-hot side is
    # exact either way, but the DATA would lose 16 mantissa bits and the
    # "sum" would no longer be segment_sum's (measured on the v5e: 5e-2
    # max abs error at default, 8e-6 at HIGHEST, PR 21)
    acc_ref[:] += jax.lax.dot_general(
        onehot, data_ref[:],
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(e_idx == n_last)
    def _():
        out_ref[:] = acc_ref[:].astype(out_ref.dtype)


def _pad_to(x, size, axis=0):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def _segment_sum_fwd_impl(data, segment_ids, num_segments: int,
                          interpret: bool = False):
    e, f = data.shape
    e_pad = pl.cdiv(e, TILE_E) * TILE_E
    n_pad = pl.cdiv(num_segments, TILE_N) * TILE_N
    # padded tail edges carry zero data; their (arbitrary) ids add nothing
    data_p = _pad_to(data, e_pad)
    ids_p = _pad_to(segment_ids.astype(jnp.int32), e_pad).reshape(1, e_pad)

    grid = (n_pad // TILE_N, e_pad // TILE_E)
    out = pl.pallas_call(
        _seg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, TILE_E), lambda n, e_: (0, e_),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((TILE_E, f), lambda n, e_: (e_, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((TILE_N, f), lambda n, e_: (n, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_pad, f), data.dtype),
        scratch_shapes=[pltpu.VMEM((TILE_N, f), jnp.float32)],
        interpret=interpret,
    )(ids_p, data_p)
    return out[:num_segments]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def segment_sum_pallas(data, segment_ids, num_segments: int,
                       interpret: bool = False):
    """Drop-in for `jax.ops.segment_sum(data, ids, num_segments)` on 2-D
    [E, F] data; MXU-based forward, gather-based backward."""
    return _segment_sum_fwd_impl(data, segment_ids, num_segments,
                                 interpret=interpret)


def _fwd(data, segment_ids, num_segments, interpret):
    out = _segment_sum_fwd_impl(data, segment_ids, num_segments,
                                interpret=interpret)
    return out, segment_ids


def _bwd(num_segments, interpret, segment_ids, g):
    return g[segment_ids], None


segment_sum_pallas.defvjp(_fwd, _bwd)
