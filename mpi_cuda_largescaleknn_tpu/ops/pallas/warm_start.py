"""The self-join's warm start as one Pallas kernel.

Each program scores one block of a bucket's query rows against that
bucket's own points and folds the exact f32 tile into fresh candidate rows
with the traversal kernel's extract-min fold
(``knn_bf.fold_tile_into_candidates``): no sort of the rows, no id gather,
and no [bucket, S, S] tile in HBM. The rows come out as a cold traversal of
that one bucket would leave them (ops/tiled.py ``warm_start_self`` wraps
it).

The fold runs one segment. A cold row adopts all k entries from this one
tile, and the partition keeps a query's neighbours in nearby lanes, so
more segments hardly cut the passes but make each one dearer: on a TPU
v5e, at 1M uniform points (496-lane tiles, ids decoded), the warm start
ran 7.88 passes a program in 108.6 ms with one segment and 7.88 in
265.0 ms with four at k=8; 98.45 in 1371.5 ms and 89.60 in 3105.5 ms at
k=100.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi_cuda_largescaleknn_tpu.ops.distance import accumulate_sq
from mpi_cuda_largescaleknn_tpu.ops.pallas.knn_bf import (
    fold_tile_into_candidates,
)


def row_block(rows: int) -> int:
    """Query rows per program: the largest divisor of a bucket's ``rows``
    that is a multiple of 8 and at most 256, else the bucket (at 1M
    points, G=2 buckets of 2 x 248 rows: one fine query bucket each)."""
    for rb in range(min(rows, 256) // 8 * 8, 0, -8):
        if rows % rb == 0:
            return rb
    return rows


def _kernel(r2_ref,                          # SMEM: [1, 1] f32 radius^2
            q_ref,                           # VMEM: [1, RB, D]
            p_ref,                           # VMEM: [1, D + 1, T_pad]
            out_d2_ref, out_pos_ref,         # VMEM: [RB, k]
            passes_ref,                      # SMEM: [1, 1, 1] i32
            *, lanes):
    q = q_ref[0]                             # [RB, D]
    p = p_ref[0]                             # [D + 1, T_pad]
    dim = q.shape[-1]
    rb, k = out_d2_ref.shape
    # exact elementwise, fixed left-to-right order with the opaque-1.0
    # contraction guard: the traversal kernel's bits (knn_tiled.py)
    one = q[0, 0] * 0.0 + 1.0
    d2 = None
    for i in range(dim):                     # static unroll over D
        d2 = accumulate_sq(d2, q[:, i:i + 1] - p[i:i + 1, :], one)
    # row D is 0 on real lanes and +inf on pad lanes (id < 0)
    d2 = d2 + p[dim:dim + 1, :]
    cd2 = jnp.full((rb, k), r2_ref[0, 0], jnp.float32)
    cidx = jnp.full((rb, k), -1, jnp.int32)
    # lane positions are flat over the unpadded buckets: bucket c's lane 0
    # is position c * lanes (pad lanes are never adopted)
    cd2, cidx, npass = fold_tile_into_candidates(
        d2, pl.program_id(0) * lanes, cd2, cidx, with_passes=True)
    out_d2_ref[:] = cd2
    # the fold stores -2 - position; keep the position, so an undecoded
    # row never reads as a later traversal fold's own entries
    out_pos_ref[:] = jnp.where(cidx <= -2, -2 - cidx, cidx)
    passes_ref[0, 0, 0] = npass


@functools.partial(jax.jit, static_argnames=("k", "lanes", "interpret"))
def warm_fold(r2, q_pts, p_t, *, k, lanes, interpret):
    num_b, rows, dim = q_pts.shape
    t_pad = p_t.shape[2]
    rb = row_block(rows)
    nrb = rows // rb
    vma = jax.typeof(q_pts).vma
    return pl.pallas_call(
        functools.partial(_kernel, lanes=lanes),
        grid=(num_b, nrb),
        in_specs=[
            pl.BlockSpec((1, 1), lambda c, r: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, rb, dim), lambda c, r: (c, r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, dim + 1, t_pad), lambda c, r: (c, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((rb, k), lambda c, r: (c * nrb + r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, k), lambda c, r: (c * nrb + r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, 1), lambda c, r: (c * nrb + r, 0, 0),
                         memory_space=pltpu.SMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((num_b * rows, k), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((num_b * rows, k), jnp.int32, vma=vma),
            jax.ShapeDtypeStruct((num_b * nrb, 1, 1), jnp.int32, vma=vma),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(r2, q_pts, p_t)
