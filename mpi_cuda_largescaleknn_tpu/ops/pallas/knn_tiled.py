"""Bucketed nearest-first traversal as ONE Pallas kernel (engine ``pallas``
inside the tiled data path).

This is the TPU-native ``cukd::stackFree::knn`` (the reference's innermost hot
loop, unorderedDataVariant.cu:86): where the GPU walks one implicit-tree node
per scalar thread, pruning subtrees beyond the query's current k-th candidate,
this kernel walks one *point bucket* per step for a whole query bucket,
pruning buckets beyond the bucket's worst k-th candidate — the identical
nearest-first, radius-pruned search at tile granularity (see ops/tiled.py for
the algorithmic argument; this kernel is its fused form).

vs. the XLA twin (``ops.tiled.knn_update_tiled``), which lock-steps ALL query
buckets through one global visit counter and materializes every [S, T]
distance tile + a width-2k sort per visit, here:

- each query bucket advances its own ``lax.while_loop`` and exits as soon as
  *its* next-nearest unvisited bucket is beyond *its* radius (the GPU's
  per-thread early exit, recovered);
- the candidate rows live in VMEM for the bucket's whole traversal — HBM sees
  them once;
- point buckets are fetched from HBM with double-buffered async DMA keyed by
  the precomputed visit order, so the next bucket streams in while the
  current one is scored (the comm/compute overlap the reference forgoes,
  unorderedDataVariant.cu:204 — here at the memory level);
- the visit order and box distances are scalar-prefetched to SMEM, steering
  the DMAs without touching the vector core.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax import lax

from mpi_cuda_largescaleknn_tpu.core.types import PAD_SENTINEL, CandidateState
from mpi_cuda_largescaleknn_tpu.ops.distance import accumulate_sq, split_bf16
from mpi_cuda_largescaleknn_tpu.ops.pallas.knn_bf import (
    default_fold_segments,
    fold_tile_into_candidates,
)
from mpi_cuda_largescaleknn_tpu.ops.partition import (
    BucketedPoints,
    nearest_first_order,
)


def _kernel(order_ref, boxd2_ref,            # SMEM: [1, 1, Bp] i32 / f32
            sskip_ref,                       # SMEM: [1, 1, 1] i32 skip-self
            q_ref, qid_ref,                  # VMEM: [1, S, 3] / [1, S, 1]
            in_d2_ref, in_idx_ref,           # VMEM: [S, k]
            p_hbm,                           # ANY (HBM): [Bp, 4, T]
            out_d2_ref, out_idx_ref,         # VMEM: [S, k]
            vis_ref,                         # SMEM: [1,1,2] i32 [visits,
                                             #        fold passes]
            p_buf, sem_p,                    # scratch: [2,rows,V*T], (2,V)
            *, visit_batch, self_group,
            fold_segments, score_mxu=False):
    num_pb = p_hbm.shape[0]
    t_p = p_hbm.shape[2]
    v_b = visit_batch
    num_chunks = (num_pb + v_b - 1) // v_b
    kk = in_d2_ref.shape[-1]
    q = q_ref[0]                             # [S, D]
    dim = q.shape[-1]
    # [S, 1] column layout so the bool mask never needs a minor-dim
    # insertion (Mosaic supports those only for 32-bit types)
    qvalid = qid_ref[0] >= 0                 # [S, 1]

    # Visits are processed V at a time: the chunk's buckets are DMAed into
    # adjacent lane windows of one [4, V*T] buffer, the distance broadcast
    # covers all of them in one [S, V*T] tile, and ONE fold merges the whole
    # chunk — amortizing the while-loop step, the DMA waits, and the fold's
    # extract-min passes over V buckets instead of paying them per bucket
    # (the per-visit form measured 85M pair-evals/s on a v5e: pure overhead).
    def chunk_copies(slot, c):
        # one descriptor per bucket; start and wait must describe the SAME
        # copies, so both go through this single generator. Point ids do
        # NOT ride along: the fold records lane positions and the wrapper
        # maps them to ids through the visit order after the kernel
        for v in range(v_b):                 # static unroll
            s_idx = jnp.minimum(c * v_b + v, num_pb - 1)
            visit = order_ref[0, 0, s_idx]
            yield pltpu.make_async_copy(
                p_hbm.at[visit], p_buf.at[slot, :, pl.ds(v * t_p, t_p)],
                sem_p.at[slot, v])

    def start_chunk(slot, c):
        for cp in chunk_copies(slot, c):
            cp.start()

    def wait_chunk(slot, c):
        for cp in chunk_copies(slot, c):
            cp.wait()

    def worst2(cd2):
        # static slice, NOT cd2[:, -1]: integer indexing lowers to
        # dynamic_slice, which Mosaic's TPU lowering rejects
        cd2_kth = lax.slice_in_dim(cd2, kk - 1, kk, axis=1)   # [S, 1]
        return jnp.max(jnp.where(qvalid, cd2_kth, -jnp.inf))

    start_chunk(0, 0)
    lane = lax.broadcasted_iota(jnp.int32, (1, v_b * t_p), 1)
    # read once at kernel scope: program_id inside the while body does not
    # lower under the CPU interpreter's HLO path. The own resident bucket
    # is b // self_group (coarsened point side, ops/partition.py)
    b_own = pl.program_id(0) // self_group
    sskip = sskip_ref[0, 0, 0] != 0

    def cond(carry):
        c, cd2, _cidx, _nv, _np = carry
        # nearest-first order is ascending in box distance, so if even the
        # chunk's FIRST bucket is beyond every query's radius, all later
        # buckets are too. & does not short-circuit in traced code: clamp
        # the index so the evaluation at c == num_chunks stays in bounds.
        first = jnp.minimum(c * v_b, num_pb - 1)
        return (c < num_chunks) & (boxd2_ref[0, 0, first] < worst2(cd2))

    def body(carry):
        c, cd2, cidx, nvis, npass = carry
        slot = lax.rem(c, 2)

        @pl.when(c + 1 < num_chunks)
        def _():
            start_chunk(lax.rem(c + 1, 2), c + 1)

        wait_chunk(slot, c)
        p = p_buf[slot]                       # [rows, V*T]; row D is ||p||^2
        if score_mxu:
            # matmul-form score (TPU-KNN): qn2 + pn2 - 2 q.p — the cross
            # term rides the MXU as the bf16x3 split (hi.hi + hi.lo +
            # lo.hi, three dot_generals with f32 accumulation, ~16
            # mantissa bits — one-pass bf16 measurably drops true top-k
            # members, ops/distance.py mxu_scores); both norms ride exact
            # f32 (||p||^2 was stowed in row D of the resident layout by
            # the wrapper). Approximate scores only SELECT candidates
            # here — the wrapper rescores every adopted entry with the
            # exact elementwise form after the kernel, against WIDENED
            # candidate rows (kk = rescore width)
            qn2 = None
            for i in range(dim):              # static unroll over D
                qi = q[:, i:i + 1]
                qn2 = qi * qi if qn2 is None else qn2 + qi * qi
            pc = p[0:dim, :]
            qh, ql = split_bf16(q)
            ph, plo = split_bf16(pc)
            dn = (((1,), (0,)), ((), ()))
            cross = (lax.dot_general(qh, ph, dn,
                                     preferred_element_type=jnp.float32)
                     + lax.dot_general(qh, plo, dn,
                                       preferred_element_type=jnp.float32)
                     + lax.dot_general(ql, ph, dn,
                                       preferred_element_type=jnp.float32))
            d2 = qn2 + p[dim:dim + 1, :] - 2.0 * cross     # [S, V*T]
        else:
            # exact elementwise, fixed left-to-right order, every square
            # carried through the opaque-1.0 contraction guard so the
            # kernel's bits match the XLA scorer's in every context
            # (ops/distance.py accumulate_sq; `one` derives from runtime
            # query data because Mosaic has no optimization_barrier)
            one = q[0, 0] * 0.0 + 1.0
            d2 = None
            for i in range(dim):
                di = q[:, i:i + 1] - p[i:i + 1, :]
                d2 = accumulate_sq(d2, di, one)
        # per-VISIT pruning inside the chunk (the per-node prune of
        # cukd::stackFree::knn, unorderedDataVariant.cu:86, recovered at
        # bucket granularity): a bucket whose box distance is at or beyond
        # the chunk-entry worst radius cannot be adopted by ANY query row
        # (point dist >= box dist >= every row's k-th), so its lanes go to
        # +inf. The distance broadcast still covers them — what this buys
        # is fewer fold extract-min passes (masked lanes never improve a
        # row) and a visits count at true per-bucket granularity. The same
        # mask drops the query bucket's OWN bucket when the heap was
        # pre-filled by warm_start_self (sskip nonzero): re-folding it
        # would adopt every self point twice.
        worst_c = worst2(cd2)
        s_idxs = [jnp.minimum(c * v_b + v, num_pb - 1) for v in range(v_b)]
        keep_v = [(boxd2_ref[0, 0, si] < worst_c)
                  & ~((order_ref[0, 0, si] == b_own) & sskip)
                  for si in s_idxs]           # static unroll, SMEM scalars
        # the last chunk may be padded with duplicates of bucket num_pb-1:
        # folding a point twice would corrupt the candidate list, so those
        # lanes are masked unconditionally (strict-< never adopts +inf).
        # The per-bucket mask rides as an f32 penalty row (+inf on dropped
        # buckets) rather than a bool vector: f32 full/concat/add are the
        # op classes this kernel already Mosaic-compiled in round 4;
        # broadcast bool vectors are not
        n_valid = (jnp.minimum(num_pb - c * v_b, v_b)) * t_p
        penalty = jnp.concatenate(
            [jnp.full((1, t_p), jnp.where(kv, 0.0, jnp.inf), jnp.float32)
             for kv in keep_v], axis=1)
        d2 = jnp.where(lane < n_valid, d2 + penalty, jnp.inf)
        # lane positions are global over the visit schedule: chunk c's lane
        # 0 sits at visit slot c*V, so pos // T = visit slot, pos % T = lane
        cd2, cidx, dp = fold_tile_into_candidates(d2, c * (v_b * t_p),
                                                  cd2, cidx,
                                                  with_passes=True,
                                                  segments=fold_segments)
        nvis = nvis + sum((kv & (c * v_b + v < num_pb)).astype(jnp.int32)
                          for v, kv in enumerate(keep_v))
        return c + 1, cd2, cidx, nvis, npass + dp

    c_exit, cd2, cidx, nvis, npass = lax.while_loop(
        cond, body, (jnp.int32(0), in_d2_ref[:], in_idx_ref[:],
                     jnp.int32(0), jnp.int32(0)))

    # a prefetch for chunk c_exit is in flight whenever the loop stopped
    # short of the end (started initially for c=0 or by the body for c+1);
    # drain it so no DMA outlives the kernel
    @pl.when(c_exit < num_chunks)
    def _():
        wait_chunk(lax.rem(c_exit, 2), c_exit)

    out_d2_ref[:] = cd2
    out_idx_ref[:] = cidx
    # buckets this query bucket actually scored (per-visit precision:
    # chunk-tail buckets beyond the entry radius and pad duplicates are
    # masked before the fold and excluded here) + tile-scan passes its
    # folds ran (each pass sweeps one whole [S, V*T] chunk and adopts up
    # to fold_segments candidates — the k-scaling cost center, see
    # fold_tile_into_candidates)
    vis_ref[0, 0, 0] = nvis
    vis_ref[0, 0, 1] = npass


def _vmem_limit(s_q: int, t_p: int, visit_batch: int, k: int) -> int:
    """Scoped-VMEM ceiling for the kernel's actual footprint.

    Dominant terms: the [S, V*T] f32 distance tile (plus its jnp.where
    twins — budget 3x), the double-buffered [2, 4, V*T] f32 chunk scratch,
    and the [S, k] x4 candidate rows. Everything else (query block, SMEM
    schedules) is noise. Keep the 16MB default whenever it suffices;
    otherwise pad the computed need by 2x for Mosaic's
    spills/temporaries, capped at 100MB (v5e physical VMEM is 128MiB).
    """
    lanes = visit_batch * t_p
    need = (3 * s_q * lanes * 4        # distance tile + masked copies
            + 2 * 4 * lanes * 4        # double-buffered chunk scratch
            + 4 * s_q * k * 4)         # candidate rows in/out
    default = 16 * 1024 * 1024
    if need <= default // 2:           # 2x headroom inside the default
        return default
    return min(max(2 * need, default), 100 * 1024 * 1024)


@functools.partial(jax.jit, static_argnames=("interpret", "visit_batch",
                                             "self_group", "fold_segments",
                                             "score_mxu"))
def _run(order, boxd2, sskip, q_pts, q_ids, in_d2, in_idx, p_t, *,
         interpret, visit_batch, self_group, fold_segments,
         score_mxu=False):
    num_qb, s_q, dim = q_pts.shape
    num_pb, _, t_p = p_t.shape
    k = in_d2.shape[-1]
    grid = (num_qb,)
    out_d2, out_idx, visits = pl.pallas_call(
        functools.partial(_kernel, visit_batch=visit_batch,
                          self_group=self_group,
                          fold_segments=fold_segments,
                          score_mxu=score_mxu),
        grid=grid,
        in_specs=[
            # Mosaic requires the LAST TWO block dims to be sublane/lane
            # aligned or equal to the array dims; a middle singleton makes
            # per-bucket rows of the SMEM schedule arrays legal blocks
            pl.BlockSpec((1, 1, num_pb), lambda b: (b, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, num_pb), lambda b: (b, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1), lambda b: (0, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, s_q, dim), lambda b: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s_q, 1), lambda b: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((s_q, k), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((s_q, k), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec((s_q, k), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((s_q, k), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, 2), lambda b: (b, 0, 0),
                         memory_space=pltpu.SMEM),
        ),
        out_shape=(
            # under shard_map the outputs vary over the same mesh axes as the
            # candidate state; outside, vma is empty and this is a no-op
            jax.ShapeDtypeStruct((num_qb * s_q, k), jnp.float32,
                                 vma=jax.typeof(in_d2).vma),
            jax.ShapeDtypeStruct((num_qb * s_q, k), jnp.int32,
                                 vma=jax.typeof(in_idx).vma),
            jax.ShapeDtypeStruct((num_qb, 1, 2), jnp.int32,
                                 vma=jax.typeof(in_idx).vma),
        ),
        scratch_shapes=[
            pltpu.VMEM((2, p_t.shape[1], visit_batch * t_p), jnp.float32),
            pltpu.SemaphoreType.DMA((2, visit_batch)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the [S, V*T] distance tile + double-buffered chunk scratch put
            # ~19MB on the VMEM stack at the 1M config, beyond the 16MB
            # default scoped limit — raise the ceiling (v5e has 128MiB
            # physical VMEM) ONLY when the computed footprint needs it, so
            # small shapes and non-v5e parts keep the default guardrail
            vmem_limit_bytes=_vmem_limit(s_q, t_p, visit_batch, k)),
        interpret=interpret,
    )(order, boxd2, sskip, q_pts, q_ids, in_d2, in_idx, p_t)
    return out_d2, out_idx, visits


def knn_update_tiled_pallas(state: CandidateState, q: BucketedPoints,
                            p: BucketedPoints, *,
                            interpret: bool | None = None,
                            with_stats: bool | str = False,
                            visit_batch: int | None = None,
                            skip_self=None, self_group: int = 1,
                            canonical_ties: bool = False,
                            score_dtype: str = "f32",
                            point_norms2=None,
                            decode_ids: bool = True):
    """Drop-in Pallas twin of ``ops.tiled.knn_update_tiled`` (same contract:
    state rows in ``q``'s bucket order; folds every real point of ``p`` in;
    ``with_stats`` additionally returns the i32 count of [S, T] tiles
    scored — here the sum over query buckets of buckets each visited, since
    every bucket advances independently instead of lock-stepping;
    ``with_stats="full"`` returns ``(out, visits, fold_passes)`` where
    fold_passes is the summed TILE-SCAN count of the fold loops (each scan
    adopts up to ``fold_segments`` candidates — compare runs only at equal
    segment settings) — the k-scaling cost the warm start and the
    multi-extract fold exist to cap, for on-chip diagnosis;
    ``skip_self``/``self_group`` as in the twin: nonzero masks point bucket
    b // self_group out of query bucket b's traversal for warm-started
    self-joins).

    ``score_dtype="bf16"``: the in-kernel distance tile becomes the
    matmul-form MXU score (one bf16 dot_general per chunk, f32
    accumulation, ||p||^2 stowed in the resident layout's spare sublane
    row) and the candidate rows are WIDENED to ``rescore_width(k)`` slots —
    the kernel's fold then keeps the top-W survivors per row BY APPROX
    SCORE, and this wrapper rescores every adopted entry with the exact
    elementwise f32 form before sorting the rows back down to k. Emitted
    distances are therefore always exact; the kept SET matches the f32
    kernel whenever the true top-k sits inside the survivor window (the
    same guarantee as the XLA twin's bf16 mode — docs/TUNING.md "Distance
    kernel"). In-kernel pruning compares against the widened row's LAST
    slot, which is conservative (never prunes a bucket the f32 kernel
    would have visited). ``point_norms2`` optionally carries precomputed
    f32[Bp, T] squared norms (the serving engine computes them once at
    index upload).

    ``canonical_ties``: re-sort the finished candidate rows by the
    (dist2, idx) total order — the serving engine's multi-bucket tie
    discipline (ops/tiled.py). NOTE the twin difference: the XLA twin's
    canonical mode also makes the kept SET at the k-boundary canonical (its
    fold adopts ties by id and its visit predicate is non-strict); this
    kernel's in-VMEM fold keeps strict-< adoption, so at an exact
    equal-distance k-boundary straddling point buckets the kept ids can
    still follow visit order. Distances are exact either way; only
    duplicate-point id choices at that razor's edge differ (docs/TUNING.md
    "Query locality").

    Precondition: ``p.ids`` and ``state.idx`` entries must be ``>= -1``
    (true of everything this package produces — real ids are ``>= 0``, the
    pad sentinel is ``-1``). Values ``<= -2`` would alias the fold's
    lane-position encoding and decode to unrelated ids
    (fold_tile_into_candidates).

    ``decode_ids=False`` is for callers that read only distances: the
    wrapper then skips the position decode (two gathers of every candidate
    slot) and the returned ``state.idx`` is opaque — entries this call
    adopted stay the kernel's encoded lane positions (``<= -2``, or under
    ``score_dtype="bf16"``, whose rescore still needs the positions,
    non-negative flat positions in this call's resident layout); carried-in
    entries pass through. The distances are the same bits as with
    ``decode_ids=True`` (the fold only carries ids), so such a state may be
    folded again with ``decode_ids=False``; its ids must never be read, and
    ``canonical_ties`` then orders equal distances by those opaque values."""
    from mpi_cuda_largescaleknn_tpu.ops.distance import (
        mxu_min_dim,
        rescore_width,
        validate_score_dtype,
    )

    validate_score_dtype(score_dtype)
    use_mxu = (score_dtype == "bf16"
               and q.pts.shape[-1] >= mxu_min_dim())
    if interpret is None:
        from mpi_cuda_largescaleknn_tpu.ops.pallas import is_tpu_backend
        interpret = not is_tpu_backend()
    num_qb, s_q = q.ids.shape
    dim = q.pts.shape[-1]
    k = state.dist2.shape[-1]

    sorted_d2, order = nearest_first_order(q.lower, q.upper,
                                           p.lower, p.upper)  # [Bq, Bp] x2

    # Mosaic DMA-slices p_hbm per bucket, so the sliced dims must match its
    # VMEM tiling: the coordinate dim rides the sublane axis (D rows, then
    # one ||p||^2 row, padded up to a multiple of 4) and the bucket dim
    # rides the lane axis (tiled in 128s — pad with the same PAD_SENTINEL/-1
    # rows partition_points uses; their distances overflow to +inf and are
    # never adopted by the fold)
    p3 = jnp.swapaxes(p.pts, 1, 2)            # [Bp, D, T]
    lane_pad = (-p3.shape[2]) % 128
    if lane_pad:
        p3 = jnp.pad(p3, ((0, 0), (0, 0), (0, lane_pad)),
                     constant_values=PAD_SENTINEL)
    # row D carries the exact f32 ||p||^2 per lane for the MXU score (the
    # previously unused tiling-pad row). Computed AFTER lane padding (or
    # +inf-padded when precomputed) so pad lanes overflow to +inf and can
    # never win a survivor slot. The f32 kernel never reads the row, so
    # the default mode keeps the old PAD_SENTINEL fill instead of paying
    # the O(Bp*T*D) norm compute on every call
    if not use_mxu:
        pn2 = jnp.full((p3.shape[0], p3.shape[2]), PAD_SENTINEL,
                       jnp.float32)
    elif point_norms2 is not None:
        pn2 = jnp.asarray(point_norms2, jnp.float32)
        if lane_pad:
            pn2 = jnp.pad(pn2, ((0, 0), (0, lane_pad)),
                          constant_values=jnp.inf)
    else:
        pn2 = None
        for i in range(dim):
            ri = p3[:, i, :]
            pn2 = ri * ri if pn2 is None else pn2 + ri * ri
    row_pad = (-(dim + 1)) % 4
    parts = [p3, pn2[:, None, :]]
    if row_pad:
        parts.append(jnp.full((p3.shape[0], row_pad, p3.shape[2]),
                              PAD_SENTINEL, jnp.float32))
    p_t = jnp.concatenate(parts, axis=1)      # [Bp, rows, T_pad]
    # id table for the post-kernel position decode (ids never enter the
    # kernel — see fold_tile_into_candidates); pad lanes decode to -1 but
    # are never adopted anyway (their coords are PAD_SENTINEL -> +inf d2)
    pid = p.ids
    if lane_pad:
        pid = jnp.pad(pid, ((0, 0), (0, lane_pad)), constant_values=-1)

    assert state.dist2.shape == (num_qb * s_q, k), (state.dist2.shape,
                                                    (num_qb, s_q, k))
    # widened candidate rows under the MXU score: the kernel's fold keeps
    # rescore_width(k) survivors per row by approx score, rescored and
    # sliced back to k after the kernel. The +inf fill preserves the
    # max_radius cutoff semantics: original slots (<= r^2) always sort
    # ahead of any widened-slot candidate at or beyond the radius
    k_eff = k
    if use_mxu:
        k_eff = rescore_width(k, p_t.shape[0] * p_t.shape[2])
        if k_eff > k:
            rows = num_qb * s_q
            state = CandidateState(
                jnp.concatenate([state.dist2,
                                 jnp.full((rows, k_eff - k), jnp.inf,
                                          jnp.float32)], axis=1),
                jnp.concatenate([state.idx,
                                 jnp.full((rows, k_eff - k), -1,
                                          jnp.int32)], axis=1))
    if visit_batch is None:
        # enough lanes per chunk to amortize the loop step (~2048) without
        # blowing the VMEM budget on the [S, V*T] distance tile.
        # LSK_CHUNK_LANES overrides for on-chip tuning — read at TRACE time,
        # so it must be set before the first run of a process (a sweep runs
        # one fresh process per value); changing it mid-process is ignored
        # by the jit cache
        lanes = int(os.environ.get("LSK_CHUNK_LANES", 2048))
        visit_batch = max(1, lanes // p_t.shape[2])
    visit_batch = min(visit_batch, p_t.shape[0])
    # multi-extract fold segments: adoptions per chunk scale with k, tile
    # scans are the expensive part — at k>=32 extract one min per 128-lane
    # segment per pass (fold_tile_into_candidates). LSK_FOLD_SEGS
    # overrides (trace-time, like LSK_CHUNK_LANES)
    lanes_total = visit_batch * p_t.shape[2]
    fold_segs = default_fold_segments(lanes_total, k_eff, env="LSK_FOLD_SEGS")
    ss = jnp.asarray(0 if skip_self is None else skip_self,
                     jnp.int32).reshape(1, 1, 1)
    out_d2, out_idx, visits = _run(order[:, None, :], sorted_d2[:, None, :],
                                   ss, q.pts, q.ids[:, :, None],
                                   state.dist2, state.idx, p_t,
                                   interpret=interpret,
                                   visit_batch=visit_batch,
                                   self_group=self_group,
                                   fold_segments=fold_segs,
                                   score_mxu=use_mxu)
    # decode encoded lane positions (<= -2) through the per-query-bucket
    # visit order: pos // T names the visit slot, pos % T the lane within
    # the visited bucket. Entries carried in from prior rounds / warm
    # starts are real ids (>= -1) and pass through untouched.
    t_pad = p_t.shape[2]
    enc = out_idx.reshape(num_qb, s_q * k_eff)
    if decode_ids or use_mxu:
        pos = jnp.clip(-2 - enc, 0, p_t.shape[0] * t_pad - 1)
        flat_pos = jnp.take_along_axis(order, pos // t_pad, axis=1) \
            * t_pad + pos % t_pad
        # without decode_ids, the rescore's own positions (>= 0) mark its
        # entries as carried in for any later fold
        ids_new = (jnp.take(pid.reshape(-1), flat_pos, axis=0)
                   if decode_ids else flat_pos)
        out_idx = jnp.where(enc <= -2, ids_new, enc).reshape(out_idx.shape)
    if use_mxu:
        # exact f32 rescore of every entry the fold adopted by approx
        # score: gather the survivor coordinates back through the same
        # position decode and recompute the elementwise distance (the f32
        # kernel's expression tree), then sort the widened rows and slice
        # back to k. Entries carried in from prior rounds kept their exact
        # distances inside the kernel and pass through unchanged.
        from mpi_cuda_largescaleknn_tpu.ops.distance import opaque_one

        pflat = jnp.swapaxes(p_t[:, :dim, :], 1, 2).reshape(-1, dim)
        pg = jnp.take(pflat, flat_pos, axis=0).reshape(num_qb, s_q,
                                                       k_eff, dim)
        one = opaque_one(q.pts)
        acc = None
        for i in range(dim):
            acc = accumulate_sq(acc, q.pts[:, :, None, i] - pg[..., i], one)
        d2_new = jnp.where(enc <= -2, acc.reshape(num_qb, s_q * k_eff),
                           out_d2.reshape(num_qb, s_q * k_eff))
        d2r = d2_new.reshape(num_qb * s_q, k_eff)
        idr = out_idx.reshape(num_qb * s_q, k_eff)
        # values changed, so re-sort before slicing: stable 1-key keeps
        # the fold's arrival order among exact ties (the kernel's
        # documented boundary discipline); canonical mode uses the
        # (dist2, idx) total order like the XLA twin
        d2r, idr = lax.sort((d2r, idr),
                            num_keys=2 if canonical_ties else 1,
                            dimension=1, is_stable=True)
        out_d2, out_idx = d2r[:, :k], idr[:, :k]
    elif canonical_ties:
        # one [rows, k] two-key sort per call (not per visit): rows come
        # back ascending (dist2, idx) like the XLA twin's canonical mode
        out_d2, out_idx = lax.sort((out_d2, out_idx), num_keys=2,
                                   dimension=1, is_stable=True)
    out = CandidateState(out_d2, out_idx)
    if with_stats == "full":
        return (out, jnp.sum(visits[:, :, 0]).astype(jnp.int32),
                jnp.sum(visits[:, :, 1]).astype(jnp.int32))
    if with_stats:
        return out, jnp.sum(visits[:, :, 0]).astype(jnp.int32)
    return out
