"""Ring exchange engine — the unordered variant's communication core.

TPU-native re-design of the reference's MPI ring
(unorderedDataVariant.cu:173-205): R ranks each hold a tree shard and a set of
stationary queries with persistent candidate heaps; each round every rank
queries the currently-resident shard(s), then rotates them. After a full
sweep every shard has visited every rank and each heap holds the global
top-k. This is the same communication/accumulation shape as ring attention
(stationary Q, rotating K/V, running accumulator) and maps 1:1 onto
``lax.ppermute`` over the ICI ring inside ``shard_map`` — here with TWO
counter-rotating copies per tree (see ``_make_ring_fns``): ICI links are
full-duplex, so both directions carry trees simultaneously and the sweep
takes R//2+1 rounds instead of the reference's R.

Deliberate improvements over the reference (not bugs to replicate):

- The reference serializes each round: ``MPI_Waitall`` completes before the
  kernel launches and ``cudaDeviceSynchronize`` before the next Isend
  (unorderedDataVariant.cu:187-204). Here the next shard's ``ppermute`` is
  issued *before* the current shard's query update and depends only on the
  incoming buffer, so XLA's latency-hiding scheduler overlaps communication
  with compute.
- The reference exchanges per-round point counts as a separate message pair
  (unorderedDataVariant.cu:183-186). Static SPMD shapes make counts
  compile-time constants: every shard is padded to a uniform size with
  sentinel points whose distances are +inf (core/types.py), generalizing the
  reference's own ``N+1`` slack alloc (:156-158) and the prepartitioned
  variant's pad-to-max trick (prePartitionedDataVariant.cu:251-266).
- 64-bit-safe sizing throughout (the reference's ``int`` arithmetic overflows
  beyond ~2^31 bytes of candidates — SURVEY.md appendix).

Two drivers share one set of per-round builders (``_make_ring_fns``): the
fused ``ring_knn`` (whole ring in one ``lax.fori_loop`` — the default) and
the host-stepped ``ring_knn_stepwise`` (one jitted step per round, enabling
checkpoint/resume between rounds).
"""

from __future__ import annotations

import logging
import math
import os
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from mpi_cuda_largescaleknn_tpu.core.types import CandidateState
from mpi_cuda_largescaleknn_tpu.obs.timers import PhaseTimers
from mpi_cuda_largescaleknn_tpu.ops.brute_force import knn_update_bruteforce
from mpi_cuda_largescaleknn_tpu.ops.build_tree import build_tree
from mpi_cuda_largescaleknn_tpu.ops.candidates import (
    extract_final_result,
    init_candidates,
    sqrt_rn,
)
from mpi_cuda_largescaleknn_tpu.ops.partition import (
    BucketedPoints,
    _partition_level,
    choose_buckets,
    coarsen_buckets,
    partition_finalize,
    partition_prep,
    scatter_back,
)
from mpi_cuda_largescaleknn_tpu.ops.tiled import (
    knn_update_tiled,
    warm_start_self,
)
from mpi_cuda_largescaleknn_tpu.ops.traverse import knn_update_tree
from mpi_cuda_largescaleknn_tpu.parallel.mesh import AXIS, pvary
from mpi_cuda_largescaleknn_tpu.utils.math import next_pow2


@lru_cache(maxsize=32)  # bounded: chunked drivers with varying chunk shapes
def _partition_smaps(mesh, num_buckets, bucket_size, dim):  # or fresh Mesh
    # objects must not pin compiled programs + device refs forever
    spec = P(AXIS)

    def smap(fn, in_specs, out_specs):
        # pure-XLA programs: vma checking always on (the engines' pallas
        # interpret-mode exemption does not apply here)
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs))

    kw = dict(num_buckets=num_buckets, bucket_size=bucket_size)
    ncols = dim + 2  # D coordinate columns + ids + pos
    prep = smap(partial(partition_prep, **kw), (spec, spec), (spec,) * ncols)
    # num_seg rides replicated so every level reuses the ONE compiled sort
    level = smap(partial(_partition_level, **kw), (spec,) * ncols + (P(),),
                 (spec,) * ncols)
    fin = smap(partial(partition_finalize, **kw), (spec,) * ncols, spec)
    return prep, level, fin


def partition_sharded(points_sharded, ids_sharded, mesh,
                      bucket_size) -> BucketedPoints:
    """Per-shard spatial partition, hoisted OUT of the ring's fused jit.

    Equivalent to ``shard_map(partition_points)`` but compiled as one prep
    program + ONE level program reused for all log2(B) sort passes + one
    finalize — tracing the partition inside the ring jit instead compiles a
    distinct million-row 7-operand sort per level, which dominated the
    1M-point compile time. Returns a BucketedPoints of global sharded
    arrays (leaf i of shard r at row block r*B_local).
    """
    num_shards = mesh.shape[AXIS]
    npad_local = points_sharded.shape[0] // num_shards
    b, s = choose_buckets(npad_local, bucket_size)
    prep, level, fin = _partition_smaps(mesh, b, s,
                                        int(points_sharded.shape[-1]))

    sharding = NamedSharding(mesh, P(AXIS))
    pts = jax.device_put(points_sharded, sharding)
    ids = jax.device_put(ids_sharded, sharding)
    cols = prep(pts, ids)
    for lvl in range(int(math.log2(b))):
        cols = level(*cols, jnp.int32(1 << lvl))
    return fin(*cols)


def _engine_fn(engine: str, query_tile: int, point_tile: int,
               score_dtype: str = "f32"):
    """(engine name, flat fold) — flat-engine dispatch only; "tiled" and
    "pallas_tiled" take the bucketed data path (_tiled_engine_fn) instead.
    The name is bound to the kernel each branch returns: the drivers'
    stats report it as the engine their programs actually traced."""
    if engine == "bruteforce":
        return "bruteforce", partial(
            knn_update_bruteforce, query_tile=query_tile,
            point_tile=point_tile, score_dtype=score_dtype)
    if score_dtype != "f32":
        raise ValueError(
            f"engine '{engine}' has no score_dtype='{score_dtype}' path "
            "(MXU scoring exists for bruteforce and the tiled engines)")
    if engine == "tree":
        return "tree", knn_update_tree
    if engine == "pallas":
        from mpi_cuda_largescaleknn_tpu.ops.pallas.knn_bf import (
            knn_update_pallas,
        )
        return "pallas", partial(knn_update_pallas, query_tile=query_tile,
                                 point_tile=point_tile)
    raise ValueError(f"unknown engine '{engine}'")


def resolve_engine(engine: str) -> str:
    """Map ``auto`` to the engine built for the current backend.

    On a TPU, ``auto`` means the fused Pallas traversal kernel
    (``pallas_tiled``); off-TPU it means the XLA twin ``tiled`` (the Pallas
    kernels would only run in interpreter mode there). Explicit engine
    names are honored unchanged, and a kernel that fails to import or
    compile raises — nothing swaps engines behind the caller's back."""
    if engine != "auto":
        return engine
    from mpi_cuda_largescaleknn_tpu.ops.pallas import is_tpu_backend

    return "pallas_tiled" if is_tpu_backend() else "tiled"


def resolve_merge(merge: str, num_shards: int) -> str:
    """Resolve the cross-shard top-k merge placement.

    ``device`` keeps the R-way reduction inside the SPMD program
    (``device_merge_final``: an ``all_to_all`` reduce-scatter by default,
    the log2(R) ``ppermute`` tree of ops/candidates.py
    ``tree_merge_candidates`` as the all-reduce form), so the host fetches
    one final [Q, k] result instead of R partial ones; ``host`` fetches all R
    partials and merges them in numpy. ``auto`` picks ``device`` whenever
    the reduction is available — every power-of-two mesh, single- or
    multi-host (the collectives ride the GLOBAL pod-mesh axis either way) —
    and falls back to ``host`` with a logged warning otherwise (recursive
    doubling needs the blocks to tile the axis), so an odd pod shape never
    hard-fails a startup that ``auto`` was supposed to keep portable.
    Results are bit-identical either way (same tie discipline); the choice
    is pure data movement. An explicit ``device`` on a non-power-of-two
    mesh still raises rather than silently degrading.
    """
    if merge == "auto":
        if num_shards & (num_shards - 1) == 0:
            return "device"
        if num_shards > 1:
            logging.getLogger(__name__).warning(
                "merge='auto': mesh of %d shards is not a power of two — "
                "falling back to the host-side merge (the device "
                "reduce-scatter needs the row blocks to tile the axis)",
                num_shards)
        return "host"
    if merge == "device":
        if num_shards & (num_shards - 1):
            raise ValueError(
                f"merge='device' needs a power-of-two shard count, got "
                f"{num_shards} (use merge='auto' to fall back to host)")
        return "device"
    if merge == "host":
        return "host"
    raise ValueError(f"unknown merge mode '{merge}' "
                     "(expected host | device | auto)")


def resolve_query_buckets(query_buckets: int, qpad: int, k: int) -> int:
    """Resolve the serving engine's query-bucket count for one padded batch
    shape (0 = auto). Like ``resolve_bucket_size``, the auto value encodes
    the measured tradeoff core/config.py names: FINE query buckets tighten
    the per-bucket prune radius (each bucket's radius is the max over only
    ITS queries — ops/tiled.py ``_worst2``) and give ``nearest_first_order``
    a tight AABB to schedule against, while buckets below ~k rows shrink
    the [S, k] candidate tile past what the sublane padding and the
    per-bucket schedule overhead repay. Auto therefore targets
    ``next_pow2(max(8, k))`` queries per bucket.

    The result always divides ``qpad`` (both are powers of two) and leaves
    at least 8 rows per bucket; explicit values are rounded up to a power
    of two and clamped into that range. 1 = the single whole-batch bucket
    (the pre-locality serving behavior, and the B=1 baseline
    tests/test_query_locality.py holds the sorted buckets to)."""
    if qpad < 16:
        return 1
    cap = qpad // 8
    if query_buckets < 1:  # auto
        b = qpad // next_pow2(max(8, k))
    else:
        b = next_pow2(query_buckets)
    return max(1, min(b, cap))


def device_merge_final(heap: CandidateState, num_shards: int,
                       via: str = "a2a"):
    """Device-side finale of a replicate-traverse-merge program (inside
    ``shard_map``): reduce the R per-shard candidate states for the SAME
    replicated queries to the global top-k and have each device emit its
    1/R row-slice of the final answer — the stitched global arrays are
    exactly [Q] dists / [Q, k] candidates, so the host fetch shrinks R x
    (the reference materializes once per run for the same reason,
    unorderedDataVariant.cu extractFinalResult; here it is per batch).

    Two reductions, bit-identical outputs:

    - ``a2a`` (default): a reduce-scatter — ONE ``all_to_all`` hands every
      device all R shards' candidate blocks for only ITS 1/R rows
      (shard-major), then a single width-R*k ``top_k`` finishes. ``top_k``
      prefers the lower column at equal (negated) keys, which over
      shard-major columns IS the host merge's stable tie discipline
      (earlier shard, then earlier slot — verified against
      ``np.argsort(kind="stable")`` in tests). Moves (R-1)/R of each
      state once and sorts each row once: less traffic AND less sort work
      than the tree, and ~30x faster on XLA:CPU, whose row-sort emits a
      scalar comparator loop while its TopK is a tuned custom call.
    - ``tree``: the log2(R) ``ppermute`` recursive-doubling all-reduce
      (ops/candidates.py ``tree_merge_candidates``) followed by a slice —
      every device transiently holds the FULL merged state, the all-reduce
      form the multi-host serving level runs on the global pod-mesh axis
      (the mesh decides whether the hops ride ICI or DCN; the program is
      the same either way).

    Returns (dists, dist2, idx) of ``Q // num_shards`` rows; Q must be
    divisible by num_shards (callers pad the batch to a bucket that is).
    Unused outputs are dead-code-eliminated by XLA, so callers that only
    fetch (dists, idx) pay nothing for the dist2 slice.
    """
    from mpi_cuda_largescaleknn_tpu.ops.candidates import (
        tree_merge_candidates,
    )

    rows, k = heap.dist2.shape
    if rows % num_shards:
        raise ValueError(f"{rows} query rows do not tile {num_shards} "
                         "shards (pad the batch to a multiple)")
    rp = rows // num_shards
    if num_shards == 1:
        return extract_final_result(heap), heap.dist2, heap.idx
    if via == "tree":
        st = tree_merge_candidates(heap, AXIS, num_shards)
        off = jax.lax.axis_index(AXIS) * rp
        return (jax.lax.dynamic_slice_in_dim(extract_final_result(st),
                                             off, rp),
                jax.lax.dynamic_slice_in_dim(st.dist2, off, rp),
                jax.lax.dynamic_slice_in_dim(st.idx, off, rp))
    if via != "a2a":
        raise ValueError(f"unknown device merge reduction '{via}'")

    def scatter(x):
        # [Q, k] -> [R*rp, k]: block j holds shard j's candidates for MY
        # rp rows -> [rp, R*k] with columns in shard-major order
        x = jax.lax.all_to_all(x, AXIS, 0, 0, tiled=True)
        return x.reshape(num_shards, rp, k).transpose(1, 0, 2).reshape(
            rp, num_shards * k)

    cat_d2 = scatter(heap.dist2)
    cat_idx = scatter(heap.idx)
    neg, cols = jax.lax.top_k(-cat_d2, k)
    top_d2 = -neg  # -(-0.0) == 0.0, -(-inf) == inf: values round-trip
    top_idx = jnp.take_along_axis(cat_idx, cols, axis=1)
    return sqrt_rn(top_d2[:, k - 1]), top_d2, top_idx


def resolve_bucket_size(bucket_size: int, engine: str) -> int:
    """0 = auto, resolved per engine: the XLA twin is pair-budget-bound on
    its low-overhead backend (CPU wall-clock tracks pairs/query, so the
    fine 128 bucket), while the Pallas kernel pays a real per-while-step
    cost that favors wider tiles (256, with G2). Neither default has been
    swept on the chip at 1M points: the benchmark's self-join cells run
    the Pallas one (PERF_LEDGER.jsonl) without comparing it to others.

    Checkpoint note: stepwise fingerprints record the RESOLVED values (a
    different bucket geometry is genuinely non-resumable state — the
    partitioned shard arrays change shape), so changing an auto default
    here (or in _effective_group) makes older default-flag checkpoints
    resumable only by passing the explicit flags of the recorded
    geometry: for pallas runs from before the round-5 retune, both
    `--bucket-size 512` and `--point-group 1`."""
    if bucket_size:
        return bucket_size
    if engine == "tiled":
        return 128
    if engine == "pallas_tiled":
        # 256/G2 (G2 from the point_group auto below) is what the
        # benchmark's batch_1m_k8 and ring4_4m_k8 cells run; a sweep of
        # bucket size and group at 1M on the chip is still to be made.
        return 256
    return 512


def _tiled_engine_fn(engine: str):
    """(engine name, bucket-granular fold) for the tiled data path: the
    fused Pallas traversal kernel for ``pallas_tiled``, the XLA twin for
    ``tiled``. As in _engine_fn, the name is the returned kernel's own."""
    if engine == "pallas_tiled":
        from mpi_cuda_largescaleknn_tpu.ops.pallas.knn_tiled import (
            knn_update_tiled_pallas,
        )
        return "pallas_tiled", knn_update_tiled_pallas
    if engine == "tiled":
        return "tiled", knn_update_tiled
    raise ValueError(f"no tiled kernel for engine '{engine}' (resolve "
                     "'auto' first)")


def _rotate_pair(shard_pair, num_shards: int):
    """One rotation of the bidirectional ring: the forward copy of every
    leaf moves to the next device and the backward copy to the previous,
    one ``ppermute`` per leaf and direction (``rotation_bytes``)."""
    fwd = [(i, (i + 1) % num_shards) for i in range(num_shards)]
    bwd = [(i, (i - 1) % num_shards) for i in range(num_shards)]
    f, b = shard_pair
    return (jax.tree.map(lambda a: jax.lax.ppermute(a, AXIS, fwd), f),
            jax.tree.map(lambda a: jax.lax.ppermute(a, AXIS, bwd), b))


def _duplicate_round(rnd, num_shards: int):
    """Whether round ``rnd`` delivers one shard in both copies: round 0
    (the own shard) and, for even R, round R/2 (the antipode). Its
    backward fold is skipped, so every shard is folded exactly once."""
    return (rnd == 0) | (2 * rnd == num_shards)


def _make_ring_fns(k, max_radius, engine, query_tile, point_tile, bucket_size,
                   num_shards, warm_start=False, point_group=1,
                   score_dtype="f32", with_ids=True):
    """(init_fn, round_fn, final_fn, shard_init_fn, query_init_fn,
    init_from_q, query_from_q, kernel) — the per-round pieces every ring
    driver executes, defined once so the fused,
    stepwise and chunked paths cannot diverge.

    - init_fn(pts_local, ids_local) -> (stationary, shard_pair, heap)
      (classic path: the slab is both tree shard and queries)
    - init_from_q(q) -> (stationary, shard_pair, heap, warm_passes)
      (tiled path, from the hoisted partition; ``warm_passes`` is i32[1]:
      the warm start's fold passes on this device, 0 for a cold heap)
    - shard_init_fn(pts_local, ids_local) -> shard (tree side only; drivers
      pair it as (shard, shard) — see below)
    - query_init_fn(qpts_local, qids_local) -> (stationary, heap)
      (query side only — may be a chunk of the slab)
    - round_fn(stationary, shard_pair, heap, rnd)
        -> (next_pair, new_heap, tiles)
      (issues the rotations before the folds so XLA overlaps them; ``tiles``
      is i32[1]: distance tiles this device actually computed — real counts
      for the pruned tiled engines, 0 for flat engines whose all-pairs count
      is analytic and added by the drivers)
    - final_fn(stationary, heap, npad) -> (dists, hd2, hidx) in input-row
      order per shard; ``(dists,)`` alone without ``with_ids``
    - kernel: the engine name _engine_fn/_tiled_engine_fn dispatched — what
      the drivers report as ``stats["engine"]``

    The ring is BIDIRECTIONAL: two copies of each tree counter-rotate, one
    ``ppermute`` per direction, so the full sweep takes
    ``ring_total_rounds(R) = R//2 + 1`` rounds of (up to) two folds instead
    of R rounds of one. Same total bytes and folds — but ICI links are
    full-duplex, so using both directions at once halves the exchange
    wall-clock the reference's one-direction ring pays
    (unorderedDataVariant.cu:178-193), and the loop/dispatch overhead
    halves with the round count. ``rnd`` disambiguates the two duplicate
    deliveries (round 0: both copies are the own shard; round R/2 for even
    R: both copies are the antipodal shard) — the backward fold is skipped
    there, keeping every shard folded exactly once.

    ``with_ids=False`` builds distance-only pieces: the Pallas fold skips
    its neighbour-id decode (``decode_ids``), so the heap's ids become
    opaque, and final_fn scatters no candidate rows back. The distances
    are the same bits.
    """
    use_tiled = engine in ("tiled", "pallas_tiled")
    # warm start only applies to self-joins on ONE shared partition (query
    # bucket b IS point bucket b in round 0) — and only pays where the
    # fold's PASS count is the cost: the Pallas kernel. The XLA twin's
    # width-2k sort-merge saves nothing from a warm heap (a sort-based
    # warm start REGRESSED it 20% at 500K/k=100 on the CPU fixture, round
    # 5), so the twin stays cold. Chunked drivers partition queries
    # separately and always stay cold.
    warm_start = warm_start and engine == "pallas_tiled"

    if use_tiled:
        kernel, tiled_update = _tiled_engine_fn(engine)
        # keyed on the kernel dispatched: only the Pallas wrapper decodes
        ids_kw = {} if kernel == "tiled" else {"decode_ids": with_ids}

        def query_from_q(q):
            # heap init for an ALREADY-partitioned query side (the drivers
            # hoist the partition out of the jit — see partition_sharded)
            heap = pvary(init_candidates(q.pts.shape[0] * q.pts.shape[1], k,
                                         max_radius))
            return q, heap

        def init_from_q(q):
            # the rotating point side is a GROUP-coarsened view of the
            # same partition (ops/partition.py coarsen_buckets): fine
            # query buckets keep the prune radius tight while the resident
            # tiles stay point_group x wider for DMA/fold efficiency
            pc = coarsen_buckets(q, point_group)
            if warm_start:
                # exact top-k of every query's own (containing) resident
                # bucket, folded before the traversal — round 0's kernel
                # then masks that bucket (skip_self below). Rows come back
                # in fine order: the coarsening is a reshape. A
                # distance-only heap keeps the kernel's lane positions
                heap, passes = warm_start_self(pc, k, max_radius,
                                               decode_ids=with_ids,
                                               with_passes=True)
                passes = passes[None]
            else:
                _, heap = query_from_q(q)
                passes = pvary(jnp.zeros((1,), jnp.int32))
            shard = _resident_shard(pc)
            return q, (shard, shard), heap, passes

        def fold_one(q, shard, heap, sskip=None):
            # the resident shard keeps its OWN bucket geometry (it may differ
            # from the query side's under chunked queries); pos is
            # query-side-only metadata, ids stand in for it
            resident = BucketedPoints(shard[0], shard[1], shard[2], shard[3],
                                      shard[1])
            return tiled_update(heap, q, resident, with_stats=True,
                                skip_self=sskip, self_group=point_group,
                                score_dtype=score_dtype, **ids_kw)

        def round_fn(q, shard_pair, heap, rnd, rotate=True):
            # the final round's rotation would be discarded — callers pass
            # rotate=False there (static flag: collectives cannot sit
            # under a traced cond)
            nxt = (_rotate_pair(shard_pair, num_shards) if rotate
                   else shard_pair)
            f, b = shard_pair
            # round 0's forward fold is the own shard: with a warm-started
            # heap its self buckets are already folded and must be masked
            sskip = ((rnd == 0).astype(jnp.int32) if warm_start else None)
            st, tiles_f = fold_one(q, f, heap, sskip)

            def fold_b(_):
                st2, t2 = fold_one(q, b, st)
                return st2.dist2, st2.idx, t2

            hd2, hidx, tiles_b = jax.lax.cond(
                # tiles_f * 0, not a fresh zero: the constant would be
                # replicated and mismatch fold_b's axis-varying count
                _duplicate_round(rnd, num_shards),
                lambda _: (st.dist2, st.idx, tiles_f * 0), fold_b, None)
            return nxt, CandidateState(hd2, hidx), (tiles_f + tiles_b)[None]

        def final_fn(q, heap, npad):
            kk = heap.dist2.shape[-1]
            bs = (q.num_buckets, q.bucket_size)
            dists = scatter_back(extract_final_result(heap).reshape(bs),
                                 q.pos, npad, fill=jnp.inf)
            if not with_ids:
                return (dists,)
            hd2 = scatter_back(heap.dist2.reshape(bs + (kk,)), q.pos, npad,
                               fill=jnp.inf)
            hidx = scatter_back(heap.idx.reshape(bs + (kk,)), q.pos, npad,
                                fill=-1)
            return dists, hd2, hidx

        # the partition itself is hoisted out of the drivers' jits
        # (partition_sharded), so the in-jit init path only exists in the
        # *_from_q form — no tiled init_fn/shard_init_fn/query_init_fn
        init_fn = shard_init_fn = query_init_fn = None
    else:
        kernel, update = _engine_fn(engine, query_tile, point_tile,
                                    score_dtype)

        def query_init_fn(qpts_local, qids_local):
            heap = pvary(init_candidates(qpts_local.shape[0], k, max_radius))
            return qpts_local, heap

        def round_fn(queries, shard_pair, heap, rnd, rotate=True):
            nxt = (_rotate_pair(shard_pair, num_shards) if rotate
                   else shard_pair)
            f, b = shard_pair
            st = update(heap, queries, f[0], f[1])
            hd2, hidx = jax.lax.cond(
                _duplicate_round(rnd, num_shards),
                lambda _: (st.dist2, st.idx),
                lambda _: (lambda s2: (s2.dist2, s2.idx))(
                    update(st, queries, b[0], b[1])), None)
            st = CandidateState(hd2, hidx)
            # flat engines score every pair: the count is analytic
            # (n_q * n_p per device-fold), added host-side by the drivers
            return nxt, st, pvary(jnp.zeros((1,), jnp.int32))

        def final_fn(_queries, heap, _npad):
            if not with_ids:
                return (extract_final_result(heap),)
            return extract_final_result(heap), heap.dist2, heap.idx

        def shard_init_fn(pts_local, ids_local):
            return _flat_shard(engine, pts_local, ids_local)

        def init_fn(pts_local, ids_local):
            q, heap = query_init_fn(pts_local, ids_local)
            shard = shard_init_fn(pts_local, ids_local)
            return q, (shard, shard), heap

        init_from_q = query_from_q = None  # flat engines have no partition

    return (init_fn, round_fn, final_fn, shard_init_fn, query_init_fn,
            init_from_q, query_from_q, kernel)


def _resident_shard(pc: BucketedPoints) -> tuple:
    """The tiled path's rotating shard: a (group-coarsened) partition's
    points, ids and bucket boxes; ``pos`` is query-side metadata and
    stays on its device."""
    return (pc.pts, pc.ids, pc.lower, pc.upper)


def _flat_shard(engine: str, pts_local, ids_local):
    """The flat engines' rotating shard: the slab itself, or its tree."""
    if engine == "tree":
        return build_tree(pts_local, ids_local)
    return (pts_local, ids_local)


def rotation_bytes(shard) -> int:
    """Bytes one device sends in one rotation of the ring: ``_rotate_pair``
    ppermutes both counter-rotating copies of every leaf of its resident
    ``shard`` (points, ids and, on the tiled path, the bucket boxes).
    ``shard`` holds one device's arrays or ``jax.ShapeDtypeStruct``s."""
    return 2 * sum(math.prod(a.shape) * np.dtype(a.dtype).itemsize
                   for a in jax.tree.leaves(shard))


@lru_cache(maxsize=32)
def _rotation_bytes_of(engine: str, point_group: int, local) -> int:
    """``rotation_bytes`` of the shard the ring programs build from one
    device's inputs ``local`` (``ShapeDtypeStruct``s: the partition on the
    tiled path, else points and ids), from shapes alone: no device work."""
    if engine in ("tiled", "pallas_tiled"):
        shard = jax.eval_shape(
            lambda q: _resident_shard(coarsen_buckets(q, point_group)), local)
    else:
        shard = jax.eval_shape(partial(_flat_shard, engine), *local)
    return rotation_bytes(shard)


def _pair_step_fn(round_fn, rotate=True):
    """Flat-argument step wrapper shared by the stepwise and chunked
    drivers (shard_map wants leaf-wise specs; the pair and round counter
    ride as separate arguments and the counter self-increments).
    ``rotate=False`` builds the final-round variant whose (discarded)
    rotation is skipped."""
    def step_fn(stationary, f_state, b_state, heap, rnd_arr):
        nxt, st, t = round_fn(stationary, (f_state, b_state), heap,
                              rnd_arr[0], rotate=rotate)
        return nxt[0], nxt[1], st, t, rnd_arr + 1
    return step_fn


def _folds_in_rounds(start: int, stop: int, num_shards: int) -> int:
    """Folds the bidirectional ring executes in rounds [start, stop):
    1 in round 0 and in the even-R antipodal round, else 2."""
    return sum(1 if (r == 0 or 2 * r == num_shards) else 2
               for r in range(start, stop))


def ring_total_rounds(num_shards: int) -> int:
    """Rounds for a full bidirectional sweep: the own shard at round 0,
    then offsets +-1, ..., +-floor(R/2)."""
    return num_shards // 2 + 1


def _effective_group(point_group: int, npad_local: int,
                     bucket_size: int, engine: str) -> int:
    """Clamp the point-side coarsening factor to the actual bucket count
    (both are powers of two, so the clamped value always divides).

    0 = auto per engine, like resolve_bucket_size: the Pallas kernel's
    256-bucket default pairs with G2 (fine prune radius, full-width
    512-lane tiles; unswept at 1M on the chip, see resolve_bucket_size);
    the XLA twin's lock-step visit loop loses from grouping, so every
    other engine resolves to 1.
    ``engine`` is deliberately required: a call site that forgot it
    would silently resolve auto to 1 instead of the engine's tuned
    group (checkpoint-recovery implications in resolve_bucket_size)."""
    if point_group == 0:
        point_group = 2 if engine == "pallas_tiled" else 1
    if point_group <= 1:
        return 1
    assert point_group & (point_group - 1) == 0, point_group
    return min(point_group, choose_buckets(npad_local, bucket_size)[0])


def _warm_tiles(engine: str, npad_local: int, bucket_size: int,
                num_shards: int) -> int:
    """[S, S] tiles the warm start scores (one per bucket, every device) —
    counted into executed-work stats alongside the kernel's measured tile
    counts, since warm_start_self does that distance work in its own
    kernel before the traversal runs (pallas_tiled self-join drivers only
    — the twin stays cold, see _make_ring_fns)."""
    if engine != "pallas_tiled":
        return 0
    return num_shards * choose_buckets(npad_local, bucket_size)[0]


def _ring_stats(engine: str, tiles_total: int, bucket_size: int,
                n_q_device_rounds: int, *, q_rows: int | None = None,
                p_rows: int | None = None, point_group: int = 1,
                warm_fold_passes: int = 0) -> dict:
    """Executed-work stats of a run of ``engine`` — the kernel the
    driver's program traced, reported back as ``stats["engine"]``: distance
    pairs actually scored, and the warm start's fold passes (0 where the
    heap started cold).

    Tiled engines report measured tile counts (pruning makes the count
    data-dependent); one tile is [S_q, S_p] where S are the ACTUAL padded
    bucket sizes from ``choose_buckets`` (the nominal ``bucket_size``
    overstated pair_evals ~6% at 1M points). ``q_rows``/``p_rows`` are the
    per-device query/point row counts the buckets were built from. Flat
    engines score every pair, so the count is analytic:
    ``n_q_device_rounds`` = sum over device-rounds of
    n_queries_local * n_points_local.

    Granularity note: the two tiled engines count DIFFERENT things and
    their pair_evals are not comparable as pruning quality. The XLA twin
    counts chunk*V tiles for every chunk with >=1 active bucket (executed
    VPU work — its dense tile really covers masked buckets,
    ops/tiled.py body). The Pallas kernel counts only KEPT buckets (its
    nvis masks chunk-tail and skip_self buckets before the fold), so its
    broadcast FLOPs over masked lanes go uncounted — a roofline share
    derived from pair_evals (perfbench/opcount.py over perfbench/peaks.json)
    is a lower bound there. Compare engines on wall-clock."""
    if engine in ("tiled", "pallas_tiled"):
        _, s_q = choose_buckets(q_rows or 1, bucket_size)
        _, s_p = choose_buckets(p_rows or q_rows or 1, bucket_size)
        # coarsened point side: one visited tile spans point_group fine
        # buckets' lanes (ops/partition.py coarsen_buckets)
        pair_evals = int(tiles_total) * s_q * s_p * point_group
    elif engine == "tree":
        # the stack-free traversal is bounds-pruned and uninstrumented:
        # all-pairs would overstate executed work by orders of magnitude
        return {"engine": engine, "pair_evals": 0, "tiles": 0,
                "flops_per_pair": 8, "warm_fold_passes": 0,
                "note": "tree engine work is pruned and not counted"}
    else:
        pair_evals = int(n_q_device_rounds)
    return {"engine": engine, "pair_evals": pair_evals,
            "tiles": int(tiles_total), "flops_per_pair": 8,
            "warm_fold_passes": int(warm_fold_passes)}


# bounded like _partition_smaps: tests build many meshes and configurations
@lru_cache(maxsize=32)
def _ring_program(mesh, k, max_radius, engine, query_tile, point_tile,
                  bucket_size, num_shards, point_group, score_dtype,
                  with_ids):
    """(program, kernel, partitioned) — ``ring_knn``'s fused program for
    one configuration, built once: a fresh closure per call would miss
    jit's cache (keyed by function identity) and trace and lower the whole
    ring again on every job. The key is everything the program captures,
    with every ``auto`` already resolved; the point count is not in it, as
    jit itself retraces once per new shape. ``partitioned``: the program
    takes the hoisted partition as a third argument (tiled engines).

    The program returns ``(dists, hd2, hidx, tiles)`` with ``with_ids``
    and ``(dists, tiles)`` without: a distance-only program neither
    decodes neighbour ids after its folds nor scatters candidate rows
    back (``_make_ring_fns``), and it is a configuration of its own.
    ``tiles`` holds, per device, one slot per round and then the warm
    start's fold passes, so one fetch reads both."""
    init_fn, round_fn, final_fn, _sif, _qif, init_from_q, _qfq, kernel = \
        _make_ring_fns(k, max_radius, engine, query_tile, point_tile,
                       bucket_size, num_shards, warm_start=True,
                       point_group=point_group, score_dtype=score_dtype,
                       with_ids=with_ids)
    total_rounds = ring_total_rounds(num_shards)

    def body(pts_local, ids_local, q_local=None):
        if q_local is not None:
            stationary, pair, heap, warm = init_from_q(q_local)
        else:
            stationary, pair, heap = init_fn(pts_local, ids_local)
            warm = pvary(jnp.zeros((1,), jnp.int32))

        def round_body(i, carry):
            pair, hd2, hidx, tiles = carry
            nxt, st, t = round_fn(stationary, pair,
                                  CandidateState(hd2, hidx), i)
            # one slot per round, not a running i32 sum: a single round's
            # count fits int32 comfortably, but the total at reference
            # scale does not — the host sums the slots in int64
            tiles = jax.lax.dynamic_update_index_in_dim(tiles, t[0], i, 0)
            return nxt, st.dist2, st.idx, tiles

        pair, hd2, hidx, tiles = jax.lax.fori_loop(
            0, total_rounds - 1, round_body,
            (pair, heap.dist2, heap.idx,
             pvary(jnp.zeros((total_rounds,), jnp.int32))))
        # final round: fold only — its rotation would be discarded
        _, st, t = round_fn(stationary, pair, CandidateState(hd2, hidx),
                            jnp.int32(total_rounds - 1), rotate=False)
        tiles = jax.lax.dynamic_update_index_in_dim(
            tiles, t[0], total_rounds - 1, 0)
        return (final_fn(stationary, st, pts_local.shape[0])
                + (jnp.concatenate([tiles, warm]),))

    shard_spec = P(AXIS)
    # interpret-mode pallas kernels re-evaluate a vma-less kernel jaxpr with
    # varying operands, which trips shard_map's vma checker (JAX's own
    # guidance: pass check_vma=False); XLA engines keep the strict typing
    check_vma = not engine.startswith("pallas")
    partitioned = init_from_q is not None
    n_args = 3 if partitioned else 2
    program = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(shard_spec,) * n_args,
        out_specs=(shard_spec,) * (4 if with_ids else 2),
        check_vma=check_vma))
    return program, kernel, partitioned


def ring_knn(points_sharded: jnp.ndarray, ids_sharded: jnp.ndarray, k: int,
             mesh, *, max_radius: float = jnp.inf, engine: str = "auto",
             query_tile: int = 2048, point_tile: int = 2048,
             bucket_size: int = 0, point_group: int = 0,
             score_dtype: str = "f32",
             return_candidates: bool = False,
             return_stats: bool = False,
             timers: PhaseTimers | None = None):
    """Run the full R-round ring on a 1-D mesh (fused ``lax.fori_loop``).

    Args:
      points_sharded: f32[R*Npad, 3], shard-major (device i owns rows
        [i*Npad, (i+1)*Npad)), sentinel-padded. Device i's rows serve as both
        its tree shard and its stationary queries (the reference uploads the
        same slab twice — unorderedDataVariant.cu:159-167).
      ids_sharded: i32[R*Npad] global point ids (-1 for padding) that travel
        with the rotating shards so candidate lists can report neighbor
        identities (the reference computes these but discards them).
      k / max_radius: the `-k` / `-r` CLI parameters.
      timers: the caller's ``PhaseTimers``, which get the spans
        ``ring.stage`` (upload), ``ring.partition``, ``ring.launch`` (the
        fused program's call: the first call for a configuration or shape
        also traces, lowers and loads or compiles it; every later one only
        enqueues) and ``ring.wait`` (the tile-count fetch, which waits for
        the device), and the counters ``ring.program_builds`` (one per
        configuration the process had not built before) and
        ``ring.exchange_bytes`` (what every device of the mesh sends in
        the job's rotations, counted from static shapes; 0 at one shard,
        and ``stats["exchange_bytes"]``), ``ring.id_decode_folds``
        (the folds whose neighbour ids the job decoded: every fold when
        ``return_candidates``, else 0) and, with ``return_stats``,
        ``ring.warm_fold_passes`` (the warm start's tile-scan passes,
        summed over its programs and devices; 0 where the heap starts
        cold, and ``stats["warm_fold_passes"]``).
      return_candidates: also return the final candidate rows. It picks
        the program: without it the job reads distances only, and its
        program decodes no ids and scatters no candidate rows
        (``_ring_program``'s ``with_ids``; ``stats["neighbor_ids"]``).

    Returns:
      f32[R*Npad] k-th-NN distances in the same shard-major order (inf for
      padding rows), plus the CandidateState if ``return_candidates``.
    """
    timers = timers if timers is not None else PhaseTimers()
    engine = resolve_engine(engine)
    bucket_size = resolve_bucket_size(bucket_size, engine)
    num_shards = mesh.shape[AXIS]
    npad_local = points_sharded.shape[0] // num_shards
    point_group = _effective_group(point_group, npad_local, bucket_size, engine)
    builds = _ring_program.cache_info().misses
    mapped, kernel, partitioned = _ring_program(
        mesh, k, float(max_radius), engine, query_tile, point_tile,
        bucket_size, num_shards, point_group, score_dtype,
        bool(return_candidates))
    if _ring_program.cache_info().misses != builds:
        timers.count("ring.program_builds")
    total_rounds = ring_total_rounds(num_shards)
    timers.count("ring.id_decode_folds",
                 _folds_in_rounds(0, total_rounds, num_shards)
                 if return_candidates else 0)

    sharding = NamedSharding(mesh, P(AXIS))
    with timers.phase("ring.stage"):
        points_sharded = jax.device_put(points_sharded, sharding)
        ids_sharded = jax.device_put(ids_sharded, sharding)
    args = (points_sharded, ids_sharded)
    if partitioned:
        # tiled path: the log2(B) partition sort passes compile ONCE outside
        # the fused program instead of once per level inside it
        with timers.phase("ring.partition"):
            args += (partition_sharded(points_sharded, ids_sharded, mesh,
                                       bucket_size),)
    with timers.phase("ring.launch"):
        outs = mapped(*args)
    dists, tiles = outs[0], outs[-1]
    # one device's share of what the program makes its shard from; every
    # round but the last rotates
    local = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((a.shape[0] // num_shards,)
                                       + a.shape[1:], a.dtype),
        args[2] if partitioned else args)
    exchange_bytes = (num_shards * (total_rounds - 1)
                      * _rotation_bytes_of(engine, point_group, local))
    timers.count("ring.exchange_bytes", exchange_bytes)
    out = (dists,)
    if return_candidates:
        out += (CandidateState(*outs[1:3]),)
    if return_stats:
        with timers.phase("ring.wait"):
            slots = np.asarray(tiles).reshape(num_shards, total_rounds + 1)
        tiles_total = int(slots[:, :-1].sum())
        warm_passes = int(slots[:, -1].sum())
        timers.count("ring.warm_fold_passes", warm_passes)
        stats = _ring_stats(
            kernel, tiles_total
            + _warm_tiles(engine, npad_local, bucket_size, num_shards),
            bucket_size,
            num_shards * num_shards * npad_local * npad_local,
            q_rows=npad_local, p_rows=npad_local,
            point_group=point_group, warm_fold_passes=warm_passes)
        out += (dict(stats, exchange_bytes=exchange_bytes,
                     neighbor_ids=bool(return_candidates)),)
    return out if len(out) > 1 else out[0]


def ring_knn_stepwise(points_sharded: jnp.ndarray, ids_sharded: jnp.ndarray,
                      k: int, mesh, *, max_radius: float = jnp.inf,
                      engine: str = "auto", query_tile: int = 2048,
                      point_tile: int = 2048, bucket_size: int = 0,
                      point_group: int = 0, score_dtype: str = "f32",
                      checkpoint_dir: str | None = None,
                      checkpoint_every: int = 1,
                      max_rounds: int | None = None,
                      return_candidates: bool = False,
                      return_stats: bool = False):
    """``ring_knn`` with host-controlled rounds + checkpoint/resume.

    Identical results to ``ring_knn`` (literally the same ``_make_ring_fns``
    per-round pieces), but the round loop runs on the host — one jitted
    shard_map step per round — so the persistent heaps and the resident
    rotating shard can be snapshotted between rounds and a preempted run
    resumed at the exact round it lost. The reference cannot do this (one
    pass, output only at the end, SURVEY.md §5); its candidate buffer is the
    natural checkpoint state and here it literally is the checkpoint.

    The checkpoint fingerprint includes a sampled digest of the input data;
    a successful full run clears its checkpoint so a later run cannot
    silently reuse stale results. ``max_rounds`` stops early (state saved if
    checkpointing), for staged runs and interruption tests.

    Returns f32[R*Npad] k-th-NN distances (numpy), shard-major like
    ``ring_knn``.
    """
    from mpi_cuda_largescaleknn_tpu.utils import checkpoint as ckpt

    engine = resolve_engine(engine)
    bucket_size = resolve_bucket_size(bucket_size, engine)
    num_shards = mesh.shape[AXIS]
    spec = P(AXIS)
    check_vma = not engine.startswith("pallas")
    npad_local = points_sharded.shape[0] // num_shards
    point_group = _effective_group(point_group, npad_local, bucket_size, engine)

    def smap(fn, n_in, out_structs):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(spec,) * n_in, out_specs=out_structs,
            check_vma=check_vma))

    sharding = NamedSharding(mesh, spec)
    pts = jax.device_put(points_sharded, sharding)
    ids = jax.device_put(ids_sharded, sharding)

    fp = None
    resuming = False
    if checkpoint_dir:
        fp = ckpt.fingerprint(
            n=int(pts.shape[0]), k=int(k), shards=num_shards, engine=engine,
            max_radius=float(max_radius), bucket_size=bucket_size,
            # key present only when active (G>1): G1 runs keep
            # resumability of checkpoints written before the knob
            # existed. Since the round-5 retune, pallas DEFAULT runs
            # resolve to G2 and so do record the key — older default
            # checkpoints need the explicit flags resolve_bucket_size's
            # docstring names.
            **({"point_group": point_group} if point_group > 1 else {}),
            # key present only for non-default scoring: f32 checkpoints
            # written before the knob existed stay resumable
            **({"score_dtype": score_dtype} if score_dtype != "f32" else {}),
            query_tile=query_tile, point_tile=point_tile, ring="bidir",
            data=ckpt.data_digest(points_sharded, ids_sharded))
        # decide resume BEFORE init: a resumed run's heap comes from the
        # checkpoint, and the warm start's [S,S]-per-bucket work would be
        # computed only to be thrown away
        resuming = ckpt.peek_round(checkpoint_dir, fp) is not None

    init_fn, round_fn, final_fn, _sif, _qif, init_from_q, _qfq, kernel = \
        _make_ring_fns(k, max_radius, engine, query_tile, point_tile,
                       bucket_size, num_shards, warm_start=not resuming,
                       point_group=point_group, score_dtype=score_dtype)

    warm = None
    if init_from_q is not None:
        q_parts = partition_sharded(pts, ids, mesh, bucket_size)
        stationary, pair, heap, warm = smap(init_from_q, 1,
                                            (spec,) * 4)(q_parts)
    else:
        stationary, pair, heap = smap(init_fn, 2,
                                      (spec, spec, spec))(pts, ids)

    step = smap(_pair_step_fn(round_fn), 5, (spec, spec, spec, spec, spec))
    step_last = smap(_pair_step_fn(round_fn, rotate=False), 5,
                     (spec, spec, spec, spec, spec))

    start = 0
    if checkpoint_dir:
        got = ckpt.load_pytree(checkpoint_dir, fp, (pair, heap), sharding)
        if got is not None:
            start, (pair, heap) = got

    total_rounds = ring_total_rounds(num_shards)
    tiles_parts = []  # device arrays; materialized ONCE after the loop so
    rounds_run = 0    # the non-stats path keeps its async round dispatch
    stop = (total_rounds if max_rounds is None
            else min(max_rounds, total_rounds))
    rnd_arr = jax.device_put(np.full(num_shards, start, np.int32), sharding)
    for r in range(start, stop):
        fn = step_last if r == total_rounds - 1 else step
        f_state, b_state, heap, tiles, rnd_arr = fn(
            stationary, pair[0], pair[1], heap, rnd_arr)
        pair = (f_state, b_state)
        if return_stats:
            tiles_parts.append(tiles)
        rounds_run += 1
        if checkpoint_dir and ((r + 1) % checkpoint_every == 0
                               or r + 1 == stop):
            ckpt.save_pytree(checkpoint_dir, r + 1, (pair, heap), fp)

    dists, hd2, hidx = smap(
        lambda s, h: final_fn(s, h, npad_local), 2,
        (spec, spec, spec))(stationary, heap)
    if checkpoint_dir and stop == total_rounds:
        # done: clear so a later (possibly different-data) run in the same
        # dir can never resume past its own work
        ckpt.clear(checkpoint_dir)
    out = (np.asarray(dists),)
    if return_candidates:
        out += (CandidateState(hd2, hidx),)
    if return_stats:
        tiles_total = int(np.sum([np.asarray(t).sum() for t in tiles_parts]))
        if not resuming:
            # the warm start ran in THIS session (a resumed run's heap
            # already carries it — its tiles belong to the first session).
            # Guarded on the same flag that gated the warm start, NOT on
            # start == 0: a checkpoint that passes peek_round but vanishes
            # before load leaves start at 0 with a COLD round 0, and the
            # kernel then counts the self-bucket tiles itself
            tiles_total += _warm_tiles(engine, npad_local, bucket_size,
                                       num_shards)
        # analytic fold count for flat engines, exact for resumed
        # sessions too (round 0 and the even-R antipodal round fold once)
        folds = _folds_in_rounds(start, stop, num_shards)
        out += (_ring_stats(
            kernel, tiles_total, bucket_size,
            folds * num_shards * npad_local * npad_local,
            q_rows=npad_local, p_rows=npad_local,
            point_group=point_group,
            warm_fold_passes=0 if warm is None else int(np.sum(warm))),)
    return out if len(out) > 1 else out[0]


def ring_knn_chunked(points_sharded: jnp.ndarray, ids_sharded: jnp.ndarray,
                     k: int, mesh, *, chunk_rows: int,
                     max_radius: float = jnp.inf, engine: str = "auto",
                     query_tile: int = 2048, point_tile: int = 2048,
                     bucket_size: int = 0, point_group: int = 0,
                     score_dtype: str = "f32",
                     checkpoint_dir: str | None = None,
                     checkpoint_every: int = 1,
                     max_chunks: int | None = None,
                     pipeline_depth: int = 2,
                     merge: str = "host",
                     return_candidates: bool = False,
                     return_stats: bool = False):
    """``ring_knn`` with the query side streamed in fixed-size chunks.

    The memory wall at reference scale is the candidate heaps, not the
    points: N*k*8 bytes (SURVEY.md §7 hard part #4 — at k=100 the heaps are
    ~67x the size of the points, which is why the reference moves trees, not
    heaps). This driver keeps every device's FULL tree shard resident (N/R
    points) but holds heaps for only ``chunk_rows`` queries per device at a
    time: per chunk, the whole R-round ring runs against the same rotating
    shards — after R ``ppermute`` rounds each shard is back home, so the next
    chunk starts from clean state with zero re-setup. Peak heap memory drops
    from Npad*k to chunk_rows*k per device at the cost of R rotations per
    chunk (tree bytes are the small term: the reference's own trade).

    Every chunk is padded to the same ``chunk_rows`` shape, so all chunks
    share one compiled step. With ``checkpoint_dir``, completed chunks'
    results are persisted and a relaunch resumes at the first unfinished
    chunk (coarser-grained than ring_knn_stepwise's per-round snapshots, and
    far smaller state: results, not heaps).

    Host/device pipelining (``pipeline_depth``, default 2): chunk c+1's
    host staging (sentinel-pad + partition dispatch) runs while chunk c's
    rounds are still in flight, and chunk c's result fetch (the only
    blocking host sync in the loop) is deferred until up to
    ``pipeline_depth`` chunks are pending — so the device never idles
    waiting for numpy. Results are bit-identical at any depth (the pipeline
    reorders nothing); depth 1 restores the fully serialized loop. Each
    pending chunk holds one extra set of result buffers on device
    (~``R * chunk_rows * k * 8`` bytes with candidates), the usual
    double-buffering cost. A due checkpoint forces a full drain first, so
    snapshots only ever record fully materialized chunks.

    Merge placement (``merge``, default ``host``): ``host`` is the ring —
    per chunk, tree shards rotate R times past stationary per-device query
    heaps, and each device's heap ends global with no cross-shard merge at
    all. ``device`` replaces the rotation with the serving engine's
    replicate-traverse-merge shape: the whole chunk is REPLICATED to every
    device, each traverses only its own resident shard (zero ``ppermute``
    rotations of tree data, one program dispatch per chunk instead of
    R//2+1 stepped rounds), and the R partial candidate states reduce to
    the final answer in-program (``device_merge_final``'s reduce-scatter)
    before ``extract_final_result`` — the deferred per-chunk fetch then
    carries final rows only. Result and candidate DISTANCES are bit-identical to
    the ring's; at equal distances the two strategies order neighbor ids
    differently (the ring in fold-arrival order — own shard first, per
    device — the device merge in ascending (shard, slot) order, the
    serving engine's discipline), both exact top-k. The trade: candidate states
    hold ALL R*chunk_rows chunk queries per device (R x the ring's heap
    memory) and the queries ride one coarse prune bucket, so device merge
    wins at SMALL chunks — the round-dispatch-bound regime — while the
    ring's fine-bucketed prune wins large ones. ``auto`` resolves like the
    engine's (``resolve_merge``: device on power-of-two meshes, host with
    a logged warning otherwise). Both placements run multi-host: the chunk
    is staged sharded (each host uploads its own rows) and the device-merge
    program all_gathers it, so ``device_merge_final``'s reduction runs on
    the GLOBAL pod-mesh axis and each host fetches only its 1/R slices of
    the pod-final rows.

    Returns like ``ring_knn``: f32[R*Npad] shard-major distances (numpy),
    plus (dist2, idx) candidate arrays when ``return_candidates``.
    """
    from mpi_cuda_largescaleknn_tpu.core.types import PAD_SENTINEL
    from mpi_cuda_largescaleknn_tpu.utils import checkpoint as ckpt

    engine = resolve_engine(engine)
    bucket_size = resolve_bucket_size(bucket_size, engine)
    num_shards = mesh.shape[AXIS]
    merge = resolve_merge(merge, num_shards)
    _init, round_fn, final_fn, shard_init_fn, query_init_fn, _ifq, \
        query_from_q, kernel = _make_ring_fns(
            k, max_radius, engine, query_tile, point_tile, bucket_size,
            num_shards, score_dtype=score_dtype)
    dim = int(points_sharded.shape[-1])
    spec = P(AXIS)
    check_vma = not engine.startswith("pallas")
    sharding = NamedSharding(mesh, spec)

    # multi-host: the input is a GLOBAL sharded jax.Array; each host sees
    # (and chunks) only its addressable blocks, checkpoints its own rows,
    # and returns {mesh position: rows} instead of the flat global vector
    # no host could hold at reference scale
    multi = jax.process_count() > 1
    if multi:
        if not isinstance(points_sharded, jax.Array):
            raise ValueError("multi-host chunked ring needs global sharded "
                             "jax.Arrays (see cli/multihost.py)")
        npad_local = points_sharded.shape[0] // num_shards
        pts_glob, ids_glob = points_sharded, ids_sharded

        def blocks(garr, width):
            out = {}
            for sh in garr.addressable_shards:
                pos = int(sh.index[0].start) // npad_local
                out[pos] = np.asarray(sh.data).reshape((npad_local,) + width)
            return out

        pts_b = blocks(pts_glob, (dim,))
        ids_b = blocks(ids_glob, ())
    else:
        points_sharded = np.asarray(points_sharded, np.float32)
        ids_sharded = np.asarray(ids_sharded, np.int32)
        npad_local = points_sharded.shape[0] // num_shards
        pts_glob = jax.device_put(points_sharded, sharding)
        ids_glob = jax.device_put(ids_sharded, sharding)
        pts_g3 = points_sharded.reshape(num_shards, npad_local, dim)
        ids_g2 = ids_sharded.reshape(num_shards, npad_local)
        pts_b = {s: pts_g3[s] for s in range(num_shards)}
        ids_b = {s: ids_g2[s] for s in range(num_shards)}

    my_pos = sorted(pts_b)
    n_my = len(my_pos)
    n_chunks = max(1, -(-npad_local // chunk_rows))
    point_group = _effective_group(point_group, npad_local, bucket_size, engine)

    def to_global(local, global_rows):
        if multi:
            return jax.make_array_from_process_local_data(
                sharding, local, (global_rows,) + local.shape[1:])
        return jax.device_put(local, sharding)

    def smap(fn, n_in, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * n_in,
                                     out_specs=out_specs,
                                     check_vma=check_vma))

    def local_rows(garr, width):
        if multi:
            rows = np.empty((n_my, chunk_rows) + width, garr.dtype)
            got = {int(sh.index[0].start) // chunk_rows:
                   np.asarray(sh.data) for sh in garr.addressable_shards}
            for j, s in enumerate(my_pos):
                rows[j] = got[s].reshape((chunk_rows,) + width)
            return rows
        return np.asarray(garr).reshape((num_shards, chunk_rows) + width)

    if query_from_q is not None:
        # tiled: hoisted partitions — ONE compiled sort pass shared by all
        # levels of the shard partition, another shared by every chunk's
        # query partition (see partition_sharded). The RESIDENT side is
        # group-coarsened (wide tiles); no warm start / skip-self here —
        # chunk queries fold the whole resident shard including their own
        # points exactly once, the normal self-inclusion. Coarsening runs
        # per device inside shard_map: group boundaries never straddle
        # shards (B_local is a power of two and the group is clamped to
        # it), and the reshape stays communication-free by construction
        qf = partition_sharded(pts_glob, ids_glob, mesh, bucket_size)
        if point_group > 1:
            qf = smap(partial(coarsen_buckets, group=point_group),
                      1, spec)(qf)
        shard0 = (qf.pts, qf.ids, qf.lower, qf.upper)
        _heapq = smap(query_from_q, 1, (spec, spec))

        def qinit(qp_glob, qi_glob):
            qq = partition_sharded(qp_glob, qi_glob, mesh, bucket_size)
            return _heapq(qq)
    else:
        shard0 = smap(shard_init_fn, 2, spec)(pts_glob, ids_glob)
        qinit = smap(query_init_fn, 2, (spec, spec))

    step = smap(_pair_step_fn(round_fn), 5, (spec, spec, spec, spec, spec))
    step_last = smap(_pair_step_fn(round_fn, rotate=False), 5,
                     (spec, spec, spec, spec, spec))
    final = smap(lambda s, h: final_fn(s, h, chunk_rows), 2,
                 (spec, spec, spec))
    total_rounds = ring_total_rounds(num_shards)
    rnd0 = to_global(np.zeros(n_my, np.int32), num_shards)

    use_tiled = query_from_q is not None
    if merge == "device":
        # replicate-traverse-merge chunk program (one dispatch per chunk):
        # the replicated chunk traverses each device's OWN resident shard,
        # the R partial candidate states tree-reduce in-program, and each
        # device emits its 1/R slice of the final rows — same global row
        # layout as the ring path, so drain/checkpoint logic is shared.
        # The chunk is staged SHARDED (each host uploads only its own rows,
        # exactly like the ring path) and replicated by an in-program
        # all_gather, so the same program runs on a single host and on the
        # global pod mesh — the reduction collectives below already ride
        # whatever axis the mesh spans (ICI or DCN)
        qrows = num_shards * chunk_rows
        # the merge program dispatches its own kernel: that is the one
        # this driver's stats report
        kernel, update_m = (
            _tiled_engine_fn(engine) if use_tiled
            else _engine_fn(engine, query_tile, point_tile, score_dtype))

        def merge_body(*args):
            q_local, shard = args[-1], args[:-1]
            q = jax.lax.all_gather(q_local, AXIS, tiled=True)
            heap = pvary(init_candidates(qrows, k, max_radius))
            if use_tiled:
                valid = q[:, 0] < PAD_SENTINEL / 2
                qids = jnp.where(valid,
                                 jnp.arange(qrows, dtype=jnp.int32), -1)
                qlo = jnp.min(jnp.where(valid[:, None], q, jnp.inf), axis=0)
                qhi = jnp.max(jnp.where(valid[:, None], q, -jnp.inf), axis=0)
                qb = BucketedPoints(q[None], qids[None], qlo[None],
                                    qhi[None], qids[None])
                resident = BucketedPoints(shard[0], shard[1], shard[2],
                                          shard[3], shard[1])
                st, tiles = update_m(heap, qb, resident, with_stats=True,
                                     score_dtype=score_dtype)
            else:
                st = update_m(heap, q, *shard)
                tiles = pvary(jnp.zeros((), jnp.int32))
            dists, d2f, idxf = device_merge_final(st, num_shards)
            return dists, d2f, idxf, tiles[None]

        merge_prog = jax.jit(jax.shard_map(
            merge_body, mesh=mesh,
            in_specs=(spec,) * (5 if use_tiled else 3),
            out_specs=(spec, spec, spec, spec), check_vma=check_vma))

    out_d = np.full((n_my, npad_local), np.inf, np.float32)
    out_hd2 = (np.full((n_my, npad_local, k), np.inf, np.float32)
               if return_candidates else None)
    out_idx = (np.full((n_my, npad_local, k), -1, np.int32)
               if return_candidates else None)

    fp = None
    start_chunk = 0
    ckpt_dir = checkpoint_dir
    if checkpoint_dir:
        if multi:
            # per-host checkpoint state under a shared dir: each host owns
            # (and resumes) exactly its rows; my_pos rides in the
            # fingerprint so a relaunch with a different host->shard map
            # starts fresh instead of mixing rows
            ckpt_dir = os.path.join(checkpoint_dir,
                                    f"host{jax.process_index()}")
        fp = ckpt.fingerprint(
            n=num_shards * npad_local, k=int(k), shards=num_shards,
            engine=engine, max_radius=float(max_radius),
            bucket_size=bucket_size, chunk_rows=chunk_rows,
            query_tile=query_tile, point_tile=point_tile,
            candidates=bool(return_candidates),
            # key present only for device merge: host-merge checkpoints
            # written before the knob existed stay resumable (results are
            # bit-identical across modes, but resuming records the plan)
            **({"merge": merge} if merge == "device" else {}),
            **({"score_dtype": score_dtype} if score_dtype != "f32" else {}),
            my_pos=",".join(str(s) for s in my_pos),
            data=ckpt.data_digest(
                np.concatenate([pts_b[s].reshape(-1) for s in my_pos]),
                np.concatenate([ids_b[s].reshape(-1) for s in my_pos])))
        got = ckpt.load_ring_state(ckpt_dir, fp)
        if got is not None:
            start_chunk, arrs = got
            out_d = arrs["out_d"]
            if return_candidates:
                out_hd2, out_idx = arrs["out_hd2"], arrs["out_idx"]

    # absolute cap, consistent with the stepwise drivers' max_rounds
    stop_chunk = (n_chunks if max_chunks is None
                  else min(max_chunks, n_chunks))
    tiles_parts = []  # materialized once at the end (see ring_knn_stepwise)
    chunks_run = 0
    depth = max(1, int(pipeline_depth))
    pending = []  # chunks dispatched on device, results not yet fetched

    def stage(c):
        # host staging for chunk c: sentinel-pad, upload, dispatch the query
        # partition + heap init (ring) or the replicated chunk upload
        # (device merge). Everything device-side here is async dispatch, so
        # staging chunk c+1 overlaps chunk c's in-flight work
        lo = c * chunk_rows
        hi = min(lo + chunk_rows, npad_local)
        qp = np.full((n_my, chunk_rows, dim), PAD_SENTINEL, np.float32)
        qi = np.full((n_my, chunk_rows), -1, np.int32)
        for j, s in enumerate(my_pos):
            qp[j, :hi - lo] = pts_b[s][lo:hi]
            qi[j, :hi - lo] = ids_b[s][lo:hi]
        if merge == "device":
            # ids stay host-side: result neighbor ids come from the
            # resident shard, and validity rides the sentinel coordinates;
            # each host uploads only ITS rows — the program all_gathers
            return lo, hi, to_global(qp.reshape(-1, dim),
                                     num_shards * chunk_rows), None
        stationary, heap = qinit(
            to_global(qp.reshape(-1, dim), num_shards * chunk_rows),
            to_global(qi.reshape(-1), num_shards * chunk_rows))
        return lo, hi, stationary, heap

    def drain_one():
        # materialize the OLDEST pending chunk (the only blocking sync in
        # the loop) — later chunks' rounds are already dispatched, so the
        # device stays busy while the host copies rows out
        lo, hi, d, hd2, hidx = pending.pop(0)
        out_d[:, lo:hi] = local_rows(d, ())[:, :hi - lo]
        if return_candidates:
            out_hd2[:, lo:hi] = local_rows(hd2, (k,))[:, :hi - lo]
            out_idx[:, lo:hi] = local_rows(hidx, (k,))[:, :hi - lo]

    staged = stage(start_chunk) if start_chunk < stop_chunk else None
    for c in range(start_chunk, stop_chunk):
        lo, hi, stationary, heap = staged
        chunks_run += 1
        if merge == "device":
            # one dispatch: traverse own shard, tree-reduce, slice final
            d, hd2, hidx, tiles = merge_prog(*shard0, stationary)
            if return_stats:
                tiles_parts.append(tiles)
        else:
            # pristine pair each chunk: the resident original never
            # rotates, so the traveling copies can be discarded wherever
            # the sweep ends
            pair = (shard0, shard0)
            rnd_arr = rnd0
            for _r in range(total_rounds):
                fn = step_last if _r == total_rounds - 1 else step
                f_state, b_state, heap, tiles, rnd_arr = fn(
                    stationary, pair[0], pair[1], heap, rnd_arr)
                pair = (f_state, b_state)
                if return_stats:
                    tiles_parts.append(tiles)
            d, hd2, hidx = final(stationary, heap)
        pending.append((lo, hi, d, hd2, hidx))
        # drain down to depth-1 pending BEFORE staging the next chunk: at
        # depth 1 that is exactly the serialized loop (fetch, then stage —
        # no extra device buffers held), while deeper pipelines fetch the
        # oldest chunk with this chunk's rounds still in flight, keeping the
        # result copy off the next dispatch's critical path
        while len(pending) >= depth:
            drain_one()
        if c + 1 < stop_chunk:
            # double-buffer: pre-pad + pre-partition the next chunk while
            # this chunk's rounds run
            staged = stage(c + 1)
        ckpt_due = checkpoint_dir and ((c + 1) % checkpoint_every == 0
                                       or c + 1 == stop_chunk)
        while pending and ckpt_due:
            drain_one()
        if ckpt_due:
            # snapshots are O(completed results) — at the target regime
            # (many chunks, k=100) keep checkpoint_every coarse enough that
            # write time stays small vs a chunk's ring
            arrs = {"out_d": out_d}
            if return_candidates:
                arrs.update(out_hd2=out_hd2, out_idx=out_idx)
            ckpt.save_ring_state(ckpt_dir, c + 1, arrs, fp)
    while pending:
        drain_one()

    def chunk_stats(tiles_total: int) -> dict:
        # shared by the single- and multi-host returns (only the tile-count
        # materialization differs between them)
        if merge == "device" and use_tiled:
            # device-merge tiles span the chunk's single query bucket
            # (R*chunk_rows rows), not the ring's fine query buckets
            _, s_p = choose_buckets(npad_local, bucket_size)
            return {"engine": kernel,
                    "pair_evals": tiles_total * num_shards * chunk_rows
                    * s_p * point_group,
                    "tiles": tiles_total, "flops_per_pair": 8,
                    "warm_fold_passes": 0}
        return _ring_stats(
            kernel, tiles_total, bucket_size,
            chunks_run * num_shards * num_shards * chunk_rows * npad_local,
            q_rows=chunk_rows, p_rows=npad_local, point_group=point_group)

    if checkpoint_dir and stop_chunk == n_chunks:
        ckpt.clear(ckpt_dir)
    if multi:
        out = ({s: out_d[j] for j, s in enumerate(my_pos)},)
        if return_candidates:
            out += (CandidateState(
                {s: out_hd2[j] for j, s in enumerate(my_pos)},
                {s: out_idx[j] for j, s in enumerate(my_pos)}),)
        if return_stats:
            # per-host view: only addressable shards' counts (a pod-global
            # sum would need a collective nobody asked to pay for here)
            out += (chunk_stats(int(np.sum([
                np.sum([np.asarray(sh.data).sum()
                        for sh in t.addressable_shards])
                for t in tiles_parts]))),)
        return out if len(out) > 1 else out[0]
    dists = out_d.reshape(-1)
    out = (dists,)
    if return_candidates:
        out += (CandidateState(out_hd2.reshape(-1, k),
                               out_idx.reshape(-1, k)),)
    if return_stats:
        out += (chunk_stats(int(np.sum([np.asarray(t).sum()
                                        for t in tiles_parts]))),)
    return out if len(out) > 1 else out[0]


def measure_exchange_bandwidth(mesh, npad_local: int, *, reps: int = 10,
                               bucket_size: int = 0,
                               engine: str = "auto") -> dict:
    """MEASURED per-round ring-rotation bandwidth (not analytic).

    Times the jitted rotation of a representative shard pytree (same
    shapes/dtypes the ring actually rotates — BOTH counter-rotating copies,
    one ``ppermute`` per direction, as the bidirectional ring moves them)
    in isolation: best of ``reps`` ``block_until_ready`` wall-clock deltas,
    minus a no-comm control (the same jitted program with the ppermutes
    replaced by an elementwise touch) to remove dispatch overhead. Every
    device sends its whole shard in each direction per round
    (``2 * shard_bytes``); the reported per-link figure counts both
    directions of the full-duplex link. The reference's equivalent transfer
    is the ring Isend/Irecv of tree buffers
    (unorderedDataVariant.cu:189-193), which it never times (SURVEY.md §5).
    """
    import time as _time

    engine = resolve_engine(engine)
    bucket_size = resolve_bucket_size(bucket_size, engine)
    num_shards = mesh.shape[AXIS]
    use_tiled = engine in ("tiled", "auto", "pallas_tiled")
    if use_tiled:
        nb, s = choose_buckets(npad_local, bucket_size)
        shard_local = (jnp.zeros((nb, s, 3), jnp.float32),
                       jnp.zeros((nb, s), jnp.int32),
                       jnp.zeros((nb, 3), jnp.float32),
                       jnp.zeros((nb, 3), jnp.float32))
    else:
        shard_local = (jnp.zeros((npad_local, 3), jnp.float32),
                       jnp.zeros((npad_local,), jnp.int32))
    shard_bytes = sum(int(a.size) * a.dtype.itemsize for a in shard_local)
    fwd = [(i, (i + 1) % num_shards) for i in range(num_shards)]
    spec = P(AXIS)
    sharding = NamedSharding(mesh, spec)
    glob = tuple(
        jax.device_put(jnp.broadcast_to(a[None], (num_shards,) + a.shape)
                       .reshape((num_shards * a.shape[0],) + a.shape[1:]),
                       sharding)
        for a in shard_local)

    bwd = [(i, (i - 1) % num_shards) for i in range(num_shards)]

    def rotate(*shard):
        # both directions in flight, as in the real ring round
        return (tuple(jax.lax.ppermute(a, AXIS, fwd) for a in shard)
                + tuple(jax.lax.ppermute(a, AXIS, bwd) for a in shard))

    def touch(*shard):
        return (tuple(a + jnp.zeros((), a.dtype) for a in shard)
                + tuple(a + jnp.ones((), a.dtype) for a in shard))

    n_in = len(shard_local)
    rot = jax.jit(jax.shard_map(rotate, mesh=mesh, in_specs=(spec,) * n_in,
                                out_specs=(spec,) * (2 * n_in)))
    ctl = jax.jit(jax.shard_map(touch, mesh=mesh, in_specs=(spec,) * n_in,
                                out_specs=(spec,) * (2 * n_in)))

    def best_of(fn):
        out = fn(*glob)  # compile + warm
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(reps):
            t0 = _time.perf_counter()
            out = fn(*glob)
            jax.block_until_ready(out)
            best = min(best, _time.perf_counter() - t0)
        return best

    t_rot = best_of(rot)
    t_ctl = best_of(ctl)
    t_comm = max(t_rot - t_ctl, 1e-9)
    round_bytes_per_device = 2 * shard_bytes  # both directions, full duplex
    return {
        "method": "jitted bidirectional ppermute rotation, best of %d, "
                  "minus no-comm control" % reps,
        "platform": jax.devices()[0].platform,
        "num_shards": num_shards,
        "shard_bytes": shard_bytes,
        "round_seconds": round(t_comm, 6),
        "control_seconds": round(t_ctl, 6),
        "exchange_GB_per_sec_per_link": round(
            round_bytes_per_device / t_comm / 1e9, 3),
        "exchange_GB_per_sec_aggregate": round(
            num_shards * round_bytes_per_device / t_comm / 1e9, 3),
    }
