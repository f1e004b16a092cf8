"""Bounds-pruned early-exit engine — the prepartitioned variant's core.

The reference (prePartitionedDataVariant.cu:304-357) runs, per round: each
rank picks the *nearest not-yet-seen* peer whose bounding box is closer than
its current worst k-th-NN distance (``computeMyPeer``, :157-174), Allgathers
the picks, exits globally when every pick is -1 (:320-322), then transfers
trees point-to-point (one Irecv, fan-out Isends, :324-345) and re-queries.
The win on spatially-coherent data: most ranks stop after visiting a handful
of neighbors instead of all R.

TPU-native re-design (NOT a translation of the MPI matching): data-dependent
point-to-point routing does not exist under XLA's static SPMD model — and on
an ICI torus it buys little, because a neighbor ``ppermute`` is cheap and
overlaps with compute. What actually costs time is the *query kernel*. So:

- shards rotate on the same static ring as parallel/ring.py;
- each device *skips the kernel* (``lax.cond``) for any arriving shard whose
  bounding box is at least its current worst radius away — the same prune
  predicate as ``computeMyPeer`` (box-distance >= cutoff, :168), evaluated
  against ``all_gather``-ed bounds (the reference Allgathers bounds the same
  way, :290-291);
- the loop ends when a ``pmax`` over "does any device still need any unseen
  shard" goes to zero — the global early exit (:320-322);
- the per-query worst-radius reduction that the reference maintains with a
  managed-memory float + ``cukd::atomicMax`` (:91-94, :297-298) is a masked
  ``jnp.max`` over the candidate state each round.

The ring is BIDIRECTIONAL: two copies of each tree counter-rotate (one
``ppermute`` forward, one backward), so after round r every device has seen
all shards within ±r of its own. Round-4 measurement motivated this: with a
forward-only ring (arrival round of shard s = (me - s) mod R) on
spatially-sorted partitions, a device's following neighbor (index i+1)
arrived LAST (round R-1) even though spatial locality makes it needed on
round one — so the early exit never fired (on the CPU fixture at 64
shards: 64 rounds vs 33 for the reference's best schedule; after this
change, 21; not measured on the chip). Needed peers cluster around ±max_offset, and
counter-rotation reaches offset o in round o: the loop runs at most
floor(R/2)+1 rounds and the exit fires after max needed offset rounds.
Total bytes moved are the same (2 trees/round x ~R/2 rounds); per-round
link traffic doubles.

Trade-off vs the reference, stated honestly: the reference visits peers
nearest-first (tightening the prune radius fastest) and can stop after its
*own* needs are met; the bidirectional ring visits in ±1, ±2, ... order —
which IS nearest-first in shard-index space, the right proxy when
partitions are spatially sorted — runs until the *slowest* device is done,
pays only a skipped-kernel's cost (~0) for unneeded shards, and keeps every
transfer on neighbor ICI links instead of arbitrary point-to-point routes.
The per-rank stop the reference gets for free (:315-322) is recovered at
direction granularity: each counter-rotating copy's ``ppermute`` is gated
off (``lax.cond``) once no device needs a future delivery from that
direction, so tail rounds — including the otherwise-discarded final
rotation — stop paying exchange bytes (``rotations_run`` in the stats
measures exactly what was paid).
Visiting two peers per round, it can finish in ceil(max_needed/2)+1 rounds
where the reference's one-tree-per-round matching needs max_needed+1.

Like the ring, the fused on-device loop (``demand_knn``) and the host-stepped
checkpointable driver (``demand_knn_stepwise``) share one set of builders
(``_make_demand_fns``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from mpi_cuda_largescaleknn_tpu.core.types import (
    PAD_SENTINEL,
    CandidateState,
    aabb_box_distance,
    aabb_of_points,
)
from mpi_cuda_largescaleknn_tpu.ops.build_tree import build_tree
from mpi_cuda_largescaleknn_tpu.ops.candidates import (
    current_worst_radius,
    extract_final_result,
    init_candidates,
)
from mpi_cuda_largescaleknn_tpu.ops.partition import (
    BucketedPoints,
    coarsen_buckets,
    scatter_back,
)
from mpi_cuda_largescaleknn_tpu.ops.tiled import warm_start_self
from mpi_cuda_largescaleknn_tpu.parallel.mesh import AXIS, pvary
from mpi_cuda_largescaleknn_tpu.parallel.ring import (
    _effective_group,
    _engine_fn,
    _tiled_engine_fn,
    partition_sharded,
    resolve_bucket_size,
    resolve_engine,
    ring_total_rounds,
)


def gathered_bounds_fn(pts_local):
    """Per-shard AABB of real points, Allgather-ed to every device
    (the reference's Allgather of 6-float boxes, :290-291). Runs inside
    shard_map."""
    valid = pts_local[:, 0] < PAD_SENTINEL / 2
    box = aabb_of_points(pts_local, valid)
    all_lower = jax.lax.all_gather(box.lower, AXIS)   # [R, 3]
    all_upper = jax.lax.all_gather(box.upper, AXIS)
    return all_lower, all_upper


def _make_demand_fns(k, max_radius, engine, query_tile, point_tile,
                     bucket_size, num_shards, warm_start=False,
                     point_group=1):
    """Per-round builders shared by the fused, stepwise, and chunked demand
    drivers. Returns (init_fn, round_fn, final_fn, shard_init_fn,
    query_init_fn, init_from_q, query_init_from_q, kernel) — ``kernel`` is
    the engine name ring._engine_fn/_tiled_engine_fn dispatched, which the
    drivers report as ``stats["engine"]``;
    for tiled engines the first/fourth/fifth are None (the partition is
    hoisted — use the *_from_q forms with ring.partition_sharded), for flat
    engines the *_from_q forms are None.

    - init_fn(pts_local, ids_local) -> (ctx, shard_state, heap)
      ctx = (stationary queries, replicated box distances, arrival schedule,
      heap validity) — everything the loop reads but never writes.
    - shard_init_fn(pts_local, ids_local) -> (shard_state, all_lo, all_hi)
      (tree side + the Allgather-ed full-shard bounds)
    - query_init_fn(qpts, qids, all_lo, all_hi) -> (ctx, heap)
      (query side only — may be a chunk of the slab; its prune distances
      use the CHUNK's own box, which is tighter than the slab's)
    - round_fn(ctx, shard_state, heap, rnd, counts)
        -> (next_shard, new_heap, rnd+1, counts', keep_going)
      counts is a per-device i32[2]: [query kernels run, direction-rotations
      run] — the second times shard_bytes is the exchange traffic actually
      paid, since each direction's ppermute is gated off once no device
      needs future deliveries from it. keep_going is replicated (pmax) —
      usable as a while_loop predicate on device or read on the host by the
      stepwise driver.
    - final_fn(ctx, heap) -> (dists, hd2, hidx) in input-row order.
    """
    use_tiled = engine in ("tiled", "pallas_tiled")
    kernel, fold = (_tiled_engine_fn(engine) if use_tiled
                    else _engine_fn(engine, query_tile, point_tile))
    # warm start needs query bucket b == resident bucket b in round 0 (the
    # self-join init path on one shared partition) and pays only where
    # fold passes are the cost — the Pallas kernel, not the sort-merge
    # twin (measured regression on the twin: see ring.py _make_ring_fns)
    warm_start = warm_start and engine == "pallas_tiled"
    use_tree = engine == "tree"
    fwd = [(i, (i + 1) % num_shards) for i in range(num_shards)]
    bwd = [(i, (i - 1) % num_shards) for i in range(num_shards)]

    def shard_init_fn(pts_local, ids_local):
        if use_tree:
            shard_state = build_tree(pts_local, ids_local)
        else:
            shard_state = (pts_local, ids_local)
        return (shard_state,) + gathered_bounds_fn(pts_local)

    def query_init_from_q(qpts, q, all_lower, all_upper):
        # bucketed structures: queries and the rotating shard both carry
        # per-bucket bounds; the tile-level prune inside the tiled update
        # subsumes most of the shard-level skip, which remains as a
        # cheap outer gate
        heap_rows = q.pts.shape[0] * q.pts.shape[1]
        heap_valid = (q.ids >= 0).reshape(-1)
        return _query_ctx(qpts, q, heap_rows, heap_valid,
                          all_lower, all_upper)

    def query_init_fn(qpts, qids, all_lower, all_upper):
        return _query_ctx(qpts, qpts, qpts.shape[0],
                          qpts[:, 0] < PAD_SENTINEL / 2,
                          all_lower, all_upper)

    def _query_ctx(qpts, stationary, heap_rows, heap_valid,
                   all_lower, all_upper):
        me = jax.lax.axis_index(AXIS)
        valid = qpts[:, 0] < PAD_SENTINEL / 2
        # min distance from MY queries' box to every shard's box
        qbox = aabb_of_points(qpts, valid)
        box_dist = aabb_box_distance(qbox.lower[None, :], qbox.upper[None, :],
                                     all_lower, all_upper)  # [R]
        # counter-rotating copies: shard s reaches this device in round
        # min((me - s) mod R, (s - me) mod R)
        off = jnp.mod(me - jnp.arange(num_shards), num_shards)
        arrival_round = jnp.minimum(off, num_shards - off)

        heap = pvary(init_candidates(heap_rows, k, max_radius))
        ctx = (stationary, box_dist, arrival_round, heap_valid)
        return ctx, heap

    def init_from_q(pts_local, q):
        # point side: group-coarsened view of the same partition (tight
        # fine-bucket prune radius, point_group x wider resident tiles)
        pc = coarsen_buckets(q, point_group)
        shard_state = (pc.pts, pc.ids, pc.lower, pc.upper)
        all_lower, all_upper = gathered_bounds_fn(pts_local)
        ctx, heap = query_init_from_q(pts_local, q, all_lower, all_upper)
        if warm_start:
            # exact top-k of every query's own (containing) resident
            # bucket (ops/tiled.py, rows stay in fine order — the
            # coarsening is a reshape); round 0's own-shard visit then
            # masks that bucket
            heap = warm_start_self(pc, k, max_radius)
        return ctx, (shard_state, shard_state), heap

    def init_fn(pts_local, ids_local):
        shard_state, all_lower, all_upper = shard_init_fn(pts_local,
                                                          ids_local)
        ctx, heap = query_init_fn(pts_local, ids_local, all_lower, all_upper)
        # the rotating "tree" travels twice: forward and backward copies
        return ctx, (shard_state, shard_state), heap

    if use_tiled:
        init_fn = shard_init_fn = query_init_fn = None
    else:
        init_from_q = query_init_from_q = None

    def round_fn(ctx, shard_pair, heap, rnd, counts):
        stationary, box_dist, arrival_round, heap_valid = ctx
        me = jax.lax.axis_index(AXIS)
        f_state, b_state = shard_pair
        total = ring_total_rounds(num_shards)

        # Per-direction rotation gating (the per-rank stop semantics of
        # prePartitionedDataVariant.cu:315-322, recovered at direction
        # granularity): each direction's ppermute runs only while SOME device
        # still needs a FUTURE delivery from it. The need test uses the
        # ROUND-ENTRY radius — no new fold result, so XLA can still overlap
        # the rotation with this round's kernels — and radii only shrink, so
        # a False is sticky: skipping the rotation can never starve a later
        # visit (the visit gate below would evaluate False for those arrivals
        # anyway). Forward delivers offsets 1..R//2 (rounds < total);
        # backward the same except the dup round (even R) is forward-only.
        idx = jnp.arange(num_shards)
        off_f = jnp.mod(me - idx, num_shards)   # fwd copy of s arrives then
        off_b = jnp.mod(idx - me, num_shards)
        cur_radius = current_worst_radius(heap, heap_valid)
        bwd_total = total - 1 if num_shards % 2 == 0 else total
        # one pmax for both direction bits: two sequential scalar
        # collectives here would sit on the critical path ahead of the
        # very rotations the gate exists to cheapen
        need = jax.lax.pmax(jnp.stack([
            jnp.any((off_f > rnd) & (off_f < total)
                    & (box_dist < cur_radius)),
            jnp.any((off_b > rnd) & (off_b < bwd_total)
                    & (box_dist < cur_radius))]).astype(jnp.int32), AXIS)
        need_f, need_b = need[0] > 0, need[1] > 0

        def rot(perm):
            return lambda s: jax.tree.map(
                lambda a: jax.lax.ppermute(a, AXIS, perm), s)

        nxt = (jax.lax.cond(need_f, rot(fwd), lambda s: s, f_state),
               jax.lax.cond(need_b, rot(bwd), lambda s: s, b_state))

        src_f = jnp.mod(me - rnd, num_shards)
        src_b = jnp.mod(me + rnd, num_shards)
        dup = src_f == src_b  # round 0 (own shard) and round R/2 (R even)

        def run(shard_state, heap, sskip=None):
            if use_tiled:
                resident = BucketedPoints(
                    shard_state[0], shard_state[1], shard_state[2],
                    shard_state[3], shard_state[1])
                st = fold(heap, stationary, resident,
                          skip_self=sskip, self_group=point_group)
            else:
                st = fold(heap, stationary, *shard_state)
            return st.dist2, st.idx

        # visit iff the resident shard's box is strictly closer than the
        # current worst k-th distance (computeMyPeer's prune, :168);
        # round 0 is the own shard at distance 0. The forward visit
        # tightens the radius before the backward visit is decided — the
        # same greedy tightening the reference gets from nearest-first.
        visit_f = jax.lax.dynamic_index_in_dim(
            box_dist, src_f, keepdims=False) < cur_radius
        # round 0's forward arrival is the own shard: with a warm-started
        # heap its self buckets are already folded and must be masked
        sskip = ((rnd == 0).astype(jnp.int32) if warm_start else None)
        hd2, hidx = jax.lax.cond(visit_f,
                                 lambda _: run(f_state, heap, sskip),
                                 lambda _: (heap.dist2, heap.idx), None)
        heap1 = CandidateState(hd2, hidx)

        radius1 = current_worst_radius(heap1, heap_valid)
        visit_b = (~dup) & (jax.lax.dynamic_index_in_dim(
            box_dist, src_b, keepdims=False) < radius1)
        hd2, hidx = jax.lax.cond(visit_b, lambda _: run(b_state, heap1),
                                 lambda _: (heap1.dist2, heap1.idx), None)
        new_heap = CandidateState(hd2, hidx)
        # counts = [kernels run, direction-rotations run] per device; the
        # second measures the bytes actually moved (x shard_bytes) so the
        # gating's savings are a reported stat, not a claim
        counts = counts + jnp.stack(
            [visit_f.astype(jnp.int32) + visit_b.astype(jnp.int32),
             need_f.astype(jnp.int32) + need_b.astype(jnp.int32)])

        # global early exit: does ANY device still need ANY unseen shard?
        new_radius = current_worst_radius(new_heap, heap_valid)
        i_need_more = jnp.any((arrival_round > rnd) & (box_dist < new_radius))
        keep_going = jax.lax.pmax(i_need_more.astype(jnp.int32), AXIS) > 0
        return nxt, new_heap, rnd + 1, counts, keep_going

    def final_fn(ctx, heap):
        stationary, _box, _arr, _hv = ctx
        dists = extract_final_result(heap)
        if use_tiled:
            q = stationary
            # scatter back to input-row order over B*S rows (an upper bound
            # on the padded slab size — input rows live in [0, npad), the
            # drivers trim with _trim_rows)
            rows = q.pos.shape[0] * q.pos.shape[1]
            kk = heap.dist2.shape[-1]
            bs = (q.num_buckets, q.bucket_size)
            dists = scatter_back(dists.reshape(bs), q.pos, rows,
                                 fill=jnp.inf)
            hd2 = scatter_back(heap.dist2.reshape(bs + (kk,)), q.pos, rows,
                               fill=jnp.inf)
            hidx = scatter_back(heap.idx.reshape(bs + (kk,)), q.pos, rows,
                                fill=-1)
            return dists, hd2, hidx
        return dists, heap.dist2, heap.idx

    return (init_fn, round_fn, final_fn, shard_init_fn, query_init_fn,
            init_from_q, query_init_from_q, kernel)


# one bidirectional-sweep definition for both engines (ring.py)
demand_total_rounds = ring_total_rounds


def demand_knn(points_sharded: jnp.ndarray, ids_sharded: jnp.ndarray, k: int,
               mesh, *, max_radius: float = jnp.inf,
               engine: str = "auto", query_tile: int = 2048,
               point_tile: int = 2048, bucket_size: int = 0,
               point_group: int = 0, return_stats: bool = False):
    """Bounds-pruned kNN over pre-partitioned shards on a 1-D mesh (fused
    on-device ``lax.while_loop``).

    Same data contract as ring_knn (shard-major padded rows); additionally
    returns, when ``return_stats``, the number of rounds executed and the
    per-device count of query kernels actually run — the observability the
    reference only exposes as per-round stdout prints (:306).
    """
    engine = resolve_engine(engine)
    bucket_size = resolve_bucket_size(bucket_size, engine)
    num_shards = mesh.shape[AXIS]
    npad = points_sharded.shape[0] // num_shards
    point_group = _effective_group(point_group, npad, bucket_size, engine)
    init_fn, round_fn, final_fn, _sif, _qif, init_from_q, _qfq, kernel = \
        _make_demand_fns(k, max_radius, engine, query_tile, point_tile,
                         bucket_size, num_shards, warm_start=True,
                         point_group=point_group)

    def body(pts_local, ids_local, q_local=None):
        if q_local is not None:
            ctx, shard_state, heap = init_from_q(pts_local, q_local)
        else:
            ctx, shard_state, heap = init_fn(pts_local, ids_local)

        total = demand_total_rounds(num_shards)

        def cond(carry):
            _s, _h2, _hi, rnd, keep_going, _n = carry
            return (rnd < total) & keep_going

        def loop_body(carry):
            shard_state, hd2, hidx, rnd, _kg, counts = carry
            nxt, heap2, rnd2, counts2, keep_going = round_fn(
                ctx, shard_state, CandidateState(hd2, hidx), rnd, counts)
            return nxt, heap2.dist2, heap2.idx, rnd2, keep_going, counts2

        # rnd and keep_going are uniform across devices (keep_going is a pmax
        # reduction, hence replicated); counts is per-device
        init = (shard_state, heap.dist2, heap.idx,
                jnp.int32(0), jnp.bool_(True),
                pvary(jnp.zeros(2, jnp.int32)))
        _, hd2, hidx, rounds, _, counts = jax.lax.while_loop(
            cond, loop_body, init)
        d, hd2, hidx = final_fn(ctx, CandidateState(hd2, hidx))
        d, hd2, hidx = _trim_rows(d, hd2, hidx, npad)
        return d, hd2, hidx, pvary(rounds)[None], counts[None]

    spec = P(AXIS)
    n_args = 3 if init_from_q is not None else 2
    # see ring.py: pallas engines need check_vma=False under shard_map
    mapped = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * n_args,
        out_specs=(spec, spec, spec, spec, spec),
        check_vma=not engine.startswith("pallas")))

    sharding = NamedSharding(mesh, spec)
    points_sharded = jax.device_put(points_sharded, sharding)
    ids_sharded = jax.device_put(ids_sharded, sharding)
    if init_from_q is not None:
        q_parts = partition_sharded(points_sharded, ids_sharded, mesh,
                                    bucket_size)
        dists, hd2, hidx, rounds, counts = mapped(points_sharded, ids_sharded,
                                                  q_parts)
    else:
        dists, hd2, hidx, rounds, counts = mapped(points_sharded, ids_sharded)
    if return_stats:
        counts = np.asarray(counts)                   # [R, 2]
        return dists, CandidateState(hd2, hidx), {
            "engine": kernel, "rounds": rounds, "kernels_run": counts[:, 0],
            "rotations_run": counts[:, 1]}
    return dists


def _trim_rows(d, hd2, hidx, npad):
    """Cut the tiled path's scatter target (B*S rows) down to the caller's
    padded slab size; flat paths are already npad rows."""
    return d[:npad], hd2[:npad], hidx[:npad]


def demand_knn_stepwise(points_sharded: jnp.ndarray,
                        ids_sharded: jnp.ndarray, k: int, mesh, *,
                        max_radius: float = jnp.inf, engine: str = "auto",
                        query_tile: int = 2048, point_tile: int = 2048,
                        bucket_size: int = 0, point_group: int = 0,
                        checkpoint_dir: str | None = None,
                        checkpoint_every: int = 1,
                        max_rounds: int | None = None,
                        return_stats: bool = False):
    """``demand_knn`` with host-controlled rounds + checkpoint/resume.

    Same builders as the fused driver; the early-exit predicate (a replicated
    pmax) is returned from each jitted step and read on the host, so the
    adaptive round count survives intact. Checkpoint state = (round, rotating
    shard, heaps, per-device kernel counts); the prelude (bounds gather,
    arrival schedule, bucketing) is recomputed deterministically on resume.
    """
    from mpi_cuda_largescaleknn_tpu.utils import checkpoint as ckpt

    engine = resolve_engine(engine)
    bucket_size = resolve_bucket_size(bucket_size, engine)
    num_shards = mesh.shape[AXIS]
    npad = points_sharded.shape[0] // num_shards
    point_group = _effective_group(point_group, npad, bucket_size, engine)
    spec = P(AXIS)
    check_vma = not engine.startswith("pallas")
    sharding = NamedSharding(mesh, spec)

    def smap(fn, n_in, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * n_in,
                                     out_specs=out_specs,
                                     check_vma=check_vma))

    pts = jax.device_put(np.asarray(points_sharded, np.float32), sharding)
    ids = jax.device_put(np.asarray(ids_sharded, np.int32), sharding)

    fp = None
    resuming = False
    if checkpoint_dir:
        fp = ckpt.fingerprint(
            n=int(pts.shape[0]), k=int(k), shards=num_shards, engine=engine,
            max_radius=float(max_radius), bucket_size=bucket_size,
            # key present only when active (G>1): G1 runs keep
            # resumability of checkpoints written before the knob
            # existed; pallas DEFAULT runs resolve to G2 since the
            # round-5 retune (flags to resume older ones:
            # ring.resolve_bucket_size docstring)
            **({"point_group": point_group} if point_group > 1 else {}),
            query_tile=query_tile, point_tile=point_tile,
            # -rg: counts carry [kernels, rotations] — older single-counter
            # checkpoints must not resume into the new shape
            kind="demand-bidir-rg",
            data=ckpt.data_digest(points_sharded, ids_sharded))
        # a resumed run's heap comes from the checkpoint: skip the warm
        # start's per-bucket top-k work instead of computing and
        # discarding it (see ring_knn_stepwise)
        resuming = ckpt.peek_round(checkpoint_dir, fp) is not None

    init_fn, round_fn, final_fn, _sif, _qif, init_from_q, _qfq, kernel = \
        _make_demand_fns(k, max_radius, engine, query_tile, point_tile,
                         bucket_size, num_shards, warm_start=not resuming,
                         point_group=point_group)

    if init_from_q is not None:
        q_parts = partition_sharded(pts, ids, mesh, bucket_size)
        ctx, shard_state, heap = smap(init_from_q, 2,
                                      (spec, spec, spec))(pts, q_parts)
    else:
        ctx, shard_state, heap = smap(init_fn, 2,
                                      (spec, spec, spec))(pts, ids)
    nrun = jax.device_put(np.zeros((num_shards, 2), np.int32), sharding)

    def step_fn(ctx, shard_state, heap, rnd_arr, nrun):
        # rnd rides as a per-device [1] array so every input is sharded;
        # keep_going comes back the same way (replicated by construction)
        nxt, heap2, rnd2, counts2, keep_going = round_fn(
            ctx, shard_state, heap, rnd_arr[0], nrun[0])
        return (nxt, heap2, rnd2[None], counts2[None],
                keep_going.astype(jnp.int32)[None])

    step = smap(step_fn, 5, (spec, spec, spec, spec, spec))

    start = 0
    if checkpoint_dir:
        got = ckpt.load_pytree(checkpoint_dir, fp,
                               (shard_state, heap, nrun), sharding)
        if got is not None:
            start, (shard_state, heap, nrun) = got

    rnd_arr = jax.device_put(
        np.full(num_shards, start, np.int32), sharding)
    rounds_done = start
    total = demand_total_rounds(num_shards)
    stop = total if max_rounds is None else min(max_rounds, total)
    # "completed" = nothing left to do (early exit fired, or every shard
    # visited) — as opposed to merely truncated by the max_rounds cap
    completed = start >= total
    finished = start >= stop
    while not finished:
        shard_state, heap, rnd_arr, nrun, kg = step(
            ctx, shard_state, heap, rnd_arr, nrun)
        rounds_done += 1
        keep_going = bool(np.asarray(kg)[0])
        completed = (not keep_going) or rounds_done >= total
        finished = completed or rounds_done >= stop
        # completed runs skip the final save (their checkpoint is cleared
        # below — saving it would be wasted sync + disk IO, and a stale
        # save would make a relaunch redo already-pruned rounds); runs
        # truncated by the round cap always save so a relaunch resumes
        if checkpoint_dir and ((rounds_done % checkpoint_every == 0
                                and not completed)
                               or (finished and not completed)):
            ckpt.save_pytree(checkpoint_dir, rounds_done,
                             (shard_state, heap, nrun), fp)

    d, hd2, hidx = smap(
        lambda c, h: _trim_rows(*final_fn(c, h), npad), 2,
        (spec, spec, spec))(ctx, heap)
    # completed runs clear their checkpoint (stale-state safety); runs
    # truncated by max_rounds keep it so a relaunch resumes
    if checkpoint_dir and completed:
        ckpt.clear(checkpoint_dir)
    if return_stats:
        counts = np.asarray(nrun)                     # [R, 2]
        return (np.asarray(d), CandidateState(np.asarray(hd2),
                                              np.asarray(hidx)),
                {"engine": kernel,
                 "rounds": np.full(num_shards, rounds_done),
                 "kernels_run": counts[:, 0],
                 "rotations_run": counts[:, 1]})
    return np.asarray(d)


def demand_knn_chunked(points_sharded: jnp.ndarray,
                       ids_sharded: jnp.ndarray, k: int, mesh, *,
                       chunk_rows: int, max_radius: float = jnp.inf,
                       engine: str = "auto", query_tile: int = 2048,
                       point_tile: int = 2048, bucket_size: int = 0,
                       point_group: int = 0,
                       checkpoint_dir: str | None = None,
                       checkpoint_every: int = 1,
                       return_candidates: bool = False,
                       return_stats: bool = False):
    """``demand_knn`` with the query side streamed in fixed-size chunks.

    The k=100-at-scale memory wall applies to the prepartitioned pipeline
    exactly as to the ring (heaps are N*k*8 bytes; at BASELINE config #4's
    full size they exceed HBM): keep every device's full shard resident,
    hold heaps for only ``chunk_rows`` queries at a time. Each chunk runs
    its own bidirectional early-exit loop from a PRISTINE shard pair (the
    original never rotates, so an early exit can leave the traveling
    copies anywhere without corrupting the next chunk), with prune
    distances from the chunk's own (tighter) bounding box. All chunks
    share one compiled step. With ``checkpoint_dir``, completed chunks'
    results persist and a relaunch resumes at the first unfinished chunk.

    Returns f32[R*Npad] shard-major distances (numpy), plus
    (CandidateState, stats) per the flags; ``stats['rounds']`` is the
    per-chunk round count list, ``kernels_run`` sums over chunks.
    """
    from mpi_cuda_largescaleknn_tpu.core.types import PAD_SENTINEL as _PS
    from mpi_cuda_largescaleknn_tpu.utils import checkpoint as ckpt

    engine = resolve_engine(engine)
    bucket_size = resolve_bucket_size(bucket_size, engine)
    num_shards = mesh.shape[AXIS]
    (_ifn, round_fn, final_fn, shard_init_fn, query_init_fn, _ifq,
     query_init_from_q, kernel) = \
        _make_demand_fns(k, max_radius, engine, query_tile, point_tile,
                         bucket_size, num_shards)
    spec = P(AXIS)
    check_vma = not engine.startswith("pallas")
    sharding = NamedSharding(mesh, spec)

    points_sharded = np.asarray(points_sharded, np.float32)
    ids_sharded = np.asarray(ids_sharded, np.int32)
    npad = points_sharded.shape[0] // num_shards
    n_chunks = max(1, -(-npad // chunk_rows))
    total_rounds = demand_total_rounds(num_shards)

    def smap(fn, n_in, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * n_in,
                                     out_specs=out_specs,
                                     check_vma=check_vma))

    pts = jax.device_put(points_sharded, sharding)
    ids = jax.device_put(ids_sharded, sharding)
    if query_init_from_q is not None:
        # bounds via a tiny smap; shard0 aliases the hoisted partition's
        # arrays directly instead of round-tripping the whole point set
        # through a jit for a second device copy. The resident side is
        # group-coarsened per device (wide tiles, no skip-self needed —
        # see ring_knn_chunked)
        q_full = partition_sharded(pts, ids, mesh, bucket_size)
        pgc = _effective_group(point_group, npad, bucket_size, engine)
        if pgc > 1:
            q_full = smap(partial(coarsen_buckets, group=pgc),
                          1, spec)(q_full)
        all_lo, all_hi = smap(gathered_bounds_fn, 1, (spec, spec))(pts)
        shard0 = (q_full.pts, q_full.ids, q_full.lower, q_full.upper)
        _qinit_q = smap(query_init_from_q, 4, (spec, spec))

        def qinit(qp_glob, qi_glob, lo, hi):
            qq = partition_sharded(qp_glob, qi_glob, mesh, bucket_size)
            return _qinit_q(qp_glob, qq, lo, hi)
    else:
        shard0, all_lo, all_hi = smap(shard_init_fn, 2, (spec, spec, spec))(
            pts, ids)
        qinit = smap(query_init_fn, 4, (spec, spec))

    def step_fn(ctx, f_state, b_state, heap, rnd_arr, nrun):
        nxt, heap2, rnd2, counts2, keep_going = round_fn(
            ctx, (f_state, b_state), heap, rnd_arr[0], nrun[0])
        return (nxt[0], nxt[1], heap2, rnd2[None], counts2[None],
                keep_going.astype(jnp.int32)[None])

    step = smap(step_fn, 6, (spec,) * 6)
    final = smap(lambda c, h: _trim_rows(*final_fn(c, h), chunk_rows), 2,
                 (spec, spec, spec))

    dim = int(points_sharded.shape[-1])
    pts_g = points_sharded.reshape(num_shards, npad, dim)
    ids_g = ids_sharded.reshape(num_shards, npad)
    out_d = np.full((num_shards, npad), np.inf, np.float32)
    # candidate arrays are N*k*12 bytes — the exact memory wall this
    # driver exists to avoid — so they materialize only on request
    out_hd2 = (np.full((num_shards, npad, k), np.inf, np.float32)
               if return_candidates else None)
    out_idx = (np.full((num_shards, npad, k), -1, np.int32)
               if return_candidates else None)
    rounds_per_chunk: list[int] = []
    nrun_total = np.zeros((num_shards, 2), np.int64)

    fp = None
    start_chunk = 0
    if checkpoint_dir:
        fp = ckpt.fingerprint(
            n=int(points_sharded.shape[0]), k=int(k), shards=num_shards,
            engine=engine, max_radius=float(max_radius),
            bucket_size=bucket_size, chunk_rows=chunk_rows,
            query_tile=query_tile, point_tile=point_tile,
            kind="demand-chunked-rg", candidates=bool(return_candidates),
            data=ckpt.data_digest(points_sharded, ids_sharded))
        got = ckpt.load_ring_state(checkpoint_dir, fp)
        if got is not None:
            start_chunk, arrs = got
            out_d = arrs["out_d"]
            rounds_per_chunk = arrs["rounds_per_chunk"].tolist()
            nrun_total = arrs["nrun_total"]
            if return_candidates:
                out_hd2, out_idx = arrs["out_hd2"], arrs["out_idx"]

    for c in range(start_chunk, n_chunks):
        lo = c * chunk_rows
        hi = min(lo + chunk_rows, npad)
        qp = np.full((num_shards, chunk_rows, dim), _PS, np.float32)
        qi = np.full((num_shards, chunk_rows), -1, np.int32)
        qp[:, :hi - lo] = pts_g[:, lo:hi]
        qi[:, :hi - lo] = ids_g[:, lo:hi]
        ctx, heap = qinit(
            jax.device_put(qp.reshape(-1, dim), sharding),
            jax.device_put(qi.reshape(-1), sharding), all_lo, all_hi)
        # pristine pair each chunk: the resident original never rotates
        f_state, b_state = shard0, shard0
        rnd_arr = jax.device_put(np.zeros(num_shards, np.int32), sharding)
        nrun = jax.device_put(np.zeros((num_shards, 2), np.int32), sharding)
        rounds = 0
        while rounds < total_rounds:
            f_state, b_state, heap, rnd_arr, nrun, kg = step(
                ctx, f_state, b_state, heap, rnd_arr, nrun)
            rounds += 1
            if not bool(np.asarray(kg)[0]):
                break
        rounds_per_chunk.append(rounds)
        nrun_total += np.asarray(nrun).astype(np.int64)
        d, hd2, hidx = final(ctx, heap)
        out_d[:, lo:hi] = np.asarray(d).reshape(
            num_shards, chunk_rows)[:, :hi - lo]
        if return_candidates:
            out_hd2[:, lo:hi] = np.asarray(hd2).reshape(
                num_shards, chunk_rows, k)[:, :hi - lo]
            out_idx[:, lo:hi] = np.asarray(hidx).reshape(
                num_shards, chunk_rows, k)[:, :hi - lo]
        # never save the final chunk: the clear below follows immediately,
        # and a stale completed-run checkpoint would otherwise survive a
        # preemption in between (cf. the stepwise driver's same rule); a
        # relaunch then simply redoes the last chunk
        if checkpoint_dir and (c + 1) % checkpoint_every == 0 \
                and c + 1 < n_chunks:
            arrs = {"out_d": out_d,
                    "rounds_per_chunk": np.asarray(rounds_per_chunk,
                                                   np.int64),
                    "nrun_total": nrun_total}
            if return_candidates:
                arrs.update(out_hd2=out_hd2, out_idx=out_idx)
            ckpt.save_ring_state(checkpoint_dir, c + 1, arrs, fp)

    if checkpoint_dir:
        ckpt.clear(checkpoint_dir)
    dists = out_d.reshape(-1)
    cands = (CandidateState(out_hd2.reshape(-1, k), out_idx.reshape(-1, k))
             if return_candidates else None)
    if return_stats:
        return dists, cands, {
            "engine": kernel, "rounds": np.asarray(rounds_per_chunk),
            "kernels_run": nrun_total[:, 0],
            "rotations_run": nrun_total[:, 1]}
    return dists
