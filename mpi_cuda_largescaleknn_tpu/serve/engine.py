"""Resident query engine: the index lives on the mesh, queries fly through.

Build once: the point set is slab-sharded over the 1-D device mesh and
median-split into spatial buckets per shard (the same partition the ring
drivers hoist out of their jits — ``partition_sharded``). Query forever: an
incoming batch is padded to its shape bucket, replicated to every device,
traversed against each device's resident buckets (the exact nearest-first
prune of ops/tiled.py — each shard returns its local top-k), and the
R-way partial candidates are reduced to the global top-k either on the
host or — the default on power-of-two meshes — inside the SPMD program
itself (``merge="device"``): a reduce-scatter over candidate states (one
``all_to_all`` + a width-R*k ``top_k``; the log2(R) ``ppermute`` tree of
ops/candidates.py ``tree_merge_candidates`` is the all-reduce sibling —
parallel/ring.py ``device_merge_final``), after which each
device emits its 1/R slice of the FINAL answer, so ``complete`` fetches a
single [Q, k] result instead of R partials (k*R x fewer bytes over the
host link) and the numpy merge leaves the critical path entirely. The two
placements are bit-identical, ties included — the tree's operand ordering
reproduces the host's stable shard-major sort (TPU-KNN, arXiv:2206.14286:
keep the top-k reduction on-device as regular VPU work; EQuARX,
arXiv:2506.17615: cross-device reductions belong inside the program).

Shape discipline is the whole point (TPU-KNN, arXiv:2206.14286: peak
throughput needs large *fixed* shapes): query programs are AOT-compiled
(``jit(...).lower(...).compile()``) per power-of-two batch bucket, so a
served shape can NEVER silently retrace — an unexpected shape raises, and
``compile_count`` is an honest counter the recompile-freedom tests assert
on. ``auto`` resolves to the Pallas kernel on TPU / the XLA twin elsewhere
(parallel/ring.py resolve_engine); a runtime Pallas failure degrades to the
twin via ``degrade()`` (driven by serve/admission.py).

Pipelining: ``query`` is split into an async ``dispatch`` (stage + pad +
queue the AOT executable call on a single launch thread, so dispatch
returns right after staging even where PJRT executes synchronously) and a
blocking ``complete`` (resolve the launch future, fetch, R-way merge,
slice) so the batcher can keep batch t+1's device traversal in flight while
batch t's host merge runs — the serving-side analogue of the ring's
communication/compute overlap.

Pod mode: when the mesh spans processes (``jax.process_count() > 1``, the
batch CLIs' ``jax.distributed`` lifecycle), the SAME engine runs on every
host over the ONE global mesh — each host uploads only its addressable
index slabs, stages the (front-end-replicated) batch from its own copy,
and fetches only its 1/R row slices of the pod-final answer
(``complete_slices``; requires ``merge="device"`` — host merge would need
partials no process can address). The query program is byte-identical to
the single-host one; only the axis the reduction collectives ride grows
(serve/frontend.py).

Query locality: the whole speedup of the tiled traversal is the per-bucket
prune radius (ops/tiled.py ``_worst2``) — and a served batch of scattered
user queries wrapped in ONE bucket widens that radius to the max over the
batch, degrading toward brute force. So ``dispatch`` first sorts each batch
by 3-D Morton code over the index bounding box (utils/math.py; pads sort
last) and the query program traverses ``query_buckets`` contiguous slices
of the sorted batch, each with its own in-program AABB and radius — sorted
order makes contiguous slices spatially tight, so the prune actually bites.
``complete`` un-permutes the merged rows, so callers never observe the
sort. The traversal runs with the canonical (dist2, id) tie order
(ops/candidates.py ``merge_candidates(canonical=True)``), which makes the
result bit-identical across bucket geometries — ``query_buckets=1``
(unsorted, the old behavior) and any B produce the same bytes, ties
included (tests/test_query_locality.py). The tile counters the traversal
already carries are surfaced as ``tiles_executed`` / ``tiles_skipped``
engine counters (and /metrics), so the locality win is a number
(tests/test_query_locality.py ``TestTileAccounting``).
"""

from __future__ import annotations

import math
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from mpi_cuda_largescaleknn_tpu.analysis import guarded_by
from mpi_cuda_largescaleknn_tpu.core.types import PAD_SENTINEL
from mpi_cuda_largescaleknn_tpu.models.sharding import (
    pad_and_flatten,
    slab_aabbs,
    slab_bounds,
)
from mpi_cuda_largescaleknn_tpu.obs.timers import (
    PhaseTimers,
    current_span_tags,
)
from mpi_cuda_largescaleknn_tpu.utils.math import next_pow2


class UnservableShapeError(ValueError):
    """A batch no shape bucket covers reached the engine (the admission
    layer should have rejected or split it)."""


class ExecutableCache:
    """Shared AOT-executable cache keyed by resident-set SHAPE CLASS.

    A compiled query program is specialized to the SHAPES of its resident
    operands, not their values — so engines whose resident arrays share a
    shape class (same per-shard padding, bucket geometry, dim, dtype; the
    tiered slab pool pads every slab engine to a common class exactly for
    this) can reuse ONE executable. The pool hands every slab engine the
    same cache; an eviction/re-promotion cycle then never recompiles, and
    ``compiles`` is the pool-wide recompile-freedom counter the streaming
    tests assert on (serve/slabpool.py)."""

    def __init__(self):
        self._cv = threading.Condition()
        # keys carry every program-identity component (engine, merge,
        # qpad, query buckets, score dtype, emit, k, radius, tie order,
        # dim) PLUS the resident arg shapes/dtypes — all reads and writes
        # under the lock (promotion thread vs stall-path builders)
        self._cache: guarded_by("_cv") = {}
        #: keys some caller is currently compiling — a concurrent miss
        #: WAITS for the build instead of paying a duplicate
        #: seconds-long XLA compile (and double-counting ``compiles``,
        #: the recompile-freedom number the tests pin)
        self._building: guarded_by("_cv") = set()
        self.compiles: guarded_by("_cv") = 0
        self.hits: guarded_by("_cv") = 0

    def get(self, key):
        """Return the cached executable, or None with the key CLAIMED
        for building — the caller then MUST ``put`` (or ``abort`` on
        failure) so parked waiters wake."""
        with self._cv:
            while True:
                exe = self._cache.get(key)
                if exe is not None:
                    self.hits += 1
                    return exe
                if key in self._building:
                    self._cv.wait(0.05)
                    continue
                self._building.add(key)
                return None

    def put(self, key, exe) -> None:
        with self._cv:
            self._cache.setdefault(key, exe)
            self._building.discard(key)
            self.compiles += 1
            self._cv.notify_all()

    def abort(self, key) -> None:
        """Release a claimed key after a failed compile (waiters retry)."""
        with self._cv:
            self._building.discard(key)
            self._cv.notify_all()

    def stats(self) -> dict:
        with self._cv:
            return {"programs": len(self._cache),
                    "compiles": self.compiles, "hits": self.hits,
                    "shapes": sorted({k[2] for k in self._cache})}


class _InFlightBatch:
    """A dispatched-but-uncompleted engine call (``dispatch`` -> ``complete``).

    ``fut`` resolves to the executable's result triple on the engine's
    launch thread — (d2, idx, tiles) per-shard partials under
    ``merge="host"``, the final (dists, idx, tiles) under
    ``merge="device"``; ``merge_mode`` records which, so ``complete``
    demuxes the right way. ``queries`` retains the ORIGINAL (unsorted) host
    batch so a completion-time failure (async Pallas errors surface at
    fetch, not at launch) can be replayed on the degraded twin — which
    replays under the engine's CURRENT merge mode, the twin contract being
    merge-placement-independent. ``engine_name`` records which engine
    DISPATCHED it — after a mid-stream degradation, stale handles are
    distinguishable from twin failures. ``perm`` is the Morton admission
    sort (None when sorting is off): row i of the staged batch is
    ``queries[perm[i]]``, so ``complete`` scatters results back through it.
    ``tiles_possible`` is the program's static tile-schedule ceiling — the
    skipped-tile counter's denominator. ``plan`` is the recall-SLO
    execution plan the batch was dispatched under (serve/recall.py;
    None = exact) — retained so a degradation replay re-runs the SAME
    plan and the completion layers can label the batch's tier.
    """

    __slots__ = ("queries", "n", "qpad", "engine_name", "merge_mode",
                 "fut", "t0", "perm", "tiles_possible", "plan")

    def __init__(self, queries, n, qpad, engine_name, merge_mode, fut, t0,
                 perm=None, tiles_possible=0, plan=None):
        self.queries = queries
        self.n = n
        self.qpad = qpad
        self.engine_name = engine_name
        self.merge_mode = merge_mode
        self.fut = fut
        self.t0 = t0
        self.perm = perm
        self.tiles_possible = tiles_possible
        self.plan = plan


class ResidentKnnEngine:
    """One resident sharded index + a family of fixed-shape query programs.

    Thread-compatibility: ``query`` is serialized by an internal lock — the
    micro-batcher is the intended (single) caller, but a direct caller must
    not corrupt the stats either.
    """

    def __init__(self, points: np.ndarray, k: int, *, mesh=None,
                 engine: str = "auto", bucket_size: int = 0,
                 max_radius: float = math.inf, max_batch: int = 1024,
                 min_batch: int = 8, merge: str = "auto",
                 query_buckets: int = 0, score_dtype: str = "f32",
                 id_offset: int = 0, emit: str = "final",
                 timers: PhaseTimers | None = None,
                 executable_cache: ExecutableCache | None = None,
                 pad_shard_rows: int = 0):
        import jax

        from mpi_cuda_largescaleknn_tpu.ops.distance import (
            mxu_min_dim,
            validate_score_dtype,
        )
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import AXIS, get_mesh
        from mpi_cuda_largescaleknn_tpu.parallel.ring import (
            resolve_bucket_size,
            resolve_engine,
            resolve_merge,
            resolve_query_buckets,
        )

        points = np.asarray(points, np.float32)
        if points.ndim != 2 or points.shape[1] < 1:
            raise ValueError(f"points must be [N, D], got {points.shape}")
        if k < 1:
            raise ValueError("k must be >= 1")
        min_batch = max(8, next_pow2(min_batch))
        max_batch = next_pow2(max_batch)

        if emit not in ("final", "candidates"):
            raise ValueError(f"emit must be 'final' or 'candidates', "
                             f"got {emit!r}")
        self.k = int(k)
        self.n_points = len(points)
        #: global row index of this engine's first point: a routed pod host
        #: (serve/frontend.py --routing bounds) serves one slab of a larger
        #: index, and its neighbor ids must be GLOBAL rows — the canonical
        #: (dist2, id) tie order then matches the replicate-everything pod
        #: bit for bit, because slab sharding keeps ids ascending by host
        self.id_offset = int(id_offset)
        #: what completions carry: "final" = the public (kth-dist, ids)
        #: contract; "candidates" = full per-candidate (dist2[Q,k], ids)
        #: rows — a PARTIAL result the routed front end folds across hosts
        #: (``complete_candidates``)
        self.emit = emit
        #: routed slab engines keep a host-side reference to their rows
        #: (a reference to the caller's array, not a copy — the slab is
        #: 1/H of the index, already resident in host RAM from loading):
        #: the slab-handoff pull path serves these on GET /slab_rows so a
        #: warm standby can adopt the slab from a surviving replica
        #: instead of re-reading the source file (serve/replica.py)
        self.host_points = points if emit == "candidates" else None
        #: point dimensionality — the whole ops/io/serve stack is D-generic
        #: (the matmul-form scorer is what makes high D affordable); only
        #: the Morton admission sort is 3-D-specific and disables itself
        self.dim = int(points.shape[1])
        self.max_radius = float(max_radius)
        self.mesh = mesh if mesh is not None else get_mesh(None)
        self.num_shards = self.mesh.shape[AXIS]
        self.engine_name = resolve_engine(engine)
        self.bucket_size = resolve_bucket_size(bucket_size, self.engine_name)
        self.merge_mode = resolve_merge(merge, self.num_shards)
        #: distance scoring mode, part of every AOT bucket key: "f32" =
        #: exact elementwise (VPU), "bf16" = matmul-form MXU score + exact
        #: f32 rescore (ops/distance.py). A mid-stream Pallas degradation
        #: keeps the mode — the XLA twin takes the same knob.
        #: ``score_mode`` is the EFFECTIVE path: below ``mxu_min_dim()``
        #: a bf16 request still scores exactly on the VPU (the matmul form
        #: cannot win there), and the per-mode tile counters follow the
        #: path that actually runs
        self.score_dtype = validate_score_dtype(score_dtype)
        self.score_mode = ("mxu" if (self.score_dtype == "bf16"
                                     and self.dim >= mxu_min_dim())
                           else "vpu")
        #: pod mode: the mesh spans processes — every host runs ONE engine
        #: over the same global mesh, dispatches IDENTICAL batches in the
        #: same order (the front end's contract), and fetches only its
        #: addressable 1/R row slices of the pod-final answer
        #: (``complete_slices``). Host merge would need remote partials no
        #: process can address, so the cross-host level REQUIRES the
        #: in-program reduction.
        self._multi = jax.process_count() > 1
        self.process_count = jax.process_count()
        self.process_index = jax.process_index()
        if self._multi and self.merge_mode != "device":
            raise ValueError(
                "multi-host serving requires the device-side merge (the R "
                "partial candidate blocks live on devices this process "
                "cannot address) — got merge="
                f"'{self.merge_mode}' on a {self.num_shards}-shard pod "
                "mesh; use merge='device' on a power-of-two mesh")
        if self.merge_mode == "device":
            # each device emits a 1/R slice of the merged result, so every
            # shape bucket must tile the mesh: both are powers of two, so
            # bucket >= R suffices. When R exceeds max_batch an explicit
            # 'device' is a config error; 'auto' quietly keeps the host
            # merge instead of failing a construction that host-merge
            # engines always served
            if self.num_shards > max_batch and merge == "auto":
                self.merge_mode = "host"
            else:
                min_batch = max(min_batch, self.num_shards)
        if max_batch < min_batch:
            raise ValueError(f"max_batch {max_batch} < min_batch {min_batch}"
                             + (" (device merge needs buckets >= num_shards)"
                                if min_batch == self.num_shards else ""))
        #: ascending power-of-two padded batch sizes; all client batch sizes
        #: in [1, max_batch] round up into one of these
        self.shape_buckets = [b for b in
                              (min_batch << i for i in range(64))
                              if b <= max_batch] or [min_batch]
        self.max_batch = self.shape_buckets[-1]
        #: query_buckets knob (0 = auto, 1 = single whole-batch bucket =
        #: the pre-locality behavior). Resolved per padded shape: the map
        #: below is part of each shape bucket's AOT program identity.
        #: Flat engines have no buckets to traverse, so they stay at 1.
        use_tiled = self.engine_name in ("tiled", "pallas_tiled")
        self.query_buckets_setting = int(query_buckets)
        self.query_buckets = {
            q: (resolve_query_buckets(query_buckets, q, self.k)
                if use_tiled and self.dim == 3 else 1)
            for q in self.shape_buckets}
        #: Morton admission: sort every dispatched batch by Z-order code
        #: over the index bbox (pads last), un-permuted at complete().
        #: Off when the caller pinned query_buckets=1 — that configuration
        #: IS the unsorted baseline the exactness tests and the locality
        #: bench compare against. The Morton encoder is 3-D (utils/math.py),
        #: so non-3-D indexes serve single-bucket unsorted batches — still
        #: exact, just without the locality prune.
        self.sort_queries = (use_tiled and self.query_buckets_setting != 1
                             and self.dim == 3)
        #: canonical (dist2, id) tie order inside the traversal — what
        #: makes results bit-identical across query bucket geometries. The
        #: boundary tie-fix routes ids through a f32 top_k (exact below
        #: 2**24; XLA:CPU's integer TopK is a scalar loop), so huge indices
        #: fall back to fold-arrival ties (distances stay exact; only
        #: equal-distance id CHOICES may then differ across geometries)
        self.canonical_ties = (use_tiled
                               and self.id_offset + self.n_points < (1 << 24))
        #: shared timers/counters sink: the tiered slab pool hands every
        #: slab engine ONE PhaseTimers so fetch/result/tile accounting
        #: accumulates across promotions and evictions instead of dying
        #: with each evicted engine (serve/slabpool.py)
        self.timers = timers if timers is not None else PhaseTimers()
        #: shared AOT cache (None = private per-engine dict only): slab
        #: engines of one pool share compiled programs per shape class
        self._exec_cache = executable_cache
        #: pad each local shard to at least this many rows — the slab
        #: pool's common shape class, so every slab engine lowers to
        #: identical program shapes (single-host engines only; pod mode
        #: already pads to the global max slab)
        self._pad_shard_rows = int(pad_shard_rows)
        self._lock = threading.Lock()
        # mutable engine identity: a mid-stream Pallas degradation
        # (degrade()) swaps engine_name while dispatches and /stats
        # scrapes run on other threads. The identity scalars live under
        # their OWN small lock (never held across an XLA compile) so a
        # /stats or /metrics scrape cannot block for the seconds-to-
        # minutes _get_executable holds _lock while compiling a cold
        # bucket (--no-warmup, post-degrade) — exactly when the health
        # monitor's scrape/rejoin probes most need an answer. _lock
        # still serializes dispatch/degrade/warmup, so identity reads
        # inside a _lock region stay mutually consistent; acquisition
        # order is always _lock -> _meta_lock (lskcheck-proven).
        self._meta_lock = threading.Lock()
        self.compile_count: guarded_by("_meta_lock") = 0
        self.degraded_reason: guarded_by("_meta_lock") = None
        self.engine_name: guarded_by("_meta_lock") = self.engine_name
        #: qpad per published executable (the stats compiled_shapes list,
        #: kept beside the scalars so scrapes never touch _executables)
        self._compiled_shapes: guarded_by("_meta_lock") = []
        #: engine name of the kernel the newest executable build dispatched
        #: (parallel/ring.py _tiled_engine_fn) — /stats "kernel"
        self.kernel: guarded_by("_meta_lock") = None
        #: (engine_name, merge_mode, qpad, B, score_dtype) -> AOT executable
        self._executables: guarded_by("_lock") = {}
        # launch pool: ``dispatch`` hands the executable call here and
        # returns after staging, so the dispatch stage never blocks on
        # device compute — even on backends whose PJRT client executes
        # synchronously (this container's CPU pin; TPU dispatch is natively
        # async and the hop is ~50us). The pool is the CPU stand-in for the
        # device's async program queue: 1 worker keeps launches strictly
        # FIFO; the server widens it to the pipeline depth so a depth-d
        # pipeline can keep d fixed-shape programs in flight (executions
        # are pure reads of the resident index, so concurrent launches
        # cannot race; result DELIVERY order is the batcher's FIFO queue)
        self._launch_workers = 1
        self._launch = ThreadPoolExecutor(max_workers=1,
                                          thread_name_prefix="knn-launch")

        with self.timers.phase("index_build"):
            self._build_index(points, jax)

    # ------------------------------------------------------------------ build

    def _build_index(self, points, jax):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from mpi_cuda_largescaleknn_tpu.parallel.mesh import (
            AXIS,
            my_mesh_positions,
        )
        from mpi_cuda_largescaleknn_tpu.parallel.ring import partition_sharded

        # index bounding box: the Morton admission sort's quantization
        # frame (queries outside it clamp to the faces — still ordered)
        self._index_lo = (points.min(axis=0) if len(points)
                          else np.zeros(self.dim))
        self._index_hi = (points.max(axis=0) if len(points)
                          else np.ones(self.dim))
        bounds = slab_bounds(len(points), self.num_shards)
        #: per-shard AABB + point count, computed ONCE at upload from the
        #: host-side slabs (exact — no sentinel rows to mask) and exposed on
        #: /stats: the pod front end's routing bounds table is assembled
        #: from these (serve/frontend.py PodBoundsTable). Per-SHARD boxes
        #: beat one whole-slab box: the router prunes on the min over a
        #: host's shard bounds, which is tighter than the union box's.
        self.shard_bounds = slab_aabbs(points, bounds)
        sharding = NamedSharding(self.mesh, P(AXIS))
        if self._multi:
            # pod mode: every host loads the same full point set (serving
            # indexes are small next to the heap/query traffic) but uploads
            # only the slabs of the mesh positions its devices own — the
            # batch CLIs' process-ownership discipline (cli/multihost.py)
            npad = max(e - b for b, e in bounds)
            my_pos = self._my_pos = my_mesh_positions(self.mesh)
            local_flat, local_ids, _counts, self.npad_local = pad_and_flatten(
                [points[bounds[s][0]:bounds[s][1]] for s in my_pos],
                id_bases=[bounds[s][0] + self.id_offset for s in my_pos],
                pad_to=npad, dim=self.dim)
            rows = self.num_shards * npad
            flat = jax.make_array_from_process_local_data(
                sharding, local_flat, (rows, self.dim))
            ids = jax.make_array_from_process_local_data(
                sharding, local_ids, (rows,))
            self._flat_pts, self._flat_ids = flat, ids
        else:
            self._my_pos = list(range(self.num_shards))
            shards = [points[b:e] for b, e in bounds]
            pad_to = None
            if self._pad_shard_rows:
                pad_to = max(self._pad_shard_rows, 1,
                             max((len(s) for s in shards), default=1))
            flat, ids, _counts, self.npad_local = pad_and_flatten(
                shards, id_bases=[b + self.id_offset for b, _ in bounds],
                pad_to=pad_to, dim=self.dim)
            # the flat resident side serves the bruteforce engine; the
            # bucketed one serves the tiled engines — both stay
            # device-resident for the life of the process (the reference
            # re-uploads per launch)
            self._flat_pts = jax.device_put(flat, sharding)
            self._flat_ids = jax.device_put(ids, sharding)
        self._buckets = partition_sharded(self._flat_pts, self._flat_ids,
                                          self.mesh, self.bucket_size)
        #: per-bucket ||p||^2, computed ONCE at upload and resident beside
        #: the buckets — the matmul expansion's precomputed norm term
        #: (ops/distance.py). Only materialized when the MXU score is on.
        self._bucket_norms2 = None
        # lsk: allow[lock-guard] _build_index runs from __init__ only —
        if self.score_mode == "mxu" and self.engine_name in (  # unshared
                "tiled", "pallas_tiled"):
            from mpi_cuda_largescaleknn_tpu.ops.distance import norms2

            # jit keeps the buckets' dim-0 sharding (elementwise reduce
            # over the component axis), single- and multi-host alike
            self._bucket_norms2 = jax.jit(norms2)(self._buckets.pts)
        self._replicated = NamedSharding(self.mesh, P())
        #: this engine's device-resident byte footprint — flat arrays,
        #: bucketed partition, and the precomputed norms (summed over the
        #: whole mesh). The tiered slab pool budgets device memory against
        #: exactly this number, and /stats reports it per slab so
        #: ``knn_slab_pool_resident`` has a truthful denominator.
        resident = [self._flat_pts, self._flat_ids, *self._buckets]
        if self._bucket_norms2 is not None:
            resident.append(self._bucket_norms2)
        self.device_bytes = int(sum(
            int(np.prod(a.shape)) * a.dtype.itemsize for a in resident))

    def _stage_replicated(self, q: np.ndarray):
        """Upload a host batch replicated to every mesh device. Single
        host: a plain ``device_put``. Pod mode: every process holds the
        identical bytes (the front end replicated them), so each builds the
        global array from its own copy — no cross-host transfer."""
        import jax

        if not self._multi:
            return jax.device_put(q, self._replicated)
        return jax.make_array_from_callback(
            q.shape, self._replicated, lambda idx: q[idx])

    # ------------------------------------------------------------- compilation

    def bucket_for(self, n: int) -> int:
        """Smallest shape bucket covering an ``n``-query batch."""
        for b in self.shape_buckets:
            if b >= n:
                return b
        raise UnservableShapeError(
            f"batch of {n} queries exceeds max_batch {self.max_batch}")

    def _build_query_fn(self, engine_name: str, qpad: int, qbuckets: int,
                        plan_key: tuple | None = None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from mpi_cuda_largescaleknn_tpu.ops.brute_force import (
            knn_update_bruteforce,
        )
        from mpi_cuda_largescaleknn_tpu.ops.candidates import init_candidates
        from mpi_cuda_largescaleknn_tpu.ops.partition import BucketedPoints
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import AXIS, pvary
        from mpi_cuda_largescaleknn_tpu.parallel.ring import (
            _tiled_engine_fn,
            device_merge_final,
        )

        k = self.k
        num_shards = self.num_shards
        device_merge = self.merge_mode == "device"
        emit_candidates = self.emit == "candidates"
        canonical = self.canonical_ties
        dim = self.dim
        score_dtype = self.score_dtype
        use_mxu = self.score_mode == "mxu"

        def finish(st, tiles):
            # per-shard local top-k -> program output. Host merge: emit the
            # R partial candidate blocks (the host's stable sort finishes).
            # Device merge: reduce to the global top-k in-program and emit
            # this device's 1/R slice of the final (dists, idx) — the
            # fetched global arrays are exactly [qpad] + [qpad, k]. The
            # third output is this device's executed-tile count [1].
            if not device_merge:
                return st.dist2, st.idx, tiles
            dists, d2, idx = device_merge_final(st, num_shards)
            if emit_candidates:
                # routed serving: emit the full merged candidate rows
                # (dist2[Q, k]) instead of the kth distances — the front
                # end's cross-host partial fold needs every candidate, not
                # just the boundary (the unused dists slice is DCE'd)
                return d2, idx, tiles
            return dists, idx, tiles

        use_tiled = engine_name in ("tiled", "pallas_tiled")

        if use_tiled:
            kernel, tiled_update = _tiled_engine_fn(engine_name)
            s_q = qpad // qbuckets

            def body(*args):
                if use_mxu:
                    # the precomputed per-bucket ||p||^2 rides as an extra
                    # resident operand (computed once at upload)
                    bpts, bids, blo, bhi, bn2, q, qr = args
                else:
                    (bpts, bids, blo, bhi, q, qr), bn2 = args, None
                # q f32[qpad,3] is REPLICATED: every device traverses its own
                # resident shard for the same queries; its local top-k is
                # exact over that shard, and the merge of the R partial
                # candidate rows — host-side or in-program — is exact over
                # the union (the ring's merge-across-rounds argument, with
                # space instead of time). The batch rides as ``qbuckets``
                # CONTIGUOUS slices, each with its own tight AABB: dispatch
                # Morton-sorted the rows, so slice = neighborhood, and the
                # per-bucket prune radius is the max over ~qpad/B coherent
                # queries instead of the whole batch. All-pad tail buckets
                # get inverted (+inf/-inf) bounds — never visited, and
                # their -inf radius never keeps the traversal alive.
                valid = q[:, 0] < PAD_SENTINEL / 2
                qids = jnp.where(valid, jnp.arange(qpad, dtype=jnp.int32), -1)
                qg = q.reshape(qbuckets, s_q, dim)
                vg = valid.reshape(qbuckets, s_q, 1)
                lo = jnp.min(jnp.where(vg, qg, jnp.inf), axis=1)
                hi = jnp.max(jnp.where(vg, qg, -jnp.inf), axis=1)
                qb = BucketedPoints(qg, qids.reshape(qbuckets, s_q), lo, hi,
                                    qids.reshape(qbuckets, s_q))
                # qr f32[qpad] is the PER-QUERY init radius — a runtime
                # operand, so a seeded batch and an unseeded one run the
                # SAME compiled program (dispatch fills max_radius rows
                # for unseeded queries and pads; serve/qcache.py supplies
                # certified triangle-inequality seeds strictly above each
                # row's true kth distance, so strict-< adoption keeps the
                # answer bitwise identical while the prune starts tighter)
                heap = pvary(init_candidates(qpad, k, qr))
                resident = BucketedPoints(bpts, bids, blo, bhi, bids)
                kw = dict(with_stats=True, canonical_ties=canonical,
                          score_dtype=score_dtype, point_norms2=bn2)
                if plan_key is not None:
                    # recall-SLO program knobs (serve/recall.py
                    # RecallPlan.program_key()): trace-time statics, so
                    # this body is a DIFFERENT compiled program from the
                    # exact one — the executable keys carry plan_key.
                    # Only the XLA tiled engine understands them
                    # (_get_executable nulls plan_key otherwise).
                    skip_rescore, prune_shrink, visit_frac = plan_key
                    kw.update(skip_rescore=skip_rescore,
                              prune_shrink=prune_shrink,
                              visit_frac=visit_frac)
                if engine_name == "tiled":
                    # chunk = ONE query bucket: the lax.map cond skips at
                    # per-bucket granularity, so a finished bucket stops
                    # paying for stragglers — measured faster at every B
                    # on the serving shapes, and it is what makes the
                    # tile-skip counters bucket-granular
                    kw["chunk_buckets"] = 1
                st, tiles = tiled_update(heap, qb, resident, **kw)
                # counters ride in TILE-ROW units (one query row folded
                # against one [T]-lane point tile): raw tile counts are
                # [s_q, T]-shaped and s_q varies with B, so scaling by s_q
                # makes executed/possible comparable across bucketings
                return finish(st, jnp.reshape(tiles * s_q, (1,)))

            in_specs = (P(AXIS),) * (5 if use_mxu else 4) + (P(), P())
        else:
            kernel = "bruteforce"

            def body(spts, sids, q, qr):
                heap = pvary(init_candidates(qpad, k, qr))
                st = knn_update_bruteforce(heap, q, spts, sids,
                                           score_dtype=score_dtype)
                # flat engines score every pair; no tiles to count
                return finish(st, pvary(jnp.zeros((1,), jnp.int32)))

            in_specs = (P(AXIS),) * 2 + (P(), P())

        with self._meta_lock:
            self.kernel = kernel
        check_vma = not engine_name.startswith("pallas")
        # donate the staged query + radius buffers: each dispatch stages a
        # fresh replicated batch, so the previous one's device memory is
        # dead the moment the executable reads it — donation lets XLA
        # reuse it for the outputs instead of growing the pipelined
        # working set. TPU only: the CPU PJRT client logs
        # unusable-donation warnings.
        donate = ((len(in_specs) - 2, len(in_specs) - 1)
                  if jax.default_backend() == "tpu" else ())
        return jax.jit(jax.shard_map(
            body, mesh=self.mesh, in_specs=in_specs,
            out_specs=(P(AXIS), P(AXIS), P(AXIS)), check_vma=check_vma),
            donate_argnums=donate)

    def _resident_args(self, engine_name: str):
        if engine_name in ("tiled", "pallas_tiled"):
            b = self._buckets
            base = (b.pts, b.ids, b.lower, b.upper)
            if self.score_mode == "mxu":
                return base + (self._bucket_norms2,)
            return base
        return (self._flat_pts, self._flat_ids)

    def _tiles_possible(self, engine_name: str, qpad: int) -> int:
        """Static ceiling of one batch's traversal in TILE-ROW units
        (query row x [T]-lane point-tile visit), summed over shards — the
        ``tiles_skipped`` counter's denominator. Row units make the
        ceiling independent of the query bucketing (B buckets x qpad/B
        rows x slots == qpad x slots), so executed/skipped are directly
        comparable across ``query_buckets`` settings. The XLA twin counts
        every schedule slot of a non-pruned step (pad visits included);
        the Pallas kernel counts only KEPT buckets, so its ceiling is the
        exact bucket count (the two engines' counters are not comparable
        as pruning quality — parallel/ring.py ``_ring_stats``)."""
        from mpi_cuda_largescaleknn_tpu.ops.tiled import tile_schedule_slots

        if engine_name not in ("tiled", "pallas_tiled"):
            return 0
        num_pb = self._buckets.ids.shape[0] // self.num_shards
        per_row = (num_pb if engine_name == "pallas_tiled"
                   else tile_schedule_slots(num_pb))
        # pod mode: counters are per-host — the denominator covers only the
        # shards this process fetches counts from (_tiles_fetch)
        return len(self._my_pos) * qpad * per_row

    def _get_executable(self, qpad: int, plan=None):  # lsk: holds[_lock]
        """AOT executable for (active engine, qpad); compiles on miss.

        ``compile_count`` increments EXACTLY when XLA is invoked — the
        recompile-freedom contract the tests assert. A compiled executable
        rejects any other input shape instead of silently retracing.
        Device-merge programs are distinct HLO from host-merge ones, and so
        are different query bucketings, score dtypes and recall-plan
        program knobs, so all are part of the bucket key — the
        recompile-freedom discipline holds per (engine, merge, shape,
        query_buckets, score_dtype, plan) tuple. ``plan`` (serve/recall.py
        ``RecallPlan``, None = exact) appends its ``program_key()`` at the
        END of the key so the exact path's keys — and the qpad-at-index-2
        layout ``ExecutableCache.stats`` reads — stay byte-identical to
        the pre-tier engine. Program knobs need the XLA tiled traversal;
        on other engines the plan runs the exact program (recall can only
        exceed the claim).
        """
        import jax

        qb = self.query_buckets[qpad]
        with self._meta_lock:
            engine_name = self.engine_name
        plan_key = None
        if plan is not None and engine_name == "tiled":
            pk = plan.program_key()
            if pk != (False, 1.0, 1.0):
                plan_key = pk
        key = (engine_name, self.merge_mode, qpad, qb, self.score_dtype)
        if plan_key is not None:
            key = key + (plan_key,)
        exe = self._executables.get(key)
        if exe is not None:
            return exe
        shared_key = None
        if self._exec_cache is not None:
            # the shared key adds every remaining program-identity knob
            # plus the resident operands' SHAPE CLASS: a compiled program
            # binds shapes, not values, so any engine of the same class
            # (the pool pads all slabs to one) can run it on its own
            # resident arrays
            args = self._resident_args(engine_name)
            shared_key = key + (
                self.emit, self.k, self.max_radius, self.canonical_ties,
                self.dim,
                tuple((tuple(a.shape), str(a.dtype)) for a in args))
            exe = self._exec_cache.get(shared_key)
            if exe is not None:
                self._executables[key] = exe
                with self._meta_lock:
                    self._compiled_shapes.append(qpad)
                return exe
            # None = this engine CLAIMED the shared key: concurrent
            # misses (another slab's promotion) park in get() until the
            # put below — or the abort, if the compile fails
        try:
            with self.timers.phase(f"compile_q{qpad}"):
                fn = self._build_query_fn(engine_name, qpad, qb,
                                          plan_key=plan_key)
                q0 = self._stage_replicated(
                    np.full((qpad, self.dim), PAD_SENTINEL, np.float32))
                r0 = self._stage_replicated(
                    np.full(qpad, self.max_radius, np.float32))
                exe = fn.lower(*self._resident_args(engine_name),
                               q0, r0).compile()
        except BaseException:
            if self._exec_cache is not None:
                self._exec_cache.abort(shared_key)
            raise
        self._executables[key] = exe
        if self._exec_cache is not None:
            self._exec_cache.put(shared_key, exe)
        with self._meta_lock:
            self.compile_count += 1
            self._compiled_shapes.append(qpad)
        return exe

    def warmup(self) -> dict:
        """Compile (and once execute) every shape bucket. Returns
        ``{"per_bucket_s": {qpad: seconds}, "query_buckets": {qpad: B},
        "tiles_executed": int, "tiles_skipped": int}`` so the serving CLI
        can report what a cold start cost and show the tile counters live
        from the first line — after this, steady-state traffic never
        compiles. (The warmup batches are all padding, so their traversals
        prune everything: executed stays 0 and skipped counts each
        program's full schedule — an honest first datapoint for the
        counters.)"""
        import jax

        per_bucket = {}
        with self._lock:
            with self._meta_lock:
                engine_name = self.engine_name
            for qpad in self.shape_buckets:
                t0 = time.perf_counter()
                exe = self._get_executable(qpad)
                # run once on an all-padding batch: pays any lazy backend
                # init; the traversal early-exits (no real queries)
                q0 = self._stage_replicated(
                    np.full((qpad, self.dim), PAD_SENTINEL, np.float32))
                r0 = self._stage_replicated(
                    np.full(qpad, self.max_radius, np.float32))
                out = exe(*self._resident_args(engine_name), q0, r0)
                jax.block_until_ready(out)
                self._count_tiles(self._tiles_fetch(out[2]),
                                  self._tiles_possible(engine_name, qpad))
                per_bucket[qpad] = round(time.perf_counter() - t0, 3)
        return {"per_bucket_s": per_bucket,
                "query_buckets": dict(self.query_buckets),
                "tiles_executed": self.timers.counter("tiles_executed"),
                "tiles_skipped": self.timers.counter("tiles_skipped")}

    def _count_tiles(self, executed: int, possible: int) -> None:
        """Fold one batch's measured tile count into the cumulative
        executed/skipped counters (flat engines report 0/0). Counted twice:
        the aggregate (the stable /stats surface) and the per-score-mode
        twin (``tiles_executed_mxu`` vs ``tiles_executed_vpu``), so the
        MXU-vs-VPU attribution is a number on /stats and /metrics."""
        if possible <= 0 and executed <= 0:
            return
        self.timers.count("tiles_executed", executed)
        self.timers.count("tiles_skipped", max(0, possible - executed))
        self.timers.count(f"tiles_executed_{self.score_mode}", executed)
        self.timers.count(f"tiles_skipped_{self.score_mode}",
                          max(0, possible - executed))

    def _tiles_fetch(self, t) -> int:
        """Sum a program's per-shard tile counts. Pod mode: only this
        process's addressable shards contribute (per-host counters; the
        possible-tile denominator is scaled to match in
        ``complete_slices``)."""
        if self._multi:
            return int(np.sum([np.asarray(sh.data).sum()
                               for sh in t.addressable_shards]))
        return int(np.asarray(t).sum())

    # ----------------------------------------------------------------- degrade

    def can_degrade(self) -> bool:
        with self._meta_lock:
            return self.engine_name == "pallas_tiled"

    def degrade(self, reason: str) -> None:
        """Swap the Pallas traversal for its XLA twin after a runtime
        failure (identical results by the twin-engine contract — see
        tests/test_pallas_tiled.py). Compiled twin programs are cached under
        their own key, so repeated degradations never recompile.

        Takes the engine lock: ``dispatch`` reads ``engine_name`` and picks
        the matching executable under that lock, so a mid-dispatch
        degradation can never produce a handle whose recorded engine name
        disagrees with the executable it actually launched (the stale-handle
        replay in admission.GracefulQueryFn depends on that agreement)."""
        with self._lock:
            with self._meta_lock:
                if self.engine_name != "pallas_tiled":
                    raise RuntimeError(
                        f"engine '{self.engine_name}' has no fallback")
                self.degraded_reason = reason
                self.engine_name = "tiled"
            # the twin may want a different tuned bucket geometry, but the
            # index is already partitioned — keep the resident geometry,
            # stay exact

    # ------------------------------------------------------------------- query

    def set_launch_workers(self, n: int) -> None:
        """Resize the launch pool toward ``n`` concurrent program launches.

        The serving layer asks for its pipeline depth; the engine clamps to
        what concurrency can actually buy: on the CPU backend one program
        already spans ``num_shards`` device threads, so extra launches only
        help while programs leave cores idle (a second launch on a saturated
        host just thrashes caches — measured slower). With one worker the
        pool still pipelines: the next staged batch launches the instant the
        current one retires, with no host work in between. Futures already
        submitted to the old pool complete unaffected (their threads drain
        and exit); a no-op when the size is unchanged.
        """
        import jax

        n = max(1, int(n))
        if jax.default_backend() != "tpu":
            cores = os.cpu_count() or 1
            n = max(1, min(n, cores // max(1, self.num_shards)))
        with self._lock:
            if n == self._launch_workers:
                return
            old = self._launch
            self._launch_workers = n
            self._launch = ThreadPoolExecutor(
                max_workers=n, thread_name_prefix="knn-launch")
            old.shutdown(wait=False)

    def dispatch(self, queries: np.ndarray, plan=None,
                 seed_radius=None) -> _InFlightBatch:
        """Issue a batch's device traversal WITHOUT blocking on the result.

        Morton-sorts (when enabled), stages + pads the batch, replicates
        it, and hands the AOT executable call to the engine's single launch
        thread; the returned ``_InFlightBatch`` wraps the launch future.
        Between ``dispatch`` and ``complete`` the device crunches while the
        host is free to merge an earlier batch (the batcher's pipelined
        mode) or stage the next one. The admission sort happens OUTSIDE the
        staged buffer's lifetime: ``queries`` is retained unsorted for
        degradation replay, and the permutation rides the handle for
        ``complete``'s demux. The lock serializes executable lookup,
        staging, and launch-queue order with ``degrade``; it is NOT held
        while the device computes or the host merges.

        ``plan`` (serve/recall.py ``RecallPlan``, None = exact) selects
        the plan-keyed approximate executable and rides the handle so a
        degradation replay re-runs the same plan.

        ``seed_radius`` (f32[n] or None) tightens individual rows' heap
        init radius below ``max_radius`` — the certified query cache's
        triangle-inequality seeds (serve/qcache.py). The radius is a
        RUNTIME operand of the same compiled program (no new AOT keys);
        rows at +inf / unseeded batches behave exactly as before. A seed
        must sit STRICTLY above the row's true kth-neighbor distance
        (rounding up in f32) or candidates at the boundary would be lost
        to the strict-< adoption; values above ``max_radius`` clamp to
        it. Degradation replays (serve/admission.py) rerun unseeded —
        sound, because seeds never change answers, only pruning."""
        import jax

        from mpi_cuda_largescaleknn_tpu.utils.math import morton_argsort

        queries = np.asarray(queries, np.float32).reshape(-1, self.dim)
        n = len(queries)
        if n == 0:
            with self._meta_lock:
                name = self.engine_name
            return _InFlightBatch(queries, 0, 0, name,
                                  self.merge_mode, None, time.perf_counter(),
                                  plan=plan)
        with self.timers.phase("serve.engine.stage"):
            qpad = self.bucket_for(n)
            perm = None
            if self.sort_queries and n > 1:
                with self.timers.phase("morton_sort",
                                       span="serve.engine.morton_sort"):
                    perm = morton_argsort(queries, self._index_lo,
                                          self._index_hi)
            staged = queries if perm is None else queries[perm]
            # per-row init radii: max_radius everywhere (pad rows included),
            # seeded rows clamped below it. The seed vector rides the SAME
            # Morton permutation as the queries, so staged row i keeps the
            # radius of the query it carries.
            r = np.full(qpad, self.max_radius, np.float32)
            if seed_radius is not None:
                sr = np.asarray(seed_radius, np.float32).reshape(-1)
                if len(sr) != n:
                    raise ValueError(
                        f"seed_radius has {len(sr)} rows for {n} queries")
                sr = np.minimum(sr, np.float32(self.max_radius))
                r[:n] = sr if perm is None else sr[perm]
                self.timers.count(
                    "seeded_rows", int(np.sum(sr < self.max_radius)))
            with self._lock:
                exe = self._get_executable(qpad, plan=plan)
                with self._meta_lock:
                    # consistent with the key _get_executable compiled under:
                    # degrade() needs _lock, which this region holds
                    engine_name = self.engine_name
                args = self._resident_args(engine_name)
                q = np.full((qpad, self.dim), PAD_SENTINEL, np.float32)
                q[:n] = staged
                t0 = time.perf_counter()
                q_dev = self._stage_replicated(q)
                r_dev = self._stage_replicated(r)
                fut = self._launch.submit(self._launched, current_span_tags(),
                                          exe, *args, q_dev, r_dev)
                possible = self._tiles_possible(engine_name, qpad)
        if plan is not None:
            self.timers.count("approx_batches")
        return _InFlightBatch(queries, n, qpad, engine_name,
                              self.merge_mode, fut, t0, perm=perm,
                              tiles_possible=possible, plan=plan)

    def _launched(self, tags: dict, exe, *args):
        """The executable call, on a launch thread, as the span
        ``serve.engine.launch`` tagged like the ``dispatch`` that staged
        it."""
        with self.timers.phase("serve.engine.launch", **tags):
            return exe(*args)

    def complete(self, batch: _InFlightBatch):
        """Block on a dispatched batch and finish its cross-shard top-k.

        ``merge="host"``: fetch the R partial [Q, k] candidate blocks and
        merge them in numpy. ``merge="device"``: the reduction already ran
        in-program, so this fetches ONE final [Q] + [Q, k] pair — R x fewer
        result bytes over the host link, no merge work at all.
        ``fetch_bytes`` / ``result_rows`` count what actually crossed; the
        per-shard tile counts ride along as an [R] i32 and feed the
        ``tiles_executed`` / ``tiles_skipped`` counters. Finally the
        Morton admission sort (if any) is undone, so rows come back in the
        caller's order.

        The future resolution + np.asarray fetches are where async dispatch
        errors surface (a Pallas runtime failure raises HERE, not in
        ``dispatch``) — the graceful wrapper replays the handle's retained
        queries on the twin. ``engine_batch_seconds`` measures
        dispatch->fetch wall-clock, which under pipelining includes time
        queued behind the previous batch.
        """
        if batch.n == 0:
            return (np.zeros(0, np.float32),
                    np.zeros((0, self.k), np.int32))
        if self._multi:
            raise RuntimeError(
                "pod-mode engines emit per-host row slices — use "
                "complete_slices (the front end assembles the full batch)")
        if self.emit == "candidates":
            raise RuntimeError(
                "emit='candidates' engines return full candidate rows — "
                "use complete_candidates (the routed front end's fold)")
        a, b, tiles = self._fetch(batch)
        # fetch accounting covers RESULT bytes only (the PR-3 merge
        # placement contract); the [R] i32 tile counter is observability,
        # not payload
        self.timers.count("fetch_bytes", a.nbytes + b.nbytes)
        self.timers.count("result_rows", batch.n)
        self._count_tiles(tiles, batch.tiles_possible)
        if batch.merge_mode == "device":
            dists, nbrs = a, b  # final already: [qpad], [qpad, k]
        else:
            with self.timers.phase("host_merge", span="serve.engine.merge"):
                dists, nbrs = _merge_shard_candidates(
                    a, b, self.num_shards, batch.qpad, self.k)
        dists, nbrs = dists[:batch.n], nbrs[:batch.n]
        if batch.perm is not None:
            # undo the Morton admission sort: staged row i answers original
            # row perm[i], so a scatter through perm restores caller order
            # (bit-identical to the unsorted path — rows are independent
            # and the traversal's tie order is canonical)
            out_d = np.empty_like(dists)
            out_n = np.empty_like(nbrs)
            out_d[batch.perm] = dists
            out_n[batch.perm] = nbrs
            dists, nbrs = out_d, out_n
        return dists, nbrs

    def _fetch(self, batch: _InFlightBatch):
        """Wait for a batch's program and fetch its two result arrays and
        its executed-tile count (the span ``serve.engine.wait``); records
        ``engine_batch_seconds``."""
        with self.timers.phase("serve.engine.wait"):
            a, b, t = batch.fut.result()
            a = np.asarray(a)
            b = np.asarray(b)
            self.timers.hist("engine_batch_seconds").record(
                time.perf_counter() - batch.t0)
            tiles = self._tiles_fetch(t)
        return a, b, tiles

    def complete_candidates(self, batch: _InFlightBatch):
        """Routed-host ``complete``: block on a dispatched batch and return
        the full merged candidate rows ``(dist2 f32[n, k], idx i32[n, k])``
        over THIS engine's points — ascending (dist2, id) per row, -1 ids /
        radius**2 distances in unfilled slots.

        This is the partial a routed pod host serves (POST /route_knn):
        the front end folds the per-host rows with the same canonical
        (dist2, id) discipline (serve/frontend.py ``RoutedPodFanout``), so
        the folded result is bit-identical to one engine over the union of
        the hosts' points. Works under both merge placements: the device
        merge emits the candidate rows in-program (``emit='candidates'``),
        the host merge keeps the full-width variant of the PR-3 fold.
        """
        if batch.n == 0:
            return (np.full((0, self.k), np.inf, np.float32),
                    np.full((0, self.k), -1, np.int32))
        if self._multi:
            raise RuntimeError(
                "pod-mode engines emit per-host row slices — routed "
                "(independent-host) serving never joins a global mesh")
        if batch.merge_mode == "device" and self.emit != "candidates":
            raise RuntimeError(
                "engine was built with emit='final': its device-merge "
                "programs emit kth distances, not candidate rows — "
                "construct the engine with emit='candidates'")
        a, b, tiles = self._fetch(batch)
        self.timers.count("fetch_bytes", a.nbytes + b.nbytes)
        self.timers.count("result_rows", batch.n)
        self._count_tiles(tiles, batch.tiles_possible)
        if batch.merge_mode == "device":
            d2, idx = a, b  # already the merged [qpad, k] candidate rows
        else:
            with self.timers.phase("host_merge", span="serve.engine.merge"):
                d2, idx = _merge_shard_candidates(
                    a, b, self.num_shards, batch.qpad, self.k, full=True)
        d2, idx = d2[:batch.n], idx[:batch.n]
        if batch.perm is not None:
            out_d = np.empty_like(d2)
            out_i = np.empty_like(idx)
            out_d[batch.perm] = d2
            out_i[batch.perm] = idx
            d2, idx = out_d, out_i
        return d2, idx

    def refetch_exact(self, queries):
        """Survivor re-fetch hook (PR-17 quantized wire): exact f32
        candidate rows for ``queries``, byte-equal to any earlier batch
        that contained these rows. Candidate rows are batch-composition
        INDEPENDENT — each row's top-k over this engine's points is a
        function of the query row alone — which is the property the
        ``?wire=x32`` re-fetch (and the routed escalation waves before
        it) relies on: re-asking costs a round trip, never bits."""
        return self.complete_candidates(self.dispatch(queries))

    def complete_slices(self, batch: _InFlightBatch):
        """Pod-mode ``complete``: fetch ONLY this process's addressable row
        slices of the pod-final answer.

        Under ``merge="device"`` on the global mesh, device at mesh
        position r holds rows [r*qpad/R, (r+1)*qpad/R) of the final
        [qpad] + [qpad, k] arrays — so each host's fetch moves 1/R of the
        result per owned position and the POD's total fetched bytes equal
        ONE final result, not one per host
        (tests/test_multihost_serve.py
        ``test_pod_fetch_bytes_are_one_result_per_batch``). Returns
        ``(rows i32[m], dists f32[m], nbrs i32[m, k])`` where ``rows`` are
        CALLER-order row indices (the Morton admission sort already undone
        per row via ``batch.perm``) and ``m`` counts only real (non-pad)
        rows this host owns. The front end scatters each host's triple into
        the full batch — bit-identical to a single-process ``complete`` of
        the same program, ties included.
        """
        if batch.n == 0:
            return (np.zeros(0, np.int32), np.zeros(0, np.float32),
                    np.zeros((0, self.k), np.int32))
        with self.timers.phase("serve.engine.wait"):
            a, b, t = batch.fut.result()
            self.timers.hist("engine_batch_seconds").record(
                time.perf_counter() - batch.t0)
            nbrs_by_row = {int(sh.index[0].start): np.asarray(sh.data)
                           for sh in b.addressable_shards}
            dists_by_row = [(int(sh.index[0].start), np.asarray(sh.data))
                            for sh in a.addressable_shards]
            tiles = self._tiles_fetch(t)
        rp = batch.qpad // self.num_shards
        rows_l, d_l, n_l = [], [], []
        fetched = 0
        for lo, d in dists_by_row:
            nb = nbrs_by_row[lo]
            fetched += d.nbytes + nb.nbytes
            staged = np.arange(lo, lo + rp)
            real = staged < batch.n  # pad rows sort/stay last
            if not np.any(real):
                continue
            staged = staged[real]
            rows_l.append(batch.perm[staged] if batch.perm is not None
                          else staged.astype(np.int32))
            d_l.append(d[real])
            n_l.append(nb[real])
        self.timers.count("fetch_bytes", fetched)
        self._count_tiles(tiles, batch.tiles_possible)
        if not rows_l:
            return (np.zeros(0, np.int32), np.zeros(0, np.float32),
                    np.zeros((0, self.k), np.int32))
        rows = np.concatenate(rows_l).astype(np.int32)
        self.timers.count("result_rows", len(rows))
        return rows, np.concatenate(d_l), np.concatenate(n_l)

    def query(self, queries: np.ndarray, plan=None, seed_radius=None):
        """f32[n,3] -> (f32[n] k-th-NN distances, i32[n,k] neighbor ids).

        Serialized ``dispatch`` + ``complete``. ``n`` may be anything in
        [0, max_batch]; the batch is padded to its shape bucket. Larger
        batches are the batcher's/admission's job to split. Distances follow
        the reference contract: sqrt of the k-th smallest squared distance,
        inf (or the ``-r`` radius) when fewer than k neighbors exist.
        Neighbor ids are global point indices, ascending by distance, -1 for
        unfilled slots. With a recall ``plan``, distances/sets are the
        plan's approximation instead (still sorted, -1-padded).
        ``seed_radius`` (serve/qcache.py certified seeds) tightens
        individual rows' heap-init radius without changing any answer bit.
        """
        return self.complete(self.dispatch(queries, plan=plan,
                                           seed_radius=seed_radius))

    def stats(self) -> dict:
        # the mutable identity (engine_name / degraded_reason /
        # compile_count / compiled shapes) is snapshotted under the small
        # metadata lock: a scrape may race a compile or a degradation on
        # the query path (--no-warmup, post-degrade) and must NOT queue
        # behind _lock while _get_executable compiles a cold bucket
        with self._meta_lock:
            engine_name = self.engine_name
            kernel = self.kernel
            degraded_reason = self.degraded_reason
            compile_count = self.compile_count
            compiled_shapes = sorted(self._compiled_shapes)
        return {
            "engine": engine_name,
            "kernel": kernel,
            "merge": self.merge_mode,
            "score_dtype": self.score_dtype,
            "score_mode": self.score_mode,
            "dim": self.dim,
            "degraded_reason": degraded_reason,
            "n_points": self.n_points,
            "k": self.k,
            "num_shards": self.num_shards,
            # pod-mode surface: which slice of the global mesh this process
            # owns (the front end sanity-checks coverage across hosts)
            "multihost": self._multi,
            "process_index": self.process_index,
            "process_count": self.process_count,
            "my_positions": list(self._my_pos),
            # routed-serving surface: which global rows this engine owns,
            # what its completions emit, whether its tie order is the
            # canonical (dist2, id) one the cross-host fold assumes, the
            # radius cap (None = inf; /stats stays strict JSON), and the
            # per-shard AABB + count table the front end routes on
            "row_offset": self.id_offset,
            "emit": self.emit,
            "canonical_ties": self.canonical_ties,
            "max_radius": (None if math.isinf(self.max_radius)
                           else self.max_radius),
            "shard_bounds": self.shard_bounds,
            # per-slab device byte footprint (flat + bucketed + norms):
            # what the tiered slab pool's --device-slab-budget counts
            "device_bytes": self.device_bytes,
            "max_batch": self.max_batch,
            "bucket_size": self.bucket_size,
            "shape_buckets": list(self.shape_buckets),
            "compiled_shapes": compiled_shapes,
            "compile_count": compile_count,
            # query-locality surface: per-shape bucket counts, whether the
            # Morton admission sort is on, and the traversal's cumulative
            # tile-skip accounting (the prune's win as a number)
            "query_buckets": {str(q): b
                              for q, b in sorted(self.query_buckets.items())},
            "sort_queries": self.sort_queries,
            "tiles_executed": self.timers.counter("tiles_executed"),
            "tiles_skipped": self.timers.counter("tiles_skipped"),
            # per-score-mode twins: which scorer (MXU matmul-form vs VPU
            # elementwise) actually burned the executed tiles
            "tiles_executed_mxu": self.timers.counter("tiles_executed_mxu"),
            "tiles_skipped_mxu": self.timers.counter("tiles_skipped_mxu"),
            "tiles_executed_vpu": self.timers.counter("tiles_executed_vpu"),
            "tiles_skipped_vpu": self.timers.counter("tiles_skipped_vpu"),
            # headline copies of the timers' counters: the stable /stats
            # API surface tools/loadgen.py binds to (timers.report()
            # nests the same values among phases/histograms for --timings)
            "fetch_bytes": self.timers.counter("fetch_bytes"),
            "result_rows": self.timers.counter("result_rows"),
            # recall-SLO tier: batches dispatched under an approximate plan
            # (serve/recall.py) — 0 on an exact-only deployment
            "approx_batches": self.timers.counter("approx_batches"),
            "timers": self.timers.report(),
        }


def load_slab_rows(path: str, host_id: int, num_hosts: int):
    """Load row slab ``[N*i/H, N*(i+1)/H)`` of ``path``; returns
    ``(points f32[n, D], begin, n_total)``.

    The ONE slab-split read every slab consumer shares —
    ``materialize_slab_engine`` (routed hosts + /adopt_slab handoff), the
    routed streaming path (serve_main ``--num-slabs`` on a routed host),
    and the slab pool's cold tier (serve/slabpool.py ``SlabSource``): the
    reference's ``read_file_portion`` integer split for ``.float3``
    (identical arithmetic to ``slab_bounds``), an mmap slice for
    ``.npy`` — so every consumer materializes byte-identical rows."""
    if path.endswith(".npy"):
        from mpi_cuda_largescaleknn_tpu.models.sharding import slab_bounds

        arr = np.load(path, mmap_mode="r")
        n_total = len(arr)
        begin, end = slab_bounds(n_total, num_hosts)[host_id]
        return np.asarray(arr[begin:end], np.float32), begin, n_total
    from mpi_cuda_largescaleknn_tpu.io.reader import read_file_portion

    return read_file_portion(path, host_id, num_hosts)


def materialize_slab_engine(path, host_id: int, num_hosts: int, *, k: int,
                            shards=None, engine: str = "auto",
                            merge: str = "auto", bucket_size: int = 0,
                            max_radius: float = math.inf,
                            max_batch: int = 1024, min_batch: int = 8,
                            query_buckets: int = 0,
                            score_dtype: str = "f32", points=None,
                            id_offset: int | None = None,
                            warmup: bool = False):
    """Load row slab ``[N*i/H, N*(i+1)/H)`` and build its routed engine.

    The ONE slab-upload + AOT-warmup path shared by ``serve_main
    --routing bounds`` hosts at launch and by the standby's
    ``POST /adopt_slab`` handoff (serve/frontend.py): both must
    materialize byte-identical slabs — the reference's
    ``read_file_portion`` split for ``.float3`` (identical integer
    arithmetic to ``slab_bounds``, so the adopted rows equal the lost
    host's exactly), an mmap slice for ``.npy``. Pass ``points`` +
    ``id_offset`` to skip the file read (the pull-from-replica path —
    serve/replica.py ``pull_slab_rows``). Returns
    ``(engine, id_offset, n_total)`` with ``n_total`` None when the rows
    came pre-loaded."""
    from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh

    if points is None:
        if not path:
            raise ValueError("need an input path or pre-loaded slab rows")
        points, id_offset, n_total = load_slab_rows(path, host_id,
                                                    num_hosts)
    else:
        if id_offset is None:
            raise ValueError("pre-loaded slab rows need their id_offset "
                             "(the slab's global row origin)")
        n_total = None
    eng = ResidentKnnEngine(
        points, k, mesh=get_mesh(shards), engine=engine, merge=merge,
        bucket_size=bucket_size, max_radius=max_radius,
        max_batch=max_batch, min_batch=min_batch,
        query_buckets=query_buckets, score_dtype=score_dtype,
        id_offset=int(id_offset), emit="candidates")
    if warmup:
        eng.warmup()
    return eng, int(id_offset), n_total


def _merge_shard_candidates(d2, idx, num_shards, qpad, k, full=False):
    """Merge R per-shard top-k candidate blocks into the global top-k.

    ``d2``/``idx`` are [R*qpad, k] shard-major. The tie discipline is the
    one a stable ascending sort over the shard-rank-ordered concatenation
    produces (earlier shard, then earlier slot, wins at equal distance —
    ops/candidates.py merge_candidates), but the full width-R*k stable sort
    is avoided: ``np.argpartition`` selects the k smallest per row in
    O(R*k), a column-ordered tie-fix picks the boundary ties the stable
    sort would have picked, and only the k survivors see a sort. Identical
    output, measurably less host CPU at serving batch sizes — this runs on
    the completion worker's critical path whenever the host path is
    selected (or degraded to).

    ``full=True`` returns the whole merged candidate rows
    ``(dist2[qpad, k], idx[qpad, k])`` instead of (sqrt-kth, idx) — the
    routed serving path's partial (``complete_candidates``).
    """
    d2 = d2.reshape(num_shards, qpad, k).transpose(1, 0, 2).reshape(qpad, -1)
    idx = idx.reshape(num_shards, qpad, k).transpose(1, 0, 2).reshape(qpad, -1)
    if num_shards == 1:
        # a single shard's block is already the sorted global top-k
        if full:
            return d2, idx
        return np.sqrt(d2[:, k - 1]), idx
    # SOME k smallest per row (boundary ties arbitrary), then the k-th value
    part = np.argpartition(d2, k - 1, axis=1)[:, :k]
    kth = np.take_along_axis(d2, part, axis=1).max(axis=1, keepdims=True)
    # every strictly-closer column is in; of the columns tied AT the k-th
    # value, the stable sort would keep the first (k - m) in column order
    below = d2 < kth
    m = below.sum(axis=1, keepdims=True)
    # lsk: allow[float-eq] the boundary tie-fix IS bitwise: kth is an element
    tied = d2 == kth  # of d2, so exact equality finds exactly the tied class
    mask = below | (tied & (np.cumsum(tied, axis=1) <= k - m))
    # exactly k selected per row; recover them in ascending column order
    # with an O(R*k) boolean partition + an O(k log k) sort, never a full
    # stable argsort over all R*k columns
    sel_cols = np.sort(np.argpartition(~mask, k - 1, axis=1)[:, :k], axis=1)
    sel_d2 = np.take_along_axis(d2, sel_cols, axis=1)
    order = np.argsort(sel_d2, axis=1, kind="stable")
    top_d2 = np.take_along_axis(sel_d2, order, axis=1)
    top_idx = np.take_along_axis(
        idx, np.take_along_axis(sel_cols, order, axis=1), axis=1)
    if full:
        return top_d2, top_idx
    return np.sqrt(top_d2[:, k - 1]), top_idx
