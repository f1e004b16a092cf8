"""Tiered slab index (serve/slabpool.py): the beyond-HBM pool must be
bit-identical to a fully-resident engine at EVERY pool size.

Four layers of coverage:

- ``SlabSource`` cold tier: slab rows byte-equal to ``read_file_portion``
  (.float3) / the mmap .npy split / ``load_slab_rows`` — the same rows a
  routed host or the slab handoff would materialize.
- ``SlabPool`` mechanics with FAKE engines (no jax, no sleeps): LRU
  eviction order, pin-vs-evict, budget overcommit, host-tier demotion and
  cap, stall accounting under an injectable clock, prefetch-then-hit with
  zero stalls, promotion-error surfacing, faults.py-injected slow and
  failed promotions.
- ``StreamingKnnEngine`` parity: the budget matrix {1 slab, half, all}
  against one ``ResidentKnnEngine`` over the union — distances AND
  neighbor ids bitwise, tie ids included (the fixture plants coordinate
  duplicates across slab boundaries), plus max-radius, candidates-emit,
  escalation behavior, prefetch-overlap (announced a batch ahead = zero
  stalls), AOT sharing across eviction/re-promotion (compile_count flat),
  and the slow-promotion drill (stall counted, answer exact, no
  deadlock).
- Serving surface: /stats + /metrics pool counters through a real
  KnnServer, and the batcher's batch-ahead ``prefetch_hint``
  announcement.
"""

from __future__ import annotations

import contextlib
import queue
import threading

import numpy as np
import pytest

K = 5


def _streaming_points():
    """600 rows, Morton-ish layout: [0:295) cluster A, [295:300) exact
    coordinate copies of rows [595:600) (B-region outliers inside the
    A-side slabs — cross-slab distance-0 ties AND boxes that overlap the
    B region, forcing escalation), [300:600) cluster B."""
    from tests.oracle import random_points

    a = random_points(295, seed=41, scale=0.4)
    b = (random_points(300, seed=42, scale=0.4) + np.float32(0.6))
    return np.concatenate([a, b[-5:], b]).astype(np.float32)


# --------------------------------------------------------------- SlabSource


class TestSlabSource:
    def test_float3_rows_byte_equal_to_read_file_portion(self, tmp_path):
        from mpi_cuda_largescaleknn_tpu.io.reader import read_file_portion
        from mpi_cuda_largescaleknn_tpu.serve.slabpool import SlabSource

        pts = _streaming_points()
        path = str(tmp_path / "pts.float3")
        pts.astype("<f4").tofile(path)
        src = SlabSource(path=path, num_slabs=4)
        assert src.n_total == len(pts) and src.dim == 3
        for s in range(4):
            want, begin, n = read_file_portion(path, s, 4)
            got = src.read(s)
            assert got.tobytes() == want.tobytes()
            assert src.bounds[s][0] == begin and n == len(pts)

    def test_npy_mmap_rows_byte_equal_to_slab_split(self, tmp_path):
        from mpi_cuda_largescaleknn_tpu.models.sharding import slab_bounds
        from mpi_cuda_largescaleknn_tpu.serve.engine import load_slab_rows
        from mpi_cuda_largescaleknn_tpu.serve.slabpool import SlabSource

        pts = _streaming_points()
        path = str(tmp_path / "pts.npy")
        np.save(path, pts)
        src = SlabSource(path=path, num_slabs=3)
        assert src.bounds == slab_bounds(len(pts), 3)
        for s in range(3):
            b, e = src.bounds[s]
            assert src.read(s).tobytes() == pts[b:e].tobytes()
            # the handoff/routed-host read path materializes the same rows
            rows, begin, _n = load_slab_rows(path, s, 3)
            assert begin == b and rows.tobytes() == src.read(s).tobytes()

    def test_float3_and_npy_sources_agree(self, tmp_path):
        from mpi_cuda_largescaleknn_tpu.serve.slabpool import SlabSource

        pts = _streaming_points()
        f3 = str(tmp_path / "pts.float3")
        npy = str(tmp_path / "pts.npy")
        pts.astype("<f4").tofile(f3)
        np.save(npy, pts)
        a = SlabSource(path=f3, num_slabs=5)
        b = SlabSource(path=npy, num_slabs=5)
        c = SlabSource(points=pts, num_slabs=5)
        for s in range(5):
            assert (a.read(s).tobytes() == b.read(s).tobytes()
                    == c.read(s).tobytes())

    def test_scan_aabbs_matches_slab_aabbs(self):
        from mpi_cuda_largescaleknn_tpu.models.sharding import slab_aabbs
        from mpi_cuda_largescaleknn_tpu.serve.slabpool import SlabSource

        pts = _streaming_points()
        src = SlabSource(points=pts, num_slabs=4)
        assert src.scan_aabbs() == slab_aabbs(pts, src.bounds)

    def test_rejects_bad_config(self):
        from mpi_cuda_largescaleknn_tpu.serve.slabpool import SlabSource

        with pytest.raises(ValueError):
            SlabSource(num_slabs=2)  # neither path nor points
        with pytest.raises(ValueError):
            SlabSource(points=np.zeros((4, 3)), num_slabs=0)


# ----------------------------------------------------------------- SlabPool


class _FakeEngine:
    def __init__(self, slab, rows, device_bytes):
        self.slab = slab
        self.host_points = rows
        self.device_bytes = device_bytes


class _PoolRig:
    """A SlabPool over fakes: injectable clock (a plain counter — no
    wall-clock, no sleeps), a per-build time cost, and a build log."""

    def __init__(self, n=80, num_slabs=8, slab_bytes=100, build_cost=0.5,
                 fail_slabs=(), **pool_kw):
        from mpi_cuda_largescaleknn_tpu.serve.slabpool import (
            SlabPool,
            SlabSource,
        )

        self.now = [0.0]
        self.built = []
        self.slab_bytes = slab_bytes
        self.build_cost = build_cost
        self.fail_slabs = set(fail_slabs)
        src = SlabSource(points=np.arange(n * 3, dtype=np.float32)
                         .reshape(n, 3), num_slabs=num_slabs)

        def factory(slab, rows, begin):
            if slab in self.fail_slabs:
                raise RuntimeError(f"build of slab {slab} exploded")
            self.now[0] += self.build_cost
            self.built.append(slab)
            return _FakeEngine(slab, rows, self.slab_bytes)

        self.pool = SlabPool(src, factory, clock=lambda: self.now[0],
                             **pool_kw)


class TestSlabPool:
    def test_lru_eviction_order(self):
        rig = _PoolRig(device_budget_bytes=200)  # budget = 2 slabs
        p = rig.pool
        p.ensure(0), p.ensure(1)
        assert p.resident_slabs() == [0, 1]
        p.ensure(2)  # 0 is LRU -> evicted
        assert p.resident_slabs() == [1, 2]
        p.ensure(1)  # refresh 1: now 2 is LRU
        p.ensure(3)
        assert p.resident_slabs() == [1, 3]
        assert p.stats()["evictions"] == 2
        p.close()

    def test_pin_blocks_eviction_and_overcommit_counted(self):
        rig = _PoolRig(device_budget_bytes=200)
        p = rig.pool
        p.ensure(0), p.ensure(1)
        p.pin([0])
        p.ensure(2)  # 0 pinned -> 1 (LRU among unpinned) evicted
        assert p.resident_slabs() == [0, 2]
        p.pin([2])
        p.ensure(3)  # both resident slabs pinned -> overcommit, no evict
        assert p.resident_slabs() == [0, 2, 3]
        assert p.stats()["overcommits"] == 1
        # releasing the pins re-enforces the budget immediately
        p.unpin([0]), p.unpin([2])
        assert len(p.resident_slabs()) == 2
        assert p.stats()["device_bytes_used"] <= 200
        p.close()

    def test_host_tier_demotion_and_cap(self):
        rig = _PoolRig(device_budget_bytes=100, host_pool_slabs=3)
        p = rig.pool
        p.ensure(0)
        p.ensure(1)  # evicts 0 -> its rows demote to the host tier
        p.ensure(2)  # evicts 1
        s = p.stats()
        assert s["cold_reads"] == 3 and s["host_resident"] == 3
        p.ensure(0)  # rows still warm in host RAM -> no cold read
        s = p.stats()
        assert s["host_hits"] == 1 and s["cold_reads"] == 3
        # cap: the host tier never exceeds host_pool_slabs, and pushing
        # enough new slabs through it evicts the oldest rows
        for slab in (3, 4, 5):
            p.ensure(slab)
        s = p.stats()
        assert s["host_resident"] <= 3 and s["host_evictions"] > 0
        # slab 1 fell out of the host tier long ago -> a cold read again
        cold_before = s["cold_reads"]
        p.ensure(1)
        assert p.stats()["cold_reads"] == cold_before + 1
        p.close()

    def test_stall_accounting_via_injectable_clock(self):
        rig = _PoolRig(device_budget_bytes=0, build_cost=0.5)
        p = rig.pool
        p.ensure(0)  # cold promote: one stall of exactly one build cost
        s = p.stats()
        assert s["stream_stalls"] == 1
        assert s["stream_stall_seconds"] == pytest.approx(0.5)
        p.ensure(0)  # resident: a device hit, no new stall
        s = p.stats()
        assert s["stream_stalls"] == 1 and s["device_hits"] == 1
        p.ensure(1, count_stall=False)  # warmup/prefetch path: no stall
        assert p.stats()["stream_stalls"] == 1
        p.close()

    def test_prefetch_then_ensure_is_stall_free(self):
        rig = _PoolRig(device_budget_bytes=0)
        p = rig.pool
        p.prefetch([3, 4])
        assert p.wait_idle(timeout_s=10)
        assert set(p.resident_slabs()) >= {3, 4}
        p.ensure(3), p.ensure(4)
        s = p.stats()
        assert s["stream_stalls"] == 0 and s["device_hits"] == 2
        assert s["prefetch_enqueued"] == 2
        p.close()

    def test_promotion_error_surfaces_and_pool_survives(self):
        rig = _PoolRig(fail_slabs={5})
        p = rig.pool
        with pytest.raises(RuntimeError, match="slab 5"):
            p.ensure(5)
        s = p.stats()
        assert s["promotion_errors"] == 1 and "slab 5" in s["last_error"]
        # the prefetch thread survives a failing slab too
        p.prefetch([5, 6])
        assert p.wait_idle(timeout_s=10)
        s = p.stats()
        assert s["prefetch_errors"] == 1 and 6 in p.resident_slabs()
        p.close()

    def test_faults_injected_slow_promotion_counts_a_stall(self):
        from mpi_cuda_largescaleknn_tpu.serve.faults import FaultInjector

        inj = FaultInjector.from_env()
        inj.set_specs("latency:path=/slab/2,delay_s=0.25")
        rig = _PoolRig(build_cost=0.0, faults=inj)
        p = rig.pool
        # injectable sleep rides the SAME fake clock — no real sleeping
        p._sleep = lambda s: rig.now.__setitem__(0, rig.now[0] + s)
        p.ensure(1)
        assert p.stats()["stream_stall_seconds"] == pytest.approx(0.0)
        p.ensure(2)  # the injected 250 ms promotion delay is a stall
        assert p.stats()["stream_stall_seconds"] == pytest.approx(0.25)
        p.close()

    def test_faults_injected_promotion_failure_raises(self):
        from mpi_cuda_largescaleknn_tpu.serve.faults import FaultInjector

        inj = FaultInjector.from_env()
        inj.set_specs("error:path=/slab/1,n=1")
        rig = _PoolRig(faults=inj)
        with pytest.raises(RuntimeError, match="injected"):
            rig.pool.ensure(1)
        rig.pool.ensure(1)  # fire budget n=1 spent -> retry succeeds
        assert 1 in rig.pool.resident_slabs()
        rig.pool.close()

    def test_concurrent_ensure_single_build(self):
        rig = _PoolRig()
        p = rig.pool
        errs = []

        def hit():
            try:
                p.ensure(2)
            except Exception as e:  # pragma: no cover - surfaced below
                errs.append(e)

        ts = [threading.Thread(target=hit) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=10)
        assert not errs
        assert rig.built.count(2) == 1  # one promotion, not four
        p.close()


# ------------------------------------------------------- streaming parity


@pytest.fixture(scope="module")
def parity_rig():
    """One fully-resident reference engine + one streaming engine over
    the same 600 points (4 slabs, shared AOT cache), both canonical."""
    from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
    from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine
    from mpi_cuda_largescaleknn_tpu.serve.slabpool import StreamingKnnEngine

    pts = _streaming_points()
    ref = ResidentKnnEngine(pts, K, mesh=get_mesh(2), engine="tiled",
                            bucket_size=64, max_batch=32, min_batch=16,
                            merge="device")
    ref.warmup()
    stream = StreamingKnnEngine(points=pts, num_slabs=4, k=K,
                                mesh=get_mesh(2), engine="tiled",
                                bucket_size=64, max_batch=32, min_batch=16,
                                merge="device")
    stream.warmup()
    yield pts, ref, stream
    stream.close()


@contextlib.contextmanager
def _promotion_worker_parked(pool):
    """Hold the pool's promotion worker off the batches run inside.

    ``dispatch`` hands its wave to the promotion thread before it ensures
    each slab, so a dispatch thread descheduled for a promotion's length
    finds the slab already landed and never stalls. Promotions enqueued
    inside go to a parked queue and reach the worker on exit; a missing
    slab is then still missing when the dispatch asks for it."""
    live, pool._pq = pool._pq, queue.Queue()
    try:
        yield
    finally:
        parked, pool._pq = pool._pq, live
        while not parked.empty():
            live.put(parked.get())


def _probe_batches(pts, seed=0):
    """Deterministic probe set: random batches, cluster-boundary rows,
    exact-duplicate coordinates (distance-0 cross-slab ties), and a
    single-row batch."""
    rng = np.random.default_rng(seed)
    return [
        rng.random((17, 3)).astype(np.float32),
        rng.random((32, 3)).astype(np.float32) * 1.2 - 0.1,
        pts[[0, 150, 295, 296, 299, 595, 599]],  # the planted dups
        np.full((3, 3), 0.5, np.float32),        # the A/B gap
        pts[42:43],
    ]


class TestStreamingParity:
    def test_bitwise_parity_across_budget_matrix(self, parity_rig):
        """THE acceptance bar: budgets {1 slab, half, all} all serve the
        fully-resident engine's exact bytes — dists and tie ids."""
        pts, ref, stream = parity_rig
        slab_b = stream.slab_device_bytes
        for budget_slabs in (1, 2, 4):
            stream.slab_pool.set_device_budget(slab_b * budget_slabs)
            for q in _probe_batches(pts):
                dr, nr = ref.query(q)
                ds, ns = stream.query(q)
                assert np.array_equal(np.asarray(dr, np.float32), ds), \
                    f"dists diverge at budget {budget_slabs} slabs"
                assert np.array_equal(np.asarray(nr), ns), \
                    f"tie/neighbor ids diverge at budget {budget_slabs}"
            assert (len(stream.slab_pool.resident_slabs())
                    <= max(1, budget_slabs) + 1)

    def test_deep_cluster_query_routes_away_from_far_slabs(self,
                                                           parity_rig):
        """Routing actually routes: a query deep inside cluster A never
        visits the B-side slabs (they certify away on bounds — that is
        the streaming win: far slabs need not even be resident); gap
        queries escalate across the boundary."""
        pts, _ref, stream = parity_rig
        stream.slab_pool.set_device_budget(0)
        h = stream.dispatch(pts[10:11])  # deep inside cluster A
        stream.complete(h)
        # slabs 2/3 hold cluster B (rows 300..599) — certified away
        assert h.visited[0, 2:].sum() == 0
        before = stream.timers.counter("stream_escalations")
        h2 = stream.dispatch(np.full((2, 3), 0.5, np.float32))  # the gap
        stream.complete(h2)
        assert h2.visited.sum(axis=1).max() > 1
        assert stream.timers.counter("stream_escalations") > before

    def test_pins_released_after_complete(self, parity_rig):
        pts, _ref, stream = parity_rig
        stream.query(pts[:8])
        assert stream.slab_pool.stats()["pinned_slabs"] == []

    def test_aot_shared_across_eviction_churn(self, parity_rig):
        """Recompile freedom pool-wide: cycling every slab through a
        1-slab budget reuses the shared executables — compile_count
        (the shared cache's compile counter) stays flat."""
        pts, _ref, stream = parity_rig
        before = stream.stats()["compile_count"]
        slab_b = stream.slab_device_bytes
        stream.slab_pool.set_device_budget(slab_b)  # churn everything
        for q in _probe_batches(pts):
            stream.query(q)
        stats = stream.stats()
        assert stats["compile_count"] == before
        assert stats["slab_pool"]["evictions"] > 0  # it really churned
        stream.slab_pool.set_device_budget(0)

    def test_prefetch_hint_announced_ahead_means_zero_stalls(self,
                                                             parity_rig):
        """The overlap contract: announcing the routed slab set a batch
        ahead (and letting the promotion thread land it) makes the later
        dispatch stall-free."""
        pts, _ref, stream = parity_rig
        slab_b = stream.slab_device_bytes
        # budget of 3 slabs: wide enough for one batch's full routed set
        # (slab 1's box spans both clusters — the planted outliers — so a
        # B batch routes to {1, 2, 3}), narrow enough that parking at one
        # end of the index evicts the other end's slabs
        pool = stream.slab_pool
        pool.set_device_budget(3 * slab_b)
        q_a, q_b = pts[10:18], pts[590:598]  # opposite ends of the index
        _lb, want = stream._wave1_want(q_b)
        b_set = set(np.nonzero(want.any(axis=0))[0].tolist())
        stream.query(q_a)  # park the pool at the A end
        assert pool.wait_idle(timeout_s=30)
        stream.prefetch_hint(q_b)  # announce the B batch one batch ahead
        assert pool.wait_idle(timeout_s=30)
        assert b_set <= set(pool.resident_slabs())
        before = pool.stats()["stream_stalls"]
        stream.query(q_b)
        assert pool.stats()["stream_stalls"] == before
        # and the un-hinted twin DOES stall after the pool moves away
        stream.query(q_a)
        assert pool.wait_idle(timeout_s=30)
        assert not b_set <= set(pool.resident_slabs())
        with _promotion_worker_parked(pool):
            stream.query(q_b)
        assert pool.stats()["stream_stalls"] > before
        assert pool.wait_idle(timeout_s=30)
        pool.set_device_budget(0)

    def test_slow_promotion_stalls_but_stays_exact(self, parity_rig):
        """faults.py latency on a promotion: the batch STALLS (counted)
        instead of deadlocking or approximating — the answer is still
        the reference's bytes."""
        from mpi_cuda_largescaleknn_tpu.serve.faults import FaultInjector

        pts, ref, stream = parity_rig
        slab_b = stream.slab_device_bytes
        stream.slab_pool.set_device_budget(slab_b)
        stream.query(pts[10:18])  # park at the A end
        stream.slab_pool.wait_idle(timeout_s=30)
        inj = FaultInjector.from_env()
        inj.set_specs("latency:path=/slab/,delay_s=0.2")
        stream.slab_pool._faults = inj
        try:
            before = stream.slab_pool.stats()
            q = pts[590:598]
            dr, nr = ref.query(q)
            with _promotion_worker_parked(stream.slab_pool):
                ds, ns = stream.query(q)
            after = stream.slab_pool.stats()
            assert np.array_equal(np.asarray(dr, np.float32), ds)
            assert np.array_equal(np.asarray(nr), ns)
            assert after["stream_stalls"] > before["stream_stalls"]
            assert (after["stream_stall_seconds"]
                    >= before["stream_stall_seconds"] + 0.2)
        finally:
            stream.slab_pool._faults = None
            stream.slab_pool.set_device_budget(0)

    def test_dispatch_promotion_failure_releases_pins(self):
        """A failed promotion mid-dispatch must raise AND release the
        batch's pins — leaked pins would make slabs permanently
        unevictable; after the fault clears the engine serves exactly."""
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
        from mpi_cuda_largescaleknn_tpu.serve.faults import FaultInjector
        from mpi_cuda_largescaleknn_tpu.serve.slabpool import (
            StreamingKnnEngine,
        )
        from tests.oracle import random_points

        pts = np.sort(random_points(96, seed=3), axis=0)  # slab locality
        # prefetch_depth=0: the escalation-insurance prefetch would
        # otherwise promote the far slab in the background and the
        # deterministic fault below would never be reached
        stream = StreamingKnnEngine(points=pts, num_slabs=2, k=3,
                                    mesh=get_mesh(1), engine="tiled",
                                    bucket_size=32, max_batch=16,
                                    min_batch=8, prefetch_depth=0)
        try:
            stream.slab_pool.set_device_budget(stream.slab_device_bytes)
            stream.query(pts[:4])  # park at the low end
            stream.slab_pool.wait_idle(timeout_s=30)
            inj = FaultInjector.from_env()
            inj.set_specs("error:path=/slab/,n=2")
            stream.slab_pool._faults = inj
            with pytest.raises(RuntimeError, match="injected"):
                stream.query(pts[90:94])  # needs the evicted far slab
            assert stream.slab_pool.stats()["pinned_slabs"] == []
            inj.clear()
            d, n = stream.query(pts[90:94])  # recovers, still exact
            assert np.isfinite(d).all()
        finally:
            stream.close()

    def test_max_radius_parity(self):
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
        from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine
        from mpi_cuda_largescaleknn_tpu.serve.slabpool import (
            StreamingKnnEngine,
        )
        from tests.oracle import random_points

        pts = random_points(96, seed=3)
        ref = ResidentKnnEngine(pts, 3, mesh=get_mesh(2), engine="tiled",
                                bucket_size=32, max_batch=16, min_batch=8,
                                max_radius=0.15)
        stream = StreamingKnnEngine(points=pts, num_slabs=2, k=3,
                                    mesh=get_mesh(2), engine="tiled",
                                    bucket_size=32, max_batch=16,
                                    min_batch=8, max_radius=0.15)
        try:
            q = random_points(16, seed=9)
            dr, nr = ref.query(q)
            ds, ns = stream.query(q)
            assert np.array_equal(np.asarray(dr, np.float32), ds)
            assert np.array_equal(np.asarray(nr), ns)
        finally:
            stream.close()

    def test_candidates_emit_parity(self):
        """emit='candidates' (the routed-host wrapper): the streamed fold
        equals a resident candidates engine's rows bitwise — what a
        routed pod folds when its hosts stream sub-slabs."""
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
        from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine
        from mpi_cuda_largescaleknn_tpu.serve.slabpool import (
            StreamingKnnEngine,
        )
        from tests.oracle import random_points

        pts = random_points(128, seed=5)
        ref = ResidentKnnEngine(pts, 4, mesh=get_mesh(2), engine="tiled",
                                bucket_size=32, max_batch=16, min_batch=8,
                                id_offset=1000, emit="candidates")
        stream = StreamingKnnEngine(points=pts, num_slabs=3, k=4,
                                    mesh=get_mesh(2), engine="tiled",
                                    bucket_size=32, max_batch=16,
                                    min_batch=8, id_offset=1000,
                                    emit="candidates")
        try:
            q = random_points(12, seed=11)
            dr, nr = ref.complete_candidates(ref.dispatch(q))
            ds, ns = stream.complete_candidates(stream.dispatch(q))
            assert np.array_equal(np.asarray(dr), ds)
            assert np.array_equal(np.asarray(nr), ns)
            with pytest.raises(RuntimeError, match="complete_candidates"):
                stream.complete(stream.dispatch(q))
        finally:
            stream.close()

    def test_empty_batch(self, parity_rig):
        _pts, _ref, stream = parity_rig
        d, n = stream.query(np.zeros((0, 3), np.float32))
        assert d.shape == (0,) and n.shape == (0, K)


# -------------------------------------------------------- serving surface


class TestStreamingServing:
    def test_stats_and_metrics_surface(self, parity_rig):
        from mpi_cuda_largescaleknn_tpu.serve.server import build_server

        _pts, _ref, stream = parity_rig
        stats = stream.stats()
        pool = stats["slab_pool"]
        for key in ("device_resident", "host_resident", "promotions",
                    "evictions", "stream_stalls", "stream_stall_seconds",
                    "device_hits", "host_hits", "cold_reads",
                    "device_budget_bytes", "slab_device_bytes"):
            assert key in pool, key
        assert stats["device_bytes"] == (stream.slab_device_bytes
                                         * pool["device_resident"])
        srv = build_server(stream, port=0)
        try:
            from mpi_cuda_largescaleknn_tpu.serve.server import _Handler

            text = _Handler._prometheus(srv)
            for line in ('knn_slab_pool_resident{tier="device"}',
                         'knn_slab_pool_resident{tier="host"}',
                         "knn_slab_promotions_total",
                         "knn_slab_evictions_total",
                         "knn_stream_stall_seconds_total",
                         'knn_slab_pool_hits_total{tier="device"}',
                         "knn_slab_pool_cold_reads_total"):
                assert line in text, line
        finally:
            srv.close()

    def test_served_e2e_oracle_exact(self, parity_rig):
        """Full HTTP stack over the streaming engine at a 2-slab budget:
        batcher + admission + server, answers equal to brute force."""
        import json
        import urllib.request

        from mpi_cuda_largescaleknn_tpu.serve.server import build_server
        from tests.oracle import kth_nn_dist

        pts, _ref, stream = parity_rig
        stream.slab_pool.set_device_budget(stream.slab_device_bytes * 2)
        srv = build_server(stream, port=0, pipeline_depth=2)
        srv.ready = True
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        try:
            rng = np.random.default_rng(0)
            q = rng.random((24, 3)).astype(np.float32)
            req = urllib.request.Request(
                base + "/knn",
                data=json.dumps({"queries": q.tolist()}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                got = np.asarray(json.loads(resp.read())["dists"],
                                 np.float32)
            want = kth_nn_dist(q, pts, K)
            assert np.allclose(got, want, rtol=5e-7, atol=1e-37)
        finally:
            srv.close()
            stream.slab_pool.set_device_budget(0)

    def test_served_parity_after_sweep_churn_at_quarter_budget(
            self, parity_rig):
        """The index at 4x the device budget (1 of 4 slabs resident),
        served over HTTP with the query cache off: a probe answers the
        fully-resident engine's bytes (dists and ids) before AND after a
        sweep that churns the pool end to end."""
        import json
        import urllib.request

        from mpi_cuda_largescaleknn_tpu.serve.server import build_server

        pts, ref, stream = parity_rig
        pool = stream.slab_pool
        pool.set_device_budget(stream.slab_device_bytes)
        srv = build_server(stream, port=0, pipeline_depth=2,
                           qcache_rows=0)
        srv.ready = True
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"

        def post(q):
            req = urllib.request.Request(
                base + "/knn", data=json.dumps(
                    {"queries": q.tolist(), "neighbors": True}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                out = json.loads(resp.read())
            return (np.asarray(out["dists"], np.float32),
                    np.asarray(out["neighbors"], np.int32))

        rng = np.random.default_rng(17)
        probe = np.concatenate([rng.random((24, 3)).astype(np.float32),
                                pts[[0, 295, 299, 595, 599]]])
        want_d, want_n = (np.asarray(a) for a in ref.query(probe))
        try:
            d, n = post(probe)
            assert np.array_equal(d, want_d.astype(np.float32))
            assert np.array_equal(n, want_n)
            evictions = pool.stats()["evictions"]
            sweep = np.linspace(0.1, 0.9, 5)
            for c in np.concatenate([sweep, sweep[::-1]]):  # there, back
                post(np.clip(c + rng.normal(0, 0.05, (16, 3)), 0, 1)
                     .astype(np.float32))
            assert pool.stats()["evictions"] >= evictions + stream.num_slabs
            d, n = post(probe)
            assert np.array_equal(d, want_d.astype(np.float32))
            assert np.array_equal(n, want_n)
        finally:
            srv.close()
            pool.set_device_budget(0)


class TestBatcherPrefetchHint:
    def test_queued_rows_announced_after_dispatch(self):
        """``_announce_prefetch`` forwards the still-QUEUED rows — the
        next batch's content, capped at max_batch — to the query_fn's
        ``prefetch_hint`` (deterministic unit drive: the queue is staged
        directly, no worker races)."""
        import time as _time

        from mpi_cuda_largescaleknn_tpu.serve.batcher import (
            DynamicBatcher,
            _Request,
        )

        hinted = []

        class _Fn:
            dim = 3

            def dispatch(self, q):
                return np.asarray(q)

            def complete(self, handle):
                n = len(handle)
                return np.zeros(n, np.float32), np.zeros((n, 2), np.int32)

            def prefetch_hint(self, q):
                hinted.append(np.asarray(q).copy())

        b = DynamicBatcher(_Fn(), max_batch=4, max_delay_s=60.0,
                           pipeline_depth=2)
        try:
            assert b._prefetch_fn is not None  # wired through
            now = _time.monotonic()
            with b._cond:
                for i in range(3):  # 6 rows queued > max_batch 4
                    b._queue.append(_Request(
                        queries=np.full((2, 3), i, np.float32),
                        deadline=None, enqueued=now))
            b._announce_prefetch()
            assert len(hinted) == 1
            # capped at max_batch whole requests: 2 of the 3 (4 rows)
            assert hinted[0].shape == (4, 3)
            assert np.array_equal(hinted[0][:2],
                                  np.zeros((2, 3), np.float32))
            # empty queue -> no announcement
            with b._cond:
                b._queue.clear()
            b._announce_prefetch()
            assert len(hinted) == 1
            assert b.stats()["prefetch_hint_errors"] == 0
        finally:
            with b._cond:
                b._queue.clear()
                b._cond.notify_all()
            b.shutdown()

    def test_hint_errors_counted_not_fatal(self):
        from mpi_cuda_largescaleknn_tpu.serve.batcher import DynamicBatcher

        release = threading.Event()

        class _Fn:
            dim = 3

            def dispatch(self, q):
                return np.asarray(q)

            def complete(self, handle):
                release.wait(10)
                n = len(handle)
                return (np.zeros(n, np.float32),
                        np.zeros((n, 2), np.int32))

            def prefetch_hint(self, q):
                raise RuntimeError("hint exploded")

        b = DynamicBatcher(_Fn(), max_batch=8, max_delay_s=0.001,
                           pipeline_depth=2)
        try:
            out = []
            ts = [threading.Thread(
                target=lambda i=i: out.append(
                    b.submit(np.full((2, 3), i, np.float32))))
                for i in range(4)]
            for t in ts:
                t.start()
            release.set()
            for t in ts:
                t.join(timeout=10)
            assert len(out) == 4  # every batch still answered
        finally:
            b.shutdown()
