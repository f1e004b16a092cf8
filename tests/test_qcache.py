"""Certified query cache (serve/qcache.py): exactness is the whole bar.

Every reuse tier must be invisible in the served bits: exact hits replay
byte-identical rows, in-flight dedup hands joiners the owner's bytes, and
triangle-inequality radius seeds must leave engine output BITWISE
unchanged — distances AND ids, ties included — across merge placements,
streaming budgets, and routed pods. The fixtures plant the adversarial
cases on purpose: exact-duplicate coordinates (distance-0 ties at the
seed boundary), ragged batches (pad rows carry the unseeded sentinel),
and anchors identical to their revisits (the tightest possible seed).
"""

from __future__ import annotations

import json
import threading
import urllib.request

import numpy as np
import pytest

from mpi_cuda_largescaleknn_tpu.serve.qcache import (
    _SEED_FLOOR,
    QueryCache,
    SeedPool,
    certified_seeds,
)
from tests.oracle import random_points

K = 8


def _dup_points(n=900, seed=11):
    """Point set with planted exact-duplicate coordinates: rows
    [n-5:n) are copies of rows [0:5), so true top-k sets contain
    distance-0 cross-row ties — the canonical-order fold's worst case,
    and the seed boundary's (a seed derived from one copy sits one ulp
    above a kth distance the other copy ties exactly)."""
    pts = random_points(n - 5, seed=seed)
    return np.concatenate([pts, pts[:5]]).astype(np.float32)


@pytest.fixture(scope="module")
def points():
    return _dup_points()


@pytest.fixture(scope="module", params=["host", "device"])
def merge_engine(request, points):
    from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
    from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine

    eng = ResidentKnnEngine(points, K, mesh=get_mesh(8), engine="tiled",
                            bucket_size=32, max_batch=64, min_batch=8,
                            merge=request.param)
    eng.warmup()
    return eng


def _anchor_seeds(engine, anchors, revisits):
    """Certified seeds for ``revisits`` from exact engine answers at
    ``anchors`` — the same math the cache's seed pool applies."""
    dk, ids = engine.query(anchors)
    assert np.all(ids >= 0) and np.all(np.isfinite(dk))
    return certified_seeds(revisits, anchors, dk.astype(np.float32))


class TestCertifiedSeedMath:
    def test_seed_strictly_exceeds_bound_after_f32_squaring(self):
        """The parity requirement: f32(seed)**2 must be STRICTLY greater
        than the f32 square of any distance <= the f64 bound — a plain
        radius-domain nextafter fails this (both squares can round to
        the same f32), which is why the slack is multiplicative."""
        rng = np.random.default_rng(0)
        src_q = rng.random((64, 3)).astype(np.float32)
        src_dk = (rng.random(64) * 0.2).astype(np.float32)
        q = rng.random((128, 3)).astype(np.float32)
        seeds = certified_seeds(q, src_q, src_dk)
        q64, s64 = q.astype(np.float64), src_q.astype(np.float64)
        d = np.sqrt(((q64[:, None, :] - s64[None, :, :]) ** 2).sum(axis=2))
        bound = np.min(src_dk.astype(np.float64)[None, :] + d, axis=1)
        s2 = np.square(seeds).astype(np.float32)
        b2 = np.square(bound.astype(np.float32))
        assert np.all(s2 > b2)

    def test_distance_zero_anchor_floor(self):
        """An anchor identical to the query with dk == 0 must still
        produce a positive seed whose square is nonzero — otherwise the
        strict-< heap would reject the distance-0 candidate itself."""
        q = np.zeros((1, 3), np.float32)
        seeds = certified_seeds(q, q, np.zeros(1, np.float32))
        assert seeds[0] >= _SEED_FLOOR
        assert np.float32(seeds[0]) ** 2 > 0.0

    def test_seed_pool_ring_and_dim_guard(self):
        pool = SeedPool(4)
        for i in range(6):  # overwrite-oldest past capacity
            pool.add(np.full(3, i, np.float32), float(i))
        q, dk = pool.snapshot()
        assert len(q) == 4 and set(dk.tolist()) == {2.0, 3.0, 4.0, 5.0}
        pool.add(np.zeros(5, np.float32), 1.0)  # dim mismatch: ignored
        q2, _ = pool.snapshot()
        assert q2.shape == (4, 3)

    def test_empty_pool_returns_none(self):
        assert SeedPool(4).snapshot() is None


class TestSeededBitwiseResident:
    """seeded == unseeded, bit for bit, on merge=host AND merge=device
    (the fixture params), over adversarial probes."""

    def _probes(self, points, seed=3):
        rng = np.random.default_rng(seed)
        return [
            rng.random((40, 3)).astype(np.float32),
            points[[0, 1, 895, 896, 897]],  # the planted duplicates
            points[10:11],                   # single ragged row
        ]

    def test_identical_anchor_tightest_seed(self, merge_engine, points):
        """Revisit == anchor: the seed is one slack step above the TRUE
        kth distance — the tightest certified seed possible — and the
        planted duplicate rows put distance-0 ties at the boundary."""
        for q in self._probes(points):
            d0, n0 = merge_engine.query(q)
            seeds = _anchor_seeds(merge_engine, q, q)
            d1, n1 = merge_engine.query(q, seed_radius=seeds)
            assert d0.tobytes() == d1.tobytes()
            assert n0.tobytes() == n1.tobytes()

    def test_jittered_revisit_and_mixed_unseeded_rows(self, merge_engine,
                                                      points):
        """Near-duplicate revisits with HALF the rows left unseeded
        (+inf = the engine's unseeded sentinel) — one program family
        serves mixed batches; ragged rows pad inside the bucket."""
        rng = np.random.default_rng(5)
        anchors = rng.random((24, 3)).astype(np.float32)
        q = (anchors + rng.normal(0, 1e-3, anchors.shape)
             ).astype(np.float32)
        seeds = _anchor_seeds(merge_engine, anchors, q)
        seeds[::2] = np.inf
        d0, n0 = merge_engine.query(q)
        d1, n1 = merge_engine.query(q, seed_radius=seeds)
        assert d0.tobytes() == d1.tobytes()
        assert n0.tobytes() == n1.tobytes()

    def test_seeded_dispatch_compiles_nothing_new(self, merge_engine):
        """The per-query radius is a dynamic operand, not a trace
        constant: seeding an already-warm bucket must not compile."""
        rng = np.random.default_rng(7)
        q = rng.random((16, 3)).astype(np.float32)
        merge_engine.query(q)  # bucket warm
        before = merge_engine.compile_count
        seeds = _anchor_seeds(merge_engine, q, q)
        merge_engine.query(q, seed_radius=seeds)
        assert merge_engine.compile_count == before

    def test_seed_length_mismatch_raises(self, merge_engine):
        q = np.zeros((4, 3), np.float32)
        with pytest.raises(ValueError, match="seed_radius"):
            merge_engine.query(q, seed_radius=np.ones(3, np.float32))

    def test_finite_max_radius_clamps_seed(self, points):
        """Engine with finite max_radius: a seed above it is clamped by
        dispatch and the under-full rows keep the radius sentinel."""
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
        from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine

        eng = ResidentKnnEngine(points, K, mesh=get_mesh(8),
                                engine="tiled", bucket_size=32,
                                max_batch=32, min_batch=8,
                                max_radius=0.05)
        rng = np.random.default_rng(9)
        q = rng.random((16, 3)).astype(np.float32)
        d0, n0 = eng.query(q)
        # seeds far above max_radius AND far below it, mixed
        seeds = np.full(16, np.inf, np.float32)
        seeds[:8] = np.float32(10.0)
        d1, n1 = eng.query(q, seed_radius=seeds)
        assert d0.tobytes() == d1.tobytes()
        assert n0.tobytes() == n1.tobytes()


class TestSeededBitwiseStreaming:
    def test_streaming_budget_matrix(self, points):
        """Seeded == unseeded across device budgets {1 slab, all}: the
        fold init starts at seed² but every slab a true candidate lives
        in is still visited, so promotions may shrink — bits may not."""
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
        from mpi_cuda_largescaleknn_tpu.serve.slabpool import (
            StreamingKnnEngine,
        )

        rng = np.random.default_rng(13)
        q = rng.random((24, 3)).astype(np.float32)
        for budget_slabs in (1, 0):  # 1-resident-slab squeeze, unbounded
            stream = StreamingKnnEngine(
                points=points, num_slabs=4, k=K, mesh=get_mesh(2),
                engine="tiled", bucket_size=64, max_batch=32,
                min_batch=16, merge="device")
            if budget_slabs:
                stream._pool.set_device_budget(stream.slab_device_bytes)
            try:
                d0, n0 = stream.query(q)
                seeds = _anchor_seeds(stream, q, q)
                d1, n1 = stream.query(q, seed_radius=seeds)
                assert d0.tobytes() == d1.tobytes()
                assert n0.tobytes() == n1.tobytes()
            finally:
                stream.close()


class TestSeededBitwiseRouted:
    @pytest.fixture(scope="class")
    def routed(self, points):
        """Two routed slab hosts + a RoutedPodFanout, overlap planted via
        the duplicate rows living in slab 0 while their copies end slab 1."""
        from mpi_cuda_largescaleknn_tpu.models.sharding import slab_bounds
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
        from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine
        from mpi_cuda_largescaleknn_tpu.serve.frontend import (
            HostSliceServer,
            build_frontend,
        )

        servers = []
        for b, e in slab_bounds(len(points), 2):
            eng = ResidentKnnEngine(points[b:e], K, mesh=get_mesh(2),
                                    engine="tiled", bucket_size=64,
                                    max_batch=32, min_batch=16,
                                    id_offset=b, emit="candidates")
            eng.warmup()
            srv = HostSliceServer(("127.0.0.1", 0), eng, routing="bounds")
            threading.Thread(target=srv.serve_forever, daemon=True).start()
            srv.ready = True
            servers.append(srv)
        urls = [f"http://127.0.0.1:{s.server_address[1]}" for s in servers]
        front = build_frontend(urls, port=0, start_monitor=False)
        front.ready = True
        threading.Thread(target=front.serve_forever, daemon=True).start()
        yield front
        front.close()
        for s in servers:
            s.close()

    def test_routed_fanout_seeded_bitwise(self, routed, points):
        """The fan-out's escalation radius starts at the seed; the
        certified answer — including the exact flags — stays bitwise."""
        fanout = routed.fanout
        rng = np.random.default_rng(17)
        for q in (rng.random((20, 3)).astype(np.float32),
                  points[[0, 1, 895, 896]],  # cross-slab distance-0 ties
                  points[42:43]):
            d0, n0, e0 = fanout(q)
            assert np.all(e0)
            dk, ids, _ = fanout(q)
            seeds = certified_seeds(q, q, dk.astype(np.float32))
            d1, n1, e1 = fanout(q, seed_radius=seeds)
            assert d0.tobytes() == d1.tobytes()
            assert n0.tobytes() == n1.tobytes()
            assert np.array_equal(e0, e1)

    def test_frontend_http_hit_path_byte_identity(self, routed):
        """Same JSON body twice through the pod front end: the second is
        served from the cache — and the response bytes are identical."""
        base = f"http://127.0.0.1:{routed.server_address[1]}"
        rng = np.random.default_rng(19)
        body = json.dumps({
            "queries": rng.random((6, 3)).astype(np.float32).tolist(),
            "neighbors": True}).encode()

        def post():
            req = urllib.request.Request(
                base + "/knn", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                return resp.read()

        first, second = post(), post()
        assert first == second
        with urllib.request.urlopen(base + "/stats", timeout=30) as r:
            stats = json.loads(r.read())
        assert stats["qcache"]["hits"] >= 6
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            metrics = r.read().decode()
        assert "knn_qcache_hits_total" in metrics
        assert "knn_qcache_seeds_total" in metrics


class TestCacheKeying:
    def _publish(self, qc, q, tenant=None, plan_token=None, dists=None,
                 ids=None):
        actions = qc.begin(q, plan_token, tenant)
        keys = [a[1] for a in actions if a[0] == "own"]
        d = dists if dists is not None else np.arange(len(q), dtype=np.float32)
        i = (ids if ids is not None
             else np.tile(np.arange(K, dtype=np.int32), (len(q), 1)))
        qc.publish(keys, (d, i), q, plan_token, tenant)
        return actions

    def test_tenants_keyed_apart(self):
        qc = QueryCache(capacity_rows=64, seed_rows=8)
        q = np.stack([np.ones(3, np.float32),
                      np.full(3, 2.0, np.float32)])
        self._publish(qc, q, tenant="a")
        actions_b = qc.begin(q, None, "b")
        assert all(a[0] == "own" for a in actions_b), "cross-tenant hit!"
        qc.abort([a[1] for a in actions_b if a[0] == "own"])
        hits = [a for a in qc.begin(q, None, "a") if a[0] == "hit"]
        assert len(hits) == 2
        # tenant twins: only tenant a has hit counters
        st = qc.stats()
        assert st["tenants"]["a"]["hits"] == 2
        assert st["tenants"].get("b", {}).get("hits", 0) == 0

    def test_plans_keyed_apart(self):
        from mpi_cuda_largescaleknn_tpu.serve.recall import DEFAULT_PLANS

        qc = QueryCache(capacity_rows=64, seed_rows=8)
        q = np.ones((1, 3), np.float32)
        tok = DEFAULT_PLANS[0].batch_key()
        self._publish(qc, q, plan_token=tok)
        assert qc.begin(q, None, None)[0][0] == "own"  # exact misses
        assert qc.begin(q, DEFAULT_PLANS[1].batch_key(), None)[0][0] == "own"
        assert qc.begin(q, tok, None)[0][0] == "hit"

    def test_generation_fences_reuse(self):
        qc = QueryCache(capacity_rows=64, seed_rows=8)
        q = np.ones((1, 3), np.float32)
        self._publish(qc, q)
        qc.invalidate()
        assert qc.begin(q, None, None)[0][0] == "own"
        assert qc.stats()["generation"] == 1

    def test_lru_eviction_bound(self):
        qc = QueryCache(capacity_rows=2, seed_rows=0)
        for v in range(3):
            self._publish(qc, np.full((1, 3), v, np.float32))
        st = qc.stats()
        assert st["size_rows"] == 2 and st["evictions"] == 1
        # the oldest row is the evicted one
        assert qc.begin(np.zeros((1, 3), np.float32), None, None)[0][0] \
            == "own"

    def test_degraded_rows_never_cached(self):
        qc = QueryCache(capacity_rows=64, seed_rows=8)
        q = np.ones((1, 3), np.float32)
        actions = qc.begin(q, None, None)
        keys = [a[1] for a in actions if a[0] == "own"]
        qc.publish(keys, (np.ones(1, np.float32),
                          np.zeros((1, K), np.int32),
                          np.zeros(1, bool)), q, None, None)
        assert qc.begin(q, None, None)[0][0] == "own"
        assert qc.stats()["inserts"] == 0

    def test_underfull_rows_never_feed_seed_pool(self):
        """A row with -1 pad ids (or an infinite kth distance) must not
        become a seed anchor — fullness is the soundness precondition."""
        qc = QueryCache(capacity_rows=64, seed_rows=8)
        q = np.ones((2, 3), np.float32)
        ids = np.tile(np.arange(K, dtype=np.int32), (2, 1))
        ids[0, -1] = -1
        d = np.array([1.0, np.inf], np.float32)
        self._publish(qc, q, dists=d, ids=ids)
        assert qc.seed_for(np.ones((1, 3), np.float32), None) is None

    def test_seed_rows_zero_disables_seeding_only(self):
        qc = QueryCache(capacity_rows=64, seed_rows=0)
        q = np.ones((1, 3), np.float32)
        self._publish(qc, q)
        assert qc.seed_for(q, None) is None
        assert qc.begin(q, None, None)[0][0] == "hit"


class TestInFlightDedup:
    def _batcher(self, fn, qc=None):
        from mpi_cuda_largescaleknn_tpu.serve.batcher import DynamicBatcher

        return DynamicBatcher(fn, max_batch=64, max_delay_s=0.001,
                              qcache=qc)

    def test_concurrent_identical_submitters_share_one_computation(self):
        """8 threads submit the same 4 rows; the engine must see far
        fewer than 32 rows and every thread gets identical bytes."""
        rows_seen = []
        gate = threading.Event()

        def query_fn(q):
            rows_seen.append(len(q))
            gate.wait(10)  # hold the owner so others join in flight
            return (np.linalg.norm(q, axis=1).astype(np.float32),
                    np.tile(np.arange(K, dtype=np.int32), (len(q), 1)))

        qc = QueryCache(capacity_rows=64, seed_rows=0)
        b = self._batcher(query_fn, qc)
        q = np.full((4, 3), 0.25, np.float32)
        results = [None] * 8

        def worker(i):
            if i == 7:
                gate.set()  # last thread releases the gate
            results[i] = b.submit(q, timeout_s=30)

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        b.shutdown()
        ref = results[0]
        for r in results[1:]:
            assert r[0].tobytes() == ref[0].tobytes()
            assert r[1].tobytes() == ref[1].tobytes()
        assert sum(rows_seen) < 32
        assert qc.stats()["dedup_rows"] + qc.stats()["hits"] > 0

    def test_owner_failure_wakes_joiners_who_retry(self):
        """The owner's batch fails once; joiners must NOT hang on the
        aborted entry — they retry as their own owners and succeed."""
        calls = {"n": 0}
        owner_in = threading.Event()
        release = threading.Event()

        def query_fn(q):
            calls["n"] += 1
            if calls["n"] == 1:
                owner_in.set()
                release.wait(10)
                raise RuntimeError("transient engine fault")
            return (np.zeros(len(q), np.float32),
                    np.tile(np.arange(K, dtype=np.int32), (len(q), 1)))

        qc = QueryCache(capacity_rows=64, seed_rows=0)
        b = self._batcher(query_fn, qc)
        q = np.full((2, 3), 0.5, np.float32)
        out = {}

        def owner():
            try:
                b.submit(q, timeout_s=30)
            except RuntimeError as e:
                out["owner_error"] = e

        def joiner():
            owner_in.wait(10)
            release.set()
            out["joiner"] = b.submit(q, timeout_s=30)

        t1 = threading.Thread(target=owner)
        t2 = threading.Thread(target=joiner)
        t1.start(); t2.start()
        t1.join(30); t2.join(30)
        b.shutdown()
        assert "owner_error" in out
        assert out["joiner"][0].shape == (2,)
        assert qc.stats()["inflight_aborts"] >= 1

    def test_intra_request_duplicates_coalesce(self):
        rows_seen = []

        def query_fn(q):
            rows_seen.append(len(q))
            return (np.linalg.norm(q, axis=1).astype(np.float32),
                    np.tile(np.arange(K, dtype=np.int32), (len(q), 1)))

        qc = QueryCache(capacity_rows=64, seed_rows=0)
        b = self._batcher(query_fn, qc)
        base = np.random.default_rng(3).random((4, 3)).astype(np.float32)
        q = np.concatenate([base, base, base[:2]])
        d, n = b.submit(q, timeout_s=30)
        b.shutdown()
        assert sum(rows_seen) == 4
        assert d[:4].tobytes() == d[4:8].tobytes()
        assert d[8:].tobytes() == d[:2].tobytes()
        assert n[:4].tobytes() == n[4:8].tobytes()
        assert qc.stats()["dedup_rows"] == 6


class TestServerHitPath:
    @pytest.fixture(scope="class")
    def server(self, points):
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
        from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine
        from mpi_cuda_largescaleknn_tpu.serve.server import build_server

        eng = ResidentKnnEngine(points, K, mesh=get_mesh(8),
                                engine="tiled", bucket_size=32,
                                max_batch=64, min_batch=8)
        eng.warmup()
        srv = build_server(eng, port=0, max_delay_s=0.002)
        srv.ready = True
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        yield srv
        srv.close()

    def test_http_binary_hit_byte_identity(self, server):
        """Binary wire, same payload twice: the hit must replay the
        exact bytes AND count in /stats + /metrics with tenant twins."""
        base = f"http://127.0.0.1:{server.server_address[1]}"
        q = np.random.default_rng(23).random((5, 3)).astype(np.float32)

        def post():
            req = urllib.request.Request(
                base + "/knn?neighbors=1", data=q.tobytes(),
                headers={"Content-Type": "application/octet-stream"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.read()

        first, second = post(), post()
        assert first == second
        with urllib.request.urlopen(base + "/stats", timeout=30) as r:
            stats = json.loads(r.read())
        qs = stats["qcache"]
        assert qs["hits"] >= 5 and qs["inserts"] >= 5
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            metrics = r.read().decode()
        for name in ("knn_qcache_hits_total", "knn_qcache_seeds_total",
                     "knn_qcache_dedup_rows_total",
                     "knn_qcache_evictions_total"):
            assert name in metrics, f"missing {name}"

    def test_http_json_hit_byte_identity(self, server):
        """JSON wire, same body twice: the hit replays the first answer's
        exact response bytes."""
        base = f"http://127.0.0.1:{server.server_address[1]}"
        q = np.random.default_rng(31).random((16, 3)).astype(np.float32)
        body = json.dumps({"queries": q.tolist(),
                           "neighbors": True}).encode()

        def post():
            req = urllib.request.Request(
                base + "/knn", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.read()

        hits = server.qcache.stats()["hits"]
        first, second = post(), post()
        assert first == second
        assert server.qcache.stats()["hits"] >= hits + len(q)

    def test_seeded_revisit_traffic_compiles_nothing(self, server):
        """Exact replays and jittered revisits at every batch width: the
        seed radius is a dynamic operand, so cached traffic mints no new
        program once the shape buckets are warm."""
        base = f"http://127.0.0.1:{server.server_address[1]}"
        eng = server.engine
        rng = np.random.default_rng(37)

        def post(q):
            req = urllib.request.Request(
                base + "/knn", data=json.dumps(
                    {"queries": q.tolist(), "neighbors": True}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                return json.loads(resp.read())

        before = eng.compile_count
        seeds = server.qcache.stats()["seeds"]
        for n in (3, 8, 17, 40, eng.max_batch):
            anchors = rng.random((n, 3)).astype(np.float32)
            post(anchors)
            post(anchors)  # exact replay
            post((anchors + rng.normal(0, 1e-3, anchors.shape))
                 .astype(np.float32))  # jittered revisit, seeded
        assert eng.compile_count == before
        assert server.qcache.stats()["seeds"] > seeds

    def test_seeded_revisit_stream_matches_cold_server(self, server,
                                                       points):
        """Near-duplicate stream through the full server stack (cache
        warm, seeds engaged) vs the raw engine — byte-identical."""
        base = f"http://127.0.0.1:{server.server_address[1]}"
        rng = np.random.default_rng(29)
        anchors = rng.random((12, 3)).astype(np.float32)
        near = (anchors + rng.normal(0, 1e-3, anchors.shape)
                ).astype(np.float32)

        def post(q):
            req = urllib.request.Request(
                base + "/knn", data=json.dumps(
                    {"queries": q.tolist(), "neighbors": True}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                return json.loads(resp.read())

        post(anchors)  # warm the seed pool with full exact rows
        got = post(near)
        d_ref, n_ref = server.engine.query(near)
        np.testing.assert_array_equal(
            np.asarray(got["dists"], np.float32),
            d_ref.astype(np.float32))
        np.testing.assert_array_equal(np.asarray(got["neighbors"]), n_ref)
        assert server.qcache.stats()["seeds"] >= 1
