"""Serving layer: resident engine, micro-batcher, admission, HTTP, loadgen.

One module-scoped engine (1500 points, 8 CPU devices, 4 shape buckets) backs
every test here — residency is the subsystem's point, so the tests share the
index exactly the way production traffic would.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from mpi_cuda_largescaleknn_tpu.obs.timers import LatencyHistogram, PhaseTimers
from mpi_cuda_largescaleknn_tpu.serve.admission import (
    AdmissionController,
    DeadlineExceeded,
    GracefulQueryFn,
    OverloadError,
)
from mpi_cuda_largescaleknn_tpu.serve.batcher import DynamicBatcher
from mpi_cuda_largescaleknn_tpu.serve.engine import (
    ResidentKnnEngine,
    UnservableShapeError,
)
from tests.oracle import assert_dist_equal, kth_nn_dist, random_points

K = 8
N_POINTS = 1500


@pytest.fixture(scope="module")
def index_points():
    return random_points(N_POINTS, seed=7)


@pytest.fixture(scope="module")
def engine(index_points):
    from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh

    eng = ResidentKnnEngine(index_points, K, mesh=get_mesh(8),
                            engine="tiled", bucket_size=32,
                            max_batch=128, min_batch=16)
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def server(engine):
    from mpi_cuda_largescaleknn_tpu.serve.server import build_server

    srv = build_server(engine, port=0, max_delay_s=0.002)
    srv.ready = True
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv
    srv.close()


def _post(base, payload: dict, timeout=60):
    req = urllib.request.Request(
        base + "/knn", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _url(server):
    return f"http://127.0.0.1:{server.server_address[1]}"


class TestResidentEngine:
    def test_matches_oracle_large_batch(self, engine, index_points):
        q = random_points(100, seed=99)
        d, _ = engine.query(q)
        assert_dist_equal(d, kth_nn_dist(q, index_points, K))

    def test_matches_oracle_single_query(self, engine, index_points):
        q = random_points(1, seed=3)
        d, _ = engine.query(q)
        assert_dist_equal(d, kth_nn_dist(q, index_points, K))

    def test_neighbor_ids_are_true_neighbors(self, engine, index_points):
        from tests.oracle import pairwise_dist2_np

        q = random_points(40, seed=11)
        d, nbrs = engine.query(q)
        full = pairwise_dist2_np(q, index_points)
        got_d2 = np.sort(full[np.arange(len(q))[:, None], nbrs], axis=1)
        want_d2 = np.sort(full, axis=1)[:, :K]
        np.testing.assert_allclose(got_d2, want_d2, rtol=5e-7)

    def test_recompile_freedom_across_client_batch_sizes(self, engine):
        """The ISSUE's acceptance bar: after warmup, client batches of 1, 3,
        17 and 100 must all be absorbed by shape bucketing with ZERO new
        compiles — ``compile_count`` is the engine's compile hook (it
        increments exactly when an XLA executable is built, and AOT
        executables cannot silently retrace)."""
        warm_compiles = engine.compile_count
        assert warm_compiles == len(engine.shape_buckets)
        for n in (1, 3, 17, 100):
            d, nbrs = engine.query(random_points(n, seed=n))
            assert d.shape == (n,) and nbrs.shape == (n, K)
        assert engine.compile_count == warm_compiles

    def test_bucket_selection(self, engine):
        assert engine.shape_buckets == [16, 32, 64, 128]
        assert engine.bucket_for(1) == 16
        assert engine.bucket_for(16) == 16
        assert engine.bucket_for(17) == 32
        assert engine.bucket_for(128) == 128
        with pytest.raises(UnservableShapeError):
            engine.bucket_for(129)

    def test_empty_batch(self, engine):
        d, nbrs = engine.query(np.zeros((0, 3), np.float32))
        assert d.shape == (0,) and nbrs.shape == (0, K)

    def test_bruteforce_engine_matches_oracle(self, index_points):
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh

        eng = ResidentKnnEngine(index_points[:300], 4, mesh=get_mesh(8),
                                engine="bruteforce", max_batch=16,
                                min_batch=16)
        q = random_points(10, seed=21)
        d, _ = eng.query(q)
        assert_dist_equal(d, kth_nn_dist(q, index_points[:300], 4))

    def test_max_radius(self, index_points):
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh

        r = 0.12
        eng = ResidentKnnEngine(index_points, 6, mesh=get_mesh(8),
                                engine="tiled", bucket_size=32,
                                max_radius=r, max_batch=32, min_batch=32)
        q = random_points(25, seed=33)
        d, _ = eng.query(q)
        assert_dist_equal(d, kth_nn_dist(q, index_points, 6, max_radius=r))


class TestBatcher:
    def test_coalesces_and_demuxes(self):
        seen_batches = []

        def query_fn(q):
            seen_batches.append(len(q))
            # identity-ish: dist = x coord, neighbors = row index
            return q[:, 0].copy(), np.arange(len(q), dtype=np.int32)[:, None]

        b = DynamicBatcher(query_fn, max_batch=64, max_delay_s=0.02)
        try:
            qs = [random_points(n, seed=n) for n in (3, 5, 7, 2)]
            out = [None] * len(qs)

            def call(i):
                out[i] = b.submit(qs[i], timeout_s=10)

            ths = [threading.Thread(target=call, args=(i,))
                   for i in range(len(qs))]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            for i, q in enumerate(qs):
                np.testing.assert_array_equal(out[i][0], q[:, 0])
            # the 20ms flush window must have coalesced the 4 concurrent
            # requests into fewer engine calls
            assert len(seen_batches) < len(qs)
            assert sum(seen_batches) == sum(len(q) for q in qs)
        finally:
            b.shutdown()

    def test_flushes_on_max_batch(self):
        def query_fn(q):
            return q[:, 0].copy(), np.zeros((len(q), 1), np.int32)

        b = DynamicBatcher(query_fn, max_batch=8, max_delay_s=30.0)
        try:
            # 8 rows reach max_batch -> flush long before the 30s deadline
            t0 = time.monotonic()
            got = [None, None]

            def call(i):
                got[i] = b.submit(random_points(4, seed=i), timeout_s=10)

            ths = [threading.Thread(target=call, args=(i,)) for i in (0, 1)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            assert time.monotonic() - t0 < 5.0
            assert all(g is not None for g in got)
            assert b.stats()["flush_full"] >= 1
        finally:
            b.shutdown()

    def test_deadline_expires_in_queue(self):
        def slow_fn(q):
            time.sleep(0.15)
            return q[:, 0].copy(), np.zeros((len(q), 1), np.int32)

        b = DynamicBatcher(slow_fn, max_batch=4, max_delay_s=0.001)
        try:
            # first request occupies the worker ~150ms...
            t1 = threading.Thread(
                target=lambda: b.submit(random_points(2, seed=1),
                                        timeout_s=10))
            t1.start()
            time.sleep(0.05)
            # ...second expires while queued behind it
            with pytest.raises(DeadlineExceeded):
                b.submit(random_points(2, seed=2), timeout_s=0.02)
            t1.join()
            assert b.stats()["rows_expired"] == 2
        finally:
            b.shutdown()

    def test_errors_propagate(self):
        def bad_fn(q):
            raise RuntimeError("engine exploded")

        b = DynamicBatcher(bad_fn, max_batch=4, max_delay_s=0.001)
        try:
            with pytest.raises(RuntimeError, match="engine exploded"):
                b.submit(random_points(2, seed=1), timeout_s=5)
        finally:
            b.shutdown()


class TestPipelinedBatcher:
    """The pipelined dispatch/complete path: overlap must change throughput,
    never results, ordering, or compile counts."""

    @staticmethod
    def _submit_all(b, qs, timeout_s=60):
        out = [None] * len(qs)
        errs = [None] * len(qs)

        def call(i):
            try:
                out[i] = b.submit(qs[i], timeout_s=timeout_s)
            except Exception as e:  # noqa: BLE001 - asserted by callers
                errs[i] = e

        ths = [threading.Thread(target=call, args=(i,))
               for i in range(len(qs))]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        return out, errs

    @pytest.mark.parametrize("depth", [1, 2, 4])
    def test_oracle_exact_at_depth(self, engine, index_points, depth):
        """The ISSUE's acceptance bar: pipelined results are oracle-exact at
        every depth — the pipeline overlaps, it never reorders or mixes."""
        b = DynamicBatcher(GracefulQueryFn(engine), max_batch=engine.max_batch,
                           max_delay_s=0.002, pipeline_depth=depth)
        try:
            assert b.pipelined == (depth > 1)
            sizes = (3, 17, 30, 9, 64, 5, 40, 12, 1, 100)
            qs = [random_points(n, seed=300 + n) for n in sizes]
            out, errs = self._submit_all(b, qs)
            assert all(e is None for e in errs), errs
            for q, (d, nbrs) in zip(qs, out):
                assert_dist_equal(d, kth_nn_dist(q, index_points, K))
                assert nbrs.shape == (len(q), K)
            assert b.inflight_batches() == 0 and b.inflight_rows() == 0
        finally:
            b.shutdown()

    def test_dispatch_complete_equals_query(self, engine):
        q = random_points(23, seed=41)
        want_d, want_n = engine.query(q)
        got_d, got_n = engine.complete(engine.dispatch(q))
        np.testing.assert_array_equal(want_d, got_d)
        np.testing.assert_array_equal(want_n, got_n)

    def test_compile_count_unchanged_vs_serialized(self, engine):
        """Pipelining must not change WHICH programs run: depth-2 traffic
        across every shape bucket adds zero compiles beyond warmup."""
        warm = engine.compile_count
        b = DynamicBatcher(GracefulQueryFn(engine), max_batch=engine.max_batch,
                           max_delay_s=0.001, pipeline_depth=2)
        try:
            qs = [random_points(n, seed=n) for n in (1, 3, 17, 100, 64, 33)]
            out, errs = self._submit_all(b, qs)
            assert all(e is None for e in errs), errs
        finally:
            b.shutdown()
        assert engine.compile_count == warm

    def test_ordering_preserved_under_concurrent_flushes(self):
        """Each caller gets exactly its own rows back even when many flushes
        are in flight concurrently: handles complete FIFO and demux offsets
        never cross batches. The fake tags every row with the request's
        marker value, completion is deliberately slow to force overlap."""

        class SlowEcho:
            def dispatch(self, q):
                return q

            def complete(self, q):
                time.sleep(0.005)
                return q[:, 0].copy(), np.arange(
                    len(q), dtype=np.int32)[:, None]

        b = DynamicBatcher(SlowEcho(), max_batch=32, max_delay_s=0.001,
                           pipeline_depth=3)
        try:
            qs = []
            for i in range(24):
                q = random_points(1 + (i % 5), seed=500 + i)
                q[:, 0] = i  # marker: row ownership is checkable
                qs.append(q)
            out, errs = self._submit_all(b, qs, timeout_s=30)
            assert all(e is None for e in errs), errs
            for i, (d, _nbrs) in enumerate(out):
                np.testing.assert_array_equal(d, np.full(len(qs[i]), i,
                                                         np.float32))
        finally:
            b.shutdown()

    def test_pipeline_drains_on_midstream_degradation(self):
        """Pallas dies at COMPLETION time (async errors surface at fetch)
        with several batches already in flight: every request must still get
        a correct answer via the twin replay, the pipeline must drain to
        zero occupancy, and only one degradation may be recorded."""

        class FakeHandle:
            def __init__(self, q, engine_name):
                self.queries = q
                self.engine_name = engine_name

        class FakeEngine:
            def __init__(self):
                self.engine_name = "pallas_tiled"
                self.degraded_reason = None

            def can_degrade(self):
                return self.engine_name == "pallas_tiled"

            def degrade(self, reason):
                self.degraded_reason = reason
                self.engine_name = "tiled"

            def dispatch(self, q):
                return FakeHandle(np.asarray(q), self.engine_name)

            def complete(self, h):
                time.sleep(0.002)
                if h.engine_name == "pallas_tiled":
                    raise RuntimeError("pallas runtime failure at fetch")
                return h.queries[:, 0].copy(), np.arange(
                    len(h.queries), dtype=np.int32)[:, None]

            def query(self, q):
                return self.complete(self.dispatch(q))

        fake = FakeEngine()
        g = GracefulQueryFn(fake)
        b = DynamicBatcher(g, max_batch=8, max_delay_s=0.001,
                           pipeline_depth=3)
        try:
            qs = [random_points(4, seed=600 + i) for i in range(10)]
            out, errs = self._submit_all(b, qs, timeout_s=30)
            assert all(e is None for e in errs), errs
            for q, (d, _n) in zip(qs, out):
                np.testing.assert_array_equal(d, q[:, 0])
            assert fake.engine_name == "tiled"
            assert "pallas runtime failure" in fake.degraded_reason
            assert g.failures >= 1
            assert g.stats()["degradations"] == 1
            assert b.inflight_batches() == 0 and b.inflight_rows() == 0
            # still serving after the drain
            q = random_points(3, seed=999)
            d, _ = b.submit(q, timeout_s=10)
            np.testing.assert_array_equal(d, q[:, 0])
        finally:
            b.shutdown()

    def test_dispatch_time_failure_degrades_too(self, caplog):
        """A failure at DISPATCH (sync lowering error) follows the same
        degrade-and-retry path as the serialized wrapper — logged at ERROR
        and counted, never silent."""

        class FakeEngine:
            def __init__(self):
                self.engine_name = "pallas_tiled"
                self.degraded_reason = None

            def can_degrade(self):
                return self.engine_name == "pallas_tiled"

            def degrade(self, reason):
                self.degraded_reason = reason
                self.engine_name = "tiled"

            def dispatch(self, q):
                if self.engine_name == "pallas_tiled":
                    raise RuntimeError("lowering failed")
                return np.asarray(q)

            def complete(self, q):
                return q[:, 0].copy(), np.zeros((len(q), 1), np.int32)

        fake = FakeEngine()
        g = GracefulQueryFn(fake)
        q = random_points(4, seed=3)
        with caplog.at_level("ERROR"):
            d, _ = g.complete(g.dispatch(q))
        np.testing.assert_array_equal(d, q[:, 0])
        assert fake.engine_name == "tiled" and g.failures == 1
        assert g.stats() == {"failures": 1, "degradations": 1}
        assert any(r.levelname == "ERROR" and "lowering failed" in r.message
                   for r in caplog.records)

    def test_stall_aware_flush_keeps_rows_queued_while_pipe_full(self):
        """The depth-2 regression fix (stalls on the CPU fixture): the
        dispatch worker reserves its pipeline slot BEFORE popping, so
        while the pipe is FULL queued requests stay in the queue —
        coalescable and deadline-cancellable — instead of being popped and
        held frozen behind the semaphore. Then everything drains exactly."""

        class GatedEcho:
            def __init__(self):
                self.release = threading.Semaphore(0)

            def dispatch(self, q):
                return q

            def complete(self, q):
                self.release.acquire()
                return q[:, 0].copy(), np.zeros((len(q), 1), np.int32)

        eng = GatedEcho()
        b = DynamicBatcher(eng, max_batch=8, max_delay_s=0.001,
                           pipeline_depth=2, min_batch=8)
        try:
            qs = [random_points(8, seed=800 + i) for i in range(4)]
            for i, q in enumerate(qs):
                q[:, 0] = i
            results = [None] * 4
            ths = [threading.Thread(
                target=lambda i=i: results.__setitem__(
                    i, b.submit(qs[i], timeout_s=30))) for i in range(4)]
            for t in ths:
                t.start()
            # two full batches dispatch and fill the pipe...
            deadline = time.monotonic() + 5
            while (b.inflight_batches() < 2
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            assert b.inflight_batches() == 2
            time.sleep(0.1)  # give a buggy dispatcher time to over-pop
            st = b.stats()
            # ...and the other two 8-row (>= max_batch) flushes WAIT in the
            # queue rather than being popped into the stalled worker
            assert st["batches"] == 2, st
            assert b.queue_depth_rows() == 16
            for _ in range(4):
                eng.release.release()
            for t in ths:
                t.join(timeout=30)
            st = b.stats()
            assert st["dispatch_stalls"] >= 1
            assert st["dispatch_stall_seconds"] > 0
            for i, got in enumerate(results):
                assert got is not None
                np.testing.assert_array_equal(
                    got[0], np.full(8, i, np.float32))
        finally:
            eng.release.release()
            b.shutdown(wait=False)

    def test_busy_deadline_flush_at_min_batch(self):
        """Stall-aware flush floor: with a free pipeline slot, a deadline
        flush of >= min_batch rows dispatches while an earlier batch is
        still in flight (the old policy waited for a fully idle pipe);
        slivers below min_batch keep waiting."""

        class GatedEcho:
            def __init__(self):
                self.release = threading.Semaphore(0)

            def dispatch(self, q):
                return q

            def complete(self, q):
                self.release.acquire()
                return q[:, 0].copy(), np.zeros((len(q), 1), np.int32)

        eng = GatedEcho()
        b = DynamicBatcher(eng, max_batch=32, max_delay_s=0.001,
                           pipeline_depth=2, min_batch=8)
        try:
            out = {}
            ths = []

            def submit(tag, q):
                t = threading.Thread(
                    target=lambda: out.__setitem__(
                        tag, b.submit(q, timeout_s=30)))
                t.start()
                ths.append(t)

            submit("a", random_points(4, seed=900))  # idle pipe: flushes
            deadline = time.monotonic() + 5
            while b.inflight_batches() < 1 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert b.inflight_batches() == 1
            # a 3-row sliver (< min_batch) must NOT flush while busy...
            submit("tiny", random_points(3, seed=901))
            time.sleep(0.05)
            assert b.stats()["batches"] == 1
            # ...but topping the queue up past min_batch flushes into the
            # free slot without waiting for batch a's completion
            submit("wide", random_points(10, seed=902))
            deadline = time.monotonic() + 5
            while b.stats()["batches"] < 2 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert b.stats()["batches"] == 2
            assert b.inflight_batches() == 2
            for _ in range(3):
                eng.release.release()
            for t in ths:
                t.join(timeout=30)
            assert set(out) == {"a", "tiny", "wide"}
            for tag, n in (("a", 4), ("tiny", 3), ("wide", 10)):
                assert len(out[tag][0]) == n
        finally:
            eng.release.release()
            b.shutdown(wait=False)

    def test_stall_accounting_bounds_inflight(self):
        """With depth 2 and a slow completer, the dispatch worker must stall
        (bounded occupancy) and record it; occupancy never exceeds depth."""
        seen_inflight = []

        class SlowEcho:
            def __init__(self, batcher_ref):
                self._b = batcher_ref

            def dispatch(self, q):
                seen_inflight.append(self._b[0].inflight_batches())
                return q

            def complete(self, q):
                time.sleep(0.02)
                return q[:, 0].copy(), np.zeros((len(q), 1), np.int32)

        ref = [None]
        b = DynamicBatcher(SlowEcho(ref), max_batch=4, max_delay_s=0.001,
                           pipeline_depth=2)
        ref[0] = b
        try:
            qs = [random_points(4, seed=700 + i) for i in range(8)]
            out, errs = self._submit_all(b, qs, timeout_s=30)
            assert all(e is None for e in errs), errs
            st = b.stats()
            assert st["dispatch_stalls"] >= 1
            assert st["dispatch_stall_seconds"] > 0
            assert b.stall_hist.count >= 1
            assert max(seen_inflight) <= 2
        finally:
            b.shutdown()


class TestAdmission:
    def test_rejects_beyond_cap(self):
        a = AdmissionController(max_queue_rows=10)
        a.admit(8)
        with pytest.raises(OverloadError):
            a.admit(3)
        a.admit(2)  # exactly at cap is fine
        a.release(8)
        a.release(2)
        assert a.inflight_rows() == 0
        assert a.stats()["rejected"] == 1

    def test_context_manager_releases_on_error(self):
        a = AdmissionController(max_queue_rows=10)
        with pytest.raises(ValueError):
            with a.admitted_rows(10):
                raise ValueError("boom")
        assert a.inflight_rows() == 0

    def test_graceful_degradation_to_twin(self):
        class FakeEngine:
            def __init__(self):
                self.engine_name = "pallas_tiled"
                self.degraded_reason = None
                self.calls = 0

            def can_degrade(self):
                return self.engine_name == "pallas_tiled"

            def degrade(self, reason):
                self.degraded_reason = reason
                self.engine_name = "tiled"

            def query(self, q):
                self.calls += 1
                if self.engine_name == "pallas_tiled":
                    raise RuntimeError("pallas lowering failed at runtime")
                return q[:, 0], np.zeros((len(q), 1), np.int32)

        fake = FakeEngine()
        g = GracefulQueryFn(fake)
        q = random_points(4, seed=1)
        d, _ = g(q)  # first call fails in pallas, retries on the twin
        np.testing.assert_array_equal(d, q[:, 0])
        assert fake.engine_name == "tiled"
        assert "pallas lowering failed" in fake.degraded_reason
        assert g.failures == 1
        g(q)  # steady state: no more failures
        assert g.failures == 1

    def test_non_degradable_engine_reraises(self):
        class FakeEngine:
            engine_name = "tiled"

            def can_degrade(self):
                return False

            def query(self, q):
                raise RuntimeError("no fallback from here")

        with pytest.raises(RuntimeError, match="no fallback"):
            GracefulQueryFn(FakeEngine())(random_points(2, seed=1))


class TestLatencyHistogram:
    def test_percentiles_within_bucket_resolution(self):
        h = LatencyHistogram()
        vals = np.linspace(0.001, 0.100, 1000)
        for v in vals:
            h.record(float(v))
        # log buckets are ~12% wide: a quantile may be conservative by one
        # bucket, never optimistic by more than the bucket below
        for p in (50, 95, 99):
            want = float(np.percentile(vals, p))
            got = h.percentile(p)
            assert want / 1.13 <= got <= want * 1.13, (p, want, got)

    def test_report_and_empty(self):
        h = LatencyHistogram()
        assert np.isnan(h.percentile(50))
        h.record(0.01)
        rep = h.report()
        assert rep["count"] == 1 and rep["sum_seconds"] > 0

    def test_prometheus_lines_cumulative(self):
        h = LatencyHistogram()
        for v in (0.001, 0.01, 0.01, 0.1):
            h.record(v)
        lines = h.prometheus_lines("x_seconds")
        assert lines[0] == "# TYPE x_seconds histogram"
        assert 'x_seconds_bucket{le="+Inf"} 4' in lines
        assert "x_seconds_count 4" in lines

    def test_merge(self):
        a, b = LatencyHistogram(), LatencyHistogram()
        a.record(0.01)
        b.record(0.02)
        a.merge(b)
        assert a.count == 2

    def test_phase_timers_hist_in_report(self):
        t = PhaseTimers()
        t.hist("req_seconds").record(0.005)
        rep = t.report()
        assert rep["req_seconds"]["count"] == 1
        assert "p99" in rep["req_seconds"]

    def test_empty_report_is_strict_json(self):
        # an empty histogram must not leak NaN into /stats or loadgen --out:
        # json.dumps(nan) emits a non-standard token strict parsers reject
        rep = LatencyHistogram().report()
        assert rep["p50"] is None and rep["p99"] is None
        json.loads(json.dumps(rep))


class TestHTTPServing:
    def test_healthz(self, server):
        with urllib.request.urlopen(_url(server) + "/healthz", timeout=10) as r:
            assert r.status == 200
            assert json.loads(r.read())["status"] == "ok"

    def test_concurrent_clients_oracle_exact(self, server, index_points):
        """The ISSUE's end-to-end bar: concurrent clients through the full
        HTTP -> admission -> batcher -> engine -> demux path get
        oracle-exact k-th-NN distances."""
        base = _url(server)
        results = {}

        def client(i):
            q = random_points(5 + 3 * i, seed=100 + i)
            status, resp = _post(base, {"queries": q.tolist(),
                                        "neighbors": True})
            results[i] = (q, status, resp)

        ths = [threading.Thread(target=client, args=(i,)) for i in range(6)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        assert len(results) == 6
        for q, status, resp in results.values():
            assert status == 200
            assert_dist_equal(np.asarray(resp["dists"], np.float32),
                              kth_nn_dist(q, index_points, K))
            assert len(resp["neighbors"]) == len(q)

    def test_binary_roundtrip(self, server, index_points):
        q = random_points(9, seed=5)
        req = urllib.request.Request(
            _url(server) + "/knn", data=q.astype("<f4").tobytes(),
            headers={"Content-Type": "application/octet-stream"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            d = np.frombuffer(resp.read(), "<f4")
        assert_dist_equal(d, kth_nn_dist(q, index_points, K))

    def test_bad_requests(self, server):
        base = _url(server)
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(base, {"queries": [[1.0, 2.0]]})  # wrong width
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(base, {"queries": (np.zeros((200, 3))).tolist()})  # > max
        assert e.value.code == 413

    def test_stats_and_metrics(self, server):
        base = _url(server)
        stats = json.loads(urllib.request.urlopen(
            base + "/stats", timeout=10).read())
        assert stats["engine"]["compile_count"] == len(
            stats["engine"]["shape_buckets"])
        assert stats["batcher"]["rows_served"] > 0
        assert stats["graceful"] == {"failures": 0, "degradations": 0}
        m = urllib.request.urlopen(base + "/metrics", timeout=10).read().decode()
        assert "# TYPE knn_requests_total counter" in m
        assert "knn_engine_degradations_total 0\n" in m
        assert "knn_request_latency_seconds_bucket" in m
        assert "knn_compile_count" in m

    def test_pipeline_occupancy_in_stats_and_metrics(self, server):
        """Pipeline occupancy gauges (depth, in-flight batches/rows, stall
        time) ride /stats and /metrics; the stall histogram shares the
        loadgen bucket geometry."""
        base = _url(server)
        # traffic so the pipelined path has actually run
        _post(base, {"queries": random_points(5, seed=88).tolist()})
        stats = json.loads(urllib.request.urlopen(
            base + "/stats", timeout=10).read())
        b = stats["batcher"]
        assert b["pipeline_depth"] == 2 and b["pipelined"] is True
        for key in ("inflight_batches", "inflight_rows", "dispatch_stalls",
                    "dispatch_stall_seconds"):
            assert key in b
        assert "pipeline_inflight_rows" in stats["admission"]
        m = urllib.request.urlopen(base + "/metrics",
                                   timeout=10).read().decode()
        assert "# TYPE knn_pipeline_depth gauge" in m
        assert "knn_pipeline_inflight_batches" in m
        assert "knn_pipeline_dispatch_stalls_total" in m
        # stall histogram renders even when empty (count 0, +Inf terminal)
        assert "# TYPE knn_pipeline_stall_seconds histogram" in m

    def test_no_recompiles_from_http_traffic(self, server, engine):
        """All the HTTP traffic above rode varied client batch sizes; the
        shape buckets must have absorbed every one of them."""
        assert engine.compile_count == len(engine.shape_buckets)

    def test_close_without_serve_forever_does_not_hang(self, engine):
        """Ctrl-C during warmup: close() runs before serve_forever() ever
        started — BaseServer.shutdown() would wait forever on the loop's
        event, so close() must skip it."""
        from mpi_cuda_largescaleknn_tpu.serve.server import build_server

        srv = build_server(engine, port=0)
        t = threading.Thread(target=srv.close, daemon=True)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive(), "close() hung without serve_forever()"

    def test_binary_zero_rows_gets_binary_response(self, server):
        req = urllib.request.Request(
            _url(server) + "/knn", data=b"",
            headers={"Content-Type": "application/octet-stream"})
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"] == "application/octet-stream"
            assert resp.read() == b""

    def test_saturation_sheds_load_then_recovers(self, engine, index_points):
        """Overload: a slow engine + a 16-row admission cap + 20 concurrent
        8-row clients => most are rejected with 429 at the door; afterwards
        the server still answers correctly (stays healthy)."""
        from mpi_cuda_largescaleknn_tpu.serve.server import build_server

        real = GracefulQueryFn(engine)

        def slow_fn(q):
            time.sleep(0.08)
            return real(q)

        srv = build_server(engine, port=0, max_delay_s=0.001,
                           max_queue_rows=16, query_fn=slow_fn)
        srv.ready = True
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        base = _url(srv)
        try:
            codes = []
            lock = threading.Lock()

            def client(i):
                q = random_points(8, seed=i)
                try:
                    status, _ = _post(base, {"queries": q.tolist()})
                except urllib.error.HTTPError as e:
                    status = e.code
                with lock:
                    codes.append(status)

            ths = [threading.Thread(target=client, args=(i,))
                   for i in range(20)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            assert codes.count(429) >= 1, codes
            assert codes.count(200) >= 1, codes
            # healthy afterwards: correct answers, zero queue
            q = random_points(4, seed=777)
            status, resp = _post(base, {"queries": q.tolist()})
            assert status == 200
            assert_dist_equal(np.asarray(resp["dists"], np.float32),
                              kth_nn_dist(q, index_points, K))
            assert srv.admission.inflight_rows() == 0
        finally:
            srv.close()


class TestLoadgen:
    def test_closed_loop_report(self, server):
        import sys

        sys.path.insert(0, "tools")
        from loadgen import run_load

        rep = run_load(_url(server), duration_s=1.0, concurrency=3, batch=8,
                       seed=1)
        assert rep["mode"] == "closed"
        assert rep["ok"] > 0 and rep["net_error"] == 0
        assert rep["qps"] > 0 and rep["rows_per_s"] > 0
        for key in ("p50_ms", "p95_ms", "p99_ms"):
            assert rep[key] > 0
        # the report must be JSON-serializable (``--out`` writes it)
        json.dumps(rep)

    def test_binary_mode_and_server_stats(self, server):
        """The octet-stream wire format over the keep-alive client, plus
        the embedded /stats pipeline-occupancy scrape."""
        import sys

        sys.path.insert(0, "tools")
        from loadgen import run_load

        rep = run_load(_url(server), duration_s=1.0, concurrency=2, batch=8,
                       seed=2, binary=True, server_stats=True)
        assert rep["binary"] is True
        assert rep["ok"] > 0 and rep["net_error"] == 0
        s = rep["server"]
        assert s is not None and s["pipeline_depth"] >= 1
        assert s["compile_count"] == 4  # binary traffic hit no new bucket
        json.dumps(rep)

    def test_round_robin_hosts_mode(self, server, engine):
        """--hosts: round-robin front-end-bypass across endpoints, with
        per-endpoint p50/p95/p99 so fan-out overhead is measurable."""
        import sys

        sys.path.insert(0, "tools")
        from loadgen import run_load

        from mpi_cuda_largescaleknn_tpu.serve.server import build_server

        srv2 = build_server(engine, port=0, max_delay_s=0.001)
        srv2.ready = True
        threading.Thread(target=srv2.serve_forever, daemon=True).start()
        try:
            urls = [_url(server), _url(srv2)]
            rep = run_load(urls[0], hosts=urls, duration_s=1.0,
                           concurrency=2, batch=4, seed=3)
            assert rep["endpoint_mode"] == "round_robin"
            assert set(rep["per_endpoint"]) == set(urls)
            for u in urls:
                ep = rep["per_endpoint"][u]
                assert ep["requests"] > 0 and ep["ok"] > 0
                assert ep["p50_ms"] > 0 and ep["p99_ms"] > 0
            # round-robin spreads requests evenly-ish across endpoints
            reqs = [rep["per_endpoint"][u]["requests"] for u in urls]
            assert min(reqs) > 0.25 * max(reqs)
            json.dumps(rep)
        finally:
            srv2.close()

    def test_binary_result_matches_oracle(self, server, index_points):
        """One keep-alive connection, two sequential binary posts — the
        socket is reused and both answers are exact."""
        import sys

        sys.path.insert(0, "tools")
        from loadgen import _Client

        client = _Client(_url(server), timeout_s=60)
        try:
            for seed in (21, 22):
                q = random_points(6, seed=seed)
                status, payload, _headers = client._request(
                    "/knn", np.ascontiguousarray(q, np.float32).tobytes(),
                    "application/octet-stream")
                assert status == 200
                got = np.frombuffer(payload, np.float32)
                assert_dist_equal(got, kth_nn_dist(q, index_points, K))
        finally:
            client.close()
