"""Recall-SLO approximate tier (serve/recall.py + its plumbing).

Three layers under test: the plan/policy values themselves (pure units),
the engine's plan-keyed approximate programs (measured recall against
the exact oracle on a fixture large enough that the knobs demonstrably
engage), and the serving stack's contract — plan-keyed sub-batching in
the batcher, the ``exact``/``X-Knn-*`` response surface, the /stats and
/metrics accounting, and the streaming engine's skip-cold trade. The
exact default path staying bitwise-unchanged is asserted at every layer
it could drift: it is the tier's founding promise.
"""

import json
import threading
import time
import urllib.error
import urllib.request
from dataclasses import replace

import numpy as np
import pytest

from mpi_cuda_largescaleknn_tpu.serve.batcher import DynamicBatcher
from mpi_cuda_largescaleknn_tpu.serve.recall import (
    DEFAULT_PLANS,
    EXACT_PLAN,
    RecallPlan,
    RecallPolicy,
    measured_recall,
)
from tools.recall_harness import workload_queries

K = 8


# ----------------------------------------------------------------- plan units


class TestRecallPlan:
    def test_exact_plan_is_exact(self):
        assert EXACT_PLAN.is_exact
        assert RecallPlan().is_exact

    def test_default_plans_are_approximate_and_cheapest_first(self):
        assert all(not p.is_exact for p in DEFAULT_PLANS)
        ests = [p.recall_estimated for p in DEFAULT_PLANS]
        assert ests == sorted(ests)

    @pytest.mark.parametrize("bad", [
        {"prune_shrink": 0.0}, {"prune_shrink": 1.5},
        {"visit_frac": 0.0}, {"visit_frac": -0.1},
        {"route_slack": 1.0}, {"route_slack": -0.01},
        {"recall_estimated": 0.0}, {"recall_estimated": 1.2},
    ])
    def test_knob_validation(self, bad):
        with pytest.raises(ValueError):
            RecallPlan(**bad)

    def test_keys_exclude_recall_target(self):
        """Two requests on one plan at different targets must share both
        the compiled program and the batch — targets are response
        metadata, not execution knobs."""
        plan = DEFAULT_PLANS[1]
        retargeted = replace(plan, recall_target=0.87)
        assert retargeted.program_key() == plan.program_key()
        assert retargeted.batch_key() == plan.batch_key()

    def test_batch_key_refines_program_key(self):
        """Dispatch-time knobs (route_slack, stream_skip_cold) split
        batches but not executables."""
        plan = DEFAULT_PLANS[0]
        slacked = replace(plan, route_slack=0.0, stream_skip_cold=False)
        assert slacked.program_key() == plan.program_key()
        assert slacked.batch_key() != plan.batch_key()

    def test_json_roundtrip_ignores_unknown_keys(self):
        plan = DEFAULT_PLANS[2]
        obj = plan.to_json()
        assert RecallPlan.from_json(obj) == plan
        obj["future_knob"] = 42  # forward compat: old servers, new tables
        assert RecallPlan.from_json(obj) == plan


# --------------------------------------------------------------- policy units


class TestRecallPolicy:
    def test_rejects_exact_plan_in_table(self):
        with pytest.raises(ValueError, match="exact"):
            RecallPolicy((EXACT_PLAN,))

    def test_rejects_out_of_order_plans(self):
        with pytest.raises(ValueError, match="cheapest"):
            RecallPolicy(tuple(reversed(DEFAULT_PLANS)))

    def test_no_target_and_full_target_are_the_exact_tier(self):
        policy = RecallPolicy()
        assert policy.plan_for(None) is None
        assert policy.plan_for(1.0) is None
        assert policy.stats()["selected"] == {"exact": 2}

    def test_selects_cheapest_plan_meeting_target(self):
        policy = RecallPolicy()
        assert policy.plan_for(0.5).name == "approx-fast"
        assert policy.plan_for(0.85).name == "approx-fast"
        assert policy.plan_for(0.9).name == "approx-balanced"
        assert policy.plan_for(0.99).name == "approx-near"
        # a target above every calibrated claim is unmeetable -> exact
        assert policy.plan_for(0.995) is None

    @pytest.mark.parametrize("bad", [0.0, -0.5, 1.5])
    def test_invalid_target_raises(self, bad):
        with pytest.raises(ValueError):
            RecallPolicy().plan_for(bad)

    def test_selection_returns_a_targeted_copy(self):
        """plan_for hands back a copy carrying the request's target; the
        table entry (shared across threads) must never mutate."""
        policy = RecallPolicy()
        got = policy.plan_for(0.9)
        assert got.recall_target == 0.9
        assert policy.plans[1].recall_target == 1.0
        assert got.batch_key() == policy.plans[1].batch_key()

    def test_stats_counts_per_plan(self):
        policy = RecallPolicy()
        for t in (0.5, 0.9, 0.9, None, 1.0):
            policy.plan_for(t)
        sel = policy.stats()["selected"]
        assert sel == {"approx-fast": 1, "approx-balanced": 2, "exact": 2}

    def test_from_dict_resorts_cheapest_first(self):
        obj = {"plans": [p.to_json() for p in reversed(DEFAULT_PLANS)]}
        policy = RecallPolicy.from_dict(obj)
        assert [p.name for p in policy.plans] == [
            "approx-fast", "approx-balanced", "approx-near"]


class TestMeasuredRecall:
    def test_identical_ids_are_recall_one(self):
        idx = np.arange(12, dtype=np.int32).reshape(3, 4)
        assert measured_recall(idx, idx) == 1.0

    def test_disjoint_ids_are_recall_zero(self):
        e = np.arange(8, dtype=np.int32).reshape(2, 4)
        assert measured_recall(e + 100, e) == 0.0

    def test_partial_overlap_and_pad_ids(self):
        exact = np.array([[0, 1, 2, 3]], np.int32)
        approx = np.array([[0, 1, -1, -1]], np.int32)  # -1 pads never hit
        assert measured_recall(approx, exact) == 0.5

    def test_shape_mismatch_raises(self):
        with pytest.raises(ValueError, match="shape"):
            measured_recall(np.zeros((2, 4)), np.zeros((2, 5)))


# ------------------------------------------------------- engine-tier recall


@pytest.fixture(scope="module")
def big_engine():
    """A fixture large enough that the approximate knobs demonstrably
    engage (on the 1500-point serving fixture every plan still measures
    recall 1.0 — too small to skip anything): 16384 uniform points,
    one 128-wide shape bucket, bucket_size 64."""
    from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
    from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine

    rng = np.random.default_rng(7)
    pts = rng.random((16384, 3)).astype(np.float32)
    eng = ResidentKnnEngine(pts, K, mesh=get_mesh(8), engine="tiled",
                            bucket_size=64, max_batch=128, min_batch=128)
    eng.warmup()
    return eng


def _chunked_ids(engine, q, plan=None):
    outs = [np.asarray(engine.query(q[i:i + 128], plan=plan)[1])
            for i in range(0, len(q), 128)]
    return np.concatenate(outs, axis=0)


class TestEngineRecallTier:
    def test_measured_recall_meets_every_calibrated_claim(self, big_engine):
        """THE tier's honesty bar, at the engine layer: each built-in
        plan's measured recall on the harness workload shapes must meet
        its calibrated claim — and approx-fast must measure BELOW 1.0
        somewhere, proving the knobs actually skipped work (a fixture
        where every plan is accidentally exact would gate nothing)."""
        engaged = False
        for wl in ("uniform", "clustered"):
            q = workload_queries(wl, 256, seed=0)
            exact = _chunked_ids(big_engine, q)
            for plan in DEFAULT_PLANS:
                r = measured_recall(_chunked_ids(big_engine, q, plan=plan),
                                    exact)
                assert r >= plan.recall_estimated, \
                    f"{plan.name} on {wl}: measured {r:.4f} < claimed " \
                    f"{plan.recall_estimated}"
                engaged = engaged or r < 1.0
        assert engaged, "no plan dropped a single neighbor — fixture " \
                        "too small to exercise the approximate tier"

    @pytest.mark.parametrize("workload", ["uniform", "clustered", "sweep"])
    def test_selected_plan_meets_requested_target(self, big_engine,
                                                  workload):
        """What a client asks for is what it gets: for each requested
        target, the plan the policy selects measures at or above that
        target against the exact engine's ids, on each harness workload
        shape."""
        policy = RecallPolicy()
        q = workload_queries(workload, 256, seed=3, blob_sigma=0.05)
        exact = _chunked_ids(big_engine, q)
        for target in (0.85, 0.95, 0.99):
            plan = policy.plan_for(target)
            r = (1.0 if plan is None else
                 measured_recall(_chunked_ids(big_engine, q, plan=plan),
                                 exact))
            assert r >= target, \
                f"target {target} on {workload}: {plan and plan.name} " \
                f"measured {r:.4f}"

    def test_plan_keyed_executables_compile_once(self, big_engine):
        """Each distinct program_key compiles its own AOT executable
        exactly once; reuse (same plan, same width) never retraces, and
        two plans can never collide on one executable."""
        q = workload_queries("uniform", 128, seed=1)
        plan = DEFAULT_PLANS[1]
        before = big_engine.compile_count
        big_engine.query(q, plan=plan)
        first = big_engine.compile_count
        assert first >= before  # may be warm from the recall sweep above
        big_engine.query(q, plan=plan)
        assert big_engine.compile_count == first
        other = DEFAULT_PLANS[0]
        assert other.program_key() != plan.program_key()
        big_engine.query(q, plan=other)
        big_engine.query(q, plan=other)
        assert big_engine.compile_count >= first  # distinct key, own exe

    def test_exact_path_bitwise_unchanged_after_approx_traffic(
            self, big_engine):
        q = workload_queries("sweep", 128, seed=2)
        d0, i0 = (np.asarray(a) for a in big_engine.query(q))
        for plan in DEFAULT_PLANS:
            big_engine.query(q, plan=plan)
        d1, i1 = (np.asarray(a) for a in big_engine.query(q))
        assert np.array_equal(d0, d1) and np.array_equal(i0, i1)


# ------------------------------------------------------ batcher sub-batching


class _PlanRecordingFn:
    """Batcher test double: records (rows, plan) per engine call and
    echoes each query row's first coordinate so submitters can verify
    they got THEIR rows back after demux."""

    def __init__(self):
        self.calls = []
        self._lock = threading.Lock()

    def __call__(self, queries, plan=None):
        with self._lock:
            self.calls.append((len(queries), plan))
        time.sleep(0.005)  # hold the worker so the queue builds depth
        d = np.asarray(queries)[:, 0].astype(np.float32)
        nbrs = np.zeros((len(queries), K), np.int32)
        return d, nbrs


class TestBatcherMixedSlo:
    def test_mixed_slo_traffic_splits_into_per_plan_batches(self):
        """Concurrent exact + two-plan traffic: every executed engine
        batch carries exactly one plan (the batcher never coalesces
        across batch_key), and each submitter's rows come back intact."""
        fn = _PlanRecordingFn()
        b = DynamicBatcher(fn, max_batch=64, max_delay_s=0.02)
        plans = [None, DEFAULT_PLANS[0], DEFAULT_PLANS[2]]
        results = {}

        def client(i):
            q = np.full((3, 3), float(i), np.float32)
            results[i] = b.submit(q, timeout_s=30.0, plan=plans[i % 3])

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        b.shutdown()
        assert len(results) == 12
        for i, (d, nbrs) in results.items():
            np.testing.assert_array_equal(d, np.full(3, float(i)))
            assert nbrs.shape == (3, K)
        # every batch single-plan, and the split actually happened:
        # 12 requests over 3 incompatible keys cannot fit one batch
        assert sum(rows for rows, _ in fn.calls) == 36
        assert len(fn.calls) >= 3
        with b._cond:
            assert b.rows_served == 36
            assert b.rows_served_approx == sum(
                rows for rows, plan in fn.calls if plan is not None)

    def test_same_plan_different_targets_share_a_batch_key(self):
        """recall_target is response metadata: two requests resolved to
        the same plan at different targets are coalescible."""
        a = replace(DEFAULT_PLANS[1], recall_target=0.9)
        b = replace(DEFAULT_PLANS[1], recall_target=0.95)
        assert a.batch_key() == b.batch_key()


# ---------------------------------------------------------- server contract


@pytest.fixture(scope="module")
def serve_rig():
    """1500-point serving fixture (the test_serve.py geometry) with the
    built-in recall policy: small enough to be fast, and on it every
    approximate plan measures recall 1.0 — which makes BITWISE
    comparisons against the exact engine meaningful for the contract
    tests without a second giant index."""
    from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
    from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine
    from mpi_cuda_largescaleknn_tpu.serve.server import build_server
    from tests.oracle import random_points

    pts = random_points(1500, seed=7)
    eng = ResidentKnnEngine(pts, K, mesh=get_mesh(8), engine="tiled",
                            bucket_size=32, max_batch=128, min_batch=16)
    eng.warmup()
    srv = build_server(eng, port=0, max_delay_s=0.002)
    srv.ready = True
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield eng, srv
    srv.close()


def _base(srv):
    return f"http://127.0.0.1:{srv.server_address[1]}"


def _post(base, payload, timeout=60):
    req = urllib.request.Request(
        base + "/knn", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _get(base, path, timeout=30):
    with urllib.request.urlopen(base + path, timeout=timeout) as resp:
        return json.loads(resp.read()) if path == "/stats" \
            else resp.read().decode()


class TestServerRecallContract:
    def test_no_recall_field_is_bitwise_exact_and_wire_unchanged(
            self, serve_rig):
        """The founding promise: a request without a recall field takes
        the pre-tier path — engine-bitwise dists, and NO new response
        fields — even after approximate traffic has run on the server."""
        eng, srv = serve_rig
        q = workload_queries("uniform", 24, seed=5)
        want = np.asarray(eng.query(q)[0], np.float64)
        for _round in range(2):
            st, out = _post(_base(srv), {"queries": q.tolist()})
            assert st == 200
            assert np.array_equal(np.asarray(out["dists"]), want)
            for field in ("exact", "recall_target", "recall_estimated",
                          "recall_plan"):
                assert field not in out
            # interleave approx traffic, then re-check the exact wire
            _post(_base(srv), {"queries": q.tolist(), "recall": 0.9})

    def test_no_recall_probe_ids_bitwise_after_approx_traffic(
            self, serve_rig):
        """The exact path's neighbour ids, not only its distances, come
        back bit for bit through the live server after approximate
        traffic has run beside them."""
        eng, srv = serve_rig
        q = workload_queries("clustered", 32, seed=12)
        _post(_base(srv), {"queries": q.tolist(), "recall": 0.5,
                           "neighbors": True})
        st, out = _post(_base(srv), {"queries": q.tolist(),
                                     "neighbors": True})
        want_d, want_i = eng.query(q)
        assert st == 200
        assert np.array_equal(np.asarray(out["dists"], np.float32),
                              np.asarray(want_d, np.float32))
        assert np.array_equal(np.asarray(out["neighbors"]),
                              np.asarray(want_i))

    def test_full_target_served_exactly(self, serve_rig):
        eng, srv = serve_rig
        q = workload_queries("uniform", 8, seed=6)
        st, out = _post(_base(srv), {"queries": q.tolist(), "recall": 1.0})
        assert st == 200
        assert out["exact"] is True
        assert out["recall_target"] == 1.0
        assert out["recall_estimated"] == 1.0
        assert "recall_plan" not in out
        assert np.array_equal(np.asarray(out["dists"]),
                              np.asarray(eng.query(q)[0], np.float64))

    def test_unmeetable_target_falls_back_to_exact(self, serve_rig):
        _eng, srv = serve_rig
        q = workload_queries("uniform", 4, seed=6)
        st, out = _post(_base(srv), {"queries": q.tolist(), "recall": 0.995})
        assert st == 200
        assert out["exact"] is True and out["recall_estimated"] == 1.0
        assert out["recall_target"] == 0.995

    def test_approx_response_contract(self, serve_rig):
        _eng, srv = serve_rig
        q = workload_queries("clustered", 8, seed=6)
        st, out = _post(_base(srv), {"queries": q.tolist(), "recall": 0.9,
                                     "neighbors": True})
        assert st == 200
        assert out["exact"] is False
        assert out["recall_plan"] == "approx-balanced"
        assert out["recall_target"] == 0.9
        assert out["recall_estimated"] == 0.95
        assert len(out["neighbors"]) == len(q)
        assert all(len(row) == K for row in out["neighbors"])

    @pytest.mark.parametrize("bad", [0.0, -0.2, 1.5])
    def test_invalid_recall_target_is_400(self, serve_rig, bad):
        _eng, srv = serve_rig
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(_base(srv), {"queries": [[0.5, 0.5, 0.5]], "recall": bad})
        assert err.value.code == 400

    def test_binary_codec_carries_recall_headers(self, serve_rig):
        eng, srv = serve_rig
        q = workload_queries("uniform", 6, seed=8)
        req = urllib.request.Request(
            _base(srv) + "/knn?recall=0.9", data=q.tobytes(),
            headers={"Content-Type": "application/octet-stream"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 200
            assert resp.headers["X-Knn-Exact"] == "0"
            assert resp.headers["X-Knn-Recall-Plan"] == "approx-balanced"
            assert resp.headers["X-Knn-Recall-Target"] == "0.9"
            assert resp.headers["X-Knn-Recall-Estimated"] == "0.95"
            body = np.frombuffer(resp.read(), "<f4")
        assert body.shape == (len(q),)
        # no recall option -> the pre-tier binary wire, headers absent
        req = urllib.request.Request(
            _base(srv) + "/knn", data=q.tobytes(),
            headers={"Content-Type": "application/octet-stream"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.headers["X-Knn-Exact"] is None
            exact_bytes = resp.read()
        assert exact_bytes == np.asarray(eng.query(q)[0],
                                         "<f4").tobytes()

    def test_stats_and_metrics_surface(self, serve_rig):
        _eng, srv = serve_rig
        base = _base(srv)
        q = workload_queries("uniform", 4, seed=9)
        _post(base, {"queries": q.tolist(), "recall": 0.5})
        _post(base, {"queries": q.tolist()})
        stats = _get(base, "/stats")
        rec = stats["recall"]
        assert rec["tiers"]["approx"] >= 1
        assert rec["tiers"]["exact"] >= 1
        hist = rec["estimated_hist"]
        assert len(hist["counts"]) == len(hist["edges"]) + 1
        assert sum(hist["counts"]) == hist["count"] == rec["tiers"]["approx"]
        pol = rec["policy"]
        assert pol["source"] == "builtin"
        assert pol["selected"].get("approx-fast", 0) >= 1
        assert [p["name"] for p in pol["plans"]] == [
            "approx-fast", "approx-balanced", "approx-near"]
        metrics = _get(base, "/metrics")
        assert 'knn_recall_requests_total{tier="approx"}' in metrics
        assert 'knn_recall_requests_total{tier="exact"}' in metrics
        assert "knn_recall_estimated_bucket" in metrics
        assert "knn_recall_estimated_count" in metrics


class TestLoadgenRecallTier:
    def test_every_targeted_request_lands_in_the_approx_tier(
            self, serve_rig):
        """The operator load generator's ``recall`` option: every answered
        request carried the target and was served by the approximate
        tier."""
        import tools.loadgen as loadgen

        _eng, srv = serve_rig
        rep = loadgen.run_load(_base(srv), duration_s=0.5, concurrency=2,
                               batch=4, seed=4, recall=0.9)
        tier = rep["recall"]
        assert rep["ok"] > 0 and tier["target"] == 0.9
        assert tier["approx_requests"] == rep["ok"]
        assert tier["approx_share"] == 1.0


# ------------------------------------------------------------ streaming tier


@pytest.fixture(scope="module")
def streaming_rig():
    """The test_slabpool.py streaming geometry: 600 points in two
    spatial clusters over 4 slabs, so a tight device budget forces real
    cold-slab decisions."""
    from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
    from mpi_cuda_largescaleknn_tpu.serve.slabpool import StreamingKnnEngine
    from tests.oracle import random_points

    a = random_points(295, seed=41, scale=0.4)
    b = (random_points(300, seed=42, scale=0.4) + np.float32(0.6))
    pts = np.concatenate([a, b[-5:], b]).astype(np.float32)
    stream = StreamingKnnEngine(points=pts, num_slabs=4, k=4,
                                mesh=get_mesh(2), engine="tiled",
                                bucket_size=64, max_batch=32, min_batch=16,
                                merge="device")
    stream.warmup()
    yield pts, stream
    stream.close()


class TestStreamingRecallTier:
    def test_skip_cold_on_a_warm_pool_is_bitwise_exact(self, streaming_rig):
        """stream_skip_cold only ever trades COLD promotions: with every
        wanted slab device-resident (unbounded budget) the plan's
        dispatch knobs are inert and the answer is the exact bytes."""
        _pts, stream = streaming_rig
        stream.slab_pool.set_device_budget(0)  # unbounded
        plan = RecallPlan(name="warm-stream", stream_skip_cold=True,
                          recall_estimated=0.9)
        rng = np.random.default_rng(3)
        q = rng.random((16, 3)).astype(np.float32)
        de, ie = stream.query(q)  # exact pass also warms the slab set
        da, ia = stream.query(q, plan=plan)
        assert np.array_equal(np.asarray(de), np.asarray(da))
        assert np.array_equal(np.asarray(ie), np.asarray(ia))

    def test_tight_budget_skips_promotions_for_recall(self, streaming_rig):
        """At a one-slab budget with traffic hopping between the two
        clusters, the skip-cold plan must (a) give up at least one cold
        promotion (the counted recall sacrifice), (b) still return k real
        candidates per row (each query's nearest slab is always ensured),
        and (c) leave the exact path bitwise intact afterwards."""
        _pts, stream = streaming_rig
        rng = np.random.default_rng(5)
        qa = (rng.random((8, 3)) * 0.4).astype(np.float32)
        qb = (rng.random((8, 3)) * 0.4 + 0.6).astype(np.float32)
        stream.slab_pool.set_device_budget(0)
        exact_ref = {id(q): [np.asarray(x) for x in stream.query(q)]
                     for q in (qa, qb)}
        stream.slab_pool.set_device_budget(stream.slab_device_bytes)
        plan = RecallPlan(name="tight-stream", stream_skip_cold=True,
                          skip_rescore=True, prune_shrink=0.3,
                          visit_frac=0.5, recall_estimated=0.9)
        before = stream.timers.counter("stream_skipped_promotions")
        skipped = 0
        for _round in range(8):
            for q in (qa, qb):
                _d, ids = stream.query(q, plan=plan)
                assert not (np.asarray(ids) < 0).any(), \
                    "approx row lost its must-visit nearest slab"
            skipped = (stream.timers.counter("stream_skipped_promotions")
                       - before)
            if skipped > 0:
                break
        assert skipped > 0, "one-slab budget + cluster-hopping traffic " \
                            "never skipped a cold promotion"
        # the exact tier is untouched by the approximate churn
        stream.slab_pool.set_device_budget(0)
        for q in (qa, qb):
            d, ids = stream.query(q)
            assert np.array_equal(np.asarray(d), exact_ref[id(q)][0])
            assert np.array_equal(np.asarray(ids), exact_ref[id(q)][1])
