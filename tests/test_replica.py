"""Replica groups + slab handoff (serve/replica.py, PR "robustness").

Unit layer: deterministic spread-pick sequences (fixed seed, no RNG),
replica grouping/validation as a pure function, and the handoff manager
driven with fake transports + explicit ``check_once`` calls — no HTTP,
no sleeps (the PR-8 monitor discipline).

Integration layer: a 2-slab x 2-replica routed pod (replicas of a slab
share one engine in-process — byte-equality between originals is then
trivially true, which makes the ADOPTED engine the real parity subject:
it re-materializes the slab from a surviving replica / the source file
and must serve the same bytes). The acceptance bars from the issue:
single-replica loss stays exact AND bit-identical (capacity, not
exactness), all-replicas-down degrades per the PR-8 contract, a
fingerprint-mismatched adoption never serves, and post-handoff queries
are bitwise-equal to a never-failed reference.
"""

from __future__ import annotations

import json
import tempfile
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

K = 5


def _post_knn(url, q, timeout=120):
    req = urllib.request.Request(
        url + "/knn",
        data=json.dumps({"queries": np.asarray(q).tolist(),
                         "neighbors": True}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _post_json(url, path, obj, timeout=30):
    req = urllib.request.Request(
        url + path, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _replica_points():
    """600 rows: [0:300) cluster A in [0, 0.4)^3, [300:600) cluster B in
    [0.6, 1.0)^3 — disjoint slabs, so routing decisions are clean."""
    from tests.oracle import random_points

    a = random_points(300, seed=61, scale=0.4)
    b = random_points(300, seed=62, scale=0.4) + np.float32(0.6)
    return np.concatenate([a, b]).astype(np.float32)


# ---------------------------------------------------------------- unit layer


def _endpoints(urls, **health_kw):
    from mpi_cuda_largescaleknn_tpu.serve.frontend import _HostEndpoint

    health_kw.setdefault("fail_threshold", 1)
    health_kw.setdefault("jitter", 0.0)
    return [_HostEndpoint(u, dict(health_kw)) for u in urls]


class TestReplicaSet:
    def _set(self, urls=("http://a", "http://b"), seed=0):
        from mpi_cuda_largescaleknn_tpu.serve.replica import ReplicaSet

        eps = _endpoints(urls)
        groups = [{"row_offset": 0, "n_points": 10, "urls": list(urls)}]
        return ReplicaSet(eps, groups, seed=seed), eps

    def test_pick_is_deterministic_and_spreads(self):
        rs1, _ = self._set(seed=7)
        rs2, _ = self._set(seed=7)
        seq1 = [rs1.pick(0) for _ in range(8)]
        seq2 = [rs2.pick(0) for _ in range(8)]
        assert seq1 == seq2  # fixed seed -> identical pick sequence
        # the least-picked rule spreads: after warm-up the picks alternate
        assert set(seq1) == {0, 1}
        counts = [seq1.count(i) for i in (0, 1)]
        assert counts == [4, 4]
        # a different seed may start on the other replica but still spreads
        rs3, _ = self._set(seed=8)
        seq3 = [rs3.pick(0) for _ in range(8)]
        assert [seq3.count(i) for i in (0, 1)] == [4, 4]

    def test_pick_skips_drained_and_respects_batch_budget(self):
        rs, eps = self._set()
        eps[0].health.force_drain("down")
        assert all(rs.pick(0) == 1 for _ in range(4))
        # per-batch penalties deprioritize a just-failed replica...
        rs2, _ = self._set()
        assert rs2.pick(0, penalties={0: 1}, budget=2) == 1
        # ...and exclude it entirely once over budget
        rs3, eps3 = self._set()
        eps3[1].health.force_drain("down")
        assert rs3.pick(0, penalties={0: 3}, budget=2) is None

    def test_live_mask_counts_and_rebind(self):
        from mpi_cuda_largescaleknn_tpu.serve.frontend import _HostEndpoint
        from mpi_cuda_largescaleknn_tpu.serve.replica import ReplicaSet

        eps = _endpoints(["http://a", "http://b", "http://c"])
        rs = ReplicaSet(eps, [
            {"row_offset": 0, "n_points": 5,
             "urls": ["http://a", "http://b"]},
            {"row_offset": 5, "n_points": 5, "urls": ["http://c"]}])
        assert rs.num_slabs == 2
        assert rs.live_counts() == [2, 1]
        eps[0].health.force_drain("x")
        eps[2].health.force_drain("x")
        assert rs.live_counts() == [1, 0]
        assert rs.slab_live_mask().tolist() == [True, False]
        # runtime re-bind: a new endpoint joins slab 1's member set
        eps.append(_HostEndpoint("http://d", {"fail_threshold": 1}))
        rs.rebind(1, 3)
        assert rs.live_counts() == [1, 1]
        st = rs.stats()
        assert st["rebinds"] == 1
        assert st["per_slab"][1]["members"] == ["http://c", "http://d"]

    def test_groups_must_cover_and_not_overlap(self):
        from mpi_cuda_largescaleknn_tpu.serve.replica import ReplicaSet

        eps = _endpoints(["http://a", "http://b"])
        with pytest.raises(ValueError, match="cover"):
            ReplicaSet(eps, [{"row_offset": 0, "n_points": 5,
                              "urls": ["http://a"]}])
        with pytest.raises(ValueError, match="more than one"):
            ReplicaSet(eps, [
                {"row_offset": 0, "n_points": 5, "urls": ["http://a"]},
                {"row_offset": 5, "n_points": 5,
                 "urls": ["http://a", "http://b"]}])


class TestGroupRoutedHosts:
    def _stats(self, off, n, **extra):
        e = {"row_offset": off, "n_points": n, "k": K, "dim": 3,
             "shard_bounds": [{"lo": [0, 0, 0], "hi": [1, 1, 1],
                               "count": n}]}
        e.update(extra)
        return e

    def test_replica_grouping_and_slab_major_order(self):
        from mpi_cuda_largescaleknn_tpu.serve.health import host_fingerprint
        from mpi_cuda_largescaleknn_tpu.serve.replica import (
            group_routed_hosts,
        )

        urls = ["u-b0", "u-a0", "u-a1", "u-b1"]
        stats = [self._stats(300, 300), self._stats(0, 300),
                 self._stats(0, 300), self._stats(300, 300)]
        fps = {u: host_fingerprint(e, "bounds")
               for u, e in zip(urls, stats)}
        g = group_routed_hosts(urls, stats, fps)
        assert g["n_points"] == 600
        assert [s["row_offset"] for s in g["slabs"]] == [0, 300]
        assert g["slabs"][0]["urls"] == ["u-a0", "u-a1"]
        assert g["slabs"][1]["urls"] == ["u-b0", "u-b1"]
        assert g["host_urls"] == ["u-a0", "u-a1", "u-b0", "u-b1"]
        assert len(g["bounds_hosts"]) == 2  # one entry per SLAB
        assert g["slab_fingerprints"][0] == fps["u-a0"]

    def test_replica_fingerprint_mismatch_rejected(self):
        from mpi_cuda_largescaleknn_tpu.serve.health import host_fingerprint
        from mpi_cuda_largescaleknn_tpu.serve.replica import (
            group_routed_hosts,
        )

        urls = ["u-a0", "u-a1"]
        stats = [self._stats(0, 300, bucket_size=64),
                 self._stats(0, 300, bucket_size=32)]
        fps = {u: host_fingerprint(e, "bounds")
               for u, e in zip(urls, stats)}
        with pytest.raises(ValueError, match="replica mismatch") as ei:
            group_routed_hosts(urls, stats, fps)
        assert "bucket_size" in str(ei.value)

    def test_slab_gap_still_a_hard_error(self):
        from mpi_cuda_largescaleknn_tpu.serve.health import host_fingerprint
        from mpi_cuda_largescaleknn_tpu.serve.replica import (
            group_routed_hosts,
        )

        urls = ["u-b0"]
        stats = [self._stats(300, 300)]
        fps = {u: host_fingerprint(e, "bounds")
               for u, e in zip(urls, stats)}
        with pytest.raises(ValueError, match="tile the index"):
            group_routed_hosts(urls, stats, fps)


def _fake_routed_fanout(urls, groups):
    """A REAL RoutedPodFanout (no HTTP happens at construction) over fake
    bounds — what the manager unit tests drive."""
    from mpi_cuda_largescaleknn_tpu.serve.frontend import (
        PodBoundsTable,
        RoutedPodFanout,
    )

    bounds = PodBoundsTable([
        {"row_offset": g["row_offset"], "n_points": g["n_points"],
         "shards": [{"lo": [0, 0, 0], "hi": [1, 1, 1],
                     "count": g["n_points"]}]} for g in groups], dim=3)
    return RoutedPodFanout(
        urls, k=K, max_batch=32, bounds=bounds, replica_groups=groups,
        health_config={"fail_threshold": 1, "jitter": 0.0})


class TestReplicaManagerUnit:
    def _harness(self, *, stats_engine, probes, adopts):
        from mpi_cuda_largescaleknn_tpu.serve.health import host_fingerprint
        from mpi_cuda_largescaleknn_tpu.serve.replica import ReplicaManager

        groups = [{"row_offset": 0, "n_points": 300, "urls": ["http://a"]},
                  {"row_offset": 300, "n_points": 300,
                   "urls": ["http://b"]}]
        fan = _fake_routed_fanout(["http://a", "http://b"], groups)
        want = host_fingerprint(
            {"row_offset": 300, "n_points": 300, "k": K}, "bounds")
        registry = {}
        mgr = ReplicaManager(
            fan, slabs=groups, slab_fingerprints=[None, want],
            standbys=["http://sb"], handoff_floor=1,
            probe_fn=lambda url: probes[url].pop(0),
            stats_fn=lambda url: {"engine": stats_engine},
            adopt_fn=lambda url, req: adopts.append((url, req)) or {},
            fingerprint_registry=registry, clock=lambda: 0.0)
        return fan, mgr, want, registry

    def test_handoff_triggers_validates_and_binds(self):
        adopts = []
        probes = {"http://sb": [(False, {"status": "adopting"}),
                                (True, {"status": "ok"})]}
        fan, mgr, want, registry = self._harness(
            stats_engine={"row_offset": 300, "n_points": 300, "k": K},
            probes=probes, adopts=adopts)
        try:
            fan.endpoints[1].health.force_drain("died")
            assert fan.replicas.live_counts() == [1, 0]
            mgr.check_once(now=0.0)  # below floor -> adoption starts
            assert len(adopts) == 1
            url, req = adopts[0]
            assert url == "http://sb"
            assert req["host_id"] == 1 and req["num_hosts"] == 2
            assert req["row_offset"] == 300 and req["n_points"] == 300
            assert "source_url" not in req  # no live member to pull from
            assert mgr.stats()["inflight_slabs"] == [1]
            mgr.check_once(now=1.0)  # standby still materializing
            assert mgr.stats()["standbys"][0]["state"] == "adopting"
            mgr.check_once(now=2.0)  # ready -> fingerprint ok -> bound
            st = mgr.stats()
            assert st["handoffs"] == 1 and st["inflight_slabs"] == []
            assert st["standbys"][0]["state"] == "bound"
            assert len(fan.endpoints) == 3
            assert fan.replicas.live_counts() == [1, 1]
            assert registry["http://sb"] == want  # rejoin gate armed
            # no repeat adoption while the floor is satisfied
            probes["http://sb"].append((True, {"status": "ok"}))
            mgr.check_once(now=3.0)
            assert mgr.stats()["handoffs"] == 1 and len(adopts) == 1
        finally:
            fan.close()

    def test_fingerprint_mismatch_never_binds(self):
        adopts = []
        probes = {"http://sb": [(True, {"status": "ok"})]}
        # the standby came back serving the WRONG slab (row_offset 0)
        fan, mgr, _want, registry = self._harness(
            stats_engine={"row_offset": 0, "n_points": 300, "k": K},
            probes=probes, adopts=adopts)
        try:
            fan.endpoints[1].health.force_drain("died")
            mgr.check_once(now=0.0)
            mgr.check_once(now=1.0)
            st = mgr.stats()
            assert st["handoff_rejections"] == 1 and st["handoffs"] == 0
            sb = st["standbys"][0]
            assert sb["state"] == "failed"
            assert "fingerprint mismatch" in sb["last_error"]
            assert "row_offset" in sb["last_error"]  # the diff is named
            # the slab stays down: nothing was bound, nothing serves
            assert len(fan.endpoints) == 2
            assert fan.replicas.live_counts() == [1, 0]
            assert "http://sb" not in registry
        finally:
            fan.close()

    def test_adopt_failure_and_starvation_are_counted(self):
        def boom(url, req):
            raise OSError("connection refused")

        from mpi_cuda_largescaleknn_tpu.serve.replica import ReplicaManager

        groups = [{"row_offset": 0, "n_points": 300, "urls": ["http://a"]}]
        fan = _fake_routed_fanout(["http://a"], groups)
        try:
            mgr = ReplicaManager(
                fan, slabs=groups, slab_fingerprints=[None],
                standbys=["http://sb"], handoff_floor=1,
                probe_fn=lambda url: (False, {}),
                stats_fn=lambda url: {}, adopt_fn=boom,
                clock=lambda: 0.0)
            fan.endpoints[0].health.force_drain("died")
            mgr.check_once(now=0.0)
            st = mgr.stats()
            assert st["handoff_failures"] == 1
            assert st["standbys"][0]["state"] == "failed"
            assert "adopt request failed" in st["standbys"][0]["last_error"]
            mgr.check_once(now=1.0)  # no idle standby left
            assert mgr.stats()["starved"] == 1
        finally:
            fan.close()


# --------------------------------------------------------- integration layer


@pytest.fixture(scope="module")
def replica_pod(tmp_path_factory):
    """2 slabs x 2 replicas over disjoint clusters. Replicas of a slab
    share ONE engine in-process (replicas are byte-interchangeable by
    contract, so this is exact); the source file rides along for the
    standby's re-materialization path."""
    from mpi_cuda_largescaleknn_tpu.models.sharding import slab_bounds
    from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
    from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine
    from mpi_cuda_largescaleknn_tpu.serve.frontend import HostSliceServer

    points = _replica_points()
    pts_path = str(tmp_path_factory.mktemp("replica") / "points.float3")
    points.tofile(pts_path)
    engines, servers = [], []
    for b, e in slab_bounds(len(points), 2):
        eng = ResidentKnnEngine(points[b:e], K, mesh=get_mesh(1),
                                engine="tiled", bucket_size=64,
                                max_batch=32, min_batch=16,
                                id_offset=b, emit="candidates")
        eng.warmup()
        engines.append(eng)
    for eng in engines:          # slab-major: A0, A1, B0, B1
        for _ in range(2):
            srv = HostSliceServer(("127.0.0.1", 0), eng, routing="bounds")
            threading.Thread(target=srv.serve_forever,
                             daemon=True).start()
            srv.ready = True
            servers.append(srv)
    urls = [f"http://127.0.0.1:{s.server_address[1]}" for s in servers]
    yield urls, points, servers, pts_path
    for s in servers:
        s.close()


@pytest.fixture(scope="module")
def reference_engine():
    from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
    from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine

    eng = ResidentKnnEngine(_replica_points(), K, mesh=get_mesh(1),
                            engine="tiled", bucket_size=64,
                            max_batch=32, min_batch=16)
    eng.warmup()
    return eng


@pytest.fixture()
def clean_faults(replica_pod):
    _, _, servers, _ = replica_pod
    for s in servers:
        s.faults.clear()
    yield
    for s in servers:
        s.faults.clear()


def _build_fe(urls, **kw):
    from mpi_cuda_largescaleknn_tpu.serve.frontend import build_frontend

    kw.setdefault("on_host_loss", "degrade")
    kw.setdefault("retries", 1)
    kw.setdefault("retry_backoff_s", 0.001)
    kw.setdefault("fail_threshold", 2)
    kw.setdefault("start_monitor", False)
    srv = build_frontend(urls, port=0, pipeline_depth=2, **kw)
    srv.ready = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


def _standby(pts_path, **overrides):
    from mpi_cuda_largescaleknn_tpu.serve.frontend import HostSliceServer

    cfg = dict(path=pts_path, num_hosts=2, k=K, shards=1, engine="tiled",
               bucket_size=64, max_batch=32, min_batch=16)
    cfg.update(overrides)
    srv = HostSliceServer(("127.0.0.1", 0), None, routing="bounds",
                          standby_config=cfg)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


def _wait_adopt(standby, want="adopted", timeout_s=180.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        snap = standby.adopt_snapshot()
        if snap["state"] == want:
            return snap
        if want == "adopted" and snap["state"] == "failed":
            raise AssertionError(f"adoption failed: {snap['error']}")
        time.sleep(0.05)
    raise AssertionError(f"adoption never reached {want!r}: "
                         f"{standby.adopt_snapshot()}")


class TestReplicaGroupsServing:
    def test_grouped_frontend_serves_bitwise_and_spreads(
            self, replica_pod, reference_engine, clean_faults):
        from tests.oracle import random_points

        urls, _points, _servers, _ = replica_pod
        fe, base = _build_fe(urls)
        try:
            st = fe.fanout.stats()["routing"]["replicas"]
            assert st["num_slabs"] == 2
            assert [len(p["members"]) for p in st["per_slab"]] == [2, 2]
            for n in (1, 7, 16):
                q = random_points(n, seed=400 + n)
                resp = _post_knn(base, q)
                assert resp["exact"] is True
                want_d, want_n = reference_engine.query(q)
                np.testing.assert_array_equal(
                    np.asarray(resp["dists"], np.float32), want_d)
                np.testing.assert_array_equal(
                    np.asarray(resp["neighbors"], np.int32), want_n)
            # the spread counters show picks landing on BOTH replicas
            spread = fe.fanout.stats()["routing"]["replicas"]["spread"]
            assert sum(1 for v in spread.values() if v > 0) >= 2
            m = urllib.request.urlopen(base + "/metrics",
                                       timeout=30).read().decode()
            assert 'knn_replica_live{slab="0"} 2' in m
            assert "knn_replica_spread{" in m
            assert "knn_handoffs_total 0" in m
        finally:
            fe.close()

    def test_single_replica_loss_costs_capacity_not_exactness(
            self, replica_pod, reference_engine, clean_faults):
        from tests.oracle import random_points

        urls, _points, servers, _ = replica_pod
        fe, base = _build_fe(urls)
        try:
            servers[1].faults.set_specs("drop:")  # slab A, replica 1
            # EVERY query stays exact and bit-identical — the drained
            # replica is routed around, never degraded
            for seed in (81, 82):
                q = random_points(16, seed=seed)  # spans A, B, the gap
                resp = _post_knn(base, q)
                assert resp["exact"] is True
                assert "exact_per_query" not in resp
                want_d, want_n = reference_engine.query(q)
                np.testing.assert_array_equal(
                    np.asarray(resp["dists"], np.float32), want_d)
                np.testing.assert_array_equal(
                    np.asarray(resp["neighbors"], np.int32), want_n)
            # the spread policy routes AROUND a suspect replica (a single
            # dispatch failure is enough to deprioritize it), so dispatch
            # alone may never push it to drained — the monitor's probes
            # finish the job
            fe.monitor.check_once(now=1e9)
            fe.monitor.check_once(now=2e9)
            assert fe.fanout.endpoints[1].health.state == "drained"
            st = fe.fanout.stats()["routing"]["replicas"]
            assert st["per_slab"][0]["live"] == 1
            m = urllib.request.urlopen(base + "/metrics",
                                       timeout=30).read().decode()
            assert 'knn_replica_live{slab="0"} 1' in m
            # and queries after the drain STILL stay exact + bitwise
            q = random_points(16, seed=86)
            resp = _post_knn(base, q)
            assert resp["exact"] is True
            want_d, want_n = reference_engine.query(q)
            np.testing.assert_array_equal(
                np.asarray(resp["dists"], np.float32), want_d)
            np.testing.assert_array_equal(
                np.asarray(resp["neighbors"], np.int32), want_n)
        finally:
            fe.close()

    @pytest.mark.parametrize("victim", [0, 1, 2, 3])
    def test_single_host_loss_under_concurrent_load_stays_exact(
            self, replica_pod, reference_engine, clean_faults, victim):
        """Any one host of the R=2 pod lost mid-traffic: every concurrent
        request is still answered, exact and bit-identical — no
        ``exact: false``, whichever replica of whichever slab went."""
        from tests.oracle import random_points

        urls, _points, servers, _ = replica_pod
        fe, base = _build_fe(urls)
        try:
            servers[victim].faults.set_specs("drop:")
            qs = [random_points(4 + 3 * i, seed=500 + 10 * victim + i)
                  for i in range(8)]
            results, errors = {}, []

            def client(i):
                try:
                    results[i] = _post_knn(base, qs[i])
                except Exception as e:  # noqa: BLE001 - asserted below
                    errors.append(e)

            ths = [threading.Thread(target=client, args=(i,))
                   for i in range(len(qs))]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            assert errors == [] and len(results) == len(qs)
            for i, resp in results.items():
                assert resp["exact"] is True
                assert "exact_per_query" not in resp
                want_d, want_n = reference_engine.query(qs[i])
                np.testing.assert_array_equal(
                    np.asarray(resp["dists"], np.float32), want_d)
                np.testing.assert_array_equal(
                    np.asarray(resp["neighbors"], np.int32), want_n)
        finally:
            fe.close()

    def test_all_replicas_down_degrades_then_rejoins(
            self, replica_pod, reference_engine, clean_faults):
        from tests.oracle import random_points

        urls, points, servers, _ = replica_pod
        fe, base = _build_fe(urls)
        try:
            servers[2].faults.set_specs("drop:")  # both replicas of B
            servers[3].faults.set_specs("drop:")
            qb = random_points(8, seed=83, scale=0.4) + np.float32(0.6)
            resp_b = _post_knn(base, qb)
            # zero live replicas for an improving slab: the PR-8 contract
            assert resp_b["exact"] is False
            assert resp_b["exact_per_query"] == [False] * len(qb)
            from tests.oracle import kth_nn_dist

            np.testing.assert_allclose(
                np.asarray(resp_b["dists"], np.float32),
                kth_nn_dist(qb, points[:300], K), rtol=5e-7, atol=1e-37)
            # A queries never touched slab B: still bit-identical
            qa = random_points(8, seed=84, scale=0.4)
            resp_a = _post_knn(base, qa)
            assert resp_a["exact"] is True
            want_d, want_n = reference_engine.query(qa)
            np.testing.assert_array_equal(
                np.asarray(resp_a["dists"], np.float32), want_d)
            st = fe.fanout.stats()["routing"]["replicas"]
            assert st["per_slab"][1]["live"] == 0
            # outage over: rejoin both, exactness returns
            servers[2].faults.clear()
            servers[3].faults.clear()
            fe.monitor.check_once(now=1e9)
            assert (fe.fanout.stats()["routing"]["replicas"]
                    ["per_slab"][1]["live"]) == 2
            resp_b2 = _post_knn(base, qb)
            assert resp_b2["exact"] is True
            want_d, want_n = reference_engine.query(qb)
            np.testing.assert_array_equal(
                np.asarray(resp_b2["dists"], np.float32), want_d)
            np.testing.assert_array_equal(
                np.asarray(resp_b2["neighbors"], np.int32), want_n)
        finally:
            fe.close()


class TestSlabHandoff:
    def test_handoff_end_to_end_with_query_during_and_parity_after(
            self, replica_pod, reference_engine, clean_faults):
        from tests.oracle import random_points

        urls, _points, servers, pts_path = replica_pod
        standby, sb_url = _standby(pts_path)
        fe, base = _build_fe(urls, standbys=[sb_url], handoff_floor=2)
        try:
            probe = random_points(24, seed=85)  # spans A, B, the gap
            before = _post_knn(base, probe)
            assert before["exact"] is True
            # kill slab A's replica 1; drive the monitor until it drains
            servers[1].faults.set_specs("drop:")
            fe.monitor.check_once(now=1e9)
            fe.monitor.check_once(now=2e9)
            assert fe.fanout.endpoints[1].health.state == "drained"
            # below the floor (live 1 < 2): the handoff started; queries
            # DURING the handoff keep serving bit-identical off the
            # surviving replica
            mid = _post_knn(base, probe)
            assert mid["exact"] is True
            assert mid["dists"] == before["dists"]
            assert mid["neighbors"] == before["neighbors"]
            snap = _wait_adopt(standby)  # pull-from-replica + warmup
            assert snap["slab"] == 0 and snap["seconds"] is not None
            # next monitor cycle: fingerprint-gate + bind
            fe.monitor.check_once(now=3e9)
            ho = fe.monitor.stats()["handoff"]
            assert ho["handoffs"] == 1 and ho["handoff_rejections"] == 0
            st = fe.fanout.stats()["routing"]["replicas"]
            assert st["per_slab"][0]["live"] == 2
            assert sb_url in st["per_slab"][0]["members"]
            assert st["rebinds"] == 1
            # now kill the OTHER original replica: slab A is served
            # EXCLUSIVELY by the adopted standby — the parity acceptance
            servers[0].faults.set_specs("drop:")
            fe.monitor.check_once(now=4e9)
            fe.monitor.check_once(now=5e9)
            assert fe.fanout.endpoints[0].health.state == "drained"
            after = _post_knn(base, probe)
            assert after["exact"] is True
            assert after["dists"] == before["dists"]
            assert after["neighbors"] == before["neighbors"]
            want_d, want_n = reference_engine.query(probe)
            np.testing.assert_array_equal(
                np.asarray(after["dists"], np.float32), want_d)
            np.testing.assert_array_equal(
                np.asarray(after["neighbors"], np.int32), want_n)
            m = urllib.request.urlopen(base + "/metrics",
                                       timeout=30).read().decode()
            assert "knn_handoffs_total 1" in m
            assert "knn_replica_rebinds_total 1" in m
        finally:
            fe.close()
            standby.close()

    def test_mismatched_standby_is_rejected_and_never_serves(
            self, replica_pod, clean_faults):
        urls, _points, servers, pts_path = replica_pod
        # wrong engine config: the adopted slab's fingerprint cannot match
        standby, sb_url = _standby(pts_path, bucket_size=32)
        fe, _base = _build_fe(urls, standbys=[sb_url], handoff_floor=2)
        try:
            servers[1].faults.set_specs("drop:")
            fe.monitor.check_once(now=1e9)
            fe.monitor.check_once(now=2e9)
            _wait_adopt(standby)  # adoption itself succeeds...
            fe.monitor.check_once(now=3e9)
            ho = fe.monitor.stats()["handoff"]
            # ...but the fingerprint gate refuses to bind it
            assert ho["handoffs"] == 0 and ho["handoff_rejections"] == 1
            sb = ho["standbys"][0]
            assert sb["state"] == "failed"
            assert "fingerprint mismatch" in sb["last_error"]
            assert "bucket_size" in sb["last_error"]
            st = fe.fanout.stats()["routing"]["replicas"]
            assert st["per_slab"][0]["live"] == 1  # still under-replicated
            assert sb_url not in st["per_slab"][0]["members"]
        finally:
            fe.close()
            standby.close()

    def test_adopt_slab_http_surface(self, replica_pod, clean_faults):
        urls, _points, _servers, pts_path = replica_pod
        # a regular routed host refuses adoption outright
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post_json(urls[0], "/adopt_slab", {"host_id": 0})
        assert ei.value.code == 409
        standby, sb_url = _standby(pts_path)
        try:
            # standby /healthz reports the lifecycle while empty
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(sb_url + "/healthz", timeout=30)
            assert ei.value.code == 503
            body = json.loads(ei.value.read())
            assert body["role"] == "standby"
            assert body["status"] == "standby"
            # malformed requests 400 without touching the lifecycle
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post_json(sb_url, "/adopt_slab", {"host_id": 9})
            assert ei.value.code == 400
            assert standby.adopt_snapshot()["state"] == "standby"
            # a valid file-path adoption materializes the slab and serves
            status, resp = _post_json(sb_url, "/adopt_slab",
                                      {"host_id": 0, "num_hosts": 2,
                                       "row_offset": 0, "n_points": 300})
            assert status == 202 and resp["status"] == "adopting"
            # adopting/adopted: a second adopt is refused (409)
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post_json(sb_url, "/adopt_slab", {"host_id": 1})
            assert ei.value.code == 409
            _wait_adopt(standby)
            assert standby.engine.n_points == 300
            assert standby.engine.id_offset == 0
            with urllib.request.urlopen(sb_url + "/healthz",
                                        timeout=30) as r:
                hz = json.loads(r.read())
            assert hz["status"] == "ok" and hz["role"] == "host-routed"
            assert hz["adopt"]["state"] == "adopted"
        finally:
            standby.close()

    def test_adoption_failure_is_surfaced_and_retryable(self, replica_pod):
        _urls, _points, _servers, pts_path = replica_pod
        standby, sb_url = _standby("/nonexistent/points.float3")
        try:
            status, _ = _post_json(sb_url, "/adopt_slab",
                                   {"host_id": 0, "num_hosts": 2})
            assert status == 202
            _wait_adopt(standby, want="failed")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(sb_url + "/healthz", timeout=30)
            assert ei.value.code == 503
            body = json.loads(ei.value.read())
            assert body["status"] == "adopt-failed"
            assert "adopt_error" in body
            # a failed standby may retry (e.g. after the operator fixes
            # the file) — the 202 proves the lifecycle reopens
            status, _ = _post_json(sb_url, "/adopt_slab",
                                   {"host_id": 0, "num_hosts": 2})
            assert status == 202
        finally:
            standby.close()

    def test_slab_rows_pull_surface(self, replica_pod, clean_faults):
        urls, points, _servers, _ = replica_pod
        from mpi_cuda_largescaleknn_tpu.serve.replica import pull_slab_rows

        rows, off = pull_slab_rows(urls[2])  # slab B, replica 0
        assert off == 300
        np.testing.assert_array_equal(rows, points[300:])
