"""Fault-tolerant serving: drain/rejoin, retry/backoff, degraded answers.

Everything here is DETERMINISTIC — no sleep-based races. Failures are
produced by serve/faults.py injectors (seeded, countable), time is an
injectable clock in every unit test, retry backoff sleeps are captured by
a recorder instead of slept, and the health monitor is driven by explicit
``check_once()`` calls rather than its background thread.

The integration fixture is a 2-host routed pod with spatially DISJOINT
slabs (cluster A rows 0..299 on host 0, cluster B rows 300..599 on
host 1), so "the certified routing set touches the drained slab" is an
exact, predictable property: A-region queries certify at host 0 and must
stay BIT-IDENTICAL to a never-failed pod while host 1 is down; B-region
queries are exactly the degraded/refused set.
"""

from __future__ import annotations

import json
import threading
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


def _post_faults(url, spec: str, timeout=30):
    req = urllib.request.Request(
        url + "/faults", data=json.dumps({"spec": spec}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _failover_points():
    """600 rows: [0:300) cluster A in [0, 0.4)^3, [300:600) cluster B in
    [0.6, 1.0)^3 — disjoint slabs, so routing decisions are clean."""
    from tests.oracle import random_points

    a = random_points(300, seed=51, scale=0.4)
    b = random_points(300, seed=52, scale=0.4) + np.float32(0.6)
    return np.concatenate([a, b]).astype(np.float32)


# ---------------------------------------------------------------- unit layer


class TestBackoff:
    def test_capped_exponential_and_deterministic(self):
        from mpi_cuda_largescaleknn_tpu.serve.health import Backoff

        b = Backoff(base_s=0.1, cap_s=1.0, factor=2.0, jitter=0.0, seed=0)
        delays = [b.delay(i) for i in range(1, 7)]
        assert delays[:4] == [0.1, 0.2, 0.4, 0.8]
        assert delays[4] == delays[5] == 1.0  # capped
        # same seed+key -> identical sequence; different key -> different
        # jitter, still within [d, d * (1 + jitter)]
        j1 = Backoff(base_s=0.1, cap_s=1.0, jitter=0.5, seed=7)
        j2 = Backoff(base_s=0.1, cap_s=1.0, jitter=0.5, seed=7)
        s1 = [j1.delay(i, key="hostA") for i in range(1, 5)]
        assert s1 == [j2.delay(i, key="hostA") for i in range(1, 5)]
        assert s1 != [j1.delay(i, key="hostB") for i in range(1, 5)]
        for i, d in enumerate(s1, start=1):
            base = min(1.0, 0.1 * 2.0 ** (i - 1))
            assert base <= d <= base * 1.5


class TestFaultInjector:
    def test_parse_and_counting(self):
        from mpi_cuda_largescaleknn_tpu.serve.faults import (
            FaultInjector,
            parse_fault_specs,
        )

        specs = parse_fault_specs(
            "error:path=/route_knn,n=2,code=503;latency:delay_s=0.01")
        assert [s.op for s in specs] == ["error", "latency"]
        assert specs[0].code == 503
        inj = FaultInjector(specs)
        # first two /route_knn requests hit the error budget, later ones
        # fall through to the catch-all latency rule
        ops = [inj.decide("/route_knn").op for _ in range(4)]
        assert ops == ["error", "error", "latency", "latency"]
        # a path the error rule doesn't match only sees latency
        assert inj.decide("/healthz").op == "latency"
        inj.clear()
        assert inj.decide("/route_knn") is None and not inj.active()

    def test_after_skips_then_arms(self):
        from mpi_cuda_largescaleknn_tpu.serve.faults import (
            FaultInjector,
            parse_fault_specs,
        )

        inj = FaultInjector(parse_fault_specs("drop:after=2,n=1"))
        assert [inj.decide("/x") for _ in range(4)][:2] == [None, None]
        assert inj.config()[0]["fires"] == 1

    def test_probabilistic_sequence_is_seed_deterministic(self):
        from mpi_cuda_largescaleknn_tpu.serve.faults import (
            FaultInjector,
            parse_fault_specs,
        )

        def seq(seed):
            inj = FaultInjector(parse_fault_specs(f"drop:p=0.5,seed={seed}"))
            return [inj.decide("/x") is not None for _ in range(32)]

        assert seq(9) == seq(9)       # reproducible
        assert seq(9) != seq(10)      # and actually seed-driven
        assert 4 < sum(seq(9)) < 28   # a real coin, not constant

    def test_method_filter_restricts_the_match(self):
        """``method=POST`` kills the serving path while GET probes keep
        answering — the probes-lie failure mode the fan-out's per-batch
        budget covers; the filter is part of the MATCH, so the skip/fire
        counters only see requests the rule could hit."""
        from mpi_cuda_largescaleknn_tpu.serve.faults import (
            FaultInjector,
            parse_fault_specs,
        )

        inj = FaultInjector(parse_fault_specs(
            "drop:path=/route_knn,method=POST"))
        assert inj.decide("/route_knn", "POST") is not None
        assert inj.decide("/route_knn", "GET") is None
        assert inj.decide("/healthz", "POST") is None
        # an unfiltered rule still matches any verb (back-compat)
        inj.set_specs("drop:")
        assert inj.decide("/x", "GET") is not None
        assert inj.decide("/x") is not None

    def test_unknown_op_and_key_raise(self):
        from mpi_cuda_largescaleknn_tpu.serve.faults import parse_fault_specs

        with pytest.raises(ValueError, match="unknown fault op"):
            parse_fault_specs("explode:")
        with pytest.raises(ValueError, match="unknown fault spec key"):
            parse_fault_specs("drop:bogus=1")


class TestHostHealth:
    def _health(self, clock, **kw):
        from mpi_cuda_largescaleknn_tpu.serve.health import HostHealth

        kw.setdefault("fail_threshold", 3)
        return HostHealth(clock=clock, **kw)

    def test_drains_at_threshold_and_success_resets(self):
        t = {"now": 0.0}
        h = self._health(lambda: t["now"])
        h.note_failure("e1")
        assert h.state == "suspect" and h.consecutive_failures == 1
        h.note_success()
        assert h.state == "healthy" and h.consecutive_failures == 0
        for i in range(3):
            h.note_failure(f"e{i}")
        assert h.state == "drained" and h.is_drained()

    def test_drained_seconds_accounting_with_fake_clock(self):
        t = {"now": 100.0}
        h = self._health(lambda: t["now"], fail_threshold=1)
        h.note_failure("down")
        t["now"] = 107.5
        assert h.drained_seconds() == pytest.approx(7.5)
        h.mark_rejoining()
        t["now"] = 110.0
        h.mark_rejoined()
        assert h.state == "healthy"
        assert h.drained_seconds() == pytest.approx(10.0)
        t["now"] = 200.0  # healthy time never accrues
        assert h.drained_seconds() == pytest.approx(10.0)

    def test_rejoin_failure_returns_to_drained(self):
        t = {"now": 0.0}
        h = self._health(lambda: t["now"], fail_threshold=1)
        h.note_failure("down")
        h.mark_rejoining()
        h.rejoin_failed("fingerprint mismatch")
        assert h.state == "drained"
        assert "fingerprint" in h.last_error

    def test_probe_scheduling_backoff_while_drained(self):
        t = {"now": 0.0}
        h = self._health(lambda: t["now"], fail_threshold=1,
                         probe_interval_s=5.0, backoff_base_s=1.0,
                         backoff_cap_s=4.0, jitter=0.0)
        assert h.probe_due(0.0)
        nxt = h.schedule_next_probe(now=0.0)
        assert nxt == 5.0 and not h.probe_due(4.9) and h.probe_due(5.0)
        h.note_failure("down")  # drained: capped exponential takes over
        delays = []
        now = 10.0
        for _ in range(4):
            nxt = h.schedule_next_probe(now=now)
            delays.append(nxt - now)
            now = nxt
        assert delays == [1.0, 2.0, 4.0, 4.0]

    def test_backoff_resets_after_rejoin_and_is_exposed(self):
        """A successful rejoin must reset the drained-probe backoff: a
        LATER flap restarts from the base interval, never the cap — and
        ``backoff_current_s`` surfaces the live value per host (the
        frontend /stats pod.health block carries the snapshot)."""
        t = {"now": 0.0}
        h = self._health(lambda: t["now"], fail_threshold=1,
                         probe_interval_s=5.0, backoff_base_s=1.0,
                         backoff_cap_s=8.0, jitter=0.0)
        assert h.snapshot()["backoff_current_s"] == 0.0  # healthy: none
        h.note_failure("down")
        now = 0.0
        for _ in range(3):  # ride the exponential to the cap
            now = h.schedule_next_probe(now=now)
        assert h.snapshot()["backoff_current_s"] == 8.0  # at the cap
        h.mark_rejoining()
        h.mark_rejoined()
        assert h.snapshot()["backoff_current_s"] == 0.0  # reset with state
        # a later flap restarts the schedule from BASE, not the cap
        h.note_failure("down again")
        assert h.snapshot()["backoff_current_s"] == 1.0
        nxt = h.schedule_next_probe(now=100.0)
        assert nxt - 100.0 == 1.0


class _FakeFanout:
    """Minimal fan-out stand-in for monitor unit tests."""

    def __init__(self, urls, clock, health_kw=None):
        from mpi_cuda_largescaleknn_tpu.serve.health import HostHealth

        class _Ep:
            def __init__(self, url):
                self.url = url
                self.health = HostHealth(clock=clock,
                                         **(health_kw or
                                            {"fail_threshold": 1}))

        self.endpoints = [_Ep(u) for u in urls]
        self.broken = None
        self.resets: list[int] = []

    def reset_stream(self, seq):
        self.broken = None
        self.resets.append(seq)


class TestHealthMonitorUnit:
    def _monitor(self, fanout, probes, stats, fingerprints, mode="bounds"):
        from mpi_cuda_largescaleknn_tpu.serve.health import HealthMonitor

        return HealthMonitor(
            fanout, fingerprints=fingerprints, mode=mode,
            probe_fn=lambda url: probes[url].pop(0),
            stats_fn=lambda url: stats[url], clock=lambda: 0.0)

    def test_probe_failures_drain_then_matching_fingerprint_rejoins(self):
        from mpi_cuda_largescaleknn_tpu.serve.health import host_fingerprint

        t = {"now": 0.0}
        fan = _FakeFanout(["u1"], lambda: t["now"],
                          {"fail_threshold": 2, "jitter": 0.0})
        engine = {"k": 5, "dim": 3, "row_offset": 0, "n_points": 10}
        fp = host_fingerprint(engine, "bounds")
        probes = {"u1": [(False, {"error": "boom"}),
                         (False, {"error": "boom"}),
                         (True, {}), (True, {})]}
        mon = self._monitor(fan, probes, {"u1": {"engine": engine}},
                            {"u1": fp})
        h = fan.endpoints[0].health
        mon.check_once(now=0.0)
        assert h.state == "suspect"
        mon.check_once(now=h.next_probe_at)
        assert h.state == "drained"
        mon.check_once(now=h.next_probe_at)
        assert h.state == "healthy" and mon.rejoins == 1

    def test_fingerprint_mismatch_blocks_rejoin(self):
        from mpi_cuda_largescaleknn_tpu.serve.health import host_fingerprint

        fan = _FakeFanout(["u1"], lambda: 0.0,
                          {"fail_threshold": 1, "jitter": 0.0})
        good = host_fingerprint({"k": 5, "row_offset": 0}, "bounds")
        # the restarted host came back serving a DIFFERENT slab
        probes = {"u1": [(False, {"error": "x"}), (True, {})]}
        stats = {"u1": {"engine": {"k": 5, "row_offset": 300}}}
        mon = self._monitor(fan, probes, stats, {"u1": good})
        h = fan.endpoints[0].health
        mon.check_once(now=0.0)
        assert h.state == "drained"
        mon.check_once(now=h.next_probe_at)
        assert h.state == "drained" and mon.rejoin_rejections == 1
        assert "row_offset" in h.last_error

    def test_replicate_pod_reset_needs_seq_consensus(self):
        from mpi_cuda_largescaleknn_tpu.serve.health import host_fingerprint

        fan = _FakeFanout(["u1", "u2"], lambda: 0.0,
                          {"fail_threshold": 1, "jitter": 0.0})
        fan.broken = "host u2 died"
        fan.endpoints[1].health.force_drain("died")
        engine = {"k": 5, "merge": "device"}
        fp = host_fingerprint(engine, "off")
        stats = {u: {"engine": engine} for u in ("u1", "u2")}
        # first pass: hosts disagree on next_seq -> no reset; second pass
        # (after the restart converges): consensus -> stream reset. The
        # reset path REUSES the cycle's probe results (no extra probes),
        # so each check_once consumes exactly one scripted result per host
        probes = {"u1": [(True, {"next_seq": 4}),
                         (True, {"next_seq": 0})],
                  "u2": [(True, {"next_seq": 0}),
                         (True, {"next_seq": 0})]}
        mon = self._monitor(fan, probes, stats, {"u1": fp, "u2": fp},
                            mode="off")
        mon.check_once(now=0.0)
        assert fan.broken is not None and fan.resets == []
        mon.check_once(now=1e9)  # everything due again
        assert fan.broken is None and fan.resets == [0]
        assert all(ep.health.state == "healthy" for ep in fan.endpoints)
        assert mon.stream_resets == 1


# --------------------------------------------------------- integration layer


@pytest.fixture(scope="module")
def routed_pod():
    """Two in-process routed slab hosts over disjoint clusters, with
    programmatic fault injectors."""
    from mpi_cuda_largescaleknn_tpu.models.sharding import slab_bounds
    from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
    from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine
    from mpi_cuda_largescaleknn_tpu.serve.frontend import HostSliceServer

    points = _failover_points()
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
    yield urls, points, servers
    for s in servers:
        s.close()


@pytest.fixture(scope="module")
def reference_engine():
    """One engine over the union — the never-failed pod's byte-identical
    stand-in (PR 7's parity chain)."""
    from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
    from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine

    eng = ResidentKnnEngine(_failover_points(), K, mesh=get_mesh(2),
                            engine="tiled", bucket_size=64,
                            max_batch=32, min_batch=16, merge="device")
    eng.warmup()
    return eng


@pytest.fixture()
def clean_faults(routed_pod):
    """Every test starts and ends with injection off on both hosts."""
    _, _, servers = routed_pod
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
    # these tests re-post identical probes to drive the failure paths;
    # the exact-hit query cache would serve the repeat without ever
    # reaching the faulted host, so it stays off here
    kw.setdefault("qcache_rows", 0)
    srv = build_frontend(urls, port=0, pipeline_depth=2, **kw)
    srv.ready = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


class TestRetryWithBackoff:
    def test_transient_5xx_is_retried_and_recorded(self, routed_pod,
                                                   reference_engine,
                                                   clean_faults):
        from tests.oracle import random_points

        urls, _points, servers = routed_pod
        fe, base = _build_fe(urls, retries=3)
        try:
            slept = []
            fe.fanout._sleep = slept.append  # retries never really sleep
            # host 0 fails its next 2 /route_knn posts, then recovers —
            # inside the retry budget, so the request must succeed exactly
            servers[0].faults.set_specs("error:path=/route_knn,n=2")
            q = random_points(8, seed=70, scale=0.4)  # A-region: host 0
            resp = _post_knn(base, q)
            want_d, want_n = reference_engine.query(q)
            np.testing.assert_array_equal(
                np.asarray(resp["dists"], np.float32), want_d)
            np.testing.assert_array_equal(
                np.asarray(resp["neighbors"], np.int32), want_n)
            assert resp["exact"] is True
            ep = fe.fanout.endpoints[0]
            assert ep.retries == 2
            assert ep.health.state == "healthy"  # success reset the streak
            # the recorded backoff delays are exactly the deterministic
            # schedule (no RNG state shared with anything else)
            want = [fe.fanout.retry_backoff.delay(i, key=ep.url)
                    for i in (1, 2)]
            assert slept == want
            m = urllib.request.urlopen(base + "/metrics",
                                       timeout=30).read().decode()
            assert f'knn_dispatch_retries_total{{host="{ep.url}"}} 2' in m
        finally:
            fe.close()

    def test_nonretryable_4xx_is_not_retried(self, routed_pod, clean_faults):
        from mpi_cuda_largescaleknn_tpu.serve.frontend import HostCallError

        urls, _points, servers = routed_pod
        fe, _base = _build_fe(urls, retries=3)
        try:
            fe.fanout._sleep = lambda s: None
            servers[0].faults.set_specs("error:path=/route_knn,code=404,n=8")
            ep = fe.fanout.endpoints[0]
            with pytest.raises(HostCallError) as ei:
                fe.fanout._post_route(ep, b"\x00" * 12, 1)
            assert not ei.value.transient
            assert ep.retries == 0  # a config error is never retried
        finally:
            fe.close()


class TestDegradedMode:
    def test_host_loss_degrades_only_affected_queries(self, routed_pod,
                                                      reference_engine,
                                                      clean_faults):
        from tests.oracle import random_points

        urls, points, servers = routed_pod
        fe, base = _build_fe(urls, on_host_loss="degrade")
        try:
            # host 1 (cluster B's slab) goes down hard: every /route_knn
            # and /healthz answer is dropped mid-connection
            servers[1].faults.set_specs("drop:")
            qb = random_points(8, seed=71, scale=0.4) + np.float32(0.6)
            resp_b = _post_knn(base, qb)
            # B queries touch the drained slab: flagged, not refused
            assert resp_b["exact"] is False
            assert resp_b["exact_per_query"] == [False] * len(qb)
            assert fe.fanout.endpoints[1].health.state == "drained"
            # degraded answers are the fold of the SURVIVING host only —
            # byte-stable across repeats, and equal to host 0's slab truth
            resp_b2 = _post_knn(base, qb)
            assert resp_b2["dists"] == resp_b["dists"]
            assert resp_b2["neighbors"] == resp_b["neighbors"]
            from tests.oracle import kth_nn_dist

            np.testing.assert_allclose(
                np.asarray(resp_b["dists"], np.float32),
                kth_nn_dist(qb, points[:300], K), rtol=5e-7, atol=1e-37)
            # A queries never routed to host 1: still bit-identical to the
            # never-failed pod
            qa = random_points(8, seed=72, scale=0.4)
            resp_a = _post_knn(base, qa)
            assert resp_a["exact"] is True
            want_d, want_n = reference_engine.query(qa)
            np.testing.assert_array_equal(
                np.asarray(resp_a["dists"], np.float32), want_d)
            np.testing.assert_array_equal(
                np.asarray(resp_a["neighbors"], np.int32), want_n)
            # observability: counters + state gauge + stats block
            st = json.loads(urllib.request.urlopen(
                base + "/stats", timeout=30).read())
            assert st["pod"]["on_host_loss"] == "degrade"
            assert st["pod"]["health"][urls[1]]["state"] == "drained"
            assert st["server"]["knn_degraded_responses_total"] >= 2
            assert st["fanout"]["routing"]["degraded_rows"] >= len(qb)
            m = urllib.request.urlopen(base + "/metrics",
                                       timeout=30).read().decode()
            assert f'knn_host_state{{host="{urls[1]}"}} 2' in m
            assert "knn_degraded_responses_total" in m
            assert f'knn_host_drained_seconds_total{{host="{urls[1]}"}}' in m
            hz = json.loads(urllib.request.urlopen(
                base + "/healthz", timeout=30).read())
            assert hz["status"] in ("ok", "degraded")
        finally:
            fe.close()

    def test_every_request_answered_under_host_loss(self, routed_pod,
                                                     reference_engine,
                                                     clean_faults):
        """Availability through a host loss: concurrent clients over the
        whole box all get answers while host 1 is down — rows that needed
        it flagged per query, every other row exact and bit-identical to
        the never-failed pod."""
        from tests.oracle import random_points

        urls, _points, servers = routed_pod
        fe, base = _build_fe(urls, on_host_loss="degrade")
        try:
            servers[1].faults.set_specs("drop:")
            qs = [random_points(4 + 3 * i, seed=90 + i) for i in range(8)]
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
            flagged = 0
            for i, resp in results.items():
                want_d, want_n = reference_engine.query(qs[i])
                exact = np.asarray(resp.get("exact_per_query",
                                            [True] * len(qs[i])))
                assert resp["exact"] is bool(exact.all())
                flagged += int((~exact).sum())
                np.testing.assert_array_equal(
                    np.asarray(resp["dists"], np.float32)[exact],
                    want_d[exact])
                np.testing.assert_array_equal(
                    np.asarray(resp["neighbors"], np.int32)[exact],
                    want_n[exact])
            assert flagged > 0
        finally:
            fe.close()

    def test_rejoin_restores_bitwise_parity(self, routed_pod,
                                            reference_engine, clean_faults):
        from tests.oracle import random_points

        urls, _points, servers = routed_pod
        fe, base = _build_fe(urls, on_host_loss="degrade")
        try:
            probe = random_points(24, seed=73)  # spans A, B, and the gap
            before = _post_knn(base, probe)
            servers[1].faults.set_specs("drop:")
            degraded = _post_knn(base, probe)
            assert degraded["exact"] is False
            assert fe.fanout.endpoints[1].health.state == "drained"
            # outage over: clear the faults and drive the monitor by hand
            servers[1].faults.clear()
            fe.monitor.check_once(now=1e9)  # every probe due
            assert fe.fanout.endpoints[1].health.state == "healthy"
            assert fe.monitor.rejoins == 1
            after = _post_knn(base, probe)
            assert after["exact"] is True
            # the acceptance bar: bitwise parity with a never-failed pod
            assert after["dists"] == before["dists"]
            assert after["neighbors"] == before["neighbors"]
            want_d, want_n = reference_engine.query(probe)
            np.testing.assert_array_equal(
                np.asarray(after["dists"], np.float32), want_d)
            np.testing.assert_array_equal(
                np.asarray(after["neighbors"], np.int32), want_n)
        finally:
            fe.close()

    def test_runtime_fault_endpoint_drives_outage(self, routed_pod,
                                                  clean_faults):
        """The chaos bench's control surface: POST /faults flips a live
        host into an outage and back, no process restarts involved."""
        urls, _points, servers = routed_pod
        cfg = _post_faults(urls[1], "error:path=/route_knn,code=500")
        assert cfg["specs"][0]["code"] == 500
        assert servers[1].faults.active()
        cfg = _post_faults(urls[1], "")
        assert cfg["specs"] == [] and not servers[1].faults.active()


class TestFailMode:
    def test_affected_queries_503_unaffected_serve(self, routed_pod,
                                                   reference_engine,
                                                   clean_faults):
        from tests.oracle import random_points

        urls, _points, servers = routed_pod
        fe, base = _build_fe(urls, on_host_loss="fail")
        try:
            servers[1].faults.set_specs("drop:")
            qb = random_points(6, seed=74, scale=0.4) + np.float32(0.6)
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post_knn(base, qb)
            assert ei.value.code == 503
            assert ei.value.headers.get("Retry-After") is not None
            body = json.loads(ei.value.read())
            assert "drained" in body["error"]
            # unaffected queries still serve, bit-identical
            qa = random_points(6, seed=75, scale=0.4)
            resp = _post_knn(base, qa)
            want_d, want_n = reference_engine.query(qa)
            np.testing.assert_array_equal(
                np.asarray(resp["dists"], np.float32), want_d)
            np.testing.assert_array_equal(
                np.asarray(resp["neighbors"], np.int32), want_n)
            st = json.loads(urllib.request.urlopen(
                base + "/stats", timeout=30).read())
            assert st["server"]["knn_unavailable_total"] >= 1
        finally:
            fe.close()


class TestReplicateDrainThenFail:
    @pytest.fixture(scope="class")
    def off_pod(self):
        """A 1-host replicate-mode pod, in-process (the seq-stream
        contract is per-host, so H=1 exercises it fully)."""
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
        from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine
        from mpi_cuda_largescaleknn_tpu.serve.frontend import HostSliceServer

        points = _failover_points()
        eng = ResidentKnnEngine(points, K, mesh=get_mesh(2),
                                engine="tiled", bucket_size=64,
                                max_batch=32, min_batch=16, merge="device")
        eng.warmup()
        srv = HostSliceServer(("127.0.0.1", 0), eng, routing="off")
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        srv.ready = True
        yield f"http://127.0.0.1:{srv.server_address[1]}", srv, eng
        srv.close()

    def test_break_503_then_clean_stream_reset(self, off_pod,
                                               reference_engine):
        from tests.oracle import random_points

        url, host_srv, _eng = off_pod
        host_srv.faults.clear()
        fe, base = _build_fe([url], on_host_loss="fail")
        try:
            q = random_points(8, seed=76)
            before = _post_knn(base, q)
            # one injected host failure breaks the collective stream
            host_srv.faults.set_specs("error:path=/shard_knn,n=1")
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post_knn(base, q)
            assert ei.value.code == 503  # drain-then-fail, not a 500
            assert ei.value.headers.get("Retry-After") is not None
            assert fe.fanout.broken is not None
            assert fe.fanout.endpoints[0].health.state == "drained"
            # while broken, requests fail FAST with 503 (no fan-out)
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post_knn(base, q)
            assert ei.value.code == 503
            # the injector's budget (n=1) is exhausted = the pod restarted
            # healthy; the monitor validates fingerprint + seq consensus
            # and resets the stream
            fe.monitor.check_once(now=1e9)
            assert fe.fanout.broken is None
            assert fe.monitor.stream_resets == 1
            assert fe.fanout.endpoints[0].health.state == "healthy"
            after = _post_knn(base, q)
            assert after["dists"] == before["dists"]
            assert after["neighbors"] == before["neighbors"]
            want_d, want_n = reference_engine.query(q)
            np.testing.assert_array_equal(
                np.asarray(after["dists"], np.float32), want_d)
            np.testing.assert_array_equal(
                np.asarray(after["neighbors"], np.int32), want_n)
        finally:
            fe.close()

    def test_seq_timeout_maps_to_503_retry_after(self, off_pod):
        url, host_srv, _eng = off_pod
        host_srv.faults.clear()
        # skip ahead of the stream: seq 10**6 can never be next — the
        # knobbed-down timeout turns the wait into a fast 503
        old = host_srv.seq_timeout_s
        host_srv.seq_timeout_s = 0.05
        try:
            body = np.zeros((1, 3), np.float32).tobytes()
            req = urllib.request.Request(
                url + "/shard_knn?seq=1000000", data=body,
                headers={"Content-Type": "application/octet-stream"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=30)
            assert ei.value.code == 503
            assert ei.value.headers.get("Retry-After") is not None
            assert "stream" in json.loads(ei.value.read())["error"]
        finally:
            host_srv.seq_timeout_s = old

    def test_seq_timeout_constructor_validation(self):
        from mpi_cuda_largescaleknn_tpu.serve.frontend import HostSliceServer

        class _Eng:  # the knob validates before touching the engine
            pass

        with pytest.raises(ValueError, match="seq_timeout_s"):
            HostSliceServer(("127.0.0.1", 0), _Eng(), routing="off",
                            seq_timeout_s=0.0)


class TestProbeErrorsSurfaced:
    def test_probe_and_scrape_failures_land_in_stats(self):
        from mpi_cuda_largescaleknn_tpu.serve.frontend import PodFanout

        # an address nothing listens on: both probes must fail LOUDLY into
        # the per-host accounting instead of being swallowed
        fan = PodFanout(["http://127.0.0.1:9"], k=2, max_batch=8)
        try:
            health = fan.probe_health(timeout_s=0.2)
            assert health["http://127.0.0.1:9"]["ok"] is False
            stats = fan.scrape_host_stats(timeout_s=0.2)
            assert "error" in stats["http://127.0.0.1:9"]
            per = fan.stats()["per_host"]["http://127.0.0.1:9"]
            assert per["probe_errors"] == 1
            assert per["scrape_errors"] == 1
            assert "failed" in per["last_error"]
        finally:
            fan.close()


class TestLoadgenAvailability:
    def test_report_carries_status_breakdown_and_degraded_rate(
            self, routed_pod, clean_faults):
        import tools.loadgen as loadgen

        urls, _points, servers = routed_pod
        fe, base = _build_fe(urls, on_host_loss="degrade")
        try:
            servers[1].faults.set_specs("drop:")
            rep = loadgen.run_load(base, duration_s=1.0, concurrency=2,
                                   batch=4, timeout_s=30, seed=3)
            assert rep["requests"] > 0
            assert set(rep["status_counts"]) >= {"200"}
            assert rep["availability"] is not None
            assert 0.0 <= rep["availability"] <= 1.0
            # uniform [0,1)^3 queries all touch cluster B's half of the
            # box, so with host 1 down most answers are degraded 200s
            assert rep["degraded"] > 0 and rep["degraded_rate"] > 0
            assert rep["ok"] == rep["status_counts"].get("200", 0)
        finally:
            fe.close()
