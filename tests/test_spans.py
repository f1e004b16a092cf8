"""Spans (obs/timers.py): aggregates, profiler host events, and the spans
placed on the self-join and serving paths."""

import glob
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np
import pytest

from mpi_cuda_largescaleknn_tpu.obs.timers import (
    PhaseTimers,
    current_span_tags,
    span_tags,
    traced_spans_snapshot,
)
from tests.oracle import random_points

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _host_events(trace_dir):
    """name, duration and metadata of every host event of the newest
    profiler trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append({"name": ev.name, "line": line.name,
                            "dur_ns": ev.duration_ns,
                            "meta": dict(ev.stats)})
    return out


def _traced(fn):
    """Run ``fn`` under a host-only profiler trace; its host events."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        return _host_events(d)


def _delta(before, after, name):
    c0, s0 = before.get(name, (0, 0.0))
    c1, s1 = after.get(name, (0, 0.0))
    return c1 - c0, s1 - s0


def _await_spans(timers, before, name, n, timeout_s=10.0):
    """``timers.spans_snapshot()`` once ``n`` more ``name`` spans than in
    ``before`` have closed, or at ``timeout_s``. ``serve.http`` closes after
    the response is written, so a client can hold its answer before the
    handler thread has closed the span."""
    deadline = time.monotonic() + timeout_s
    snap = timers.spans_snapshot()
    while _delta(before, snap, name)[0] < n and time.monotonic() < deadline:
        time.sleep(0.01)
        snap = timers.spans_snapshot()
    return snap


class TestSpanAggregates:
    def test_nested_spans_add_count_and_seconds(self):
        t = PhaseTimers()
        with t.phase("outer"):
            with t.phase("inner"):
                time.sleep(0.01)
            with t.phase("inner"):
                pass
        snap = t.spans_snapshot()
        assert snap["inner"][0] == 2 and snap["outer"][0] == 1
        assert snap["outer"][1] >= snap["inner"][1] >= 0.01
        rep = t.report()
        assert rep["inner"] == {"seconds": round(snap["inner"][1], 6),
                                "calls": 2}

    def test_record_adds_to_the_same_aggregate(self):
        t = PhaseTimers()
        with t.phase("wait"):
            pass
        t.record("wait", 0.25)
        t.record("queue", 0.5)
        snap = t.spans_snapshot()
        assert snap["wait"][0] == 2 and snap["wait"][1] >= 0.25
        assert snap["queue"] == (1, 0.5)
        assert t.phases["queue"].calls == 1
        assert t.phases["queue"].seconds == 0.5

    def test_phase_key_stays_when_the_trace_name_differs(self):
        t = PhaseTimers()
        with t.phase("ring", span="selfjoin.ring"):
            pass
        assert set(t.phases) == {"ring"}
        assert t.report()["ring"]["calls"] == 1

    def test_span_failing_block_still_counts(self):
        t = PhaseTimers()
        with pytest.raises(ValueError):
            with t.phase("boom"):
                raise ValueError("x")
        assert t.spans_snapshot()["boom"][0] == 1

    def test_concurrent_spans_lose_no_update(self):
        t = PhaseTimers()

        def work():
            for _ in range(500):
                with t.phase("hot"):
                    pass
                t.record("hot", 0.0)

        workers = 4 * (os.cpu_count() or 1)
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=work) for _ in range(workers)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=60)
        finally:
            sys.setswitchinterval(old)
        assert not any(th.is_alive() for th in threads)
        assert t.spans_snapshot()["hot"][0] == workers * 500 * 2

    def test_span_tags_nest_restore_and_stay_on_their_thread(self):
        seen = {}
        assert current_span_tags() == {}
        with span_tags(batch=3):
            with span_tags(req=9):
                assert current_span_tags() == {"batch": 3, "req": 9}
                th = threading.Thread(
                    target=lambda: seen.update(current_span_tags()))
                th.start()
                th.join()
            assert current_span_tags() == {"batch": 3}
        assert current_span_tags() == {}
        assert seen == {}

    def test_timers_import_without_jax(self):
        """The JAX-free load generator imports obs/timers.py: spans work
        there, and nothing pulls JAX in."""
        code = (
            "import sys\n"
            "class Block:\n"
            "    def find_spec(self, name, path=None, target=None):\n"
            "        if name == 'jax' or name.startswith('jax.'):\n"
            "            raise ImportError('jax blocked')\n"
            "sys.meta_path.insert(0, Block())\n"
            "from mpi_cuda_largescaleknn_tpu.obs.timers import (\n"
            "    PhaseTimers, traced_spans_snapshot)\n"
            "t = PhaseTimers()\n"
            "with t.phase('a', req=1):\n"
            "    pass\n"
            "t.record('b', 0.5)\n"
            "assert t.spans_snapshot()['a'][0] == 1\n"
            "assert traced_spans_snapshot() == {}\n"
            "assert 'jax' not in sys.modules\n"
            "print('ok')\n")
        out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "ok"


class TestProfilerEvents:
    def test_span_is_a_host_event_with_metadata_while_traced(self):
        t = PhaseTimers()
        before = traced_spans_snapshot()

        def work():
            with span_tags(batch=5), t.phase("key", span="layer.key",
                                             req=11):
                time.sleep(0.002)

        events = _traced(work)
        ev = [e for e in events if e["name"] == "layer.key"]
        assert len(ev) == 1
        assert ev[0]["meta"] == {"batch": 5, "req": 11}
        assert ev[0]["dur_ns"] >= 2e6
        # the traced aggregate is keyed by the name the trace shows
        assert _delta(before, traced_spans_snapshot(), "layer.key")[0] == 1
        # outside a trace: the aggregate moves, the traced one does not
        with t.phase("key", span="layer.key"):
            pass
        assert t.spans_snapshot()["key"][0] == 2
        assert _delta(before, traced_spans_snapshot(), "layer.key")[0] == 1

    def test_selfjoin_trace_holds_ring_spans(self):
        from mpi_cuda_largescaleknn_tpu.core.config import KnnConfig
        from mpi_cuda_largescaleknn_tpu.models.unordered import UnorderedKNN
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh

        pts = random_points(600, seed=3)
        model = UnorderedKNN(KnnConfig(k=4, engine="tiled", bucket_size=32,
                                       num_shards=2), mesh=get_mesh(2))
        model.run(pts)  # compile outside the trace
        before = traced_spans_snapshot()
        events = _traced(lambda: model.run(pts))
        names = {e["name"] for e in events}
        assert {"selfjoin.shard_and_pad", "selfjoin.ring",
                "selfjoin.extract", "ring.stage", "ring.partition",
                "ring.launch", "ring.wait"} <= names
        # the ring's spans nest inside the model's ring phase
        ring = next(e for e in events if e["name"] == "selfjoin.ring")
        launch = next(e for e in events if e["name"] == "ring.launch")
        assert launch["dur_ns"] <= ring["dur_ns"]
        after = traced_spans_snapshot()
        assert _delta(before, after, "ring.launch")[0] == 1
        assert _delta(before, after, "selfjoin.ring")[0] == 1
        # the phases keep their keys; the ring's spans join them
        snap = model.timers.spans_snapshot()
        assert snap["ring"][0] == snap["ring.launch"][0] == 2
        assert snap["shard_and_pad"][0] == snap["extract"][0] == 2
        assert snap["ring.wait"][0] == 2


@pytest.fixture(scope="module")
def span_server():
    from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
    from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine
    from mpi_cuda_largescaleknn_tpu.serve.server import build_server

    eng = ResidentKnnEngine(random_points(800, seed=5), 4, mesh=get_mesh(2),
                            engine="tiled", bucket_size=32, max_batch=16,
                            min_batch=16)
    eng.warmup()
    srv = build_server(eng, port=0, max_delay_s=0.002, pipeline_depth=2)
    srv.ready = True
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv
    srv.close()


def _post(srv, queries):
    url = f"http://127.0.0.1:{srv.server_address[1]}/knn"
    req = urllib.request.Request(
        url, data=json.dumps({"queries": queries.tolist()}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.status, json.loads(r.read())


class TestServingSpans:
    def test_request_spans_join_the_engine_batch(self, span_server):
        timers = span_server.engine.timers
        before = timers.spans_snapshot()
        answers = []

        def load():
            for seed in range(3):
                answers.append(_post(span_server,
                                     random_points(4, seed=100 + seed)))
            _await_spans(timers, before, "serve.http", 3)

        events = _traced(load)
        after = timers.spans_snapshot()
        assert [s for s, _ in answers] == [200] * 3
        http = [e for e in events if e["name"] == "serve.http"]
        assert len(http) == 3 and all("req" in e["meta"] for e in http)
        # every request waited in the queue once
        assert _delta(before, after, "serve.queue")[0] == 3
        by_name = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e)
        for name in ("serve.engine.stage", "serve.engine.launch",
                     "serve.engine.wait", "serve.demux"):
            assert by_name[name] and all("batch" in e["meta"]
                                         for e in by_name[name]), name
        assert by_name["serve.http.decode"] and by_name["serve.http.encode"]
        assert by_name["serve.batcher.wait"]
        # join: the demux that answered a request names its batch, and the
        # engine staged and waited for that batch
        req = http[0]["meta"]["req"]
        demux = next(e for e in by_name["serve.demux"]
                     if str(req) in str(e["meta"]["reqs"]).split(","))
        batch = demux["meta"]["batch"]
        for name in ("serve.engine.stage", "serve.engine.launch",
                     "serve.engine.wait"):
            assert batch in {e["meta"]["batch"] for e in by_name[name]}
        sub = next(e for e in by_name["serve.submit"]
                   if e["meta"].get("req") == req)
        assert sub["dur_ns"] <= http[0]["dur_ns"]

    def test_handler_self_time_is_not_negative(self, span_server):
        timers = span_server.engine.timers
        before = timers.spans_snapshot()
        for seed in range(4):
            assert _post(span_server, random_points(3, seed=200 + seed))[0] \
                == 200
        after = _await_spans(timers, before, "serve.http", 4)
        n_http, s_http = _delta(before, after, "serve.http")
        n_sub, s_sub = _delta(before, after, "serve.submit")
        assert n_http == n_sub == 4
        assert s_http - s_sub >= 0
        # the spans show up in /stats beside the other phases
        stats = span_server.engine.stats()["timers"]
        assert stats["serve.http"]["calls"] >= 4
        assert stats["serve.queue"]["calls"] >= 4


class _SlowFn:
    """A dispatch/complete test double whose device takes 20 ms a batch."""

    dim = 3

    def dispatch(self, queries):
        return queries, time.perf_counter() + 0.02

    def complete(self, handle):
        q, ready = handle
        time.sleep(max(0.0, ready - time.perf_counter()))
        return (np.linalg.norm(q, axis=1).astype(np.float32),
                np.zeros((len(q), 1), np.int32))


def test_batcher_records_queue_wait_stall_and_demux():
    from mpi_cuda_largescaleknn_tpu.serve.batcher import DynamicBatcher

    timers = PhaseTimers()
    b = DynamicBatcher(_SlowFn(), max_batch=4, max_delay_s=0.001,
                       timers=timers, pipeline_depth=2, min_batch=4)
    try:
        threads = [threading.Thread(target=b.submit,
                                    args=(np.ones((4, 3), np.float32),),
                                    kwargs={"req": i}) for i in range(6)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    finally:
        b.shutdown()
    snap = timers.spans_snapshot()
    batches = b.stats()["batches"]
    assert snap["serve.queue"][0] == 6
    assert snap["serve.demux"][0] == batches
    # six full batches through a two-deep pipe: the dispatcher stalled
    assert snap["serve.batcher.stall"][0] == b.stats()["dispatch_stalls"] > 0
    assert snap["serve.batcher.wait"][0] >= 1
