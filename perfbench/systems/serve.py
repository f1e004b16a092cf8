"""Serving driver: the resident index behind ``serve/server.py``, driven over
HTTP by the open-loop load generator in a child process.

Set-up builds ``ResidentKnnEngine`` on the seeded points with the
configuration's server flags (those of ``cli/serve_main.py``), warms every
shape bucket, starts the HTTP server in a thread of this process, and
starts ``perfbench/loadgen.py``, which runs a short warm-up load before the
window. The window is the generator's schedule; every request due in it is
waited for, and every answered query is compared with the plain reference
afterwards.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from perfbench import loadgen
from perfbench.systems.common import compare, make_points

LOADGEN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "loadgen.py")

#: the traffic kind this driver reads (its parameters: ``loadgen.PARAMS``)
TRAFFIC_KIND = "open_loop"


def _counters(engine, batcher) -> dict:
    c = engine.timers.counters_snapshot()
    h = engine.timers.histograms.get("engine_batch_seconds")
    return {"result_rows": c.get("result_rows", 0),
            "tiles_executed": c.get("tiles_executed", 0),
            "engine_batches": h.count if h is not None else 0,
            "engine_batch_s": h.sum_seconds if h is not None else 0.0,
            "batcher_batches": batcher.stats()["batches"],
            "t": time.perf_counter()}


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


class Cell:
    def __init__(self, spec, seed: int, seconds: float):
        self.spec = spec
        self.seed = int(seed)
        self.seconds = float(seconds)
        cfg = spec.config
        self.n = int(cfg["points"])
        self.k = int(cfg["k"])
        self.limit = int(cfg["guarantee"]["kth_ulp_max"])
        self.traffic = loadgen.mix(spec.traffic)
        self.tmp = tempfile.mkdtemp(prefix="perfbench_serve_")
        self.child = None
        self.server = None
        self.thread = None
        self.result = None

    # -------------------------------------------------------------- set-up

    def setup(self) -> None:
        self.start_server()
        self.start_generator(self.traffic, self.seconds)

    def start_server(self) -> None:
        from mpi_cuda_largescaleknn_tpu.parallel.mesh import get_mesh
        from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine
        from mpi_cuda_largescaleknn_tpu.serve.server import (
            build_server,
            serve_forever,
        )

        cfg = self.spec.config
        srv = cfg["server"]
        self.points = make_points(cfg.get("data", {}), self.n, self.seed)
        engine = ResidentKnnEngine(
            self.points, self.k, mesh=get_mesh(int(cfg["shards"])),
            engine=cfg["engine"], merge=cfg["merge"],
            bucket_size=int(cfg["geometry"]["bucket_size"]),
            min_batch=int(cfg["min_batch"]), max_batch=int(cfg["max_batch"]))
        self.server = build_server(
            engine, port=0, max_delay_s=float(srv["max_delay_ms"]) / 1e3,
            pipeline_depth=int(srv["pipeline_depth"]),
            max_queue_rows=int(srv["max_queue_rows"]),
            default_timeout_s=float(srv["timeout_ms"]) / 1e3,
            qcache_rows=int(srv["qcache_rows"]),
            qcache_seed_rows=int(srv["qcache_seed_rows"]))
        self.failure: list = []

        def serve():
            try:
                serve_forever(self.server, warmup=True)
            except BaseException as e:  # noqa: BLE001 - reported by setup
                self.failure.append(e)

        self.thread = threading.Thread(target=serve, name="perfbench-server")
        self.thread.start()
        while not self.server.ready:
            if self.failure or not self.thread.is_alive():
                raise RuntimeError(f"server failed to warm up: "
                                   f"{self.failure}")
            time.sleep(0.05)
        self.engine = engine

    def start_generator(self, traffic: dict, seconds: float) -> None:
        """Start the load generator and let it finish its warm-up load,
        which is set-up too: it ends before the window's GO."""
        self.out = os.path.join(self.tmp, "window.npz")
        self.child = subprocess.Popen(
            [sys.executable, LOADGEN], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True)
        self.child.stdin.write(json.dumps(dict(
            traffic, host="127.0.0.1", port=self.server.server_address[1],
            seed=self.seed, seconds=seconds, out=self.out)) + "\n")
        self.child.stdin.flush()
        line = self.child.stdout.readline().strip()
        if line != "READY":
            raise RuntimeError(f"load generator did not get ready: {line!r}")

    # -------------------------------------------------------------- window

    def window(self, tracer) -> None:
        batcher = self.server.batcher
        c0 = _counters(self.engine, batcher)
        self.child.stdin.write("GO\n")
        self.child.stdin.flush()
        self.traced = None
        if tracer.enabled:
            tr = self.traffic
            time.sleep(float(tr["trace_delay_s"]))
            with tracer.slice():
                t0 = _counters(self.engine, batcher)
                time.sleep(float(tr["trace_seconds"]))
                t1 = _counters(self.engine, batcher)
            self.traced = _delta(t0, t1)
        line = self.child.stdout.readline().strip()
        c1 = _counters(self.engine, batcher)
        if not line.startswith("DONE "):
            raise RuntimeError(f"load generator failed: {line!r}")
        self._reap_child()
        self.summary = json.loads(line[len("DONE "):])
        self.window_counters = _delta(c0, c1)
        self.result = dict(np.load(self.out))

    # ------------------------------------------------------------ readings

    def end_to_end(self) -> dict:
        s = self.summary
        return {"serve_p50_ms": s["serve_p50_ms"],
                "serve_qps": s["serve_qps"]}

    def layer_data(self) -> dict:
        from perfbench.opcount import choose_buckets

        return {
            "lag_p99_ms": self.summary["lag_p99_ms"],
            "window": self.window_counters,
            "traced": self.traced,
            "k": self.k,
            "points": self.n,
            "shards": int(self.spec.config["shards"]),
            "point_tile": choose_buckets(
                self.n // int(self.spec.config["shards"]),
                int(self.spec.config["geometry"]["bucket_size"]))[1],
            "query_rows_per_bucket": min(
                q // b for q, b in self.engine.query_buckets.items()),
        }

    def attempted_failed(self) -> tuple[int, int]:
        return self.summary["attempted"], self.summary["failed"]

    def release(self) -> None:
        self.stop()
        self.engine = None
        shutil.rmtree(self.tmp, ignore_errors=True)

    def _reap_child(self) -> None:
        if self.child is None:
            return
        try:
            self.child.wait(timeout=60)
        except subprocess.TimeoutExpired:
            self.child.kill()
            self.child.wait(timeout=60)
        for f in (self.child.stdin, self.child.stdout):
            f.close()
        self.child = None

    def stop(self) -> None:
        if self.child is not None and self.child.poll() is None:
            self.child.kill()
        self._reap_child()
        if self.server is not None:
            self.server.close()
            self.thread.join(timeout=60)
            self.server = None

    def _compared(self):
        r = self.result
        ok = r["status"] == 200
        bad = int((ok & (r["nvals"] != r["dists"].shape[1])).sum())
        good = ok & (r["nvals"] == r["dists"].shape[1])
        return r["queries"][good].reshape(-1, 3), \
            r["dists"][good].reshape(-1), bad

    def check(self) -> dict:
        queries, got, bad = self._compared()
        out = compare(self.points, self.k, queries, got, bad, self.limit)
        out["unanswered"] = {"value": self.summary["failed"], "limit": 0}
        return out

    def control_check(self) -> dict:
        queries, got, _ = self._compared()
        return compare(self.points, self.k, queries, got, 0, self.limit,
                       precision="bf16")
