#!/usr/bin/env python3
"""Find a serving cell's knee once: one server, several open-loop rates.

    python perfbench/sweep.py --workload serve_k100_clustered --seed 7 \
        --seconds 10 --rates 50,100,200,400

Builds the cell's server once, then runs the cell's traffic mix at each
rate for ``--seconds`` (after a short warm-up each) and prints one JSON
line per rate: p50/p99 from due time, failures, generator lag, and the
backlog trend (median latency of the last quarter of requests over the
first quarter). The knee is the highest rate whose trend stays near 1 and
that fails nothing; the cell's traffic file then fixes about 0.8 x knee.
Answers are not checked here: the cell's own runs do that.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from perfbench.run import enable_compile_cache  # noqa: E402


def trend(res: dict) -> float:
    ok = (res["status"] == 200) & np.isfinite(res["done"])
    lat = (res["done"] - res["due"])[ok]
    q = max(1, len(lat) // 4)
    return float(np.median(lat[-q:]) / np.median(lat[:q]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    args = ap.parse_args(argv)
    enable_compile_cache()
    import jax

    from perfbench import harness
    from perfbench.systems.serve import Cell

    spec = harness.find_cell(os.path.join(os.path.dirname(HERE),
                                          "BENCHMARK.json"), args.workload)
    harness.device_info(jax, spec.chips, require_tpu=True)
    cell = Cell(spec, args.seed, args.seconds)
    tracer = harness.Tracer(jax, False)
    try:
        cell.start_server()
        for rate in (float(r) for r in args.rates.split(",")):
            traffic = dict(spec.traffic, rate_rps=rate, warm_seconds=1.0)
            cell.start_generator(traffic, args.seconds)
            cell.window(tracer)
            s = cell.summary
            print(json.dumps({
                "rate_rps": rate,
                "queries_per_s_offered": rate * int(traffic["batch"]),
                **{k: s[k] for k in ("attempted", "failed", "serve_p50_ms",
                                     "serve_p99_ms",
                                     "serve_qps",
                                     "lag_p99_ms")},
                "backlog_trend": trend(cell.result),
                "batch_rows": cell.window_counters["result_rows"]
                / max(1, cell.window_counters["engine_batches"]),
                "engine_batch_ms": 1e3 * cell.window_counters[
                    "engine_batch_s"] / max(
                        1, cell.window_counters["engine_batches"])}),
                flush=True)
    finally:
        cell.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
