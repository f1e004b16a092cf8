#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last line of stdout.

    python perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's configuration, traffic mix and metrics are found by name from
``BENCHMARK.json`` at the repository root (``perfbench/harness.py``). With
``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, from a profiler trace of a short slice
of the window. Either way the answers of the window are compared with the
plain reference afterwards, and each compared number is printed beside its
limit as the last lines of stderr and under ``checks`` in the line.

It runs on a TPU or fails: with no accelerator, or fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache() -> None:
    """The program's persistent compile cache, before JAX is imported.
    Every program is kept however fast it compiled, so that a run's
    set-up loads every program the window drives from the cache: the
    self-join's first call per configuration builds and loads its ring
    program, and every later call only enqueues it."""
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    from mpi_cuda_largescaleknn_tpu.utils.compile_cache import (
        enable_persistent_cache,
    )

    enable_persistent_cache()


def main(argv=None, *, bench: str | None = None,
         require_tpu: bool = True) -> int:
    args = parse_args(argv)
    enable_compile_cache()
    from perfbench import harness

    spec = harness.find_cell(bench or os.path.join(ROOT, "BENCHMARK.json"),
                             args.workload)
    try:
        out = harness.run_cell(spec, args.seed, args.seconds,
                               bool(args.trace), require_tpu=require_tpu,
                               t_start=T_START)
    except harness.NoDevice as e:
        sys.stderr.write(f"perfbench: {e}\n")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
