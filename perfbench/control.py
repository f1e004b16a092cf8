#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's on many seeds, and the
control's on the same answers' queries, in one process.

    python perfbench/control.py --workload batch_1m_k8 --seconds 10 \
        --seeds 11,12,13

For each seed the cell runs as a benchmark run does (set-up, window, the
program's state freed), then two readings are taken over the same compared
queries: the program's answers against the plain reference (the sound
reading, which sets the lower end of the limit), and the control -- the
reference computed in bfloat16, the precision step below the float32 the
configuration states, put in the program's place -- against the same
reference (which sets the upper end). One JSON line per seed. The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from perfbench.run import enable_compile_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    enable_compile_cache()
    import jax

    from perfbench import harness

    spec = harness.find_cell(os.path.join(os.path.dirname(HERE),
                                          "BENCHMARK.json"), args.workload)
    harness.device_info(jax, spec.chips, require_tpu=True)
    system = harness.load_system(spec)
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = system.Cell(spec, seed, args.seconds)
        try:
            cell.setup()
            cell.window(harness.Tracer(jax, False))
        finally:
            cell.release()
        print(json.dumps({"seed": seed, "program": cell.check(),
                          "control_bf16": cell.control_check()}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
