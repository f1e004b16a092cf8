"""The harness end to end on the CPU, at tiny sizes: a run that looks for
a chip fails without a result; with the look skipped, both kinds of cell
run, report their metrics and come out correct."""

from __future__ import annotations

import contextlib
import io
import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from perfbench import run  # noqa: E402
from perfbench.tests.conftest import add_cell_as_files  # noqa: E402


def run_cell(root, workload, trace=0, seconds=1.0, seed=2**31 + 7,
             require_tpu=False):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      bench=os.path.join(root, "BENCHMARK.json"),
                      require_tpu=require_tpu)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if rc == 0 else None), \
        out.getvalue(), err.getvalue()


def test_cpu_run_fails_without_result(tiny_root):
    rc, res, out, err = run_cell(tiny_root, "batch_tiny", require_tpu=True)
    assert rc != 0
    assert res is None
    assert '"metrics"' not in out
    assert "no TPU" in err


def test_batch_cell_correct_with_end_to_end_metrics(tiny_root):
    rc, res, _, err = run_cell(tiny_root, "batch_tiny")
    assert rc == 0, err
    assert res["correct"] is True
    assert set(res["metrics"]) == {"selfjoin_points_per_s", "setup_s"}
    assert res["metrics"]["selfjoin_points_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check kth_ulp_max ")
    compiles = json.loads(next(ln for ln in err.splitlines()
                               if ln.startswith("COMPILES "))[9:])
    assert compiles["window"]["compiles"] == 0


def test_batch_cell_traced_reports_program_metrics(tiny_root):
    rc, res, _, err = run_cell(tiny_root, "batch_tiny", trace=1)
    assert rc == 0, err
    assert res["correct"] is True
    assert {"selfjoin.host_ms", "ring.pairs_per_point"} <= set(res["metrics"])
    # no device plane in a CPU trace: device metrics stay silent
    assert "device_idle.batch" not in res["metrics"]
    assert "knn_tiled_roofline.batch" not in res["metrics"]


def test_serve_cell_correct_with_end_to_end_metrics(tiny_root):
    rc, res, _, err = run_cell(tiny_root, "serve_tiny", seconds=2.0)
    assert rc == 0, err
    assert res["correct"] is True
    assert set(res["metrics"]) == {"serve_p50_ms", "serve_qps",
                                   "setup_s"}
    assert res["attempted"] == 40 and res["failed"] == 0
    assert res["checks"]["unanswered"] == {"value": 0, "limit": 0}


def test_cell_added_as_files_runs_correct(tiny_root):
    """Clustered index, uniform on/off queries with repeats and revisits:
    a cell no committed file describes runs from its data files alone."""
    cell = add_cell_as_files(tiny_root)
    rc, res, _, err = run_cell(tiny_root, cell, seconds=2.0)
    assert rc == 0, err
    assert res["correct"] is True
    # 20/s on average over 2 s, sent in the on-halves of each 0.5 s
    assert res["attempted"] == 40 and res["failed"] == 0
