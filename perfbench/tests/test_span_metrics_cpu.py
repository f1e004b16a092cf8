"""The span metrics end to end on the CPU, at tiny sizes: a traced run of
each kind reports them, and on a program without spans they read nothing
and raise nothing."""

from __future__ import annotations

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from perfbench.harness import load_reader  # noqa: E402
from perfbench.tests.conftest import ROOT  # noqa: E402
from perfbench.tests.test_run_cpu import run_cell  # noqa: E402

SERVE_SPAN_METRICS = ("serve.queue_wait_ms", "serve.handler_ms",
                      "serve.stage_ms")


def test_batch_cell_traced_reports_ring_launch(tiny_root):
    rc, res, _, err = run_cell(tiny_root, "batch_tiny", trace=1)
    assert rc == 0, err
    assert res["correct"] is True
    assert res["metrics"]["ring.launch_ms"]["value"] > 0
    assert res["metrics"]["ring.launch_ms"]["unit"] == "ms"


def test_serve_cell_traced_reports_span_metrics(tiny_root):
    rc, res, _, err = run_cell(tiny_root, "serve_tiny", trace=1, seconds=2.0)
    assert rc == 0, err
    assert res["correct"] is True
    for name in SERVE_SPAN_METRICS:
        assert res["metrics"][name]["value"] >= 0, name
    assert res["metrics"]["serve.stage_ms"]["value"] > 0


def test_untraced_runs_report_no_span_metrics(tiny_root):
    rc, res, _, err = run_cell(tiny_root, "batch_tiny")
    assert rc == 0, err
    assert "ring.launch_ms" not in res["metrics"]


def test_span_readers_silent_on_a_program_without_spans(monkeypatch):
    from mpi_cuda_largescaleknn_tpu.obs import timers

    monkeypatch.delattr(timers, "traced_spans_snapshot")
    for name in ("ring.launch_ms",) + SERVE_SPAN_METRICS:
        assert load_reader(ROOT, name)(None) is None, name
