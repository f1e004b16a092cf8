"""A run with the timed path broken underneath must come out not correct,
once for each fault these cells can have: an answer altered where it is
produced, and half of a batch left out (its rows answered with the other
half's results). The cells run on one chip, so there is no exchange
between chips to leave out. The control -- the reference in bfloat16 in
the program's place -- must fail the same comparison."""

from __future__ import annotations

import numpy as np
import pytest

from perfbench import harness
from perfbench.tests.test_run_cpu import run_cell


def alter(d: np.ndarray) -> np.ndarray:
    d = np.array(d, copy=True)
    d[::7] = d[::7] * np.float32(1 + 2**-18)      # ~32 ulp off
    return d


def halve(d: np.ndarray) -> np.ndarray:
    h = max(1, len(d) // 2)
    return np.concatenate([d[:h], d[:h]])[:len(d)]


@pytest.fixture
def selfjoin_fault(monkeypatch):
    from mpi_cuda_largescaleknn_tpu.models.unordered import UnorderedKNN

    orig = UnorderedKNN.run

    def use(kind):
        def run(self, points, return_neighbors=False):
            if kind == "half":
                # the job sees half of its points; the rest get copies
                out = orig(self, points[:len(points) // 2])
                return halve(np.concatenate([out, out]))[:len(points)]
            return alter(orig(self, points))
        monkeypatch.setattr(UnorderedKNN, "run", run)
    return use


@pytest.fixture
def serve_fault(monkeypatch):
    from mpi_cuda_largescaleknn_tpu.serve.engine import ResidentKnnEngine

    orig = ResidentKnnEngine.complete

    def use(kind):
        def complete(self, batch):
            d, n = orig(self, batch)
            return (halve(d) if kind == "half" else alter(d)), n
        monkeypatch.setattr(ResidentKnnEngine, "complete", complete)
    return use


@pytest.mark.parametrize("kind", ["altered", "half"])
def test_selfjoin_fault_is_not_correct(tiny_root, selfjoin_fault, kind):
    selfjoin_fault(kind)
    rc, res, _, err = run_cell(tiny_root, "batch_tiny")
    assert rc == 0, err
    assert res["correct"] is False
    c = res["checks"]["kth_ulp_max"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("kind", ["altered", "half"])
def test_serve_fault_is_not_correct(tiny_root, serve_fault, kind):
    serve_fault(kind)
    rc, res, _, err = run_cell(tiny_root, "serve_tiny", seconds=1.0)
    assert rc == 0, err
    assert res["correct"] is False


@pytest.mark.parametrize("workload", ["batch_tiny", "serve_tiny"])
def test_control_fails_where_the_program_passes(tiny_root, workload):
    import jax

    spec = harness.find_cell(tiny_root + "/BENCHMARK.json", workload)
    cell = harness.load_system(spec).Cell(spec, 2**31 + 99, 1.0)
    try:
        cell.setup()
        cell.window(harness.Tracer(jax, False))
    finally:
        cell.release()
    sound = cell.check()["kth_ulp_max"]
    control = cell.control_check()["kth_ulp_max"]
    assert sound["value"] <= sound["limit"]
    assert control["value"] > 100 * control["limit"]
