"""The ring's exchange readings: ``ring.exchange_mb`` reads the program's
traced ``ring.exchange_bytes`` count per chip and per traced job, and
``ring.exchange_ms`` the device time of the ring's collective-permute
operations per chip and per traced job; each reads nothing where there is
nothing to read."""

from __future__ import annotations

import os

import pytest

from mpi_cuda_largescaleknn_tpu.obs import timers
from mpi_cuda_largescaleknn_tpu.ops.partition import choose_buckets
from mpi_cuda_largescaleknn_tpu.parallel.ring import resolve_bucket_size
from perfbench import harness, tracereduce
from perfbench.tests.conftest import ROOT, write_json
from perfbench.tests.test_new_cells import (
    TINY_RING4,
    add_ring4_cell_as_files,
)
from perfbench.tests.test_run_cpu import run_cell
from perfbench.tests.test_trace_lines_devices import (
    ANNOTATION,
    HOST,
    KERNEL_EVENT,
    plane,
)

#: the v5e trace's events of one rotation's two directions: the whole
#: HLO instruction, as for every XLA op
START = ("%collective-permute-start.2 = (f32[2048,496,3]{2,1,0}, "
         "f32[2048,496,3]{2,1,0}) collective-permute-start("
         "f32[2048,496,3]{2,1,0} %get-tuple-element.7), "
         "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}")
DONE = ("%collective-permute-done.2 = f32[2048,496,3]{2,1,0} "
        "collective-permute-done((f32[2048,496,3]{2,1,0}, "
        "f32[2048,496,3]{2,1,0}) %collective-permute-start.2)")
#: an operation that takes the done's result: not the exchange
CONSUMER = ("%fusion.9 = f32[2048,496,3]{2,1,0} fusion("
            "f32[2048,496,3]{2,1,0} %collective-permute-done.2), "
            "kind=kLoop, calls=%fused_computation.9")


def reader(name):
    return harness.load_reader(ROOT, name)


def add_ring4_twin(root: str) -> str:
    """The tiny four-shard cell, reporting every metric that the real
    BENCHMARK.json lists for ``ring4_4m_k8``. Returns the cell's name."""
    cell = add_ring4_cell_as_files(root)
    real = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    ring4 = {m["name"] for m in real["end_to_end"] + real["per_layer"]
             if "ring4_4m_k8" in m.get("workloads", [])}
    path = os.path.join(root, "BENCHMARK.json")
    b = harness.load_json(path)
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"] in ring4 and cell not in m["workloads"]:
            m["workloads"].append(cell)
    write_json(path, b)
    return cell


def test_exchange_mb_reads_the_traced_count_per_chip_and_job(tiny_root,
                                                             monkeypatch):
    # the process-wide sum starts empty, as in a benchmark run's process
    monkeypatch.setattr(timers, "_traced_counts", {})
    cell = add_ring4_twin(tiny_root)
    rc, res, _, err = run_cell(tiny_root, cell, trace=1)
    assert rc == 0, err
    assert res["correct"] is True
    shards = TINY_RING4["shards"]
    b, s = choose_buckets(TINY_RING4["points"] // shards,
                          resolve_bucket_size(0, "tiled"))
    # the CPU's engine coarsens nothing: one box pair per bucket
    copy = b * s * (3 * 4 + 4) + 2 * b * 3 * 4
    rotations = shards // 2
    assert res["metrics"]["ring.exchange_mb"] == {
        "value": pytest.approx(2 * copy * rotations / 1e6), "unit": "MB"}
    # every batch reading the real cell lists that a CPU trace can give
    assert {"ring.pairs_per_point", "selfjoin.host_ms",
            "ring.launch_ms"} <= set(res["metrics"])
    # no device plane in a CPU trace
    assert "ring.exchange_ms" not in res["metrics"]


def test_exchange_mb_is_silent_without_the_counter(monkeypatch):
    rec = harness.RunRecord(None, {"traced_jobs": 2, "shards": 4}, None, {})
    monkeypatch.setattr(timers, "_traced_counts", {})
    assert reader("ring.exchange_mb")(rec) is None
    # a program with no traced counters at all (the import fails)
    monkeypatch.delattr(timers, "traced_counters_snapshot")
    assert reader("ring.exchange_mb")(rec) is None


def four_device_exchange(start_ns, done_ns):
    """One traced window [0, 1000) of four devices, each running the
    kernel for 300 ns, then a rotation's start and done, then a fusion
    that reads the done's result."""
    planes = [plane(HOST, {"python3": [(ANNOTATION, 0, 1000),
                                       ("ring.wait", 0, 1000)]})]
    for i in range(4):
        planes.append(plane(f"/device:TPU:{i}", {"XLA Ops": [
            (KERNEL_EVENT, 0, 300),
            (START, 300, start_ns[i]),
            (DONE, 400, done_ns[i]),
            (CONSUMER, 600, 100)]}))
    return tracereduce.reduce(tracereduce.flatten(planes), ANNOTATION)


@pytest.mark.parametrize("jobs", [1, 2])
def test_exchange_ms_reads_collectives_per_chip_and_job(jobs):
    trace = four_device_exchange([10, 20, 30, 40], [100, 150, 50, 100])
    rec = harness.RunRecord(None, {"traced_jobs": jobs}, trace, {})
    got = reader("ring.exchange_ms")(rec)
    assert got == pytest.approx((100 + 400) * 1e-9 / 4 / jobs * 1e3)


def test_exchange_ms_is_silent_on_a_cpu_trace():
    host_only = tracereduce.reduce(tracereduce.flatten([
        plane(HOST, {"python3": [(ANNOTATION, 0, 1000)]})]), ANNOTATION)
    assert host_only["devices"] == 0
    rec = harness.RunRecord(None, {"traced_jobs": 2}, host_only, {})
    assert reader("ring.exchange_ms")(rec) is None
    rec = harness.RunRecord(None, {"traced_jobs": 2}, None, {})
    assert reader("ring.exchange_ms")(rec) is None
