"""Room for cells the CPU does not run: a real cell added to
BENCHMARK.json leaves the tiny fixture building, and a four-chip ring cell
added by files alone runs correct on the CPU's virtual devices."""

from __future__ import annotations

import json
import os

import pytest

from perfbench import harness
from perfbench.tests.conftest import (
    ROOT,
    TINY_SELFJOIN,
    tiny_bench,
    write_json,
)
from perfbench.tests.test_run_cpu import run_cell

#: a self-join over four shards, one per chip: the ring exchanges shards
TINY_RING4 = dict(TINY_SELFJOIN, name="tiny_ring4", points=8000, shards=4)


def test_real_cell_without_tiny_twin_leaves_the_fixture_building(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    real["workloads"].append({"name": "ring4_4m_k8",
                              "config": "unordered_4x1m_k8",
                              "traffic": "selfjoin_back_to_back",
                              "chips": 4, "why": "test"})
    for m in real["end_to_end"] + real["per_layer"]:
        if m["name"] == "selfjoin_points_per_s":
            m["workloads"].append("ring4_4m_k8")
    real["per_layer"].append({"name": "ring.exchange_ms", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "ring: parallel/ring.py",
                              "moves": "selfjoin_points_per_s",
                              "workloads": ["ring4_4m_k8"]})
    bench = tiny_bench(real)
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    assert by_name["selfjoin_points_per_s"]["workloads"] == ["batch_tiny"]
    assert by_name["ring.exchange_ms"]["workloads"] == []
    path = str(tmp_path / "BENCHMARK.json")
    write_json(path, bench)
    with pytest.raises(KeyError, match="no workload 'ring4_4m_k8'"):
        harness.find_cell(path, "ring4_4m_k8")


def add_ring4_cell_as_files(root: str) -> str:
    """A four-chip self-join cell, the way a later change adds one: a
    configuration file and entries in BENCHMARK.json, on the traffic mix
    the one-chip tiny cell already has. Returns the cell's name."""
    write_json(os.path.join(root, "perfbench", "configs", "tiny_ring4.json"),
               TINY_RING4)
    path = os.path.join(root, "BENCHMARK.json")
    b = harness.load_json(path)
    b["configs"].append({"name": "tiny_ring4", "source": "test",
                         "file": "perfbench/configs/tiny_ring4.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "ring4_tiny", "config": "tiny_ring4",
                           "traffic": "tiny_jobs", "chips": 4,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "batch_tiny" in m.get("workloads", []):
            m["workloads"].append("ring4_tiny")
    write_json(path, b)
    return "ring4_tiny"


@pytest.mark.parametrize("trace, reported", [
    (0, {"selfjoin_points_per_s", "setup_s"}),
    (1, {"ring.pairs_per_point", "selfjoin.host_ms", "ring.launch_ms"}),
])
def test_four_shard_cell_added_as_files_runs_correct(tiny_root, trace,
                                                     reported):
    cell = add_ring4_cell_as_files(tiny_root)
    rc, res, _, err = run_cell(tiny_root, cell, trace=trace)
    assert rc == 0, err
    assert res["correct"] is True
    assert res["checks"]["kth_ulp_max"]["value"] == 0
    assert res["device"]["count"] == 4
    assert reported <= set(res["metrics"])
    if trace == 0:
        assert set(res["metrics"]) == reported
