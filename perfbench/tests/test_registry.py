"""Files are found by name: every cell, configuration, traffic mix and
per-layer metric of BENCHMARK.json has its file, and a new one is added by
adding files alone."""

from __future__ import annotations

import hashlib
import json
import os
import re

import pytest

from perfbench import harness
from perfbench.tests.conftest import ROOT, add_cell_as_files, write_json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_resolves_to_its_files():
    b = bench()
    for w in b["workloads"]:
        spec = harness.find_cell(os.path.join(ROOT, "BENCHMARK.json"),
                                 w["name"])
        assert spec.config["name"] == w["config"]
        assert harness.load_system(spec).Cell
        names = {m["name"] for m in spec.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert spec.per_layer, w["name"]
        for m in spec.per_layer:
            assert callable(harness.load_reader(ROOT, m["name"]))
            assert m["moves"] in names


def test_benchmark_json_keeps_its_contract():
    b = bench()
    assert b["command"] == ["python3", "perfbench/run.py"]
    assert b["paths"] == ["perfbench"]
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"}}
    for group, allowed in keys.items():
        for entry in b[group]:
            assert set(entry) == allowed, entry
            assert NAME.match(entry["name"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"])
        assert m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert set(m["workloads"]) <= cells
        assert 1 <= len(m["layer"]) <= 200
    for entry in b["configs"] + b["workloads"]:
        assert 1 <= len(entry["why"]) <= 200, entry["name"]
    assert 1 <= b["run_seconds"] <= 51
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= \
        max(1, len(b["workloads"]) // 2)
    assert len(json.dumps(b)) < 64 * 1024
    for c in b["configs"]:
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["reduced"] == c["reduced"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peak rates"):
        harness.peaks_for(ROOT, "TPU v99")
    assert harness.peaks_for(ROOT, "TPU v5 lite")["hbm_bytes_per_s"] > 0


def digest(root: str) -> dict:
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_config_traffic_and_metric_are_files_only(tiny_root):
    pb = os.path.join(tiny_root, "perfbench")
    before = digest(pb)
    write_json(os.path.join(pb, "configs", "tiny_k4.json"),
               {"name": "tiny_k4", "system": "selfjoin", "points": 2000,
                "dim": 3, "k": 4, "shards": 1, "engine": "auto",
                "guarantee": {"kth_ulp_max": 1},
                "geometry": {"bucket_size": 256, "point_group": 2},
                "reduced": []})
    write_json(os.path.join(pb, "traffic", "tiny_jobs_more.json"),
               {"kind": "closed_jobs", "warm_jobs": 1,
                "check_rows_per_job": 64, "trace_jobs": 1})
    with open(os.path.join(pb, "metrics", "selfjoin.jobs.py"), "w") as f:
        f.write("def read(run):\n    return run.layer['jobs']\n")
    after = digest(pb)
    assert {k: v for k, v in after.items() if k in before} == before
    # the one file that lists cells gains entries; no other file changes
    path = os.path.join(tiny_root, "BENCHMARK.json")
    b = harness.load_json(path)
    b["configs"].append({"name": "tiny_k4", "source": "test",
                         "file": "perfbench/configs/tiny_k4.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "batch_tiny_k4", "config": "tiny_k4",
                           "traffic": "tiny_jobs_more", "chips": 1,
                           "why": "test"})
    b["per_layer"].append({"name": "selfjoin.jobs", "unit": "jobs",
                           "better": "higher", "source": "program_counter",
                           "layer": "driver: models/unordered.py",
                           "moves": "selfjoin_points_per_s",
                           "workloads": ["batch_tiny_k4"]})
    write_json(path, b)
    spec = harness.find_cell(path, "batch_tiny_k4")
    assert spec.config["k"] == 4
    assert spec.traffic["check_rows_per_job"] == 64
    assert [m["name"] for m in spec.per_layer] == ["selfjoin.jobs"]
    rec = harness.RunRecord(spec, {"jobs": 3}, None, {})
    assert harness.load_reader(tiny_root, "selfjoin.jobs")(rec) == 3


def test_new_data_and_arrival_kinds_are_files_only(tiny_root):
    """Clustered index data, uniform queries, on/off arrivals and revisits
    come from parameters: a cell with all of them is two data files."""
    pb = os.path.join(tiny_root, "perfbench")
    before = digest(pb)
    cell = add_cell_as_files(tiny_root)
    after = digest(pb)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "configs/tiny_serve_blobs.json", "traffic/onoff_revisit.json"}
    spec = harness.find_cell(os.path.join(tiny_root, "BENCHMARK.json"), cell)
    assert harness.load_system(spec).TRAFFIC_KIND == "open_loop"
    assert spec.traffic["arrivals"] == "onoff"


def test_traffic_of_another_kind_is_refused(tiny_root):
    path = os.path.join(tiny_root, "BENCHMARK.json")
    b = harness.load_json(path)
    b["workloads"].append({"name": "batch_open", "config": "tiny_selfjoin",
                           "traffic": "tiny_open", "chips": 1, "why": "t"})
    write_json(path, b)
    with pytest.raises(ValueError, match="reads 'closed_jobs'"):
        harness.load_system(harness.find_cell(path, "batch_open"))
