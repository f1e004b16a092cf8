"""Fixtures: a copy of the benchmark tree with tiny cells that the CPU can
run in seconds. The copy holds a BENCHMARK.json of its own, so the tests
drive the harness exactly as the command line does, by cell name.

The CPU stands in for a host of several chips with eight virtual devices,
set before any test imports JAX."""

from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

HERE = os.path.dirname(os.path.abspath(__file__))
PKG = os.path.dirname(HERE)
ROOT = os.path.dirname(PKG)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_SELFJOIN = {
    "name": "tiny_selfjoin", "system": "selfjoin", "points": 3000,
    "dim": 3, "k": 8, "shards": 1, "engine": "auto",
    "guarantee": {"kth_ulp_max": 1},
    "geometry": {"bucket_size": 256, "point_group": 2}, "reduced": []}
TINY_SERVE = {
    "name": "tiny_serve", "system": "serve", "points": 5000, "dim": 3,
    "k": 16, "shards": 1, "engine": "auto", "merge": "auto",
    "min_batch": 16, "max_batch": 32,
    "server": {"max_delay_ms": 2.0, "pipeline_depth": 2,
               "max_queue_rows": 4096, "timeout_ms": 5000.0,
               "qcache_rows": 4096, "qcache_seed_rows": 512},
    "guarantee": {"kth_ulp_max": 1},
    "geometry": {"bucket_size": 256}, "reduced": []}
TINY_JOBS = {"kind": "closed_jobs", "warm_jobs": 1,
             "check_rows_per_job": 256, "trace_jobs": 1}
TINY_OPEN = {"kind": "open_loop", "rate_rps": 20.0, "batch": 16,
             "blobs": 4, "blob_sigma": 0.02, "scale": 1.0, "workers": 8,
             "warm_seconds": 0.5, "grace_s": 30.0, "trace_delay_s": 0.2,
             "trace_seconds": 0.5}


@pytest.fixture(scope="session", autouse=True)
def compile_cache():
    """The run's compile-cache settings, in place before any test imports
    JAX (a test that imports the program first would otherwise fix JAX's
    cache threshold at its default)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from perfbench.run import enable_compile_cache

    enable_compile_cache()


def tiny_bench(real: dict) -> dict:
    """The real BENCHMARK.json's metrics, over two tiny cells. A cell a
    metric lists that has no tiny twin here (one only the chip runs) is
    dropped from its list; a metric left with none applies to no tiny
    cell."""
    bench = dict(real)
    bench["configs"] = [
        {"name": "tiny_selfjoin", "source": "test",
         "file": "perfbench/configs/tiny_selfjoin.json", "reduced": [],
         "why": "test"},
        {"name": "tiny_serve", "source": "test",
         "file": "perfbench/configs/tiny_serve.json", "reduced": [],
         "why": "test"}]
    bench["workloads"] = [
        {"name": "batch_tiny", "config": "tiny_selfjoin",
         "traffic": "tiny_jobs", "chips": 1, "why": "test"},
        {"name": "serve_tiny", "config": "tiny_serve",
         "traffic": "tiny_open", "chips": 1, "why": "test"}]
    rename = {"batch_1m_k8": "batch_tiny",
              "serve_k100_clustered": "serve_tiny"}
    for group in ("end_to_end", "per_layer"):
        bench[group] = [dict(m, workloads=[rename[w] for w in m["workloads"]
                                       if w in rename])
                        if "workloads" in m else dict(m)
                        for m in real[group]]
    return bench


def write_json(path: str, obj: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout-like directory: BENCHMARK.json plus a copy of perfbench/
    with the tiny cells' files added."""
    root = str(tmp_path / "checkout")
    shutil.copytree(PKG, os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    write_json(os.path.join(root, "BENCHMARK.json"), tiny_bench(real))
    pb = os.path.join(root, "perfbench")
    write_json(os.path.join(pb, "configs", "tiny_selfjoin.json"),
               TINY_SELFJOIN)
    write_json(os.path.join(pb, "configs", "tiny_serve.json"), TINY_SERVE)
    write_json(os.path.join(pb, "traffic", "tiny_jobs.json"), TINY_JOBS)
    write_json(os.path.join(pb, "traffic", "tiny_open.json"), TINY_OPEN)
    return root


#: a serving cell of a kind no committed file has: an index of clustered
#: points, uniform queries on an on/off law, with repeats and revisits
BLOB_SERVE = dict(TINY_SERVE, name="tiny_serve_blobs",
                  data={"dist": "blobs", "blobs": 8, "blob_sigma": 0.03})
ONOFF_REVISIT = {"kind": "open_loop", "rate_rps": 20.0, "batch": 16,
                 "blobs": 0, "arrivals": "onoff", "on_s": 0.25,
                 "off_s": 0.25, "dup_frac": 0.2, "revisit_frac": 0.4,
                 "revisit_sigma": 0.001, "workers": 8, "warm_seconds": 0.5,
                 "grace_s": 30.0, "trace_delay_s": 0.2,
                 "trace_seconds": 0.5}


def add_cell_as_files(root: str) -> str:
    """Add that cell the way a later change would: two new data files and
    entries in BENCHMARK.json. Returns the new cell's name."""
    pb = os.path.join(root, "perfbench")
    write_json(os.path.join(pb, "configs", "tiny_serve_blobs.json"),
               BLOB_SERVE)
    write_json(os.path.join(pb, "traffic", "onoff_revisit.json"),
               ONOFF_REVISIT)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        b = json.load(f)
    b["configs"].append({"name": "tiny_serve_blobs", "source": "test",
                         "file": "perfbench/configs/tiny_serve_blobs.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "serve_blobs_onoff",
                           "config": "tiny_serve_blobs",
                           "traffic": "onoff_revisit", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "serve_tiny" in m.get("workloads", []):
            m["workloads"].append("serve_blobs_onoff")
    write_json(path, b)
    return "serve_blobs_onoff"
