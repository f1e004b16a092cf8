"""The trace reduction on a small recorded trace: busy union, idle share,
kernel time by event name, and idle gaps named by the host's spans."""

from __future__ import annotations

import json
import os

import pytest

from perfbench import tracereduce

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_small.json")


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def test_union_merges_overlaps():
    assert tracereduce.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == \
        [(0, 3), (5, 8)]


def test_busy_idle_and_kernel_time_by_hand():
    d, h = "/device:TPU:0", "/host:CPU"
    events = [
        ev(h, "python", "perfbench.trace_window", 0, 1000),
        ev(h, "python", "job", 0, 1000),
        ev(h, "python", "np.asarray", 600, 300),
        ev(d, "XLA Ops", "_kernel", 100, 300),
        ev(d, "XLA Ops", "fusion.1", 350, 150),     # overlaps the kernel
        ev(d, "XLA Modules", "jit_ring", 100, 400),  # not an op: ignored
        ev(d, "XLA Ops", "_kernel", 950, 200),       # clipped at 1000
    ]
    r = tracereduce.reduce(events, "perfbench.trace_window")
    assert r["window_s"] == pytest.approx(1e-6)
    # busy = [100, 500) + [950, 1000) = 450 ns
    assert r["busy_s"] == pytest.approx(450e-9)
    assert r["idle_share"] == pytest.approx(0.55)
    assert tracereduce.seconds_matching(r, r"^_kernel") == \
        pytest.approx(350e-9)
    gaps = dict((n, s) for n, s in r["breakdown"]["idle_gaps"])
    # [500, 950) is mostly np.asarray; [0, 100) only the whole job covers
    assert gaps["np.asarray"] == pytest.approx(450e-9)
    assert gaps["job"] == pytest.approx(100e-9)
    assert r["breakdown"]["device_ops"][0][0] == "_kernel"


def test_no_device_plane_reads_nothing():
    events = [ev("/host:CPU", "python", "perfbench.trace_window", 0, 10)]
    r = tracereduce.reduce(events, "perfbench.trace_window")
    assert r["busy_s"] is None and r["idle_share"] is None


def test_recorded_chip_trace():
    """A slice of a real v5e trace of the self-join cell; the expected
    numbers were worked out from the file's events by hand."""
    with open(DATA) as f:
        rec = json.load(f)
    r = tracereduce.reduce(rec["events"], rec["annotation"])
    exp = rec["expect"]
    assert r["window_s"] == pytest.approx(exp["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(exp["busy_s"], rel=1e-9)
    assert tracereduce.seconds_matching(r, exp["kernel_pattern"]) == \
        pytest.approx(exp["kernel_s"], rel=1e-9)
