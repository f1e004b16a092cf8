"""Which trace lines the reduction reads, and what it makes of several
devices: the host line is named after the executable (``python3`` on the
chip's machine), and a four-chip trace reads per-chip averages."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
from types import SimpleNamespace

import pytest

from perfbench import harness, opcount, tracereduce
from perfbench.tests.conftest import ROOT

ANNOTATION = "perfbench.trace_window"
HOST = "/host:CPU"
#: a kernel event as the v5e trace names it (knn_tiled_roofline readers)
KERNEL_EVENT = ('%_run.1 = f32[8] custom-call(f32[8] %p), '
                'custom_call_target="tpu_custom_call"')


def plane(name, lines):
    """A profiler plane as ``tracereduce.flatten`` reads it: ``lines`` maps
    a line name to its (name, start_ns, duration_ns) events."""
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=line, events=[
            SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in evs])
        for line, evs in lines.items()])


def traced_job(host_line):
    """One device busy at [100, 400) and [700, 900) of a [0, 1000) window;
    the host line holds the annotation, a job span and the spans that
    explain each gap. A runtime worker line holds an event that, were it
    read, would name the longest gap: it is shorter than ``ring.wait`` and
    covers more than half of the gap."""
    host = {host_line: [(ANNOTATION, 0, 1000), ("selfjoin.job", 0, 1000),
                        ("ring.stage", 0, 100), ("ring.wait", 400, 300),
                        ("selfjoin.extract", 900, 100)],
            "tf_Compute": [("worker", 400, 160)]}
    dev = {"XLA Ops": [(KERNEL_EVENT, 100, 300), ("%fusion.1", 700, 200)],
           "XLA Modules": [("jit_ring", 100, 800)]}
    return [plane(HOST, host), plane("/device:TPU:0", dev)]


@pytest.mark.parametrize("line", ["python3", "python3.11"])
def test_python3_host_line_reads_as_python(line):
    ref = tracereduce.reduce(tracereduce.flatten(traced_job("python")),
                             ANNOTATION)
    got = tracereduce.reduce(tracereduce.flatten(traced_job(line)),
                             ANNOTATION)
    assert got == ref
    assert ref["window_s"] == pytest.approx(1000e-9)
    assert ref["busy_s"] == pytest.approx(500e-9)
    gaps = ref["breakdown"]["idle_gaps"]
    assert gaps[0] == ["ring.wait", pytest.approx(300e-9)]
    assert {n for n, _ in gaps[1:]} == {"ring.stage", "selfjoin.extract"}


def test_other_host_lines_are_skipped():
    events = tracereduce.flatten(traced_job("python3"))
    assert {e["line"] for e in events} == {"python3", "XLA Ops",
                                           "XLA Modules"}
    assert "worker" not in {e["name"] for e in events}
    assert tracereduce.reads_line(HOST, "python")
    assert not tracereduce.reads_line(HOST, "tf_Compute")
    assert not tracereduce.reads_line(HOST, "tf_xla-cpu-codegen/1")
    assert not tracereduce.reads_line("/device:TPU:0", "python3")
    assert not tracereduce.reads_line("/host:metadata", "Steps")


def test_trace_of_a_python3_process_finds_its_window(tmp_path):
    """A real profiler trace, taken in a process started as ``python3`` as
    the benchmark's command starts it: the annotation is read, so the
    window is the slice's and not the extent of the events."""
    exe = shutil.which("python3")
    if exe is None:
        pytest.skip("no python3 on PATH")
    script = (
        "import json, sys, time\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "import jax, jax.numpy as jnp\n"
        "from perfbench import harness, tracereduce\n"
        "jnp.arange(8).sum().block_until_ready()\n"
        "t = harness.Tracer(jax, True)\n"
        "t0 = time.perf_counter()\n"
        "with t.slice():\n"
        "    jnp.arange(8).sum().block_until_ready()\n"
        "    time.sleep(0.2)\n"
        "took = time.perf_counter() - t0\n"
        "events = tracereduce.load_xplane(t.dir)\n"
        "t.discard()\n"
        f"hit = [e['line'] for e in events if e['name'] == {ANNOTATION!r}]\n"
        "print(json.dumps({'lines': hit, 'took': took,\n"
        f"    'window_s': tracereduce.reduce(events, {ANNOTATION!r})"
        "['window_s']}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    out = subprocess.run([exe, "-c", script], capture_output=True,
                         text=True, env=env, timeout=240, check=True)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["lines"] == ["python3"]
    assert 0.2 <= got["window_s"] <= got["took"]


def four_devices(busy_ns, kernel_ns):
    """Four device planes of one traced window [0, 1000): device i is busy
    ``busy_ns[i]`` from t=0, of which ``kernel_ns[i]`` is the kernel; a
    fifth plane ran nothing inside the window."""
    planes = [plane(HOST, {"python3": [(ANNOTATION, 0, 1000),
                                       ("ring.wait", 0, 1000)]})]
    for i, (b, kn) in enumerate(zip(busy_ns, kernel_ns)):
        planes.append(plane(f"/device:TPU:{i}", {"XLA Ops": [
            (KERNEL_EVENT, 0, kn), ("%fusion.1", kn, b - kn)]}))
    planes.append(plane("/device:TPU:4", {"XLA Ops": [
        (KERNEL_EVENT, 2000, 50)]}))
    return tracereduce.flatten(planes)


def test_four_devices_mean_busy_summed_ops_first_device_gaps():
    r = tracereduce.reduce(four_devices([400, 600, 800, 200],
                                        [100, 200, 300, 50]), ANNOTATION)
    assert r["devices"] == 4
    assert r["busy_s"] == pytest.approx(500e-9)    # mean of the four
    assert r["idle_share"] == pytest.approx(0.5)
    assert r["op_seconds"][KERNEL_EVENT] == pytest.approx(650e-9)
    assert r["op_seconds"]["%fusion.1"] == pytest.approx(1350e-9)
    # gaps are device 0's: [400, 1000) only
    assert r["breakdown"]["idle_gaps"] == [["ring.wait",
                                            pytest.approx(600e-9)]]


def test_four_device_roofline_is_a_per_chip_average():
    """knn_tiled_roofline.batch over four chips that each ran the kernel
    for t reads what one chip doing a quarter of the work in t reads."""
    layer = {"points": 8000, "shards": 4, "bucket_size": 256,
             "point_group": 2, "traced_tiles": 400, "traced_jobs": 1,
             "k": 8}
    t_ns = 300
    trace = tracereduce.reduce(four_devices([400] * 4, [t_ns] * 4),
                               ANNOTATION)
    peaks = harness.peaks_for(ROOT, "TPU v5 lite")
    rec = harness.RunRecord(None, layer, trace, peaks)
    share = harness.load_reader(ROOT, "knn_tiled_roofline.batch")(rec)
    work = opcount.selfjoin_work(8000, 4, 256, 2, 400, 1, 8)
    per_chip = max(work["flops"] / 4 / peaks["f32_vpu_flops_per_s"],
                   work["bytes"] / 4 / peaks["hbm_bytes_per_s"])
    assert share == pytest.approx(100.0 * per_chip / (t_ns * 1e-9))
