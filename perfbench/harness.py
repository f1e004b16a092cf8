"""Benchmark harness: finds a cell's files by name, runs it, prints one line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

- ``configs[].file``: the deployment (``system`` names the driver module in
  ``perfbench/systems/``, the rest are its sizes and guarantees);
- ``perfbench/traffic/<traffic>.json``: the traffic mix, parameters only;
- ``perfbench/metrics/<metric>.py``: a reader ``read(run) -> float | None``.

So a later change adds a cell, a mix or a metric by adding files, and
edits none. Only the process that runs this module touches JAX; the load
generator is a JAX-free child (``perfbench/loadgen.py``).
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ lookup

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class CellSpec:
    """One ``workloads`` entry with everything its name leads to."""
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    root: str

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _applies(metric: dict, cell: str, reported: set | None = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def find_cell(bench_path: str, workload: str) -> CellSpec:
    """Resolve a workload name to its configuration, traffic and metrics."""
    bench = load_json(bench_path)
    root = os.path.dirname(os.path.abspath(bench_path))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench_path} "
                       f"(has {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "perfbench", "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _applies(m, workload, reported)]
    return CellSpec(w, config, traffic, e2e, layer, root)


def load_system(spec: CellSpec):
    """The driver module named by the configuration's ``system`` key; the
    traffic's ``kind`` has to be the one that driver reads."""
    mod = importlib.import_module(f"perfbench.systems.{spec.config['system']}")
    if spec.traffic.get("kind") != mod.TRAFFIC_KIND:
        raise ValueError(
            f"traffic {spec.workload['traffic']!r} is of kind "
            f"{spec.traffic.get('kind')!r}; system {spec.config['system']!r} "
            f"reads {mod.TRAFFIC_KIND!r}")
    return mod


def load_reader(root: str, metric: str):
    """``perfbench/metrics/<metric>.py``'s ``read`` function."""
    path = os.path.join(root, "perfbench", "metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def peaks_for(root: str, device_kind: str) -> dict:
    """Peak rates of one device kind; a kind not in the table is an error."""
    table = load_json(os.path.join(root, "perfbench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peak rates for device kind {device_kind!r} in "
                       f"perfbench/peaks.json (has {sorted(table['devices'])})")
    return table["devices"][device_kind]


# ---------------------------------------------------------------- probes

class CompileClock:
    """Compile work as JAX reports it through its monitoring events.

    A persistent-cache hit still fires ``backend_compile_duration`` (with
    the load time), so true compiles are backend compiles minus cache
    retrievals."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.backend = 0
        self.retrieved = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                self.backend += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.retrieved += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds,
                "compiles": self.backend - self.retrieved,
                "cache_loads": self.retrieved}

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}


def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {dev.platform}")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX sees "
                       f"{len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def memory_peak_bytes(jax, chips: int) -> int | None:
    """``peak_bytes_in_use`` of the fullest of the cell's chips."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Tracer:
    """Profiler trace of one short slice of the window (``--trace 1``)."""

    def __init__(self, jax, enabled: bool):
        self.jax = jax
        self.enabled = enabled
        self.dir = tempfile.mkdtemp(prefix="perfbench_trace_") if enabled \
            else None
        self.taken = False

    @contextlib.contextmanager
    def slice(self):
        if not self.enabled or self.taken:
            yield False
            return
        self.taken = True
        opts = self.jax.profiler.ProfileOptions()
        # host spans (TraceMe, annotations) yes; a per-call Python tracer
        # no: it would slow the host path it is meant to observe
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            with self.jax.profiler.TraceAnnotation("perfbench.trace_window"):
                yield True
        finally:
            self.jax.profiler.stop_trace()

    def reduce(self) -> dict | None:
        if not self.taken:
            return None
        from perfbench import tracereduce

        try:
            events = tracereduce.load_xplane(self.dir)
            return tracereduce.reduce(events, "perfbench.trace_window")
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

    def discard(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


# ------------------------------------------------------------------ run

@dataclass
class RunRecord:
    """What per-layer readers see: the cell, the window's program counters
    and spans, and the reduced trace of the traced slice (or None)."""
    spec: CellSpec
    layer: dict
    trace: dict | None
    peaks: dict
    extra: dict = field(default_factory=dict)


def _log(msg: str) -> None:
    sys.stderr.write(msg + "\n")
    sys.stderr.flush()


def run_cell(spec: CellSpec, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, t_start: float | None = None,
             log=None) -> dict:
    """Set up, warm, measure, check; returns the result object."""
    log = log or _log
    t_start = time.perf_counter() if t_start is None else t_start
    import jax

    device = device_info(jax, spec.chips, require_tpu)
    clock = CompileClock(jax)
    system = load_system(spec)
    cell = system.Cell(spec, seed, seconds)
    tracer = Tracer(jax, trace)
    try:
        c0 = clock.snapshot()
        cell.setup()
        c1 = clock.snapshot()
        setup_s = time.perf_counter() - t_start
        log(f"STAGE set-up done at {setup_s:.3f} s")
        cell.window(tracer)
        c2 = clock.snapshot()
        log(f"STAGE window done at {time.perf_counter() - t_start:.3f} s")
        reduced = tracer.reduce()
        mem = memory_peak_bytes(jax, spec.chips)
        layer = cell.layer_data()
        e2e = cell.end_to_end()
    finally:
        # the program's state goes before the reference runs
        cell.release()
        tracer.discard()
    log(f"STAGE program released at {time.perf_counter() - t_start:.3f} s")
    compiles = {"setup": CompileClock.delta(c0, c1),
                "window": CompileClock.delta(c1, c2)}
    log("COMPILES " + json.dumps(compiles))
    metrics = {}
    notes = []
    if not trace:
        for m in spec.end_to_end:
            value = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        rec = RunRecord(spec, layer, reduced,
                        peaks_for(spec.root, device["kind"])
                        if require_tpu else {}, {"compiles": compiles})
        for m in spec.per_layer:
            value = load_reader(spec.root, m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        notes = rec.extra.get("notes", [])
        if reduced is not None and reduced["busy_s"] is not None:
            device = dict(device, busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
    for note in notes:
        log("NOTE " + note)
    device = dict(device, memory_peak_bytes=mem)
    attempted, failed = cell.attempted_failed()
    out = {"correct": None, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace and reduced is not None:
        out["breakdown"] = reduced["breakdown"]
    # the comparison comes last, and so do its lines on stderr
    checks = cell.check()
    log(f"STAGE compared at {time.perf_counter() - t_start:.3f} s")
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    out["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    out["checks"] = checks
    return out
