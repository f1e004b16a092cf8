"""Reduction of a JAX profiler trace to device busy time, idle gaps and
per-operation device time.

The trace is first flattened to plain event records (``flatten``, from the
planes ``load_xplane`` reads), so the arithmetic (``reduce``) runs on lists
of dicts that a test can build by hand or load from a small recorded file.

- Lines read: on a device plane (``/device:<X>:<n>``) the ``XLA Ops`` and
  ``XLA Modules`` lines; on a host plane (``/host:...``) every line whose
  name starts with ``python``. Every Python thread's events (JAX's own
  spans, the program's spans and the benchmark's annotation) sit on one
  host line named after the executable: ``python``, or ``python3`` when
  started as the benchmark's command starts it (``python3
  perfbench/run.py``), or ``python3.11``. The runtime's worker threads
  log 10^5 events per second on lines of other names (``tf_Compute``,
  ...) and name no work a user would recognise, so they are skipped
  unread.
- Window: the span of the host annotation that brackets the traced slice;
  without one, the extent of every event read.
- Devices: busy time per device is the union of its operation intervals
  (the ``XLA Ops`` line where the plane has one; otherwise its ``XLA
  Modules`` line) clipped to the window. ``busy_s`` is the mean over the
  devices that ran anything in the window; ``op_seconds`` sums each
  operation's device time over every device. So a reader that divides
  the work of all devices, at one chip's peak, by summed ``op_seconds``
  reads a per-chip average, as ``busy_s`` is one.
- Idle gaps: the holes in the union of busy intervals of the first device
  (by plane name), each named by the host event that best explains it --
  the shortest host event covering at least half of the gap, else the one
  overlapping it most.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
#: lines worth reading (module docstring): device operations and programs,
#: and the host line of the Python threads, named after the executable
DEVICE_LINES = (OPS_LINE, "XLA Modules")
HOST_LINE_PREFIX = "python"


def reads_line(plane: str, line: str) -> bool:
    """Whether the events of ``line`` on ``plane`` are read."""
    if DEVICE_PLANE.match(plane):
        return line in DEVICE_LINES
    return plane.startswith("/host:") and line.startswith(HOST_LINE_PREFIX)


def load_xplane(trace_dir: str) -> list[dict]:
    """The events read from the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return flatten(ProfileData.from_file(paths[-1]).planes)


def flatten(planes) -> list[dict]:
    """Event records of the lines ``reads_line`` keeps. ``planes`` are
    profiler planes, or anything with their ``name``, ``lines`` and, per
    line, ``name`` and ``events`` (``name``, ``start_ns``,
    ``duration_ns``)."""
    events = []
    for plane in planes:
        for line in plane.lines:
            if not reads_line(plane.name, line.name):
                continue
            for ev in line.events:
                events.append({"plane": plane.name, "line": line.name,
                               "name": ev.name,
                               "start_ns": float(ev.start_ns),
                               "dur_ns": float(ev.duration_ns)})
    return events


def short_name(name: str) -> str:
    """An XLA op event is named by its whole HLO instruction; keep the
    instruction's name, and a custom call's target."""
    if not name.startswith("%") or " = " not in name:
        return name
    short = name.split(" = ", 1)[0]
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{short} {target.group(1)}" if target else short


def union(intervals) -> list[tuple[float, float]]:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def device_ops(events) -> dict[str, list[dict]]:
    """Device plane -> its operation events."""
    planes: dict[str, list[dict]] = {}
    for ev in events:
        if DEVICE_PLANE.match(ev["plane"]):
            planes.setdefault(ev["plane"], []).append(ev)
    out = {}
    for plane, evs in planes.items():
        ops = [e for e in evs if e["line"] == OPS_LINE]
        out[plane] = ops or evs
    return out


def window_of(events, annotation: str) -> tuple[float, float]:
    spans = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
             if e["name"] == annotation and not DEVICE_PLANE.match(e["plane"])]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    starts = [e["start_ns"] for e in events]
    ends = [e["start_ns"] + e["dur_ns"] for e in events]
    return min(starts), max(ends)


def _gap_name(gap: tuple[float, float], host: list[dict]) -> str:
    g0, g1 = gap
    glen = g1 - g0
    best, best_cover = None, 0.0
    covering = []
    for ev in host:
        s, e = ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
        cover = min(e, g1) - max(s, g0)
        if cover <= 0:
            continue
        if cover >= 0.5 * glen:
            covering.append((ev["dur_ns"], ev["name"]))
        if cover > best_cover:
            best, best_cover = ev["name"], cover
    if covering:
        return min(covering)[1]
    return best or "host: no span"


def reduce(events, annotation: str, top: int = 10) -> dict:
    """busy_s, window_s, idle_share, per-op device seconds, breakdown."""
    if not events:
        # a trace with nothing on the lines read (a CPU run): no reading
        return {"window_s": None, "busy_s": None, "idle_share": None,
                "devices": 0, "op_seconds": {},
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    w0, w1 = window_of(events, annotation)
    window_s = (w1 - w0) / 1e9
    per_dev = device_ops(events)
    busy = {}
    op_s: dict[str, float] = {}
    for plane, ops in sorted(per_dev.items()):
        merged = union(clip([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                             for e in ops], w0, w1))
        if merged:
            busy[plane] = merged
        for e in ops:
            s, t = max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1)
            if t > s:
                op_s[e["name"]] = op_s.get(e["name"], 0.0) + (t - s) / 1e9
    # no device plane (a CPU run): nothing to read, not an idle device
    busy_s = (sum(sum(e - s for s, e in iv) for iv in busy.values())
              / len(busy) / 1e9) if busy else None
    gaps = []
    if busy:
        first = busy[sorted(busy)[0]]
        edges = [w0] + [x for iv in first for x in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
    host = [e for e in events
            if e["plane"].startswith("/host:") and e["name"] != annotation
            and e["dur_ns"] > 0]
    named = sorted(((g[1] - g[0]) / 1e9, _gap_name(g, host)) for g in gaps)
    named.reverse()
    by_short: dict[str, float] = {}
    for name, sec in op_s.items():
        by_short[short_name(name)] = by_short.get(short_name(name), 0.0) + sec
    ranked_ops = sorted(by_short.items(), key=lambda kv: -kv[1])
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": (1.0 - busy_s / window_s)
        if busy_s is not None and window_s > 0 else None,
        "devices": len(busy),
        "op_seconds": op_s,
        "breakdown": {
            "device_ops": [[n, s] for n, s in ranked_ops[:top]],
            "idle_gaps": [[n, s] for s, n in named[:top]],
        },
    }


def seconds_matching(reduced: dict, pattern: str) -> float:
    """Device seconds of every operation whose event name (the whole HLO
    instruction, on a TPU) matches ``pattern``, a regular expression."""
    rx = re.compile(pattern)
    return sum(sec for name, sec in reduced["op_seconds"].items()
               if rx.search(name))
