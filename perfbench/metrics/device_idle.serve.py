"""device_idle.serve: share of the traced slice of the serving window in
which no operation ran on the device, in %: 1 - (union of the device's busy
intervals) / (the traced slice), from the profiler trace
(``perfbench/tracereduce.py``). Moves ``serve_p50_ms``."""


def read(run):
    if run.trace is None or run.trace["idle_share"] is None:
        return None
    return 100.0 * run.trace["idle_share"]
