"""loadgen.lag_p99_ms: how late the load generator sent its requests
against their schedule, 99th percentile over the window, in ms (host clock
of ``perfbench/loadgen.py``). A starved generator shows here instead of as
a fast server. Moves ``serve_p50_ms``."""


def read(run):
    return run.layer.get("lag_p99_ms")
