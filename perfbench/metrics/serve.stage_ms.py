"""serve.stage_ms: host time of one engine batch's staging, in ms -- the
``serve.engine.stage`` span (``serve/engine.py`` ``dispatch``: Morton sort,
pad, upload and the launch's hand-off) over its count in the traced slice,
from the program's own sum of the spans a profiler recorded
(``obs/timers.py`` ``traced_spans_snapshot``). Moves ``serve_p50_ms``."""


def read(run):
    try:
        from mpi_cuda_largescaleknn_tpu.obs.timers import (
            traced_spans_snapshot,
        )
    except ImportError:  # a program without spans: nothing to read
        return None
    count, seconds = traced_spans_snapshot().get("serve.engine.stage",
                                                 (0, 0.0))
    return seconds / count * 1e3 if count else None
