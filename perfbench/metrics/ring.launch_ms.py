"""ring.launch_ms: host time of one self-join job's call of the fused ring
program, in ms -- the ``ring.launch`` span (``parallel/ring.py``
``ring_knn``; the device runs after it) over the jobs of the traced
slice, from the program's own sum of the spans a profiler recorded
(``obs/timers.py`` ``traced_spans_snapshot``). The first call per
configuration and shape builds and loads the program, in set-up; every
call in the window only enqueues it. Moves ``selfjoin_points_per_s``."""


def read(run):
    try:
        from mpi_cuda_largescaleknn_tpu.obs.timers import (
            traced_spans_snapshot,
        )
    except ImportError:  # a program without spans: nothing to read
        return None
    count, seconds = traced_spans_snapshot().get("ring.launch", (0, 0.0))
    return seconds / count * 1e3 if count else None
