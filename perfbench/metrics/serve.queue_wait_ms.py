"""serve.queue_wait_ms: mean wait of a request in the batcher's queue, in
ms -- the ``serve.queue`` span (``serve/batcher.py``: from ``submit`` to
the pop that flushes it, whether the flush deadline, coalescing or a
pipeline slot held it) over its count in the traced slice, from the
program's own sum of the spans a profiler recorded (``obs/timers.py``
``traced_spans_snapshot``). Moves ``serve_p50_ms``."""


def read(run):
    try:
        from mpi_cuda_largescaleknn_tpu.obs.timers import (
            traced_spans_snapshot,
        )
    except ImportError:  # a program without spans: nothing to read
        return None
    count, seconds = traced_spans_snapshot().get("serve.queue", (0, 0.0))
    return seconds / count * 1e3 if count else None
