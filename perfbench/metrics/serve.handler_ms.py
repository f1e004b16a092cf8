"""serve.handler_ms: the HTTP handler's own time per request, in ms -- the
``serve.http`` span (``serve/server.py``: parse to response written) less
the ``serve.submit`` span inside it (the batcher: queue, engine, demux),
over the ``serve.http`` count in the traced slice, from the program's own
sum of the spans a profiler recorded (``obs/timers.py``
``traced_spans_snapshot``). Moves ``serve_p50_ms``."""


def read(run):
    try:
        from mpi_cuda_largescaleknn_tpu.obs.timers import (
            traced_spans_snapshot,
        )
    except ImportError:  # a program without spans: nothing to read
        return None
    spans = traced_spans_snapshot()
    count, http_s = spans.get("serve.http", (0, 0.0))
    _, submit_s = spans.get("serve.submit", (0, 0.0))
    return (http_s - submit_s) / count * 1e3 if count else None
