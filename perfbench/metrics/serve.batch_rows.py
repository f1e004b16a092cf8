"""serve.batch_rows: query rows per engine batch over the window, from the
engine's counters: the delta of ``result_rows`` over the delta of the
``engine_batch_seconds`` histogram's count (``serve/engine.py``, fed by the
micro-batcher ``serve/batcher.py``). How well the batcher coalesces.
Moves ``serve_p50_ms``."""


def read(run):
    w = run.layer.get("window")
    if not w or not w["engine_batches"]:
        return None
    return w["result_rows"] / w["engine_batches"]
