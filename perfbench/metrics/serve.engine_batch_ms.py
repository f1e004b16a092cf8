"""serve.engine_batch_ms: mean time of one engine batch from dispatch to
completed fetch, in ms: the delta of the ``engine_batch_seconds``
histogram's sum over the delta of its count in the window
(``serve/engine.py``). Moves ``serve_p50_ms``."""


def read(run):
    w = run.layer.get("window")
    if not w or not w["engine_batches"]:
        return None
    return w["engine_batch_s"] / w["engine_batches"] * 1e3
