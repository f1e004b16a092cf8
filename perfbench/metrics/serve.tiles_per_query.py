"""serve.tiles_per_query: point tiles the kernel scored per answered query
over the window: the delta of the engine's ``tiles_executed`` counter
(tile-row units: one query row against one point tile) over the delta of
``result_rows`` (``serve/engine.py``). Lower is tighter pruning. Moves
``serve_p50_ms``."""


def read(run):
    w = run.layer.get("window")
    if not w or not w["result_rows"]:
        return None
    return w["tiles_executed"] / w["result_rows"]
