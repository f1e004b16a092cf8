"""ring.pairs_per_point: distance pairs the ring scored per point, from the
ring driver's ``last_stats["pair_evals"]`` (``parallel/ring.py``) summed
over the window's jobs, divided by the points those jobs answered. Fewer
pairs for the same answers is better pruning. Moves
``selfjoin_points_per_s``."""


def read(run):
    layer = run.layer
    if not layer.get("jobs"):
        return None
    return layer["pair_evals"] / (layer["jobs"] * layer["points"])
