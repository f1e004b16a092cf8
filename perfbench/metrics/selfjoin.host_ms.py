"""selfjoin.host_ms: host time of one self-join job outside the ring --
``shard_and_pad`` plus ``extract`` -- in ms, from the driver's own phase
timers (``models/unordered.py`` ``PhaseTimers``), averaged over the jobs of
the window. Moves ``selfjoin_points_per_s``."""


def read(run):
    layer = run.layer
    if not layer.get("jobs"):
        return None
    phase = layer["phase_s"]
    return (phase["shard_and_pad"] + phase["extract"]) / layer["jobs"] * 1e3
