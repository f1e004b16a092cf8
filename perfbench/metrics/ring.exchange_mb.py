"""ring.exchange_mb: MB one chip sends in one self-join job's rotations --
``ring_knn``'s ``ring.exchange_bytes`` counter (``parallel/ring.py``: both
counter-rotating copies of the resident shard's points, ids and bucket
boxes, once per round but the last, summed over the mesh's devices;
counted on the host from static shapes) over the traced jobs, from the
program's own sum of the counts a profiler trace saw (``obs/timers.py``
``traced_counters_snapshot``), per device and per job. 0 at one shard.
Moves ``selfjoin_points_per_s``."""


def read(run):
    try:
        from mpi_cuda_largescaleknn_tpu.obs.timers import (
            traced_counters_snapshot,
        )
    except ImportError:  # a program without traced counters
        return None
    layer = run.layer
    sent = traced_counters_snapshot().get("ring.exchange_bytes")
    if sent is None or not layer.get("traced_jobs"):
        return None
    return sent / 1e6 / layer["shards"] / layer["traced_jobs"]
