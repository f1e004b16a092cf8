"""knn_tiled_roofline.batch: the ``knn_tiled`` Pallas kernel's share of its
roofline in the traced self-join jobs, in %.

The least time the chip could take for the kernel's work -- the larger of
its operations over the f32 vector peak and its HBM bytes over the HBM
bandwidth (``perfbench/opcount.py``, ``perfbench/peaks.json``) -- divided
by the kernel's device time in the trace. At 248-row buckets a tile brings
124 operations per byte, far above the v5e's ridge point, so the vector
bound applies. Operations count only the distance arithmetic (not the
fold), so the share is a lower bound. Moves ``selfjoin_points_per_s``.
"""

from perfbench.opcount import roofline_share, selfjoin_work
from perfbench.tracereduce import seconds_matching

#: the kernel's device events as the v5e trace names them: the Pallas
#: custom call, named after its jitted wrapper ``_run`` in knn_tiled.py
#: (the kernel function itself is ``_kernel``, as is knn_bf.py's)
KERNEL = r'^%_run(\.\d+)? = .*custom_call_target="tpu_custom_call"'


def read(run):
    layer = run.layer
    if run.trace is None or not run.peaks or not layer.get("traced_jobs"):
        return None
    seconds = seconds_matching(run.trace, KERNEL)
    if seconds <= 0:
        return None
    work = selfjoin_work(layer["points"], layer["shards"],
                         layer["bucket_size"], layer["point_group"],
                         layer["traced_tiles"], layer["traced_jobs"],
                         layer["k"])
    share, bound = roofline_share(work, seconds, run.peaks)
    run.extra.setdefault("notes", []).append(
        f"knn_tiled_roofline.batch: {bound} bound, {work['flops']} flops, "
        f"{work['bytes']} bytes, {seconds} s")
    return share
