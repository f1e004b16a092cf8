"""knn_tiled_roofline.serve: the ``knn_tiled`` Pallas kernel's share of its
roofline in the traced slice of the serving window, in %.

Operations and bytes come from the engine's ``tiles_executed`` counter over
the traced slice (``perfbench/opcount.py serve_work``), the least time is
the larger of operations over the f32 vector peak and bytes over HBM
bandwidth (``perfbench/peaks.json``), and it is divided by the kernel's
device time in the trace. Counters move when a batch's answers are
fetched, so the slice's edges shift the count by a batch or so. Operations
count only the distance arithmetic, so the share is a lower bound. Moves
``serve_p50_ms``.
"""

from perfbench.opcount import roofline_share, serve_work
from perfbench.tracereduce import seconds_matching

#: the kernel's device events as the v5e trace names them: the Pallas
#: custom call, named after its jitted wrapper ``_run`` in knn_tiled.py
#: (the kernel function itself is ``_kernel``, as is knn_bf.py's)
KERNEL = r'^%_run(\.\d+)? = .*custom_call_target="tpu_custom_call"'


def read(run):
    layer = run.layer
    traced = layer.get("traced")
    if run.trace is None or not run.peaks or not traced:
        return None
    seconds = seconds_matching(run.trace, KERNEL)
    if seconds <= 0 or traced["tiles_executed"] <= 0:
        return None
    work = serve_work(traced["tiles_executed"], layer["point_tile"],
                      layer["query_rows_per_bucket"])
    share, bound = roofline_share(work, seconds, run.peaks)
    run.extra.setdefault("notes", []).append(
        f"knn_tiled_roofline.serve: {bound} bound, {work['flops']} flops, "
        f"{work['bytes']} bytes, {seconds} s")
    return share
