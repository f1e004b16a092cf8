"""ring.exchange_ms: device time of the ring's exchange in one self-join
job on one chip, in ms -- the ``collective-permute`` operations that
``lax.ppermute`` lowers to (``parallel/ring.py`` ``_rotate_pair``), summed
over the devices of the traced jobs (``perfbench/tracereduce.py``) and
divided by the devices and the jobs.

A rotation is asynchronous: ``collective-permute-start`` starts the
transfer and ``collective-permute-done`` waits for it to land. Both
count here, and both are time the chip spends in the exchange rather
than in a fold: the start waits until the peer device can take the
transfer (on a v5e nearly all of this metric, and it follows how far
the devices drift apart between rotations), and the done is the part
of the transfer that the fold scheduled between the two did not hide
(on a v5e it reads ~0). The transfer time that the fold hides is not
in it. Moves ``selfjoin_points_per_s``."""

from perfbench.tracereduce import seconds_matching

#: an XLA op event is named by its whole HLO instruction: match the
#: opcode (``%x = ... collective-permute-start(...)``), not the
#: instruction's name, which the compiler chooses
EXCHANGE = r" collective-permute(-start|-done)?\("


def read(run):
    trace, layer = run.trace, run.layer
    if trace is None or not trace.get("devices") \
            or not layer.get("traced_jobs"):
        return None
    seconds = seconds_matching(trace, EXCHANGE)
    if seconds <= 0:
        return None
    return seconds / trace["devices"] / layer["traced_jobs"] * 1e3
