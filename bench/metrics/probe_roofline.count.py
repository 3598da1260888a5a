"""probe_roofline.count: the owner probe's share of its HBM roofline.

The least bytes are counted from the work, not from the kernel
(``bench/roofline.py::probe_least_bytes``): for each batch of the
window, the blocks its distinct keys touch under a uniform hash, read
and written once, plus the query rows in and an ok word out, per chip.
The least time is those bytes at the chip's peak HBM bandwidth
(``bench/peaks.json``); the share is that over the summed device time
of the insert kernel (``_insert_kernel``), in percent.  No operation
bounds it: the probe does no arithmetic worth counting.  Whatever
implements the probe later, the share reads the same work.  Moves
``ops_per_s``.
"""

PATTERN = r"^_insert_kernel$"


def read(ctx):
    t = ctx.trace.op_seconds(PATTERN)
    least = ctx.counters.get("probe_least_bytes", 0)
    if t <= 0 or not least or ctx.peaks is None:
        return None
    return least / (t * ctx.peaks["hbm_bytes_per_s"]) * 100.0
