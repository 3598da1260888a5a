"""probe_ms.count: owner-probe device time per batch, k-mer counting.

Summed device time of the hash-insert kernel's events
(``kernels/hash_probe.py::_insert_kernel``) over the traced window,
averaged over chips, divided by the batches in the window.  A batch's
re-sends are part of it.  Moves ``ops_per_s``.
"""

PATTERN = r"^_insert_kernel$"


def read(ctx):
    batches = ctx.counters.get("batches", 0)
    if not batches or not ctx.trace.count(PATTERN):
        return None
    return ctx.trace.op_seconds(PATTERN) / batches * 1e3
