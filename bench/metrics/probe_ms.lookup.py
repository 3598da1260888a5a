"""probe_ms.lookup: owner-probe device time per lookup batch.

Summed device time of the hash-find kernel's events
(``kernels/hash_probe.py::_find_kernel``) over the traced window,
averaged over chips, divided by the lookup batches served in it.
Moves ``lookup_p95_ms``.
"""

PATTERN = r"^_find_kernel$"


def read(ctx):
    steps = ctx.counters.get("steps", 0)
    if not steps or not ctx.trace.count(PATTERN):
        return None
    return ctx.trace.op_seconds(PATTERN) / steps * 1e3
