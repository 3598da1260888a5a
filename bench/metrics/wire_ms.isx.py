"""wire_ms.isx: wire device time per ISx step.

The wire is built by the binning and slot kernels of
``kernels/binning.py`` (histogram, offsets, ragged slots, and, where
they run, the fused pack/place and row-mix kernels) and, on the TPU, by
XLA's scatter, which writes the rows into their slots: ops labelled by
the kernel's function name, or ``scatter``/``scatter-fusion``
(``bench/hlo_names.py``).  On the ISx path the same scatter family also
appends the arrivals to the queue's ring, so that append is counted
here too.  Gathers are not: they show in the breakdown.  Summed device time of those events
over the traced window, averaged over chips, per step.  Moves
``ops_per_s``.
"""

PATTERN = (r"^(_hist_kernel|_offsets_kernel|_ragged_slots_kernel"
           r"|_pack_rows_kernel|_place_rows_kernel|_row_mix_kernel)$"
           r"|^scatter")


def read(ctx):
    steps = ctx.counters.get("steps", 0)
    if not steps or not ctx.trace.count(PATTERN):
        return None
    return ctx.trace.op_seconds(PATTERN) / steps * 1e3
