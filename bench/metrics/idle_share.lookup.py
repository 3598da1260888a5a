"""idle_share.lookup: the device's idle share of the traced window,
lookup serving.

1 - (union of the device-op intervals inside the window) / the window,
averaged over chips; a fraction in [0, 1].  The window of the open loop
includes the time the loop waits for arrivals.  Moves
``lookup_p95_ms``.
"""


def read(ctx):
    return ctx.trace.idle_share()
