"""idle_share: the device's idle share of the traced window.

1 - (union of the device-op intervals inside the window) / the window,
averaged over chips; a fraction in [0, 1].  Moves ``ops_per_s``.
"""


def read(ctx):
    return ctx.trace.idle_share()
