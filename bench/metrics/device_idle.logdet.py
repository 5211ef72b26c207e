"""Device idle share of the logdet cells: 100 * (1 - busy / window), busy
the union of device op intervals in the traced window."""


def read(ctx):
    return ctx.trace.idle_pct()
