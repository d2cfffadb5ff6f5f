"""``idle_pct.stream``: share of the traced window in which no operation
ran on a chip, mean over chips, in the stream cells (moves
``edges_per_s``). Layer: device. Source: device trace."""


def read(ctx):
    return ctx.trace.idle_pct()
