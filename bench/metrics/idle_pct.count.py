"""``idle_pct.count``: share of the traced window in which no operation ran
on the chip, in the resident cells (moves ``count_s``). Layer: device.
Source: device trace."""


def read(ctx):
    return ctx.trace.idle_pct()
