"""``resident_device_ms``: device milliseconds per resident count, the union
of the chip's operation intervals inside each ``bench.count`` span that
lies wholly in the traced window, mean over those counts. Layer: resident
counting core. Source: device trace."""


def read(ctx):
    counts = ctx.trace.spans_named("bench.count")
    if not counts:
        return None
    busy = [ctx.trace.busy_in(s) for s in counts]
    ms = 1e3 * sum(busy) / len(busy)
    return ms if ms > 0 else None
