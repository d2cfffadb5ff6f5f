"""``plan_ms``: host milliseconds of ``counter.plan_for(g)`` (graph
statistics and the planner), mean per count, over the counts of the window.
Layer: front door and planner. Source: host clock (the driver's samples)."""


def read(ctx):
    samples = ctx.stats.get("plan_s") or []
    return 1e3 * sum(samples) / len(samples) if samples else None
