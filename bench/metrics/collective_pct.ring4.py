"""``collective_pct.ring4``: share of the traced window in which a
collective (all-reduce, all-gather, collective-permute, reduce-scatter,
all-to-all, or a ``psum`` under its JAX name) ran on a chip, mean over the chips, in the ring-sharded cell
(moves ``edges_per_s``). Layer: device. Source: device trace."""

COLLECTIVE = r"all-reduce|all-gather|collective-permute|reduce-scatter|all-to-all|psum"


def read(ctx):
    pct = ctx.trace.busy_pct_of_ops(COLLECTIVE)
    return pct if pct else None
