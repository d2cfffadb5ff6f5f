"""``sweep_roofline``: the resident count's share of the chip's memory
roofline: ``work.bitset_count_bytes(n, m)``, the bytes a bitset count of
the graph needs at its own width, over ``resident_device_ms`` times the
chip's HBM bandwidth. The count moves 8 bytes per word of two rows and
does one AND and popcount on them, so bandwidth bounds it. Source: device
trace."""


def read(ctx):
    ms = ctx.value("resident_device_ms")
    if not ms:
        return None
    need = ctx.work.bitset_count_bytes(ctx.stats["n_nodes"], ctx.stats["n_edges"])
    return 100.0 * need / (ms * 1e-3 * ctx.peaks["hbm_bytes_per_s"])
