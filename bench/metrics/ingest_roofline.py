"""``ingest_roofline``: a stream ingest block's share of the chip's memory
roofline: ``work.ingest_bytes``, the four row gathers per edge against each
live epoch that the count needs (16 * B * W_s * E bytes), over
``ingest_ms_per_block`` times the chip's HBM bandwidth. Source: device
trace."""


def read(ctx):
    ms = ctx.value("ingest_ms_per_block")
    if not ms:
        return None
    s = ctx.stats
    need = ctx.work.ingest_bytes(s["block_size"], s["n_nodes"], s["n_stages"], s["epochs"])
    return 100.0 * need / (ms * 1e-3 * ctx.peaks["hbm_bytes_per_s"])
