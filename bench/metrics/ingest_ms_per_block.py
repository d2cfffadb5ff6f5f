"""``ingest_ms_per_block``: device milliseconds per block of stream
ingest: the summed durations of the ingest executables' runs in the traced
window over the number of those runs, mean over chips. Every run ingests
one block. Layer: stream ingest core. Source: device trace."""

# The XLA module names of the ingest executables (``core/streaming.py``):
# ``jit__ingest_block_impl`` and ``jit__ingest_block_windowed_impl`` on one
# chip; on a mesh the ingest steps are jitted by
# ``dynamic_pipeline.ShardedStateStream.jit_step`` as
# ``jit_ingest_block_mesh`` and ``jit_ingest_block_windowed_mesh``, the only
# shard_map executables a stream cell runs (``jit_stage_fn`` before the
# program named its steps).
INGEST = r"ingest|^jit_stage_fn$"


def read(ctx):
    runs = ctx.trace.module_runs(INGEST)
    per_chip = [sum(e.end - e.start for e in evs) / len(evs)
                for evs in runs.values() if evs]
    if not per_chip:
        return None
    return sum(per_chip) / len(per_chip) / 1e6
