"""``hybrid_ingest_roofline``: a hybrid-state ingest block's share of the
chip's memory roofline: the bytes an exact ingest has to read
(``bench/hybrid_work.py``, from the hybrid counters of the blocks that ran
while the profiler recorded, over those blocks) over ``ingest_ms_per_block``
(the mean of the same blocks' runs) times the chip's HBM bandwidth. Cells
whose sessions carry no hybrid counters read nothing.
Layer: stream ingest core. Source: device trace."""
import importlib.util
import os


def _hybrid_work():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "hybrid_work.py")
    spec = importlib.util.spec_from_file_location("bench_hybrid_work", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(ctx):
    h = ctx.stats.get("hybrid_traced")
    ms = ctx.value("ingest_ms_per_block")
    if not h or not h.get("blocks") or not ms:
        return None
    need = _hybrid_work().hybrid_ingest_bytes(
        ctx.stats["words"], h["pairs_hub"], h["fill_mixed"], h["fill_tail"])
    return 100.0 * need / h["blocks"] / (ms * 1e-3 * ctx.peaks["hbm_bytes_per_s"])
