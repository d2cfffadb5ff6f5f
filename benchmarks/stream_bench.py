"""Hybrid-vs-dense streaming-state benchmark: the n²/8 wall, measured.

The dense streaming state pins ``4·n·ceil(n/32)`` bytes regardless of how
sparse the stream is; the degree-aware hybrid state (docs/STREAMING.md §7)
pins ``4·(H·W + n·(C+2))`` — linear in n. This bench runs ONE power-law
edge stream through both layouts and reports, per layout:

- ``median_ms`` — wall-clock to ingest the whole stream (blocked, padded);
- ``state_bytes`` — what a session would pin for its lifetime, from the
  same formulas the planner charges at admission;
- ``edges_per_s`` — raw-edge ingest rate derived from the median.

The two counts are asserted identical before anything is recorded (the
hybrid path additionally raises on any dropped endpoint), so every row in
the json is a verified-exact run. Rows (op = ``stream_hybrid``) are MERGED
into BENCH_kernels.json; other ops' records are preserved. ``--quick`` is
the CI-cheap variant (n=16k); the full run is the n=100k power-law stream,
where the dense state pins ~1.25 GB against the hybrid's ~0.44 GB at the
edge-count-informed sizing used here (admission under a tight budget sizes
far smaller still — 512 hub slots fit the same stream in ~33 MB, the
acceptance pin in tests/test_api_planner.py).

Usage: PYTHONPATH=src python benchmarks/stream_bench.py [--quick] [--out F]
"""
from __future__ import annotations

import argparse
import os
import sys

import jax
import numpy as np

from common import timed_ms

from repro.api import GraphStats, Resources, hybrid_sizing
from repro.core.streaming import count_stream, count_stream_hybrid
from repro.utils import enable_compile_cache

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_kernels.json")


def powerlaw_stream(n_nodes: int, n_edges: int, *, alpha: float = 0.85,
                    seed: int = 0) -> np.ndarray:
    """Raw (m, 2) int32 endpoints with Zipf-ish vertex popularity — hubs,
    duplicates and self-loops included, exactly what a generated stream
    feeds the session front door."""
    rng = np.random.default_rng(seed)
    w = np.arange(1, n_nodes + 1, dtype=np.float64) ** -alpha
    w /= w.sum()
    return np.stack([rng.choice(n_nodes, n_edges, p=w),
                     rng.choice(n_nodes, n_edges, p=w)], 1).astype(np.int32)


def bench_stream(*, quick: bool = False, reps: int | None = None) -> list[dict]:
    n, m = (16_384, 65_536) if quick else (100_000, 400_000)
    reps = reps or (3 if quick else 5)
    edges = powerlaw_stream(n, m, seed=n)

    stats = GraphStats(n_nodes=n, n_edges=m, replication_factor=0,
                       max_degree=0, max_fwd_degree=0, edges_in_memory=False)
    hyb = hybrid_sizing(stats, Resources.detect())
    assert hyb is not None, "bench sizes must be past the hybrid break-even"
    block = hyb.block_size
    blocks = [edges[i:i + block] for i in range(0, m, block)]
    n_blocks = -(-m // block)
    w = -(-n // 32)
    dense_bytes = 4 * n * w
    shape = f"n{n}/m{m}/b{block}"
    print(f"  stream: {shape}  dense state {dense_bytes / 1e6:.1f} MB, "
          f"hybrid {hyb.state_bytes / 1e6:.1f} MB "
          f"(H={hyb.hub_slots}, C={hyb.tail_capacity})")

    # correctness gate first, outside any timed region: bit-identical counts
    # (count_stream_hybrid raises on dropped endpoints, so a pass here means
    # the run was exact, not approximately exact)
    want = count_stream(n, blocks, block_size=block)
    got = count_stream_hybrid(n, blocks, hub_slots=hyb.hub_slots,
                              tail_capacity=hyb.tail_capacity,
                              hub_threshold=hyb.hub_threshold,
                              block_size=block)
    assert got == want, (got, want)

    records = []
    runs = (
        # the dense side re-pins the full n²/8 state every rep — one rep
        # keeps the full-size (1.25 GB) variant usable
        ("dense_bitset",
         lambda: count_stream(n, blocks, block_size=block),
         1 if not quick else reps, dense_bytes),
        ("hybrid_degree_aware",
         lambda: count_stream_hybrid(n, blocks, hub_slots=hyb.hub_slots,
                                     tail_capacity=hyb.tail_capacity,
                                     hub_threshold=hyb.hub_threshold,
                                     block_size=block),
         reps, hyb.state_bytes),
    )
    for method, fn, r, nbytes in runs:
        ms, out = timed_ms(fn, reps=r)
        assert out == want, (method, out, want)
        records.append({
            "op": "stream_hybrid", "shape": shape, "method": method,
            "median_ms": round(ms, 3), "grid_steps": n_blocks,
            "state_bytes": nbytes,
            "edges_per_s": int(m / (ms / 1e3)),
        })
        print(f"  {method:20s} {ms:9.1f} ms  {nbytes / 1e6:9.1f} MB pinned  "
              f"{records[-1]['edges_per_s']:>10,d} edges/s")
    records[-1]["bytes_ratio"] = round(dense_bytes / hyb.state_bytes, 1)
    print(f"  hybrid pins {records[-1]['bytes_ratio']}x fewer bytes")
    return records


def merge_bench_json(records: list[dict], out_path: str = DEFAULT_OUT) -> str:
    """kernel_bench's writer owns the one merge implementation — same
    pattern as serve_bench / stream_window_bench."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from kernel_bench import write_bench_json

    return write_bench_json(records, out_path)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: n=16k stream, 3 reps")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help=f"BENCH json to merge into (default {DEFAULT_OUT})")
    args = ap.parse_args()
    enable_compile_cache()
    print(f"stream_bench: backend={jax.default_backend()} quick={args.quick}")
    records = bench_stream(quick=args.quick)
    path = merge_bench_json(records, args.out)
    print(f"merged {len(records)} stream_hybrid records into {path}")


if __name__ == "__main__":
    main()
