"""Sliding-window streaming benchmark: windowed ingest + expiry cost.

Measures the three costs of the epoch-ring design (docs/STREAMING.md §5)
on a generated edge stream of T epochs under a window of E epochs:

- ``windowed_ingest``: total wall-clock to ingest the whole stream through
  ``ingest_block_windowed`` (E age-cumulative sweeps per block), with the
  one-trace-across-epochs contract asserted;
- ``unbounded_ingest``: the same stream through the unbounded
  ``ingest_block`` — the ×E sweep overhead the window pays for deletions;
- ``expire_epoch``: median cost of ONE window slide (a single epoch-slot
  clear — the design's whole point: O(state/E) bytes written, zero edges
  touched);
- ``recount_window``: what a slide would cost WITHOUT the ring — re-ingest
  the live window's epochs from scratch (the from-scratch alternative the
  epoch ring replaces).

Every run is asserted bit-identical to the python recount oracle from
``tests/test_windowed_stream.py``. Rows (op = ``stream_window``) are MERGED
into BENCH_kernels.json — all other ops' records are preserved. ``--quick``
is the CI-cheap variant.

Usage: PYTHONPATH=src python benchmarks/stream_window_bench.py [--quick]
           [--window E] [--out F]
"""
from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

import jax
import numpy as np

from common import timed_ms

from repro.core import streaming
from repro.utils import enable_compile_cache

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "tests"))
from test_windowed_stream import windowed_oracle  # noqa: E402  (the oracle)

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_kernels.json")


def build_epochs(n_nodes: int, n_epochs: int, edges_per_epoch: int, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, n_nodes, size=(edges_per_epoch, 2)).astype(np.int32)
            for _ in range(n_epochs)]


def bench_window(*, quick: bool = False, window: int | None = None,
                 reps: int | None = None) -> list[dict]:
    E = window or 4
    n, n_epochs, m_epoch, block = ((256, 8, 2048, 512) if quick
                                   else (1024, 12, 16384, 4096))
    reps = reps or (3 if quick else 5)
    epochs = build_epochs(n, n_epochs, m_epoch, seed=7)
    m_total = n_epochs * m_epoch
    shape = f"n{n}/E{E}/T{n_epochs}/m{m_total}/b{block}"
    want = windowed_oracle(n, epochs, E)
    records = []

    # -- windowed ingest (and the trace contract) ---------------------------
    traces0 = streaming.ingest_trace_count()
    got = streaming.count_windowed_stream(n, [[e] for e in epochs], E,
                                          block_size=block)
    fresh_traces = streaming.ingest_trace_count() - traces0
    assert got == want, f"windowed count {got} != oracle {want}"
    assert fresh_traces <= 1, \
        f"expected ONE ingest trace across {n_epochs} epochs, got {fresh_traces}"

    def run_windowed():
        return streaming.count_windowed_stream(n, [[e] for e in epochs], E,
                                               block_size=block)

    def run_unbounded():
        return streaming.count_stream(n, [e for e in epochs], block_size=block)

    for method, fn, check in (("windowed_ingest", run_windowed, want),
                              ("unbounded_ingest", run_unbounded, None)):
        samples = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            samples.append((time.perf_counter() - t0) * 1e3)
            if check is not None:
                assert out == check
        ms = statistics.median(samples)
        records.append({
            "op": "stream_window", "shape": shape, "method": method,
            "median_ms": round(ms, 3),
            "grid_steps": n_epochs * (m_epoch // block),
            "edges_per_s": round(m_total / (ms / 1e3)),
        })
        print(f"  {method:18s} {ms:9.1f} ms  ({m_total} edges, "
              f"{records[-1]['edges_per_s']:,} edges/s)")

    # -- expiry: one slot clear vs re-ingesting the live window -------------
    state = streaming.init_windowed_state(n, E)
    for e in epochs[:E]:
        for b in streaming.padded_blocks([e], n, block):
            state = streaming.ingest_block_windowed(state, b)
    jax.block_until_ready(state["epochs"])
    cell = [state]  # expire chains: each sample slides the previous state

    def expire_once():
        cell[0] = streaming.expire_epoch(cell[0])
        return cell[0]["epochs"]

    ms_expire, _ = timed_ms(expire_once, reps=max(reps * 4, 10), warmup=False)
    state = cell[0]
    records.append({
        "op": "stream_window", "shape": shape, "method": "expire_epoch",
        "median_ms": round(ms_expire, 3), "grid_steps": 1,
    })
    print(f"  {'expire_epoch':18s} {ms_expire:9.3f} ms  (one slot clear)")

    def recount_live_window():
        # the ring-free alternative: rebuild the window's count from its
        # E live epochs on every slide
        return streaming.count_stream(n, [e for e in epochs[:E]],
                                      block_size=block)

    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        recount_live_window()
        samples.append((time.perf_counter() - t0) * 1e3)
    ms_recount = statistics.median(samples)
    records.append({
        "op": "stream_window", "shape": shape, "method": "recount_window",
        "median_ms": round(ms_recount, 3), "grid_steps": E * (m_epoch // block),
        "expiry_speedup": round(ms_recount / max(ms_expire, 1e-6), 1),
    })
    print(f"  {'recount_window':18s} {ms_recount:9.1f} ms  "
          f"(the from-scratch alternative: {records[-1]['expiry_speedup']}x "
          f"an epoch-slot clear)")
    return records


def merge_bench_json(records: list[dict], out_path: str = DEFAULT_OUT) -> str:
    """kernel_bench's writer owns the one merge implementation — see
    serve_bench for the same pattern."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from kernel_bench import write_bench_json

    return write_bench_json(records, out_path)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: small stream, 3 reps")
    ap.add_argument("--window", type=int, default=None,
                    help="window width in epochs (default 4)")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help=f"BENCH json to merge into (default {DEFAULT_OUT})")
    args = ap.parse_args()
    enable_compile_cache()
    print(f"stream_window_bench: backend={jax.default_backend()} "
          f"quick={args.quick}")
    records = bench_window(quick=args.quick, window=args.window)
    path = merge_bench_json(records, args.out)
    print(f"merged {len(records)} stream_window records into {path}")


if __name__ == "__main__":
    main()
