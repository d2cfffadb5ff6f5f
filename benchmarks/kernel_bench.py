"""Micro-benchmarks of the counting paths + the kernel perf trajectory.

Two outputs:

- the legacy ``run()`` rows (name, us_per_call, derived) consumed by
  benchmarks/run.py's CSV contract — the XLA paths the kernels replace 1:1;
- ``BENCH_kernels.json`` — the machine-readable perf trajectory started by
  the dead-block-elimination PR: one record per (op, shape, method) with the
  median wall-clock and the kernel grid-step count, seed baseline next to
  the optimized path so every later perf PR appends comparable numbers.

On hosts without a TPU the Pallas kernels run in interpret mode; their
absolute timings are not hardware numbers, but the grid-step counts are
exact and the interpret-mode wall-clock scales with them, so the dead-block
win is still visible end-to-end. Run with ``--quick`` for the CI smoke
variant (small shapes, 3 reps). On a TPU the kernels run compiled
(``repro.utils.interpret_mode`` decides from the backend).

Usage: PYTHONPATH=src python benchmarks/kernel_bench.py [--quick] [--out F]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import Plan, TriangleCounter
from repro.core.dynamic_pipeline import run_sequential, run_sequential_python
from repro.core.triangle_mapreduce import build_mapreduce_operands, _mapreduce_count
from repro.core.triangle_pipeline import (
    build_bitset_ring_operands,
    build_dense_ring_operands,
    count_triangles_dense,
    count_triangles_sparse,
    dense_ring_spec,
)
from repro.graphs.formats import degree_order, forward_adjacency_dense, forward_adjacency_padded
from repro.graphs import generators as gen
from repro.kernels.bitset_count.bitset_count import bitset_edge_count_per_edge_kernel
from repro.kernels.bitset_count.ops import bitset_edge_count, bitset_grid_steps
from repro.kernels.triangle_count.ops import triangle_count, triangle_count_grid_steps
from repro.utils import enable_compile_cache, interpret_mode

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_kernels.json")


def _median_ms(fn, *args, reps: int = 5) -> float:
    fn(*args)  # compile / warm caches
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(samples)


def _time(fn, *args, reps=1):
    return _median_ms(fn, *args, reps=max(reps, 1)) * 1e3  # µs, legacy contract


def bench_kernels(*, quick: bool = False, reps: int | None = None) -> list[dict]:
    """Seed-vs-optimized records for both triangle kernels and the scanned
    sequential runtime."""
    reps = reps or (3 if quick else 7)
    records: list[dict] = []

    # ---- dense triangle kernel: full grid (seed) vs live grid ----
    n, block = (256, 64) if quick else (512, 128)
    g = gen.gnp(n, 0.4, seed=n)
    u = jnp.asarray(forward_adjacency_dense(g))
    for method, live in (("full_grid_seed", False), ("live_grid", True)):
        ms = _median_ms(
            lambda live=live: triangle_count(u, block=block, live_grid=live),
            reps=reps,
        )
        records.append({
            "op": "triangle_count_kernel", "shape": f"{n}x{n}/b{block}",
            "method": method, "median_ms": round(ms, 3),
            "grid_steps": triangle_count_grid_steps(n, block=block, live_grid=live),
        })

    # ---- bitset edge-closure kernel: per-edge (seed) vs blocked tile ----
    gn = 128 if quick else 256
    gb = gen.gnp(gn, 0.4, seed=3)
    _, masks, edge_blocks = build_bitset_ring_operands(gb, 1)
    mask, eb = jnp.asarray(masks[0]), jnp.asarray(edge_blocks[0])
    b = int(eb.shape[0])
    seed_fn = jax.jit(partial(bitset_edge_count_per_edge_kernel,
                              interpret=interpret_mode()))
    runs = (
        ("per_edge_seed", lambda: seed_fn(mask, eb), b),
        ("blocked_tile128", lambda: bitset_edge_count(mask, eb, edge_tile=128),
         bitset_grid_steps(b, edge_tile=128)),
    )
    for method, fn, steps in runs:
        ms = _median_ms(fn, reps=reps)
        records.append({
            "op": "bitset_count_kernel", "shape": f"masks{mask.shape[0]}x{mask.shape[1]}/edges{b}",
            "method": method, "median_ms": round(ms, 3),
            "grid_steps": steps,
        })

    # ---- sequential pipeline runtime: python double loop (seed) vs scan ----
    sn, stages = (128, 4) if quick else (256, 8)
    gs = gen.gnp(sn, 0.4, seed=17)
    part, blocks = build_dense_ring_operands(gs, stages)
    spec = dense_ring_spec(part.rows_per_stage)
    blocks = jnp.asarray(blocks)
    for method, fn in (("python_loop_seed", run_sequential_python),
                       ("scanned_jit", run_sequential)):
        ms = _median_ms(lambda fn=fn: fn(spec, blocks, blocks, stages), reps=reps)
        records.append({
            "op": "run_sequential", "shape": f"n{sn}/S{stages}",
            "method": method, "median_ms": round(ms, 3),
            "grid_steps": stages * stages,  # (stage, block) visits either way
        })

    # counter_bench's reps means "number of benchmark graphs", not timing
    # repetitions — let it use its own defaults (4 quick / 8 full)
    records += counter_bench(quick=quick)
    records += stream_bench(quick=quick)
    return records


def stream_bench(*, quick: bool = False, reps: int | None = None) -> list[dict]:
    """Streaming-ingest trajectory on a 65k-edge stream: the seed per-edge
    ``lax.scan`` fold vs the two-phase blocked ingest vs the ring-sharded
    (4-stage, host-emulated) variant. ``grid_steps`` records sequential scan
    steps for the oracle and ingest dispatches (× stages when sharded) for
    the blocked paths — the blocked ingest collapses 65k dependent steps into
    8 dispatches, which is the whole point."""
    from repro.core.streaming import count_stream, count_stream_per_edge

    reps = reps or (3 if quick else 5)
    n, block = 2048, 8192
    # ~65k edges: the ISSUE's stream_bench case (density ≈ 65536 / C(n, 2))
    g = gen.gnp(n, 65536 / (n * (n - 1) / 2), seed=65)
    rng = np.random.default_rng(65)
    edges = g.edges[rng.permutation(g.n_edges)]
    blocks = [edges[i:i + block] for i in range(0, len(edges), block)]
    n_blocks = -(-len(edges) // block)
    stages = 4
    shape = f"n{n}/m{len(edges)}/b{block}"

    runs = (
        # the oracle is the slow side: one timing rep keeps --quick usable
        ("per_edge_scan_seed", lambda: count_stream_per_edge(n, blocks), 1,
         n_blocks * block),
        ("blocked_ingest", lambda: count_stream(n, blocks), reps, n_blocks),
        ("sharded_ring_s4", lambda: count_stream(n, blocks, n_stages=stages),
         reps, n_blocks * stages),
    )
    want = None
    records = []
    for method, fn, r, steps in runs:
        got = fn()
        want = got if want is None else want
        assert got == want, (method, got, want)  # cross-check while timing
        ms = _median_ms(fn, reps=r)
        records.append({
            "op": "stream_ingest", "shape": shape, "method": method,
            "median_ms": round(ms, 3), "grid_steps": steps,
        })
    return records


def counter_bench(*, quick: bool = False, reps: int | None = None) -> list[dict]:
    """Compile-cache trajectory of the unified API: a stream of graphs with
    DISTINCT node counts in one padded-shape bucket. The per-shape jit path
    (seed behavior of repeated ``count_triangles`` calls) retraces on every
    new shape; ``TriangleCounter`` pads to the bucket and traces once, so
    steady-state per-call latency is a cache hit. ``grid_steps`` records the
    number of traces taken over the run."""
    reps = reps or (4 if quick else 8)
    n0 = 96 if quick else 192
    ns = [n0 + 2 * i for i in range(reps)]  # all inside one power-of-two bucket
    graphs = [gen.gnp(n, 0.4, seed=n) for n in ns]
    shape = f"n{ns[0]}..{ns[-1]}/dense"
    records = []

    legacy = jax.jit(count_triangles_dense)
    samples = []
    for g in graphs:
        u = jnp.asarray(forward_adjacency_dense(g))
        t0 = time.perf_counter()
        int(legacy(u))
        samples.append((time.perf_counter() - t0) * 1e3)
    # one trace per distinct shape; _cache_size is private jax API, so fall
    # back to the shape count (equal by construction) if it disappears
    cache_size = getattr(legacy, "_cache_size", lambda: len(set(ns)))()
    records.append({
        "op": "triangle_counter", "shape": shape, "method": "per_shape_retrace_seed",
        "median_ms": round(statistics.median(samples), 3),
        "grid_steps": cache_size,
    })

    counter = TriangleCounter()
    p = Plan(method="dense", reason="counter_bench fixed dense plan")
    samples = []
    for g in graphs:
        t0 = time.perf_counter()
        counter.count(g, plan=p).item()  # lint: disable=R2 -- each iteration IS one latency sample; .item() is its stop-clock sync
        samples.append((time.perf_counter() - t0) * 1e3)
    records.append({
        "op": "triangle_counter", "shape": shape, "method": "counter_cache_hit",
        "median_ms": round(statistics.median(samples), 3),
        "grid_steps": counter.cache_info["traces"],
    })
    return records


def write_bench_json(records: list[dict], out_path: str = DEFAULT_OUT) -> str:
    out_path = os.path.abspath(out_path)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    # preserve rows owned by other benches (e.g. serve_bench's
    # serve_multiplex records) — each bench refreshes only its own ops
    ours = {r["op"] for r in records}
    foreign: list[dict] = []
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                foreign = [r for r in json.load(f).get("records", [])
                           if r.get("op") not in ours]
        except (json.JSONDecodeError, OSError):
            foreign = []
    payload = {
        "schema": ["op", "shape", "method", "median_ms", "grid_steps"],
        "backend": jax.default_backend(),
        "records": records + foreign,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    return out_path


def run(verbose: bool = True) -> list[dict]:
    """Legacy CSV rows for benchmarks/run.py (XLA paths, µs/call)."""
    rows = []
    for n, p in [(512, 0.3), (1024, 0.5)]:
        g = gen.gnp(n, p, seed=n)
        u = jnp.asarray(forward_adjacency_dense(g))
        us_dense = _time(lambda u=u: count_triangles_dense(u))
        rank = degree_order(g)
        nbrs, _ = forward_adjacency_padded(g, rank)
        ru, rv = rank[g.edges[:, 0]], rank[g.edges[:, 1]]
        edges = jnp.asarray(np.stack([np.minimum(ru, rv), np.maximum(ru, rv)], 1))
        us_sparse = _time(lambda: count_triangles_sparse(jnp.asarray(nbrs), edges))
        mr_nbrs, mr_keys, _ = build_mapreduce_operands(g)
        us_mr = _time(lambda: _mapreduce_count(jnp.asarray(mr_nbrs), jnp.asarray(mr_keys),
                                               n=n, node_batch=256))
        rows.append({"name": f"tri_dense_n{n}_p{p}", "us_per_call": us_dense,
                     "derived": f"m={g.n_edges}"})
        rows.append({"name": f"tri_sparse_n{n}_p{p}", "us_per_call": us_sparse,
                     "derived": f"m={g.n_edges}"})
        rows.append({"name": f"tri_mapreduce_n{n}_p{p}", "us_per_call": us_mr,
                     "derived": f"rf~{int((g.degrees()**2).sum())}"})
        if verbose:
            print(f"  n={n} p={p}: dense {us_dense/1e3:8.1f}ms  sparse {us_sparse/1e3:8.1f}ms  "
                  f"mapreduce {us_mr/1e3:8.1f}ms")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: small shapes, 3 reps")
    ap.add_argument("--out", default=DEFAULT_OUT, help="BENCH_kernels.json path")
    ap.add_argument("--skip-legacy", action="store_true",
                    help="only the kernel trajectory, skip the XLA-path table")
    args = ap.parse_args()
    enable_compile_cache()

    records = bench_kernels(quick=args.quick)
    path = write_bench_json(records, args.out)
    print(f"wrote {len(records)} records -> {path}")
    for r in records:
        print(f"  {r['op']:24s} {r['shape']:28s} {r['method']:18s} "
              f"{r['median_ms']:9.2f} ms  {r['grid_steps']:6d} grid steps")
    if not (args.quick or args.skip_legacy):
        print("\nXLA-path table (µs/call):")
        run()


if __name__ == "__main__":
    main()
