"""Multi-stream serving benchmarks: interleaved sessions vs sequential
streams, and the heavy-tailed FIFO vs fair-share+preemption scenario.

This is the paper's "graph dynamically generated" regime turned into a
serving workload: S edge streams arrive concurrently at one
``TriangleServer``; the ``StreamMultiplexer`` interleaves block ingest
across all of them over ONE shared compile cache.

``bench_serve`` (op = ``serve_multiplex``) verifies the two serving claims
and measures the cost of concurrency:

- correctness: interleaved counts are bit-identical to S sequential
  ``count_stream`` runs (asserted every rep);
- compile economics: S sessions with one block shape cost exactly ONE
  ingest trace — shared across sessions AND with the sequential path
  (asserted, and recorded as ``ingest_traces`` in the output rows);
- throughput: total wall-clock for all S streams, interleaved vs
  sequential. Same total work, same cache — multiplexing should cost ~0;
  the win is concurrency (S live streams per server instead of 1), not
  speed.

``bench_preempt`` (op = ``serve_preempt``) is the ROADMAP's 100-session
heavy-tailed scenario: a couple of WHALE streams whose bitset state pins
nearly the whole device budget, plus ~98 small streams. Under strict FIFO
the whales head-of-line-block everything — a small request's
time-to-first-count is the whales' entire runtime. Under
``policy="fair"`` the smalls open at higher priority, PREEMPT the whale
(checkpoint to host), drain in parallel, and the whale readmits
bit-identically afterwards — p50/p99 time-to-first-count collapse while
every count stays exact (asserted against sequential oracles). Both
policies drive the same backpressure-aware loop (feed only ACTIVE
sessions, ``next_sid`` picks who goes next), so the delta is pure
scheduling policy.

``bench_cluster`` (op = ``serve_cluster``) prices the multi-host tier:
the same mixed-session workload through one in-process multiplexer vs a
``ClusterServer`` routing to 2 worker SUBPROCESSES over the
length-prefixed socket protocol (per-block RPC + journaling overhead),
plus the cost of a forced mid-stream live migration
(checkpoint → evict → restore on a warm target; counts stay
bit-identical and — asserted from the workers' own trace counters — the
migration itself compiles NOTHING new).

``bench_async`` (op = ``serve_async``) prices the async double-buffered
session driver: S=32 mixed dense+windowed sessions driven round-robin,
synchronous mux vs ``prefetch_depth=2`` (background host re-blocking in a
bounded device-ready queue + donated-buffer ingest) vs prefetch with
adaptive block resizing — all against the single-stream sequential rate.
The tentpole target is ASSERTED on full runs: the async multiplex
sustains >= 90% of the single-stream ingest rate, and every mode's counts
are bit-identical.

Rows are MERGED into BENCH_kernels.json — all other ops' records are
preserved. ``--quick`` is the CI-cheap variant (4 streams / 24 sessions,
small graphs, interpret-safe CPU defaults).

Usage: PYTHONPATH=src python benchmarks/serve_bench.py [--quick]
           [--streams S] [--out F] [--skip-preempt] [--skip-multiplex]
           [--skip-cluster] [--skip-async]
"""
from __future__ import annotations

import argparse
import os
import statistics
import time

import jax
import numpy as np

from common import timed_ms

from repro.api import TriangleCounter
from repro.core.streaming import ingest_trace_count
from repro.core.triangle_ref import count_triangles_brute
from repro.graphs import generators as gen
from repro.serve.serve_loop import TriangleServer
from repro.utils import enable_compile_cache

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_kernels.json")


def build_streams(n_streams: int, n_nodes: int, m_target: int, block: int):
    """S distinct shuffled edge streams (ragged tails included) + their
    brute-force triangle counts."""
    density = m_target / (n_nodes * (n_nodes - 1) / 2)
    streams = []
    for i in range(n_streams):
        g = gen.gnp(n_nodes, density, seed=1000 + i)
        rng = np.random.default_rng(i)
        e = g.edges[rng.permutation(g.n_edges)]
        blocks = [e[j:j + block] for j in range(0, len(e), block)]
        streams.append((g, blocks, count_triangles_brute(g)))
    return streams


def bench_serve(*, quick: bool = False, n_streams: int | None = None,
                reps: int | None = None) -> list[dict]:
    S = n_streams or (4 if quick else 8)
    n, m, block = (256, 4096, 512) if quick else (1024, 65536, 8192)
    reps = reps or (3 if quick else 5)
    streams = build_streams(S, n, m, block)
    m_total = sum(len(g.edges) for g, _, _ in streams)
    shape = f"S{S}/n{n}/m{m_total}/b{block}"
    requests = [(n, blocks) for _, blocks, _ in streams]
    wants = [want for _, _, want in streams]

    server = TriangleServer()

    # -- trace economics, measured on the FRESH cache -----------------------
    traces0 = ingest_trace_count()
    inter = server.serve_streams(requests, block_size=block)
    traces_interleaved = ingest_trace_count() - traces0
    assert [r.item() for r in inter] == wants, "interleaved counts wrong"
    assert traces_interleaved == 1, \
        f"expected ONE shared ingest trace for {S} sessions, got {traces_interleaved}"

    traces0 = ingest_trace_count()
    seq = [server.serve_stream(n, blocks, block_size=block)
           for _, blocks, _ in streams]
    traces_sequential = ingest_trace_count() - traces0
    assert [r.item() for r in seq] == wants, "sequential counts wrong"
    assert traces_sequential == 0, "sequential reruns must reuse the session trace"
    for a, b in zip(inter, seq):
        assert np.asarray(a.count) == np.asarray(b.count)  # bit-identical

    # -- steady-state throughput (cache warm for both modes) ----------------
    n_blocks_total = sum(len(b) for _, b, _ in streams)

    def run_interleaved():
        return server.serve_streams(requests, block_size=block)

    def run_sequential():
        return [server.serve_stream(n, blocks, block_size=block)
                for _, blocks, _ in streams]

    records = []
    for method, fn, traces in (
            ("sequential_streams", run_sequential, traces_sequential),
            ("interleaved_sessions", run_interleaved, traces_interleaved)):
        # cache is already warm (parity passes above) — every rep is steady
        ms, out = timed_ms(fn, reps=reps, warmup=False,
                           sync=lambda rs: [r.count for r in rs])
        assert [r.item() for r in out] == wants  # lint: disable=R2 -- verifying the last rep's counts after its clock stopped
        records.append({
            "op": "serve_multiplex", "shape": shape, "method": method,
            "median_ms": round(ms, 3), "grid_steps": n_blocks_total,
            "ingest_traces": traces,
            "edges_per_s": round(m_total / (ms / 1e3)),
        })
        print(f"  {method:22s} {ms:9.1f} ms for {S} streams "
              f"({m_total} edges, {n_blocks_total} block dispatches, "
              f"{records[-1]['edges_per_s']:,} edges/s, "
              f"{traces} fresh ingest trace(s))")
    return records


def _drive(mux, sids, blocks, t0):
    """Backpressure-aware serving loop: close exhausted actives (recording
    each session's time-to-first-count), then feed whichever ACTIVE session
    ``next_sid`` picks — waiting sessions are never fed (no host buffering),
    they get their turn when admission restores/admits them. Returns
    {sid: (ttfc_s, CountResult)}."""
    done = {}
    pos = {sid: 0 for sid in sids}
    while len(done) < len(sids):
        for sid in sids:
            if sid not in done and pos[sid] >= len(blocks[sid]) \
                    and mux.status(sid) == "active":
                r = mux.close(sid)
                r.item()  # lint: disable=R2 -- TTFC is time-to-READY count, so the clock must stop on a completed device value, not a dispatched one
                done[sid] = (time.perf_counter() - t0, r)
        live = {sid for sid in sids
                if sid not in done and pos[sid] < len(blocks[sid])
                and mux.status(sid) == "active"}
        sid = mux.next_sid(candidates=live) if live else None
        if sid is not None:
            mux.feed(sid, blocks[sid][pos[sid]])
            pos[sid] += 1
    return done


def bench_preempt(*, quick: bool = False) -> list[dict]:
    """Heavy-tailed TTFC: FIFO vs fair-share+preemption over one budget."""
    from repro.api import Resources, TriangleCounter
    from repro.serve.sessions import StreamMultiplexer

    if quick:
        n_whales, whale_n, whale_m = 1, 1024, 12_000
        n_smalls, small_n, small_m = 23, 128, 600
    else:
        n_whales, whale_n, whale_m = 2, 2048, 30_000
        n_smalls, small_n, small_m = 98, 256, 2_000
    block = 1024
    whale_state = 4 * whale_n * (-(-whale_n // 32))   # n²/8 dense bitset
    small_state = 4 * small_n * (-(-small_n // 32))
    # one whale + 8 smalls fit; everything else must queue or preempt
    res = Resources(memory_bytes=whale_state + 8 * small_state, max_stages=1)

    def make(n, m, seed):
        g = gen.gnp(n, m / (n * (n - 1) / 2), seed=seed)
        rng = np.random.default_rng(seed)
        e = g.edges[rng.permutation(g.n_edges)]
        return [e[j:j + block] for j in range(0, len(e), block)]

    specs = ([(whale_n, make(whale_n, whale_m, 7000 + i), 0)
              for i in range(n_whales)] +
             [(small_n, make(small_n, small_m, 8000 + i), 1)
              for i in range(n_smalls)])
    S = len(specs)
    shape = (f"S{S}/whales{n_whales}x{whale_n}/smalls{n_smalls}x{small_n}"
             f"/b{block}")
    oracle_counter = TriangleCounter()
    oracles = [oracle_counter.count_stream(n, bs).item() for n, bs, _ in specs]

    records = []
    p99s = {}
    for policy in ("fifo", "fair"):
        # two passes: the first warms the (process-wide) ingest traces so
        # neither policy is charged compile time the other reuses
        for rep in ("warmup", "measured"):
            mux = StreamMultiplexer(
                TriangleCounter(res), res, block_size=block, policy=policy,
                # the store must hold every concurrently-preempted whale
                checkpoint_budget_bytes=2 * n_whales * whale_state)
            t0 = time.perf_counter()
            sids, blocks = [], {}
            for n, bs, prio in specs:  # whales arrive FIRST — the worst case
                sid = mux.open(n, priority=prio if policy == "fair" else 0)
                sids.append(sid)
                blocks[sid] = bs
            done = _drive(mux, sids, blocks, t0)
            total_ms = (time.perf_counter() - t0) * 1e3
        for sid, want, (n, _, _) in zip(sids, oracles, specs):
            got = done[sid][1].item()  # lint: disable=R2 -- post-run verification; every TTFC clock already stopped in _drive
            assert got == want, f"{policy} sid={sid} n={n}: {got} != {want}"
        ttfc = np.array(sorted(t * 1e3 for t, _ in done.values()))
        p50, p99 = np.percentile(ttfc, 50), np.percentile(ttfc, 99)
        p99s[policy] = p99
        method = "fifo" if policy == "fifo" else "fair_preempt"
        records.append({
            "op": "serve_preempt", "shape": shape, "method": method,
            "median_ms": round(float(p50), 3), "grid_steps": S,
            "p99_ms": round(float(p99), 3),
            "total_ms": round(total_ms, 3),
            "preemptions": mux.sched_stats["preemptions"],
            "restores": mux.sched_stats["restores"],
        })
        print(f"  {method:22s} TTFC p50 {p50:9.1f} ms  p99 {p99:9.1f} ms  "
              f"total {total_ms:9.1f} ms  "
              f"({mux.sched_stats['preemptions']} preemptions, "
              f"{mux.sched_stats['restores']} restores)")
    if not quick:
        assert p99s["fair"] < p99s["fifo"], (
            f"fair-share+preemption must beat FIFO p99 TTFC: "
            f"{p99s['fair']:.1f} vs {p99s['fifo']:.1f} ms")
    return records


def bench_async(*, quick: bool = False, n_streams: int | None = None) -> list[dict]:
    """Async double-buffered driver (op = ``serve_async``): S mixed
    (dense + sliding-window) sessions driven round-robin, synchronous mux
    vs ``prefetch_depth=2`` (background re-blocking + donated ingest) vs
    prefetch + adaptive block resizing — against the SINGLE-stream
    sequential rate as the ceiling. The tentpole target (asserted on full
    runs): S=32 concurrent async sessions sustain >= 90% of the
    single-stream ingest rate, i.e. host re-blocking overlapped with device
    ingest makes S-way concurrency nearly free. Counts are asserted
    bit-identical across all four drive modes every rep."""
    from repro.serve.sessions import StreamMultiplexer

    S = n_streams or (8 if quick else 32)
    n, m, block = (256, 2_000, 256) if quick else (512, 8_000, 1024)
    reps = 3 if quick else 5
    streams = build_streams(S, n, m, block)
    m_total = sum(len(g.edges) for g, _, _ in streams)
    shape = f"S{S}/n{n}/m{m_total}/b{block}/d2"
    counter = TriangleCounter()  # ONE compile cache across every mode
    windows = [3 if i % 4 == 3 else None for i in range(S)]

    def run_single():
        """The ceiling: each stream alone on the device, one after another
        — same total work, zero multiplexing."""
        mux = StreamMultiplexer(counter, block_size=block)
        out = []
        for i, (_, blocks, _) in enumerate(streams):
            sid = mux.open(n, window=windows[i])
            for j, b in enumerate(blocks):
                mux.feed(sid, b)
                if windows[i] and (j + 1) % 8 == 0:
                    mux.advance(sid)
            out.append(mux.close(sid))
        return out

    def make_concurrent(**mux_kwargs):
        def run():
            mux = StreamMultiplexer(counter, block_size=block, **mux_kwargs)
            sids = [mux.open(n, window=w) for w in windows]
            pos = [0] * S
            live = set(range(S))
            out = [None] * S
            while live:
                for i in sorted(live):
                    blocks = streams[i][1]
                    mux.feed(sids[i], blocks[pos[i]])
                    pos[i] += 1
                    if windows[i] and pos[i] % 8 == 0:
                        mux.advance(sids[i])
                    if pos[i] >= len(blocks):
                        live.discard(i)
                        # close as soon as the stream ends: the quiesce of
                        # THIS session's pipeline overlaps every other
                        # session's still-running feeds
                        out[i] = mux.close(sids[i])
            return out
        return run

    modes = [
        ("single_stream", run_single),
        ("sync_multiplex", make_concurrent()),
        ("async_multiplex", make_concurrent(prefetch_depth=2)),
        ("async_adaptive", make_concurrent(prefetch_depth=2,
                                           adaptive_block=True)),
    ]
    # correctness + warmup pass: every mode bit-identical to the first
    # (dense sessions additionally checked against brute force)
    ref = None
    for name, fn in modes:
        out = fn()
        counts = [r.item() for r in out]  # lint: disable=R2 -- untimed warmup/correctness pass; syncs are the point here
        for i, (g, _, want) in enumerate(streams):
            if windows[i] is None:
                assert counts[i] == want, f"{name} stream {i} wrong count"
        if ref is None:
            ref = counts
        else:
            assert counts == ref, f"{name} diverged from single_stream"

    records, rates = [], {}
    for name, fn in modes:
        ms, out = timed_ms(fn, reps=reps, warmup=False,
                           sync=lambda rs: [r.count for r in rs])
        assert [r.item() for r in out] == ref  # lint: disable=R2 -- verifying the last rep's counts after its clock stopped
        rates[name] = m_total / (ms / 1e3)
        records.append({
            "op": "serve_async", "shape": shape, "method": name,
            "median_ms": round(ms, 3),
            "grid_steps": sum(len(b) for _, b, _ in streams),
            "edges_per_s": round(rates[name]),
            "rate_vs_single": round(rates[name] / rates["single_stream"], 4),
        })
        print(f"  {name:22s} {ms:9.1f} ms for {S} streams "
              f"({records[-1]['edges_per_s']:,} edges/s, "
              f"{100 * records[-1]['rate_vs_single']:.1f}% of single-stream)")
    if not quick:
        ratio = rates["async_multiplex"] / rates["single_stream"]
        assert ratio >= 0.90, (
            f"S={S} async sessions must sustain >=90% of the single-stream "
            f"ingest rate, got {100 * ratio:.1f}%")
    return records


def _cluster_traces(server) -> int:
    """Sum of the worker processes' ingest-trace counters."""
    return sum(w.get("ingest_traces", 0) for w in server.stats()["workers"]
               if w.get("alive"))


def bench_cluster(*, quick: bool = False) -> list[dict]:
    """Multi-host tier: in-process multiplexer vs router + 2 worker
    subprocesses on the same mixed workload, plus live-migration cost.

    The cluster runs first, while this process holds no device: on a TPU
    host each worker owns one chip and the router none. The in-process
    baseline runs after the workers are reaped, so it is the only holder
    of the chip when it runs — call this before anything else in the
    process touches JAX's devices."""
    from repro.serve.serve_loop import ClusterServer

    S = 8 if quick else 16
    n, m, block = (256, 2_000, 256) if quick else (512, 8_000, 1024)
    reps = 3 if quick else 5
    streams = build_streams(S, n, m, block)
    m_total = sum(len(g.edges) for g, _, _ in streams)
    requests = [(n, blocks) for _, blocks, _ in streams]
    wants = [want for _, _, want in streams]
    shape = f"S{S}/n{n}/m{m_total}/b{block}/w2"
    n_blocks_total = sum(len(b) for _, b, _ in streams)

    def timed(method, server):
        ms, out = timed_ms(
            lambda: server.serve_streams(requests, block_size=block),
            reps=reps, warmup=False, sync=lambda rs: [r.count for r in rs])
        assert [r.item() for r in out] == wants
        records.append({
            "op": "serve_cluster", "shape": shape, "method": method,
            "median_ms": round(ms, 3), "grid_steps": n_blocks_total,
            "edges_per_s": round(m_total / (ms / 1e3)),
        })
        print(f"  {method:22s} {ms:9.1f} ms for {S} streams "
              f"({m_total} edges, {records[-1]['edges_per_s']:,} edges/s)")

    state = 4 * n * (-(-n // 32))  # dense bitset per session
    specs = [{"memory_bytes": S * state}, {"memory_bytes": S * state}]
    records = []
    with ClusterServer(specs, checkpoint_every_bytes=None) as srv:
        # warm (workers compile their shared ingest trace once)
        base = srv.serve_streams(requests, block_size=block)
        assert [r.item() for r in base] == wants, "cluster counts wrong"
        timed("cluster_2workers", srv)

        # forced mid-stream live migration: feed half, move one session to
        # the other worker, feed the rest — exact counts, zero new traces
        mig, traces0 = [], _cluster_traces(srv)
        for _ in range(min(reps, 3)):
            sids = [srv.open_stream(nn, block_size=block)
                    for nn, _ in requests]
            for sid, (_, blocks) in zip(sids, requests):
                for b in blocks[:len(blocks) // 2]:
                    srv.feed(sid, b)
            t0 = time.perf_counter()
            srv.migrate_stream(sids[0])
            mig.append((time.perf_counter() - t0) * 1e3)
            for sid, (_, blocks) in zip(sids, requests):
                for b in blocks[len(blocks) // 2:]:
                    srv.feed(sid, b)
            out = [srv.close_stream(sid) for sid in sids]
            assert [r.item() for r in out] == wants, "migrated counts wrong"  # lint: disable=R2 -- correctness check per migration rep; the migration clock stopped two lines up
        new_traces = _cluster_traces(srv) - traces0
        assert new_traces == 0, \
            f"live migration must compile nothing new, got {new_traces}"
        ms = statistics.median(mig)
        records.append({
            "op": "serve_cluster", "shape": shape, "method": "live_migration",
            "median_ms": round(ms, 3), "grid_steps": len(mig),
            "migrations": srv.stats()["migrations"],
            "ingest_traces": new_traces,
        })
        print(f"  {'live_migration':22s} {ms:9.1f} ms per migration "
              f"(checkpoint→evict→restore, {new_traces} new traces)")

    local = TriangleServer()
    ref = local.serve_streams(requests, block_size=block)  # warm
    for a, b in zip(ref, base):
        assert np.asarray(a.count) == np.asarray(b.count)  # bit-identical
    timed("single_process", local)
    return records


def merge_bench_json(records: list[dict], out_path: str = DEFAULT_OUT) -> str:
    """Append/refresh the serve rows in BENCH_kernels.json, preserving every
    other op's records — kernel_bench's writer owns the one merge
    implementation (incl. the corrupt-file recovery), so the two benches
    cannot drift."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from kernel_bench import write_bench_json

    return write_bench_json(records, out_path)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: 4 small streams, 3 reps")
    ap.add_argument("--streams", type=int, default=None,
                    help="number of concurrent streams (default 4 quick / 8 full)")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help=f"BENCH json to merge into (default {DEFAULT_OUT})")
    ap.add_argument("--skip-preempt", action="store_true",
                    help="skip the heavy-tailed FIFO-vs-fair scenario")
    ap.add_argument("--skip-multiplex", action="store_true",
                    help="skip the interleaved-vs-sequential scenario")
    ap.add_argument("--skip-cluster", action="store_true",
                    help="skip the multi-host router + worker-process scenario")
    ap.add_argument("--skip-async", action="store_true",
                    help="skip the async double-buffered driver scenario")
    args = ap.parse_args()
    enable_compile_cache()
    records = []
    if not args.skip_cluster:  # first: its workers need the chips free
        records += bench_cluster(quick=args.quick)
    print(f"serve_bench: backend={jax.default_backend()} quick={args.quick}")
    if not args.skip_multiplex:
        records += bench_serve(quick=args.quick, n_streams=args.streams)
    if not args.skip_preempt:
        records += bench_preempt(quick=args.quick)
    if not args.skip_async:
        records += bench_async(quick=args.quick)
    path = merge_bench_json(records, args.out)
    print(f"merged {len(records)} serve records into {path}")


if __name__ == "__main__":
    main()
