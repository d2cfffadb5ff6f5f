#!/usr/bin/env python3
"""Smoke test of the triangle counter on TPU: its main paths, end to end.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the paths that exist only across chips

One chip runs the paper's resident comparison and the stream sessions:

- ``TriangleCounter`` on the Table 1 graphs DSJC.9 (n = 1,000) and FNA.1
  (n = 10,000, 10M edges) at their published sizes: every method by plan
  and the planner's own choice on DSJC.9, the planner's choice on FNA.1;
- ``TriangleServer`` stream sessions (``open_stream`` / ``feed`` /
  ``close_stream``): a 100,000-node, 400,000-edge power-law stream into the
  dense bitset state and into the hybrid state, a 4-epoch sliding window,
  and streams small enough for the ``bitset_count`` kernels, with the same
  stream counted again on the XLA path.

``--chips 4`` runs only what needs four chips: a ``ClusterServer`` with two
workers on distinct chips (a live migration, a killed worker, an exact
close), then a ring-sharded stream of 262,144 nodes over
``make_ring_mesh(4)``. This process holds no chip while the workers run.

Every phase compares its count with an independent host count (numpy or
``scipy.sparse``) and logs its route: the Pallas kernels traced during the
phase (compiled or interpreted) or XLA. These log lines are not metrics.
The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script exits non-zero, without that line, when JAX finds no TPU, when
any phase fails or miscounts, or when the ``repro`` package is not beside
it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------------------
# Independent host counts and inputs
# --------------------------------------------------------------------------
def simple_edges(n: int, edges):
    """Canonical (lo, hi) int64 arrays of the simple graph ``edges`` spans:
    self-loops, duplicates and reversed copies dropped."""
    import numpy as np

    e = np.asarray(edges, np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    key = np.unique(np.minimum(e[:, 0], e[:, 1]) * n + np.maximum(e[:, 0], e[:, 1]))
    return key // n, key % n


def host_count_sparse(n: int, edges) -> int:
    """Triangles by ``scipy.sparse``: edges oriented from lower to higher
    degree rank, then Σ (L @ L) ⊙ L — each triangle counted once."""
    import numpy as np
    import scipy.sparse as sp

    lo, hi = simple_edges(n, edges)
    deg = np.bincount(lo, minlength=n) + np.bincount(hi, minlength=n)
    rank = np.empty(n, np.int64)
    rank[np.argsort(deg, kind="stable")] = np.arange(n)
    a, b = rank[lo], rank[hi]
    lower = sp.csr_matrix((np.ones(len(a), np.int64),
                           (np.minimum(a, b), np.maximum(a, b))), shape=(n, n))
    return int((lower @ lower).multiply(lower).sum())


def host_count_dense(n: int, edges) -> int:
    """Triangles by a numpy (BLAS) matmul: Σ (U @ U) ⊙ U over the strictly
    upper adjacency U. Exact: every entry is an integer below 2^24 in f32,
    and the masked sum is taken in f64."""
    import numpy as np

    lo, hi = simple_edges(n, edges)
    u = np.zeros((n, n), np.float32)
    u[lo, hi] = 1.0
    return int(((u @ u) * u).sum(dtype=np.float64))


def powerlaw_stream(n: int, m: int, *, seed: int, alpha: float = 0.85):
    """(m, 2) int32 raw stream endpoints with Zipf-like vertex popularity,
    duplicates and self-loops included (the law of
    ``benchmarks/stream_bench.powerlaw_stream``)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    w = np.arange(1, n + 1, dtype=np.float64) ** -alpha
    w /= w.sum()
    return np.stack([rng.choice(n, m, p=w), rng.choice(n, m, p=w)],
                    1).astype(np.int32)


def chunks(edges, size: int):
    return [edges[i:i + size] for i in range(0, len(edges), size)]


# --------------------------------------------------------------------------
# Phase bookkeeping
# --------------------------------------------------------------------------
class Smoke:
    """Runs phases, logs each one's route, count and oracle, and remembers
    which failed; a failed phase does not stop the phases after it."""

    def __init__(self):
        self.failed: list[str] = []

    def run(self, name: str, fn) -> None:
        """``fn() -> (count, oracle, note)``; the route is read from the
        kernel traces the phase recorded."""
        from repro.kernels import kernel_traces

        k0 = len(kernel_traces())
        t0 = time.perf_counter()
        try:
            count, oracle, note = fn()
        except Exception as e:  # noqa: BLE001 — logged, counted, and the run fails
            traceback.print_exc()
            log(f"[{name}] FAILED: {type(e).__name__}: {e}")
            self.failed.append(name)
            return
        kernels = sorted({f"pallas:{k}({mode})" for k, mode in kernel_traces()[k0:]})
        route = ", ".join(kernels) or "xla"
        ok = int(count) == int(oracle)
        log(f"[{name}] route={route} {note} count={int(count)} "
            f"oracle={int(oracle)} {'OK' if ok else 'MISMATCH'} "
            f"({time.perf_counter() - t0:.1f} s with compiles)")
        if not ok:
            self.failed.append(name)

    def finish(self) -> None:
        if self.failed:
            raise SmokeFailure(f"phases failed: {', '.join(self.failed)}")


def tpu_devices(n_chips: int) -> list:
    """The TPU devices, or :class:`SmokeFailure` when JAX finds fewer than
    ``n_chips`` of them (a CPU-only JAX counts as none)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n_chips:
        raise SmokeFailure(
            f"needs {n_chips} TPU chip(s); JAX finds {len(devices)} "
            f"{devices[0].platform} device(s)")
    return devices


def device_line(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def log_devices(devices) -> None:
    for d in devices:
        limit = (d.memory_stats() or {}).get("bytes_limit")
        log(f"device {d.id}: platform={d.platform} kind={d.device_kind} "
            f"bytes_limit={limit}")


def plan_note(p, stats: dict | None = None) -> str:
    """The plan's method and backend switch; for streams also the state
    layout, ring width, window and the block size the session ran."""
    note = f"method={p.method}"
    if p.method == "stream":
        block = (stats or {}).get("block_size", p.block_size)
        note += (f" layout={p.state_layout} block={block} "
                 f"stages={p.n_stages} window={p.window_epochs}")
    return note + f" use_kernel={p.use_kernel}"


# --------------------------------------------------------------------------
# One chip
# --------------------------------------------------------------------------
RESIDENT_METHODS = ("dense", "ring", "sparse", "bitset_ring", "mapreduce")


def resident_phases(smoke: Smoke, *, scale: float = 1.0) -> None:
    """The paper's comparison through ``TriangleCounter``: every method by
    plan, then the planner's choice, on DSJC.9; the planner's choice on
    FNA.1. FNA.1's size admits counts past 2^31 (its edge count alone
    bounds them at ~1.5e10), so it is counted with 64-bit counts, as the
    counter's capacity guard requires."""
    import jax

    from repro.api import GraphStats, TriangleCounter, plan
    from repro.graphs.datasets import load

    counter = TriangleCounter()
    log(f"resources: {counter.resources}")
    g = load("DSJC.9", scale=scale, seed=0)
    want = host_count_dense(g.n_nodes, g.edges)
    stats = GraphStats.from_graph(g)
    log(f"DSJC.9: n={g.n_nodes} m={g.n_edges}")

    def by_plan(method):
        def go():
            p = plan(stats, counter.resources, allow={method})
            res = counter.count(g, plan=p)
            return res.item(), want, plan_note(p)
        return go

    for method in RESIDENT_METHODS:
        smoke.run(f"DSJC.9 {method}", by_plan(method))

    def auto():
        res = counter.count(g)
        hit = res.stats["cache"]["hit"]
        return (res.item(), want,
                f"planner {plan_note(res.plan)}"
                + (" (cache hit: the executable of that method's phase)"
                   if hit else ""))

    smoke.run("DSJC.9 planner", auto)

    def fna():
        with jax.enable_x64(True):
            h = load("FNA.1", scale=scale, seed=0)
            log(f"FNA.1: n={h.n_nodes} m={h.n_edges}")
            res = TriangleCounter().count(h)
            count = res.item()
            return (count, host_count_dense(h.n_nodes, h.edges),
                    f"planner {plan_note(res.plan)} x64")

    smoke.run("FNA.1 planner", fna)


def stream_phases(smoke: Smoke, *, seed: int, n: int = 100_000,
                  m: int = 400_000, window_n: int = 65_536,
                  kernel_n: tuple = (8192, 4096), kernel_m: int = 200_000,
                  kernel_block: int = 32_768, hybrid_budget: int = 256 << 20,
                  feed: int = 50_000) -> None:
    """Stream sessions through ``TriangleServer``."""
    import numpy as np

    from repro.api import Resources
    from repro.serve.serve_loop import TriangleServer

    def session(server, n_nodes, edges, **open_kw):
        sid = server.open_stream(n_nodes, **open_kw)
        for c in chunks(edges, feed):
            server.feed(sid, c)
        res = server.close_stream(sid)
        return res.item(), plan_note(res.plan, res.stats)

    edges = powerlaw_stream(n, m, seed=seed)
    want = host_count_sparse(n, edges)

    def dense():
        count, note = session(TriangleServer(), n, edges)
        return count, want, note

    def hybrid():
        budget = Resources.detect(memory_bytes=hybrid_budget)
        count, note = session(TriangleServer(budget), n, edges)
        return count, want, note + f" budget={hybrid_budget}"

    smoke.run(f"stream n={n} m={m} dense state", dense)
    smoke.run(f"stream n={n} m={m} hybrid state", hybrid)

    def windowed(window=4, n_epochs=6):
        lo, hi = simple_edges(window_n, powerlaw_stream(window_n, 6 * feed,
                                                        seed=seed + 1))
        distinct = np.stack([lo, hi], 1).astype(np.int32)
        distinct = distinct[np.random.default_rng(seed).permutation(len(distinct))]
        epochs = np.array_split(distinct, n_epochs)
        server = TriangleServer()
        sid = server.open_stream(window_n, window=window)
        for t, ep in enumerate(epochs):
            if t:
                server.advance_stream(sid)
            for c in chunks(ep, feed):
                server.feed(sid, c)
        res = server.close_stream(sid)
        live = np.concatenate(epochs[-window:])
        return (res.item(), host_count_sparse(window_n, live),
                plan_note(res.plan, res.stats) + f" epochs={n_epochs}")

    smoke.run(f"stream n={window_n} window=4", windowed)

    for i, kn in enumerate(kernel_n):
        kedges = powerlaw_stream(kn, kernel_m, seed=seed + 2 + i)
        kwant = host_count_sparse(kn, kedges)
        counts = {}

        def kernel(kn=kn, kedges=kedges, kwant=kwant, block=kernel_block):
            count, note = session(TriangleServer(), kn, kedges, block_size=block)
            counts[block] = count
            return count, kwant, note

        smoke.run(f"stream n={kn} block={kernel_block} (kernel gate)", kernel)
        if i == 0:  # the same stream on the XLA path: a block past the gate
            xla_block = 2 * kernel_block

            def xla(kn=kn, kedges=kedges, block=xla_block):
                count, note = session(TriangleServer(), kn, kedges,
                                      block_size=block)
                return count, counts.get(kernel_block, -1), (
                    note + " oracle=the kernel-routed count")

            smoke.run(f"stream n={kn} block={xla_block} (XLA path)", xla)


def run_one_chip(seed: int) -> dict:
    from repro.utils import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    devices = tpu_devices(1)
    log_devices(devices)
    smoke = Smoke()
    resident_phases(smoke)
    stream_phases(smoke, seed=seed)
    smoke.finish()
    return device_line(devices)


# --------------------------------------------------------------------------
# Four chips
# --------------------------------------------------------------------------
def cluster_phase(smoke: Smoke, *, seed: int, n: int = 32_768,
                  m: int = 150_000, feed: int = 12_500) -> None:
    """Two workers, one chip each: two sessions, a live migration, a killed
    worker, exact closes. Runs while this process holds no chip."""
    from repro.serve.serve_loop import ClusterServer

    streams = [powerlaw_stream(n, m, seed=seed + 10 + i) for i in range(2)]
    wants = [host_count_sparse(n, e) for e in streams]

    def go():
        with ClusterServer([{}, {}]) as srv:
            for i, w in enumerate(srv.router.workers):
                log(f"worker {i}: pid={w.pid} {w.resources}")
            sids = [srv.open_stream(n) for _ in streams]
            parts = [chunks(e, feed) for e in streams]
            half = len(parts[0]) // 2
            for sid, ps in zip(sids, parts):
                for c in ps[:half]:
                    srv.feed(sid, c)
            src = srv.router.worker_of(sids[0])
            dst = srv.migrate_stream(sids[0])
            log(f"migrated session {sids[0]}: worker {src} -> {dst}")
            for sid, ps in zip(sids, parts):
                for c in ps[half:half + 2]:
                    srv.feed(sid, c)
            victim = srv.router.worker_of(sids[0])
            srv.router.workers[victim].proc.kill()
            log(f"killed worker {victim} (pid "
                f"{srv.router.workers[victim].pid})")
            for sid, ps in zip(sids, parts):
                for c in ps[half + 2:]:
                    srv.feed(sid, c)
            counts = [srv.close_stream(sid).item() for sid in sids]
            stats = srv.stats()
            log(f"cluster stats: migrations={stats['migrations']} "
                f"worker_deaths={stats['worker_deaths']} "
                f"resurrections={stats['resurrections']}")
            if stats["worker_deaths"] != 1 or stats["migrations"] < 1:
                raise SmokeFailure(f"cluster did not migrate and fail over: {stats}")
        return (sum(counts), sum(wants),
                f"sessions={len(sids)} counts={counts} oracles={wants}")

    smoke.run(f"cluster 2 workers n={n} migrate+kill", go)


def mesh_phase(smoke: Smoke, *, seed: int, n: int = 262_144,
               m: int = 1_000_000, block: int = 16_384,
               feed: int = 100_000) -> None:
    """A ring-sharded stream over ``make_ring_mesh(4)``: the bitset state
    (n²/8 = 8.6 GB at n = 262,144) lives as one 2.15 GB column shard per
    chip."""
    from repro.api import Plan, TriangleCounter
    from repro.api.planner import backend_exec_flags
    from repro.launch.mesh import make_ring_mesh

    edges = powerlaw_stream(n, m, seed=seed + 20)
    want = host_count_sparse(n, edges)

    def go():
        mesh = make_ring_mesh(4)
        counter = TriangleCounter(mesh=mesh)
        p = Plan(method="stream", n_stages=4, block_size=block,
                 **backend_exec_flags(counter.resources))
        session = counter.open_stream(n, plan=p)
        held = session.state["adj"].sharding.device_set
        if len(held) != 4:
            raise SmokeFailure(f"sharded state spans {len(held)} devices")
        for c in chunks(edges, feed):
            session.feed(c)
        res = session.finalize()
        ids = sorted(d.id for d in mesh.devices.flat)
        return (res.item(), want, plan_note(p, res.stats)
                + f" mesh={ids} state_devices={len(held)}")

    smoke.run(f"mesh stream n={n} m={m} stages=4", go)


def run_four_chips(seed: int) -> dict:
    from repro.serve.cluster.client import host_tpu_chips
    from repro.utils import enable_compile_cache

    if host_tpu_chips() < 4:  # found without loading the TPU runtime
        raise SmokeFailure(f"needs 4 TPU chips; this host shows "
                           f"{host_tpu_chips()} to JAX")
    log(f"compile cache: {enable_compile_cache()}")
    smoke = Smoke()
    cluster_phase(smoke, seed=seed)  # before this process touches a chip
    devices = tpu_devices(4)
    log_devices(devices)
    mesh_phase(smoke, seed=seed)
    smoke.finish()
    return device_line(devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: resident plans and stream sessions on one chip; "
                         "4: the cluster and the ring-sharded stream only")
    ap.add_argument("--seed", type=int, default=0, help="seed of the streams")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        log(f"FAIL: no repro package under {SRC}; run from a checkout")
        return 2
    sys.path.insert(0, SRC)
    try:
        run = run_four_chips if args.chips == 4 else run_one_chip
        device = run(args.seed)
    except SmokeFailure as e:
        log(f"FAIL: {e}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
