"""Tenants of one ``TriangleServer``, each streaming whole graphs through
stream sessions in a closed loop: open a session, feed the graph's raw
tuples in chunks, close it and wait for its count on the host, open the
next. The tenants take turns chunk by chunk (round robin), so their
sessions share the server's multiplexer and the chip.

Parameters (``traffic`` in the workload file): ``tenants``; ``graphs``, the
pool drawn in set-up, which tenant t walks from graph t on; ``chunk``,
tuples per ``feed``. In set-up every tenant opens its first session and
feeds ``t / tenants`` of its graph, so the sessions of the window start
staggered; one extra session is opened, fed and closed first, which warms
every shape the window uses.

When the window closes no session is opened any more; the sessions in
flight are fed to their graph's end and closed (the drain). End to end:
``edges_per_s`` is the raw tuples fed from the window's start to the
drain's end, over that time; ``session_p75_s`` is the 75th percentile,
over the sessions opened in the window, of the time from ``open_stream``
to ``close_stream``'s count on the host.
"""
from __future__ import annotations

import statistics
import time
import traceback

import jax
import numpy as np


def make_data(ctx) -> dict:
    k = int(ctx.params["graphs"])
    with jax.profiler.TraceAnnotation("bench.generate"):
        pool = ctx.law.draw(ctx.cfg, ctx.seed, n_streams=k, parts=1,
                            tuples=ctx.law.tuples_per_graph(ctx.cfg))[:, 0]
    return {"pool": pool, "items": {i: {"graph": pool[i]} for i in range(k)}}


class _Tenant:
    def __init__(self, index: int):
        self.index = index
        self.visits = 0
        self.sid = None
        self.graph = None
        self.next_chunk = 0
        self.opened_at = None  # host clock at open_stream, None if in set-up


def _chunks(edges: np.ndarray, size: int) -> list:
    return [edges[i:i + size] for i in range(0, len(edges), size)]


def setup(ctx, data) -> dict:
    from repro.serve.serve_loop import TriangleServer

    n, prm = ctx.n_nodes, ctx.params
    chunks = [_chunks(g, int(prm["chunk"])) for g in data["pool"]]
    server = TriangleServer()
    warm = server.open_stream(n)
    for c in chunks[0]:
        server.feed(warm, c)
    res = server.close_stream(warm)
    res.item()
    tenants = [_Tenant(t) for t in range(int(prm["tenants"]))]
    for t in tenants:
        _open(server, t, len(chunks), n, None)
        for _ in range(len(chunks[t.graph]) * t.index // len(tenants)):
            server.feed(t.sid, chunks[t.graph][t.next_chunk])
            t.next_chunk += 1
    (jax.numpy.zeros(()) + 1).block_until_ready()  # queued after the feeds
    return {"server": server, "tenants": tenants, "chunks": chunks, "n": n,
            "plan": res.plan, "block_size": res.stats["block_size"]}


def _open(server, t: _Tenant, pool: int, n: int, now) -> None:
    with jax.profiler.TraceAnnotation("bench.open"):
        t.sid = server.open_stream(n)
    t.graph = (t.index + t.visits) % pool
    t.visits += 1
    t.next_chunk = 0
    t.opened_at = now


def window(st, clock) -> dict:
    server, tenants, chunks, n = st["server"], st["tenants"], st["chunks"], st["n"]
    answers, latency = [], []
    fed = failed = 0
    open_ = True
    live = list(tenants)
    while live:
        for t in list(live):
            if t.sid is None:
                live.remove(t)
                continue
            graph_chunks = chunks[t.graph]
            if t.next_chunk < len(graph_chunks):
                c = graph_chunks[t.next_chunk]
                with jax.profiler.TraceAnnotation("bench.feed"):
                    server.feed(t.sid, c)
                fed += len(c)
                t.next_chunk += 1
                continue
            try:
                with jax.profiler.TraceAnnotation("bench.close"):
                    count = server.close_stream(t.sid).item()
            except Exception:  # noqa: BLE001 — a failed session is counted
                traceback.print_exc()
                count = None
                failed += 1
            done = time.perf_counter()
            answers.append((t.graph, count))
            if t.opened_at is not None and count is not None:
                latency.append(done - t.opened_at)
            t.sid = None
            open_ = open_ and clock.running()
            if open_:
                _open(server, t, len(chunks), n, time.perf_counter())
    elapsed = clock.elapsed()
    p75 = (statistics.quantiles(latency, n=4, method="inclusive")[-1]
           if len(latency) >= 2 else float("nan"))
    blocks = fed // st["block_size"]
    return {
        "answers": answers,
        "attempted": len(answers),
        "failed": failed,
        "metrics": {"edges_per_s": fed / elapsed, "session_p75_s": p75},
        "stats": {"block_size": st["block_size"],
                  "n_nodes": n, "n_stages": st["plan"].n_stages, "epochs": 1},
        "log": (f"{fed} tuples, {len(answers)} sessions closed "
                f"({len(latency)} opened in the window) in {elapsed:.3f} s; "
                f"plan layout={st['plan'].state_layout} block={st['block_size']} "
                f"stages={st['plan'].n_stages} use_kernel={st['plan'].use_kernel}; "
                f"about {blocks} blocks; session latency median "
                f"{statistics.median(latency) if latency else float('nan'):.3f} s, "
                f"p75 {p75:.3f} s"),
    }


def free(st) -> None:
    st.clear()
