"""Tenants of one ``TriangleServer`` streaming whole graphs whose dense
state fits no chip, so that admission gives each session the degree-aware
hybrid state: the closed loop of ``tenant_sessions.py`` (loaded by path,
not copied), with a guarded warm session and the hybrid layout's counters.

Parameters (``traffic`` in the workload file): those of
``tenant_sessions.py`` (``tenants``, ``graphs``, ``chunk``), and for the
warm session, which is opened, fed and closed first: ``warm_piece``, the
tuples fed at a time, each piece waited on before the next; ``guard_s``
and ``guard_share``: the warm session is fed until ``guard_share`` of its
graph is in, then closed, which warms the close path as a whole graph
would; set-up stops with an error, naming the projected time a graph, if
it runs longer than ``guard_s`` seconds past its first piece (which
compiles the ingest) before that. The tenants' sessions then start
staggered as in ``tenant_sessions.py``.

End to end: ``edges_per_s`` as in ``tenant_sessions.py``. The driver adds
to its stats, for the per-layer readers: the sums of the hybrid counters
of the sessions closed in the window, and their blocks (``hybrid``); the
same sums over just the blocks that ran while the profiler recorded
(``hybrid_traced``, in a traced run); the layout (``hub_slots``,
``tail_capacity``, ``words``) and the block size.
"""
from __future__ import annotations

import importlib.util
import os
import time

import jax
import numpy as np


def _tenant_sessions():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tenant_sessions.py")
    spec = importlib.util.spec_from_file_location("bench_traffic_tenant_sessions", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


tenants = _tenant_sessions()
make_data = tenants.make_data


# The state counters the roofline's bytes are counted from.
_TRACED = ("pairs_hub", "fill_mixed", "fill_tail")


def _add(into: dict, counters: dict, sign: int) -> None:
    for k, v in counters.items():
        into[k] = into.get(k, 0) + sign * v


class _Recording:
    """The server, with the stats of every closed session kept, and the
    hybrid counters of the blocks that run while the profiler records
    (``traced``): at the trace's start the in-flight sessions' counters are
    taken off, at its end added, and a session closed in between adds its
    final ones."""

    def __init__(self, server):
        self.server = server
        self.closed = []
        self.open = set()
        self.recording = False
        self.traced: dict = {}

    def __getattr__(self, name):
        return getattr(self.server, name)

    def open_stream(self, n_nodes: int) -> int:
        sid = self.server.open_stream(n_nodes)
        self.open.add(sid)
        return sid

    def close_stream(self, sid):
        res = self.server.close_stream(sid)
        self.open.discard(sid)
        self.closed.append(res.stats)
        if self.recording and "hybrid" in res.stats:
            _add(self.traced, dict(res.stats["hybrid"],
                                   blocks=res.stats["n_blocks"]), +1)
        return res

    def in_flight(self, sign: int) -> None:
        """Add ``sign`` times the counters of every session in flight."""
        for sid in self.open:
            session = self.server.streams._recs[sid].session
            if session is None or _TRACED[0] not in session.state:
                continue  # waiting, or a state without the counters
            _add(self.traced, {k: int(np.asarray(session.state[k])) for k in _TRACED},
                 sign)
            _add(self.traced, {"blocks": session.n_blocks}, sign)


class _Clock:
    """The window's clock as ``tenant_sessions.window`` asks it (at each
    close), telling the server when the profiler starts and stops. In a
    traced run the device is settled before each ask, so that no block
    straddles the trace's edges."""

    def __init__(self, clock, server: _Recording):
        self.clock, self.server = clock, server

    def __getattr__(self, name):
        return getattr(self.clock, name)

    def _recording(self) -> bool:
        return getattr(self.clock, "_span", None) is not None

    def running(self) -> bool:
        if getattr(self.clock, "trace_dir", None) is not None:
            _settle()
        was = self._recording()
        out = self.clock.running()
        if self._recording() != was:
            self.server.in_flight(-1 if not was else +1)
            self.server.recording = not was
        return out


def _settle() -> None:
    # one op queued after the feeds: done when they are
    (jax.numpy.zeros(()) + 1).block_until_ready()


def _warm(server, n: int, graph, prm) -> object:
    """Open one session of ``graph``, feed it piece by piece until
    ``guard_share`` of the graph is in, and close it; raise once it runs
    past the guard."""
    piece, guard_s = int(prm["warm_piece"]), float(prm["guard_s"])
    share_min = float(prm["guard_share"])
    sid = server.open_stream(n)
    server.feed(sid, graph[:piece])  # compiles the ingest: not timed
    _settle()
    t0 = time.perf_counter()
    for i in range(piece, len(graph), piece):
        server.feed(sid, graph[i:i + piece])
        _settle()
        took = time.perf_counter() - t0
        share = (min(i + piece, len(graph)) - piece) / len(graph)
        if share >= share_min:
            break
        if took > guard_s:
            raise RuntimeError(
                f"set-up guard: the warm session fed {share:.2%} of its graph "
                f"in {took:.1f} s, so a graph would take about "
                f"{took / share:.0f} s; stopping")
    res = server.close_stream(sid)
    res.item()
    return res


def setup(ctx, data) -> dict:
    from repro.serve.serve_loop import TriangleServer

    n, prm = ctx.n_nodes, ctx.params
    chunks = [tenants._chunks(g, int(prm["chunk"])) for g in data["pool"]]
    server = _Recording(TriangleServer())
    res = _warm(server, n, data["pool"][0], prm)
    ts = [tenants._Tenant(t) for t in range(int(prm["tenants"]))]
    for t in ts:
        tenants._open(server, t, len(chunks), n, None)
        for _ in range(len(chunks[t.graph]) * t.index // len(ts)):
            server.feed(t.sid, chunks[t.graph][t.next_chunk])
            t.next_chunk += 1
    _settle()
    server.closed.clear()
    return {"server": server, "tenants": ts, "chunks": chunks, "n": n,
            "plan": res.plan, "block_size": res.stats["block_size"]}


def window(st, clock) -> dict:
    server = st["server"]
    out = tenants.window(st, _Clock(clock, server))
    if server.recording:
        # the trace is still on: it stops after the drain, which closed
        # every session, so their final counters are all in
        server.recording = False
    closed = [s for s in st["server"].closed if "hybrid" in s]
    counters: dict = {"blocks": sum(s["n_blocks"] for s in closed)}
    for s in closed:
        for k, v in s["hybrid"].items():
            counters[k] = counters.get(k, 0) + v
    p = st["plan"]
    out["stats"].update(
        hybrid=counters if closed else None,
        hybrid_traced=dict(server.traced) if server.traced else None,
        state_layout=p.state_layout,
        hub_slots=p.hub_slots, tail_capacity=p.tail_capacity,
        words=-(-st["n"] // 32))
    out["log"] += (f"; hybrid H={p.hub_slots} C={p.tail_capacity} "
                   f"threshold={p.hub_threshold}; counters over "
                   f"{len(closed)} closed sessions: {counters}")
    return out


def free(st) -> None:
    st.clear()
