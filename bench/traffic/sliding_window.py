"""One monitoring session over an endless edge stream: a sliding window of
``window`` epochs through ``TriangleServer.open_stream(n, window=E)``. Each
epoch's raw tuples are fed in chunks, then ``advance_stream`` slides the
window; at the window's end ``close_stream`` gives the live window's count.

Parameters (``traffic`` in the workload file): ``window`` (E);
``epoch_tuples``; ``epochs``, the pool of epochs drawn in set-up from one
stream (one vertex relabelling), fed in turn and again from the first;
``chunk``, tuples per ``feed``. Set-up fills the window (E epochs, with
their advances) in the session the window goes on with, after one short
session that warms the close.

End to end, ``edges_per_s`` is the raw tuples fed in the window over the
time from its start to ``close_stream``'s count on the host. The window's
last epoch may be cut short; the reference takes every epoch fed since the
session opened, the cut one as fed.
"""
from __future__ import annotations

import time
import traceback

import jax
import numpy as np


def make_data(ctx) -> dict:
    prm = ctx.params
    with jax.profiler.TraceAnnotation("bench.generate"):
        pool = ctx.law.draw(ctx.cfg, ctx.seed, n_streams=1,
                            parts=int(prm["epochs"]),
                            tuples=int(prm["epoch_tuples"]))[0]
    # the compared item is known only once the window has closed
    return {"pool": pool, "items": {}}


def setup(ctx, data) -> dict:
    from repro.serve.serve_loop import TriangleServer

    n, prm = ctx.n_nodes, ctx.params
    E, size = int(prm["window"]), int(prm["chunk"])
    pool = data["pool"]
    server = TriangleServer()
    warm = server.open_stream(n, window=E)
    server.feed(warm, pool[0][:size])
    server.advance_stream(warm)
    server.feed(warm, pool[1][:size])
    server.close_stream(warm).item()
    st = {"server": server, "pool": pool, "n": n, "E": E, "size": size,
          "data": data, "fed": [], "epoch": 0, "next_chunk": 0}
    st["sid"] = server.open_stream(n, window=E)
    for _ in range(E):
        _next_epoch(st, first=not st["fed"])
        while st["next_chunk"] * size < len(pool[st["epoch"]]):
            _feed(st)
    (jax.numpy.zeros(()) + 1).block_until_ready()  # queued after the feeds
    return st


def _next_epoch(st, *, first: bool = False) -> None:
    if not first:
        with jax.profiler.TraceAnnotation("bench.advance"):
            st["server"].advance_stream(st["sid"])
        st["epoch"] = (st["epoch"] + 1) % len(st["pool"])
    st["fed"].append([st["epoch"], 0])
    st["next_chunk"] = 0


def _feed(st) -> int:
    ep = st["pool"][st["epoch"]]
    c = ep[st["next_chunk"] * st["size"]:(st["next_chunk"] + 1) * st["size"]]
    with jax.profiler.TraceAnnotation("bench.feed"):
        st["server"].feed(st["sid"], c)
    st["next_chunk"] += 1
    st["fed"][-1][1] += len(c)
    return len(c)


def window(st, clock) -> dict:
    fed = advances = failed = 0
    epoch_len = len(st["pool"][0])
    while clock.running():
        if st["next_chunk"] * st["size"] >= epoch_len:
            _next_epoch(st)
            advances += 1
        fed += _feed(st)
    try:
        with jax.profiler.TraceAnnotation("bench.close"):
            res = st["server"].close_stream(st["sid"])
            count = res.item()
    except Exception:  # noqa: BLE001 — a failed close is counted
        traceback.print_exc()
        res, count, failed = None, None, 1
    elapsed = clock.elapsed()
    pool = st["pool"]
    st["data"]["items"]["window"] = {
        "epochs": [pool[e][:k] for e, k in st["fed"]], "window": st["E"]}
    block = res.stats["block_size"] if res is not None else 0
    plan = res.plan if res is not None else None
    return {
        "answers": [("window", count)],
        "attempted": 1,
        "failed": failed,
        "metrics": {"edges_per_s": fed / elapsed},
        "stats": {"block_size": block, "n_nodes": st["n"],
                  "n_stages": plan.n_stages if plan else 1, "epochs": st["E"]},
        "log": (f"{fed} tuples, {advances} advances in {elapsed:.3f} s; "
                f"{len(st['fed'])} epochs since open; plan "
                f"layout={plan.state_layout if plan else '?'} block={block} "
                f"window={st['E']} use_kernel={plan.use_kernel if plan else '?'}"),
    }


def free(st) -> None:
    st.clear()
