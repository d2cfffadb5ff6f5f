"""One client streaming whole graphs, closed loop, through a stream
session whose bitset state is ring-sharded over a mesh of chips: open a
session (``TriangleCounter(mesh=make_ring_mesh(S)).open_stream`` with a
``Plan(method="stream", n_stages=S)``), feed the graph's raw tuples in
chunks, finalize and wait for its count on the host, open the next. The
planner would keep an n²/8 state that fits one chip's budget on one chip,
so the plan is built as ``chip_smoke.py`` builds it.

Parameters (``traffic`` in the workload file): ``graphs``, the pool drawn
in set-up, streamed in turn; ``chunk``, tuples per ``feed``; ``stages``,
the ring width; ``block_size``, the ingest block. One whole session runs
in set-up, which warms every shape the window uses.

When the window closes, the session in flight is fed to its graph's end
and finalized (the drain). ``edges_per_s`` is the raw tuples fed from the
window's start to the drain's end, over that time.
"""
from __future__ import annotations

import traceback

import jax


def make_data(ctx) -> dict:
    k = int(ctx.params["graphs"])
    with jax.profiler.TraceAnnotation("bench.generate"):
        pool = ctx.law.draw(ctx.cfg, ctx.seed, n_streams=k, parts=1,
                            tuples=ctx.law.tuples_per_graph(ctx.cfg))[:, 0]
    return {"pool": pool, "items": {i: {"graph": pool[i]} for i in range(k)}}


def _session(st, g: int) -> int:
    with jax.profiler.TraceAnnotation("bench.open"):
        s = st["counter"].open_stream(st["n"], plan=st["plan"])
    edges, size = st["pool"][g], st["chunk"]
    for i in range(0, len(edges), size):
        with jax.profiler.TraceAnnotation("bench.feed"):
            s.feed(edges[i:i + size])
    with jax.profiler.TraceAnnotation("bench.close"):
        return s.finalize().item()


def setup(ctx, data) -> dict:
    from repro.api import Plan, TriangleCounter
    from repro.api.planner import backend_exec_flags
    from repro.launch.mesh import make_ring_mesh

    prm = ctx.params
    counter = TriangleCounter(mesh=make_ring_mesh(int(prm["stages"])))
    plan = Plan(method="stream", n_stages=int(prm["stages"]),
                block_size=int(prm["block_size"]),
                **backend_exec_flags(counter.resources))
    st = {"counter": counter, "plan": plan, "pool": data["pool"],
          "n": ctx.n_nodes, "chunk": int(prm["chunk"])}
    _session(st, 0)
    return st


def window(st, clock) -> dict:
    answers, fed, failed, g = [], 0, 0, 0
    while clock.running():
        try:
            count = _session(st, g % len(st["pool"]))
        except Exception:  # noqa: BLE001 — a failed session is counted
            traceback.print_exc()
            count, failed = None, failed + 1
        answers.append((g % len(st["pool"]), count))
        fed += len(st["pool"][g % len(st["pool"])])
        g += 1
    elapsed = clock.elapsed()
    p = st["plan"]
    return {
        "answers": answers,
        "attempted": len(answers),
        "failed": failed,
        "metrics": {"edges_per_s": fed / elapsed},
        "stats": {"block_size": p.block_size, "n_nodes": st["n"],
                  "n_stages": p.n_stages, "epochs": 1},
        "log": (f"{fed} tuples, {len(answers)} sessions in {elapsed:.3f} s; plan "
                f"stages={p.n_stages} block={p.block_size} "
                f"use_kernel={p.use_kernel} on {len(st['counter'].mesh.devices.flat)} chips"),
    }


def free(st) -> None:
    st.clear()
