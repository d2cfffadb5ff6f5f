"""One analyst in a closed loop, counting resident graphs through
``TriangleCounter.count``: plan the graph, count it, wait for the count on
the host, and go on to the next graph of a pool drawn in set-up.

Parameters (``traffic`` in the workload file): ``graphs``, the pool size,
counted in turn; ``warm_counts``, counts made in set-up.

Each call is wrapped in a host span: ``bench.plan`` around
``counter.plan_for(g)`` and ``bench.count`` around ``counter.count(g,
plan=p)`` up to its count on the host; ``count`` with that plan does the
same work as ``count(g)``. End to end, ``count_s`` is the time from the
window's start to the end of the last count begun in it, over the counts
made.
"""
from __future__ import annotations

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


def setup(ctx, data) -> dict:
    from repro.api import TriangleCounter
    from repro.graphs.formats import Graph

    graphs = [Graph(edges=np.ascontiguousarray(e), n_nodes=ctx.n_nodes)
              for e in data["pool"]]
    counter = TriangleCounter()
    st = {"counter": counter, "graphs": graphs, "plan": None}
    for i in range(int(ctx.params["warm_counts"])):
        g = graphs[i % len(graphs)]
        p = counter.plan_for(g)
        counter.count(g, plan=p).item()
        st["plan"] = p
    return st


def window(st, clock) -> dict:
    from repro.kernels import kernel_traces

    counter, graphs = st["counter"], st["graphs"]
    k0 = len(kernel_traces())
    answers, plan_s, count_s = [], [], []
    failed = i = 0
    p = st["plan"]
    while clock.running():
        g = graphs[i % len(graphs)]
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.plan"):
                p = counter.plan_for(g)
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.count"):
                c = counter.count(g, plan=p).item()
        except Exception:  # noqa: BLE001 — a failed count is counted, not fatal
            traceback.print_exc()
            answers.append((i % len(graphs), None))
            failed += 1
            i += 1
            continue
        t2 = time.perf_counter()
        answers.append((i % len(graphs), c))
        plan_s.append(t1 - t0)
        count_s.append(t2 - t1)
        i += 1
    elapsed = clock.elapsed()
    kernels = sorted({f"{k}({mode})" for k, mode in kernel_traces()[k0:]})
    n = len(answers)
    return {
        "answers": answers,
        "attempted": n,
        "failed": failed,
        "metrics": {"count_s": elapsed / max(n, 1)},
        "stats": {"plan_s": plan_s, "count_s": count_s,
                  "n_nodes": graphs[0].n_nodes, "n_edges": graphs[0].n_edges},
        "log": (f"{n} counts in {elapsed:.3f} s; plan method={p.method} "
                f"stages={p.n_stages} use_kernel={p.use_kernel}; kernels "
                f"traced in the window: {kernels or 'none (XLA)'}; plan "
                f"{np.mean(plan_s) * 1e3:.1f} ms, count {np.mean(count_s) * 1e3:.1f} ms "
                f"mean (count {min(count_s) * 1e3:.1f}–{max(count_s) * 1e3:.1f} ms)")
               if n else "no count completed",
    }


def free(st) -> None:
    st.clear()
