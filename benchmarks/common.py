"""Benchmark substrate: each (graph × algorithm) job runs in a SUBPROCESS so
we can report the paper's two metrics faithfully — ET (wall seconds) and VM
(peak RSS via getrusage) — and enforce the paper's timeout semantics (grey
bars in Figs 10-13). The subprocess also pins the XLA host device count for
the core-scaling figure (an XLA CPU device executes on its own threads)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

WORKER = textwrap.dedent(
    """
    import json, os, resource, sys, time
    spec = json.loads(sys.argv[1])
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={spec.get('devices', 1)}"
    import numpy as np
    from repro.api import GraphStats, Resources, TriangleCounter, plan
    from repro.utils import enable_compile_cache

    enable_compile_cache()

    from repro.graphs.datasets import load

    g = load(spec["graph"], scale=spec.get("scale", 1.0), seed=0)
    t0 = time.time()
    method = spec["method"]
    plan_info = None
    if method in ("auto", "pipeline"):
        # Method selection is the LIBRARY's job: the planner picks among the
        # paper-grounded regimes ("pipeline" restricts it to the pipeline
        # family; "auto" considers everything) and records why.
        allow = None if method == "auto" else {"dense", "ring", "sparse", "bitset_ring"}
        devices = spec.get("devices", 1)
        mesh = None
        if devices > 1:
            from repro.launch.mesh import make_ring_mesh
            mesh = make_ring_mesh(devices)
        counter = TriangleCounter(Resources.detect(), mesh=mesh)
        p = plan(GraphStats.from_graph(g), counter.resources, allow=allow)
        res = counter.count(g, plan=p)
        count = res.item()
        plan_info = p.to_dict()
    elif method == "pipeline_ring":
        from repro.launch.mesh import make_ring_mesh
        from repro.core.triangle_pipeline import count_triangles_ring
        mesh = make_ring_mesh(spec.get("devices", 1))
        count = count_triangles_ring(g, mesh=mesh)
    elif method == "mapreduce":
        from repro.core.triangle_mapreduce import count_triangles_mapreduce
        count = count_triangles_mapreduce(g, streaming=spec.get("streaming", True))
    else:
        raise ValueError(method)
    wall = time.time() - t0
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    print("RESULT " + json.dumps({
        "count": int(count), "wall_s": wall, "maxrss_mb": rss_mb,
        "n": g.n_nodes, "m": g.n_edges, "density": g.density,
        "plan": plan_info,
    }))
    """
)


def timed_ms(fn, *, reps: int = 5, warmup: bool = True, sync=None):
    """Standardized block-until-ready-then-stop-clock timing loop.

    Runs ``fn()`` ``reps`` times; each sample's clock stops only after the
    result is device-complete (``jax.block_until_ready`` on the output, or
    on ``sync(output)`` when the arrays live inside wrapper objects such as
    ``CountResult``). jax dispatches asynchronously, so timing without the
    block measures dispatch latency, not the computation — every bench
    timing loop must go through this helper or carry a reasoned
    ``# lint: disable=R2`` (enforced by tools/repro_lint).

    Returns ``(median_ms, last_output)`` so callers can verify correctness
    once, outside the timed region.
    """
    import statistics
    import time

    import jax

    if warmup:
        jax.block_until_ready(sync(fn()) if sync else fn())
    samples, out = [], None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(sync(out) if sync else out)
        samples.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(samples), out


def run_job(spec: dict, timeout_s: float = 120.0) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    try:
        r = subprocess.run(
            [sys.executable, "-c", WORKER, json.dumps(spec)],
            env=env, capture_output=True, text=True, timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        return {"timeout": True, "timeout_s": timeout_s}
    if r.returncode != 0:
        return {"error": r.stderr[-1000:]}
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    return {"error": "no result line"}
