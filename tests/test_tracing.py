"""Spans and named scopes (``repro.tracing``).

- Host spans: a CPU profiler trace of a resident count and of a mux
  session holds the ``repro.*`` spans, nested as the calls are, with the
  session id on the mux spans.
- Named scopes: the compiled HLO of every ingest family carries its
  ``ingest.*`` phases in the ops' ``op_name`` metadata. The per-edge fold
  (``ingest_block_per_edge``) is the differential oracle, not a session
  path, and has none.
- Executable names: the dense, windowed, emulated-sharded and mesh ingest
  steps compile under stable names that the benchmark's
  ``ingest_ms_per_block`` matches (the mesh ones on 4 virtual CPU devices,
  in a subprocess).
"""
from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import pytest

from repro import tracing
from repro.api import Plan, TriangleCounter
from repro.core import streaming
from repro.graphs import generators as gen
from repro.serve.sessions import StreamMultiplexer

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
OP_NAME = re.compile(r'op_name="([^"]*)"')
# ``bench/metrics/ingest_ms_per_block.py``'s pattern for the ingest runs
INGEST_RUNS = re.compile(r"ingest|^jit_stage_fn$")


def _scopes(compiled) -> set[str]:
    """The ``ingest.*`` scopes in the ops' names; under a transform a scope
    reads ``vmap(ingest.live)``."""
    names = OP_NAME.findall(compiled.as_text())
    parts = {p.rstrip(")").rsplit("(", 1)[-1] for name in names for p in name.split("/")}
    return {p for p in parts if p.startswith("ingest.")}


def _host_spans(trace_dir: str) -> list[tuple]:
    """``(name, start, end, stats)`` of every ``repro.*`` host event."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, e.start_ns, e.end_ns, dict(list(e.stats))))
    return sorted(out, key=lambda s: s[1])


def _inside(spans, child, parent) -> bool:
    """Every ``child`` span lies inside some ``parent`` span."""
    outer = [s for s in spans if s[0] == parent]
    kids = [s for s in spans if s[0] == child]
    return bool(kids) and all(any(p[1] <= k[1] and k[2] <= p[2] for p in outer)
                              for k in kids)


def test_span_names_and_arguments():
    sp = tracing.span("count.put", sid=3)
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    with sp:
        pass
    assert {tracing.INGEST_LIVE, tracing.INGEST_AGE_CUM, tracing.INGEST_UPDATE,
            tracing.INGEST_TERMS} == {"ingest.live", "ingest.age_cum",
                                      "ingest.update", "ingest.terms"}


def test_a_span_outside_a_trace_costs_microseconds():
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tracing.span("session.ingest"):
            pass
    assert (time.perf_counter() - t0) / n < 20e-6


def test_spans_of_a_count_and_a_mux_session_nest(tmp_path):
    g = gen.gnp(60, 0.3, seed=1)
    counter = TriangleCounter(plan=Plan(method="bitset_ring", n_stages=1))
    counter.count(g).item()  # compiled outside the trace
    planner = TriangleCounter()
    mux = StreamMultiplexer(block_size=64)
    with jax.profiler.trace(str(tmp_path)):
        planner.plan_for(g)
        counter.count(g).item()
        sid = mux.open(60)
        mux.feed(sid, g.edges)
        mux.close(sid).item()
    spans = _host_spans(str(tmp_path))
    names = {s[0] for s in spans}
    assert {"repro.plan", "repro.plan.stats", "repro.plan.choose", "repro.count",
            "repro.count.operands", "repro.count.put", "repro.count.dispatch",
            "repro.mux.open", "repro.mux.feed", "repro.mux.close",
            "repro.session.feed", "repro.session.ingest",
            "repro.session.finalize"} <= names
    for child, parent in [("repro.plan.stats", "repro.plan"),
                          ("repro.plan.choose", "repro.plan"),
                          ("repro.count.operands", "repro.count"),
                          ("repro.count.put", "repro.count"),
                          ("repro.count.dispatch", "repro.count"),
                          ("repro.session.feed", "repro.mux.feed"),
                          ("repro.session.finalize", "repro.mux.close")]:
        assert _inside(spans, child, parent), (child, parent)
    # 2 full blocks of 64 in the feed, the padded tail at finalize
    ingests = [s for s in spans if s[0] == "repro.session.ingest"]
    assert len(ingests) == g.n_edges // 64 + 1
    parents = [s for s in spans if s[0] in ("repro.session.feed", "repro.session.finalize")]
    assert all(any(p[1] <= s[1] and s[2] <= p[2] for p in parents) for s in ingests)
    for s in spans:
        if s[0].startswith("repro.mux."):
            assert s[3].get("sid") == sid, s


def test_a_windowed_session_traces_advance_and_checkpoint(tmp_path):
    g = gen.gnp(50, 0.3, seed=2)
    counter = TriangleCounter()
    session = counter.open_stream(50, window=2, plan=Plan(
        method="stream", block_size=32, window_epochs=2))
    with jax.profiler.trace(str(tmp_path)):
        session.feed(g.edges[:40])
        session.advance()
        session.feed(g.edges[40:])
        session.checkpoint()
        session.finalize()
    names = [s[0] for s in _host_spans(str(tmp_path))]
    for name in ("repro.session.feed", "repro.session.advance",
                 "repro.session.checkpoint", "repro.session.finalize",
                 "repro.session.ingest"):
        assert name in names, name


N, B = 300, 64


def _families():
    e = jnp.zeros((B, 2), jnp.int32)
    four = {"ingest.live", "ingest.update", "ingest.terms"}
    return {
        "dense": (lambda: streaming.ingest_block_donated.lower(
            streaming.init_state(N), e), four),
        "dense_kernel": (lambda: streaming.ingest_block_donated.lower(
            streaming.init_state(N), e, use_kernel=True), four),
        "sharded": (lambda: streaming.ingest_block_sharded_donated.lower(
            streaming.init_sharded_state(N, 4), e), four),
        "windowed": (lambda: streaming.ingest_block_windowed_donated.lower(
            streaming.init_windowed_state(N, 3), e), four | {"ingest.age_cum"}),
        "windowed_sharded": (lambda: streaming.ingest_block_windowed_sharded_donated.lower(
            streaming.init_windowed_sharded_state(N, 3, 4), e), four | {"ingest.age_cum"}),
        "hybrid": (lambda: streaming.ingest_block_hybrid_donated.lower(
            streaming.init_hybrid_state(N, 16, 8), e, hub_threshold=6), four),
    }


@pytest.mark.parametrize("family", sorted(_families()))
def test_ingest_hlo_carries_the_phase_scopes(family):
    lower, want = _families()[family]
    assert _scopes(lower().compile()) == want


MESH_SNIPPET = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import glob, json, re, sys, tempfile
    import jax, jax.numpy as jnp
    from repro.api import Plan, TriangleCounter
    from repro.core import streaming
    from repro.graphs import generators as gen
    from repro.launch.mesh import make_ring_mesh

    n, b = 128, 64
    mesh = make_ring_mesh(4)
    e = jnp.zeros((b, 2), jnp.int32)
    scopes = {}
    for name, make, st in [
            ("mesh", streaming.make_mesh_ingest, streaming.init_sharded_state(n, 4, mesh=mesh)),
            ("mesh_windowed", streaming.make_mesh_ingest_windowed,
             streaming.init_windowed_sharded_state(n, 2, 4, mesh=mesh))]:
        txt = jax.jit(make(mesh)).lower(st, e).compile().as_text()
        scopes[name] = sorted({p for s in re.findall(r'op_name="([^"]*)"', txt)
                               for p in s.split("/") if p.startswith("ingest.")})
    g = gen.gnp(n, 0.2, seed=5)
    plans = {
        "dense": (Plan(method="stream", block_size=b), None),
        "windowed": (Plan(method="stream", block_size=b, window_epochs=2), None),
        "sharded": (Plan(method="stream", block_size=b, n_stages=4), None),
        "mesh": (Plan(method="stream", block_size=b, n_stages=4), mesh),
        "mesh_windowed": (Plan(method="stream", block_size=b, n_stages=4,
                               window_epochs=2), mesh),
    }
    modules = {}
    for name, (plan, m) in plans.items():
        counter = TriangleCounter(plan=plan, mesh=m)
        d = tempfile.mkdtemp()
        with jax.profiler.trace(d):
            s = counter.open_stream(n, window=plan.window_epochs or None)
            s.feed(g.edges)
            s.finalize().item()
        (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        found = set()
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    mod = dict(ev.stats).get("hlo_module")
                    if mod and "ingest" in str(mod):
                        found.add(str(mod))
        modules[name] = sorted(found)
    print("RESULT " + json.dumps({"scopes": scopes, "modules": modules}))
    """
)


def test_ingest_executables_keep_their_names_and_mesh_scopes():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", MESH_SNIPPET], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    (line,) = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    got = json.loads(line[len("RESULT "):])
    phases = ["ingest.live", "ingest.terms", "ingest.update"]
    assert got["scopes"] == {"mesh": phases,
                             "mesh_windowed": sorted(phases + ["ingest.age_cum"])}
    assert got["modules"] == {
        "dense": ["jit__ingest_block_impl"],
        "windowed": ["jit__ingest_block_windowed_impl"],
        "sharded": ["jit__ingest_block_sharded_impl"],
        "mesh": ["jit_ingest_block_mesh"],
        "mesh_windowed": ["jit_ingest_block_windowed_mesh"],
    }
    assert all(INGEST_RUNS.search(m) for ms in got["modules"].values() for m in ms)
