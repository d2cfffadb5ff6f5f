"""Quickstart: planned, compile-cached triangle counting via ``repro.api``,
cross-checked against MapReduce and the brute-force oracle.

    PYTHONPATH=src python examples/quickstart.py
"""
from repro.api import GraphStats, Resources, TriangleCounter, plan
from repro.core.triangle_mapreduce import count_triangles_mapreduce
from repro.core.triangle_ref import count_triangles_brute
from repro.graphs import generators as gen

graph = gen.gnp(400, 0.3, seed=7)
print(f"G(n={graph.n_nodes}, m={graph.n_edges}, density={graph.density:.3f})")

# The planner turns measured input properties into an inspectable Plan.
p = plan(GraphStats.from_graph(graph), Resources.detect())
print(f"plan: method={p.method} n_stages={p.n_stages} "
      f"predicted_bytes={p.predicted_bytes} ({p.reason})")

counter = TriangleCounter()
result = counter.count(graph)  # planner-chosen path, compile-cached
oracle = count_triangles_brute(graph)
print(f"oracle (trace A³/6):          {oracle}")
print(f"planned ({result.plan.method}):              {result.item()}  "
      f"[{result.wall_s * 1e3:.1f} ms]")

# Any method is still one plan away — same counter, same cache.
from repro.api import Plan

for method in ("dense", "sparse", "ring", "bitset_ring"):
    r = counter.count(graph, plan=Plan(method=method, n_stages=4))
    print(f"pipeline ({method:11s}):        {r.item()}")
print(f"mapreduce (Suri–Vassilvitskii): {count_triangles_mapreduce(graph)}")

# Streaming: same contract, the graph arrives as edge blocks.
blocks = (graph.edges[i:i + 1024] for i in range(0, graph.n_edges, 1024))
rs = counter.count_stream(graph.n_nodes, blocks)
print(f"stream (bitset fold):          {rs.item()}  "
      f"[{rs.stats['n_blocks']} blocks, {rs.stats['ingest_traces']} trace(s)]")

# Sliding window: count over the last E epochs only — deletions via an
# epoch-rotated bitset ring (docs/STREAMING.md §5; full tour:
# examples/windowed_stream.py).
epochs = [[graph.edges[i:i + 1024]] for i in range(0, graph.n_edges, 1024)]
rw = counter.count_windowed(graph.n_nodes, epochs, window=4)
print(f"sliding window (last 4 of {rw.stats['epochs_advanced'] + 1} epochs): "
      f"{rw.item()}  [{rw.stats['n_blocks']} blocks, 1 slot clear per slide]")

# Batched: many small graphs, one vmapped executable.
small = [gen.gnp(60, 0.3, seed=s) for s in range(4)]
rb = counter.count_batch(small)
print(f"batch of {len(small)}:   {[int(x) for x in rb.count]}")

# Served: batched resident requests + CONCURRENT stream sessions, one server.
# prefetch_depth=2 enables the async double-buffered session driver: host
# re-blocking overlaps device ingest, bit-identical to the sync path.
from repro.serve.serve_loop import TriangleServer

server = TriangleServer(prefetch_depth=2)
served = server.serve(small)
print(f"served batch:  {[r.item() for r in served]}")
streams = [(graph.n_nodes, [graph.edges[i:i + 1024]
                            for i in range(0, graph.n_edges, 1024)])
           for _ in range(4)]
multi = server.serve_streams(streams, block_size=1024)  # interleaved ingest
print(f"4 concurrent streams:          {[r.item() for r in multi]}  "
      f"(all sessions share one ingest trace)")
print(f"compile cache: {counter.cache_info}")
