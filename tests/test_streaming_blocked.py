"""Two-phase blocked ingest + ring-sharded streaming state, and the
count_stream/count_batch plan-handling fixes. No hypothesis dependency —
this module always runs in tier-1.

The per-edge ``lax.scan`` fold (``ingest_block_per_edge``) is the retained
oracle: every differential test folds the SAME stream through it and through
the blocked (and sharded) ingest and demands bit-equal counts."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import GraphStats, Plan, Resources, TriangleCounter, plan, stream_sizing
from repro.core import streaming
from repro.core.triangle_ref import count_triangles_brute
from repro.graphs import generators as gen
from repro.utils import interpret_mode


def _stream_of(g, *, seed=0, dups=0, self_loops=0, reversed_dups=0):
    """A shuffled edge stream with optional duplicate/reversed/self-loop noise
    (all of which the ingest must ignore)."""
    rng = np.random.default_rng(seed)
    edges = g.edges[rng.permutation(g.n_edges)] if g.n_edges else g.edges
    parts = [edges]
    if g.n_edges and dups:
        parts.append(edges[rng.integers(0, g.n_edges, size=dups)])
    if g.n_edges and reversed_dups:
        parts.append(edges[rng.integers(0, g.n_edges, size=reversed_dups)][:, ::-1])
    if self_loops:
        loops = rng.integers(0, g.n_nodes, size=self_loops)
        parts.append(np.stack([loops, loops], axis=1).astype(np.int32))
    stream = np.concatenate(parts)
    return stream[rng.permutation(len(stream))]


# --------------------------------------------------------------------------
# Differential: blocked and sharded ingest vs the per-edge scan oracle
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,p,seed,block", [
    (21, 0.4, 0, 5),     # ragged blocks
    (45, 0.7, 1, 11),    # dense-ish, ragged
    (30, 0.3, 2, 1000),  # single block covering the whole stream
    (17, 0.9, 3, 1),     # one edge per block
])
def test_blocked_ingest_matches_per_edge_oracle(n, p, seed, block):
    g = gen.gnp(n, p, seed=seed)
    stream = _stream_of(g, seed=seed, dups=6, reversed_dups=4, self_loops=3)
    blocks = [stream[i:i + block] for i in range(0, len(stream), block)]
    want = count_triangles_brute(g)
    assert streaming.count_stream_per_edge(n, blocks) == want  # oracle sanity
    assert streaming.count_stream(n, blocks) == want
    assert streaming.count_stream(n, blocks, use_kernel=True) == want


@pytest.mark.parametrize("n_stages", [2, 3, 5])
def test_sharded_ingest_matches_oracle(n_stages):
    g = gen.gnp(52, 0.5, seed=7)
    stream = _stream_of(g, seed=7, dups=5, self_loops=2)
    blocks = [stream[i:i + 13] for i in range(0, len(stream), 13)]
    want = streaming.count_stream_per_edge(52, blocks)
    assert want == count_triangles_brute(g)
    assert streaming.count_stream(52, blocks, n_stages=n_stages) == want


def test_sharded_state_is_column_sharded():
    """The per-stage shard holds n · ceil(W/S) words — n²/8/S bytes, the
    memory model that lets streams larger than one device fit a ring."""
    state = streaming.init_sharded_state(1000, 4)
    w = -(-1000 // 32)  # 32
    assert state["adj"].shape == (4, 1000, -(-w // 4))
    full = streaming.init_state(1000)["adj"]
    assert 4 * state["adj"][0].size >= full.size  # shards cover every word
    assert state["adj"][0].nbytes <= -(-full.nbytes // 4) + 1000 * 4


def test_empty_and_degenerate_streams():
    assert streaming.count_stream(10, []) == 0
    assert streaming.count_stream(10, [np.zeros((0, 2), np.int32)]) == 0
    assert streaming.count_stream(10, [np.array([[3, 3], [4, 4]])]) == 0
    assert streaming.count_stream(10, [np.array([[3, 3]])], n_stages=2) == 0
    # duplicate-only stream: one edge, restated forever -> no triangles
    assert streaming.count_stream(10, [np.array([[1, 2]] * 50)]) == 0


def test_triangle_split_across_blocks_and_within_block():
    """Exercise every correction term: triangle 0-1-2 arrives with its last
    two edges in one block (mixed term), triangle 3-4-5 entirely in one block
    (dd term), triangle 6-7-8 one edge per block (pure phase 1)."""
    blocks = [
        np.array([[0, 1], [3, 4], [6, 7]]),
        np.array([[3, 5], [4, 5], [7, 8]]),          # 3-4-5 completes: dd
        np.array([[0, 2], [1, 2], [6, 8]]),          # 0-1-2 completes: mixed
    ]
    assert streaming.count_stream_per_edge(9, blocks) == 3
    assert streaming.count_stream(9, blocks) == 3
    assert streaming.count_stream(9, blocks, n_stages=3) == 3


# --------------------------------------------------------------------------
# Trace-count contract for the two-phase ingest
# --------------------------------------------------------------------------
def test_blocked_ingest_one_trace_per_fixed_shape_stream():
    g = gen.gnp(97, 0.4, seed=23)  # node count unique to this test
    blocks = [g.edges[i:i + 23] for i in range(0, g.n_edges, 23)]
    assert len(blocks[-1]) < 23  # genuinely ragged tail
    before = streaming.ingest_trace_count()
    assert streaming.count_stream(97, blocks) == count_triangles_brute(g)
    assert streaming.ingest_trace_count() - before == 1
    # same shapes again: zero new traces
    before = streaming.ingest_trace_count()
    assert streaming.count_stream(97, blocks) == count_triangles_brute(g)
    assert streaming.ingest_trace_count() - before == 0


def test_sharded_ingest_one_trace_per_fixed_shape_stream():
    g = gen.gnp(91, 0.5, seed=29)
    blocks = [g.edges[i:i + 31] for i in range(0, g.n_edges, 31)]
    before = streaming.ingest_trace_count()
    assert streaming.count_stream(91, blocks, n_stages=3) == count_triangles_brute(g)
    assert streaming.ingest_trace_count() - before == 1


def test_small_stream_under_huge_block_size_pads_pow2_not_block_size():
    """A planner-sized 1M block must not make a 100-edge stream scan 1M
    phantom rows: a stream that never fills one block is padded to the next
    power of two instead (still one shape, hence one trace)."""
    g = gen.gnp(41, 0.4, seed=31)
    got = list(streaming.padded_blocks([g.edges], 41, block_size=1 << 20))
    assert len(got) == 1
    assert got[0].shape[0] < 2 * max(g.n_edges, 8)  # pow2 bucket, not 1M
    assert streaming.count_stream(41, [g.edges], block_size=1 << 20) == \
        count_triangles_brute(g)


# --------------------------------------------------------------------------
# count_stream plan handling (the satellite bugfixes)
# --------------------------------------------------------------------------
def test_count_stream_rejects_non_stream_plan():
    g = gen.gnp(20, 0.5, seed=1)
    c = TriangleCounter()
    for bad in (Plan(method="dense"), Plan(method="bitset_ring"),
                Plan(method="mapreduce")):
        with pytest.raises(ValueError, match="method='stream'"):
            c.count_stream(20, [g.edges], plan=bad)
    # a fixed non-stream plan on the counter is rejected the same way
    with pytest.raises(ValueError, match="method='stream'"):
        TriangleCounter(plan=Plan(method="dense")).count_stream(20, [g.edges])


def test_count_stream_applies_plan_block_size():
    """Regression: the plan used to be derived AFTER block-size resolution,
    so a planner/fixed plan's block_size never applied. The plan resolves
    first now: a fixed block_size=17 plan must split a 1-block stream."""
    g = gen.gnp(66, 0.4, seed=13)
    c = TriangleCounter(plan=Plan(method="stream", block_size=17))
    res = c.count_stream(66, [g.edges])
    assert res.item() == count_triangles_brute(g)
    assert res.stats["block_size"] == 17
    assert res.stats["n_blocks"] == -(-g.n_edges // 17)
    # explicit argument still overrides the plan
    res2 = c.count_stream(66, [g.edges], block_size=2048)
    assert res2.item() == count_triangles_brute(g)
    assert res2.stats["block_size"] == 2048 and res2.stats["n_blocks"] == 1


def test_count_stream_plan_none_uses_planner_sizing():
    g = gen.gnp(58, 0.5, seed=17)
    blocks = [g.edges[i:i + 19] for i in range(0, g.n_edges, 19)]
    res = TriangleCounter().count_stream(58, blocks)
    assert res.item() == count_triangles_brute(g)
    assert res.plan.method == "stream"
    # the planner's block_size is the one that executed (the regression was
    # stats/block resolution ignoring it)
    assert res.stats["block_size"] == res.plan.block_size
    assert res.stats["n_stages"] == res.plan.n_stages
    assert res.stats["cache"]["key"][0] == res.plan.cache_key()


def test_count_stream_sharded_plan_routes_sharded_state():
    g = gen.gnp(60, 0.5, seed=19)
    c = TriangleCounter(plan=Plan(method="stream", n_stages=4, block_size=64))
    res = c.count_stream(60, [g.edges])
    assert res.item() == count_triangles_brute(g)
    assert res.stats["sharded"] is True and res.stats["n_stages"] == 4
    assert res.stats["on_mesh"] is False  # no mesh on this host


# --------------------------------------------------------------------------
# Planner stream sizing
# --------------------------------------------------------------------------
def test_stream_plan_carries_planner_sizing():
    stats = GraphStats(n_nodes=100_000, n_edges=0, replication_factor=0,
                       max_degree=0, max_fwd_degree=0, edges_in_memory=False)
    res = Resources(n_devices=8, memory_bytes=256 << 20)
    p = plan(stats, res)
    n_stages, block_size, shard_bytes = stream_sizing(stats, res)
    assert p.method == "stream"
    assert (p.n_stages, p.block_size) == (n_stages, block_size)
    assert p.n_stages > 1  # 1.25 GB state cannot sit on a 256 MB device
    assert shard_bytes <= res.memory_bytes
    assert "ring-sharded" in p.reason


def test_stream_plan_single_stage_when_state_fits():
    stats = GraphStats(n_nodes=10_000, n_edges=0, replication_factor=0,
                       max_degree=0, max_fwd_degree=0, edges_in_memory=False)
    p = plan(stats, Resources(n_devices=8))  # 12.5 MB state, 4 GB budget
    assert p.method == "stream" and p.n_stages == 1
    assert p.block_size >= 4096


def test_stream_plan_warns_when_even_full_ring_does_not_fit():
    stats = GraphStats(n_nodes=1_000_000, n_edges=0, replication_factor=0,
                       max_degree=0, max_fwd_degree=0, edges_in_memory=False)
    # unbounded: the degree-aware hybrid state is the smallest layout; at
    # n=1M even it overflows 64 MB, so the plan still carries a WARNING
    p = plan(stats, Resources(n_devices=2, memory_bytes=64 << 20))
    assert p.method == "stream" and p.state_layout == "hybrid"
    assert p.n_stages == 1
    assert "WARNING" in p.reason
    # windowed streams have no hybrid fallback (the epoch ring stays
    # bitset): the old full-ring bitset warning survives there
    pw = plan(stats, Resources(n_devices=2, memory_bytes=64 << 20),
              window_epochs=2)
    assert pw.state_layout == "bitset" and pw.n_stages == 2
    assert "WARNING" in pw.reason


# --------------------------------------------------------------------------
# count_batch / serve plan derivation (satellite bugfix)
# --------------------------------------------------------------------------
def test_batch_plan_derived_from_resources():
    assert TriangleCounter(Resources(backend="tpu")).batch_plan().use_kernel
    cpu = TriangleCounter(Resources(backend="cpu")).batch_plan()
    assert not cpu.use_kernel
    # compiled-vs-interpreted is the live backend's call, not a plan field
    assert "interpret" not in cpu.to_dict() and interpret_mode()
    with pytest.raises(ValueError, match="dense"):
        TriangleCounter().count_batch([gen.gnp(10, 0.5, seed=0)],
                                      plan=Plan(method="stream"))


def test_count_batch_executes_plan_backend():
    """The vmapped executable must honor the plan's use_kernel — the
    regression built Plan(method='dense') defaults and dropped it."""
    graphs = [gen.gnp(n, 0.5, seed=n) for n in (18, 25, 31)]
    want = [count_triangles_brute(g) for g in graphs]
    res = TriangleCounter().count_batch(
        graphs, plan=Plan(method="dense", use_kernel=True))
    assert [int(x) for x in np.asarray(res.count)] == want
    assert res.plan.use_kernel is True


def test_serve_loop_batches_under_planner_plan_and_serves_streams():
    from repro.serve.serve_loop import TriangleServeConfig, TriangleServer

    server = TriangleServer(serve_cfg=TriangleServeConfig(max_batch=4))
    graphs = [gen.gnp(n, 0.5, seed=n) for n in (22, 28, 34)]
    results = server.serve(graphs)
    for g, r in zip(graphs, results):
        assert r.item() == count_triangles_brute(g)
        if r.stats.get("batched"):
            # the executed batch plan is the planner's, not Plan defaults
            assert r.plan.reason != "batched dense path"
    g = gen.gnp(77, 0.4, seed=5)
    blocks = [g.edges[i:i + 25] for i in range(0, g.n_edges, 25)]
    rs = server.serve_stream(77, blocks)
    assert rs.item() == count_triangles_brute(g)
    assert rs.plan.method == "stream"
    # the stream's jitted ingest landed in the server's shared compile cache
    assert any(isinstance(k[1], tuple) and k[1][0] == "stream"
               for k in server.counter._cache)
    rs2 = server.serve_stream(77, [g.edges[i:i + 25] for i in range(0, g.n_edges, 25)])
    assert rs2.item() == rs.item() and rs2.stats["cache"]["hit"] is True


# --------------------------------------------------------------------------
# Pair kernel (the mixed-term closure) vs oracle
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n_pad,w,b,seed", [(64, 2, 32, 0), (96, 3, 41, 1)])
def test_bitset_pair_kernel_matches_ref(n_pad, w, b, seed):
    from repro.kernels.bitset_count.ops import bitset_pair_count
    from repro.kernels.bitset_count.ref import bitset_pair_count_ref

    key = jax.random.PRNGKey(seed)
    ka, kb, ke, kp = jax.random.split(key, 4)
    a = jax.random.randint(ka, (n_pad, w), 0, 2**31 - 1, dtype=jnp.int32).astype(jnp.uint32)
    bt = jax.random.randint(kb, (n_pad, w), 0, 2**31 - 1, dtype=jnp.int32).astype(jnp.uint32)
    edges = jax.random.randint(ke, (b, 2), 0, n_pad)
    phantom = jax.random.uniform(kp, (b,)) < 0.2
    edges = jnp.where(phantom[:, None], n_pad, edges).astype(jnp.int32)
    assert int(bitset_pair_count(a, bt, edges, interpret=True)) == \
        int(bitset_pair_count_ref(a, bt, edges))
    # asymmetric by construction: swapping tables swaps gather sides
    assert int(bitset_pair_count(bt, a, edges, interpret=True)) == \
        int(bitset_pair_count_ref(bt, a, edges))


# --------------------------------------------------------------------------
# Real multi-device shard_map ring (subprocess, 8 forced host devices)
# --------------------------------------------------------------------------
SHARDED_SNIPPET = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro.api import Plan, TriangleCounter
    from repro.core import streaming
    from repro.core.triangle_ref import count_triangles_brute
    from repro.graphs import generators as gen
    from repro.launch.mesh import make_ring_mesh

    g = gen.gnp(200, 0.2, seed=11)
    want = count_triangles_brute(g)
    rng = np.random.default_rng(1)
    edges = g.edges[rng.permutation(g.n_edges)]
    blocks = [edges[i:i + 300] for i in range(0, len(edges), 300)]
    mesh = make_ring_mesh(8)
    got = streaming.count_stream(200, blocks, n_stages=8, mesh=mesh)
    assert got == want, (got, want)
    c = TriangleCounter(plan=Plan(method="stream", n_stages=8, block_size=300),
                        mesh=mesh)
    res = c.count_stream(200, [edges[i:i + 300] for i in range(0, len(edges), 300)])
    assert res.item() == want and res.stats["on_mesh"], res.stats
    print("SHARDED_STREAM_OK", want)
    """
)


@pytest.mark.slow
def test_sharded_stream_on_eight_devices_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", SHARDED_SNIPPET], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr
    assert "SHARDED_STREAM_OK" in r.stdout
