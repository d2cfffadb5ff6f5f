"""Planner + unified-API coverage: differential tests (every plan the planner
can emit counts exactly), regime pinning on the paper's input families, the
compile-cache contract, the CountResult contract, and the streaming padding
fix. No hypothesis dependency — this module always runs in tier-1."""
import json

import jax
import numpy as np
import pytest

from repro.api import (
    METHODS,
    MR_RF_FACTOR,
    CountResult,
    GraphStats,
    Plan,
    Resources,
    TriangleCounter,
    count_triangles,
    plan,
)
from repro.core.triangle_ref import count_triangles_brute
from repro.core import streaming
from repro.graphs import generators as gen


# --------------------------------------------------------------------------
# Differential: every emittable plan counts exactly
# --------------------------------------------------------------------------
@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("n,p,seed", [(40, 0.2, 0), (72, 0.6, 1)])
def test_every_plan_matches_brute(method, n, p, seed):
    g = gen.gnp(n, p, seed=seed)
    want = count_triangles_brute(g)
    stats = GraphStats.from_graph(g)
    # allow={method} forces the planner to emit exactly this method's plan
    p_ = plan(stats, Resources(n_devices=4), allow={method})
    assert p_.method == method
    res = TriangleCounter().count(g, plan=p_)
    assert res.item() == want
    assert res.plan is p_


def test_planner_ring_uses_stages():
    g = gen.gnp(64, 0.5, seed=3)
    p_ = plan(GraphStats.from_graph(g), Resources(n_devices=4), allow={"ring"})
    assert p_.n_stages == 4
    assert TriangleCounter().count(g, plan=p_).item() == count_triangles_brute(g)


# --------------------------------------------------------------------------
# Regime pinning (the paper's Table 1 families)
# --------------------------------------------------------------------------
def _dsjc5_stats() -> GraphStats:
    # DSJC.5-like: n=1000 at density .5 — the dense regime where the paper's
    # pipeline wins by orders of magnitude
    return GraphStats(n_nodes=1_000, n_edges=250_000,
                      replication_factor=62_000_000, max_degree=560,
                      max_fwd_degree=280)


def test_dense_dsjc_regime_plans_pipeline():
    p_ = plan(_dsjc5_stats(), Resources())
    assert p_.method in ("dense", "ring")  # the MXU pipeline path
    # with a device ring available, the planner shards it
    p_ring = plan(_dsjc5_stats(), Resources(n_devices=8))
    assert p_ring.method == "ring" and p_ring.n_stages == 8


def test_high_replication_factor_never_mapreduce():
    # sweep regimes; whenever RF blows past MR_RF_FACTOR x m, mapreduce must
    # not be auto-chosen (Afrati–Ullman communication blowup)
    for stats in [
        _dsjc5_stats(),
        GraphStats(500, 50_000, MR_RF_FACTOR * 50_000 + 1, 300, 150),
        GraphStats(10_000, 1_000_000, 500_000_000, 2_000, 900),
    ]:
        assert stats.replication_factor > MR_RF_FACTOR * stats.n_edges
        for res in (Resources(), Resources(memory_bytes=1 << 20),
                    Resources(n_devices=16)):
            assert plan(stats, res).method != "mapreduce"


def test_not_memory_resident_plans_stream():
    stats = GraphStats(n_nodes=5_000_000, n_edges=0, replication_factor=0,
                       max_degree=0, max_fwd_degree=0, edges_in_memory=False)
    p_ = plan(stats, Resources())
    assert p_.method == "stream"
    assert p_.predicted_bytes > 0  # the bitset state estimate


def test_memory_pressure_avoids_dense():
    # n=20000: dense needs ~4.8 GB, the bitset masks ~50 MB — a 100 MB budget
    # must not plan the dense matmul
    stats = GraphStats(n_nodes=20_000, n_edges=400_000,
                       replication_factor=1_600_000, max_degree=50,
                       max_fwd_degree=25)
    p_ = plan(stats, Resources(memory_bytes=100 << 20))
    assert p_.method not in ("dense", "ring")
    assert p_.predicted_bytes <= 100 << 20


def test_sparse_road_network_regime():
    # NY-like: huge, density ~1e-5 — the memory-bound sparse path
    stats = GraphStats(n_nodes=264_346, n_edges=733_846,
                       replication_factor=1_100_000, max_degree=8,
                       max_fwd_degree=6)
    assert plan(stats, Resources()).method == "sparse"


# --------------------------------------------------------------------------
# Hybrid memory regime: degree-aware state beside dense/sharded
# --------------------------------------------------------------------------
def test_hybrid_pinned_on_sparse_stream_dense_on_clique_like():
    """Informative stream stats steer the layout: a sparse power-law-scale
    stream gets the degree-aware hybrid state (linear in n); a clique-like
    stream keeps the dense bitset (every row would be a hub anyway)."""
    sparse = GraphStats(n_nodes=100_000, n_edges=400_000,
                        replication_factor=0, max_degree=900,
                        max_fwd_degree=40, edges_in_memory=False)
    p_ = plan(sparse, Resources(memory_bytes=4 << 30))
    assert p_.method == "stream" and p_.state_layout == "hybrid"
    assert p_.hub_slots > 0 and p_.tail_capacity > 0 and p_.hub_threshold > 0
    assert "hybrid" in p_.reason
    clique = GraphStats(n_nodes=2000, n_edges=1_800_000,
                        replication_factor=0, max_degree=1900,
                        max_fwd_degree=1000, edges_in_memory=False)
    q = plan(clique, Resources(memory_bytes=4 << 30))
    assert q.state_layout == "bitset" and q.hub_slots == 0


def test_hybrid_plan_fields_live_in_cache_key():
    """The hybrid fields are trace-static (they fix state shapes / the jit
    static promotion arg), so two plans differing in any of them must NOT
    share a compiled executable."""
    base = plan(GraphStats(n_nodes=100_000, n_edges=400_000,
                           replication_factor=0, max_degree=900,
                           max_fwd_degree=40, edges_in_memory=False),
                Resources(memory_bytes=4 << 30))
    import dataclasses as dc
    for field, bump in (("state_layout", "bitset"), ("hub_slots", 1),
                        ("tail_capacity", 1), ("hub_threshold", 1)):
        old = getattr(base, field)
        mutated = dc.replace(base, **{field: bump if isinstance(bump, str)
                                      else old + bump})
        assert mutated.cache_key() != base.cache_key(), field


def test_planner_predicted_bytes_equal_session_allocation_on_random_mixes():
    """The honesty pin: for randomized stream-stat mixes that land on the
    hybrid regime, ``plan.predicted_bytes`` equals BOTH the closed-form
    ``hybrid_state_nbytes`` and the real allocation's ``state_nbytes`` —
    the planner never charges a byte the session does not pin."""
    rng = np.random.default_rng(42)
    checked = 0
    for _ in range(12):
        n = int(rng.integers(20_000, 120_000))
        m = int(rng.integers(0, 8 * n))
        stats = GraphStats(n_nodes=n, n_edges=m, replication_factor=0,
                           max_degree=0, max_fwd_degree=0,
                           edges_in_memory=False)
        budget = int(rng.integers(16 << 20, 256 << 20))
        p_ = plan(stats, Resources(memory_bytes=budget))
        if p_.state_layout != "hybrid":
            continue
        checked += 1
        assert p_.predicted_bytes == streaming.hybrid_state_nbytes(
            n, p_.hub_slots, p_.tail_capacity)
    assert checked >= 4  # the mix must actually exercise the hybrid arm
    # one real allocation (kept small): formula == device bytes
    p_ = plan(GraphStats(n_nodes=20_000, n_edges=60_000, replication_factor=0,
                         max_degree=0, max_fwd_degree=0,
                         edges_in_memory=False),
              Resources(memory_bytes=16 << 20))
    assert p_.state_layout == "hybrid"
    state = streaming.init_hybrid_state(20_000, p_.hub_slots, p_.tail_capacity)
    assert streaming.state_nbytes(streaming.snapshot_state(state)) \
        == p_.predicted_bytes


def test_acceptance_powerlaw_100k_admits_hybrid_where_dense_rejected():
    """THE acceptance scenario for the hybrid regime: a 100k-node stream
    (dense bitset: n²/8 ≈ 1.25 GB; even the 2-stage shard ≈ 625 MB) must be
    ADMITTED on a 64 MB budget via the hybrid state, with the verdict and
    plan reasons naming the regime."""
    from repro.api import admit_session

    res = Resources(n_devices=2, memory_bytes=64 << 20)
    dense_bytes = 4 * 100_000 * (-(-100_000 // 32))
    assert dense_bytes > res.memory_bytes  # the n²/8 wall this escapes
    a = admit_session(100_000, res)
    assert a.action == "admit-hybrid" and a.admitted
    assert "hybrid" in a.reason and "hybrid" in a.plan.reason
    assert a.plan.state_layout == "hybrid" and a.plan.n_stages == 1
    assert a.state_bytes == a.plan.predicted_bytes <= res.memory_bytes
    assert a.state_bytes == streaming.hybrid_state_nbytes(
        100_000, a.plan.hub_slots, a.plan.tail_capacity)


# --------------------------------------------------------------------------
# Plan contract
# --------------------------------------------------------------------------
def test_plan_is_serializable():
    p_ = plan(_dsjc5_stats(), Resources(n_devices=4))
    d = json.loads(p_.to_json())
    assert Plan.from_dict(d) == p_ == Plan.from_json(p_.to_json())
    assert d["predicted_bytes"] > 0 and d["reason"]


def test_plan_rejects_unknown_methods():
    with pytest.raises(ValueError):
        plan(_dsjc5_stats(), allow={"quantum"})


# --------------------------------------------------------------------------
# CountResult + compile cache
# --------------------------------------------------------------------------
def test_count_result_contract():
    g = gen.gnp(50, 0.4, seed=9)
    res = TriangleCounter().count(g)
    assert isinstance(res, CountResult)
    assert isinstance(res.count, jax.Array)  # device array until .item()
    assert res.item() == int(res) == count_triangles_brute(g)
    assert res.plan.method in METHODS and res.plan.predicted_bytes > 0
    assert res.wall_s >= 0 and "cache" in res.stats


def test_compile_cache_hits_across_same_bucket_graphs():
    c = TriangleCounter()
    p_ = Plan(method="dense")
    for n in (40, 50, 60):  # all pad to the same 64-bucket
        res = c.count(gen.gnp(n, 0.5, seed=n), plan=p_)
        assert res.item() == count_triangles_brute(gen.gnp(n, 0.5, seed=n))
    info = c.cache_info
    assert info["entries"] == 1 and info["traces"] == 1 and info["hits"] == 2
    assert res.stats["cache"]["hit"] is True


def test_count_batch_matches_brute():
    graphs = [gen.gnp(n, 0.5, seed=n) for n in (20, 33, 47, 12, 64)]
    c = TriangleCounter()
    res = c.count_batch(graphs)
    got = np.asarray(res.count)
    assert got.shape == (len(graphs),)
    assert [int(x) for x in got] == [count_triangles_brute(g) for g in graphs]
    # same-bucket second batch reuses the vmapped executable
    res2 = c.count_batch([gen.gnp(30, 0.4, seed=7), gen.gnp(41, 0.6, seed=8)])
    assert res2.stats["cache"]["hit"] is True


def test_acceptance_dense_1000_node_gnp():
    """ISSUE acceptance: planner-chosen run on a dense 1000-node gnp graph
    matches brute force; CountResult.plan records method + predicted bytes."""
    g = gen.gnp(1000, 0.5, seed=1)
    res = TriangleCounter().count(g)
    assert res.item() == count_triangles_brute(g)
    assert res.plan.method in ("dense", "ring")
    assert res.plan.predicted_bytes > 0 and res.plan.reason


# --------------------------------------------------------------------------
# Shim + streaming satellites
# --------------------------------------------------------------------------
def test_count_triangles_shim_all_methods():
    g = gen.gnp(45, 0.5, seed=4)
    want = count_triangles_brute(g)
    assert count_triangles(g) == want  # default stays "dense"
    for method in ("auto", "dense", "sparse", "ring", "bitset"):
        assert count_triangles(g, method=method) == want
    # legacy kwargs still reach the original entry points
    assert count_triangles(g, method="ring", n_stages=2) == want
    assert count_triangles(g, method="ring", sequential=True, n_stages=2) == want


def test_stream_ragged_blocks_single_trace():
    """Satellite: the trailing partial block must not cost an extra compile —
    ragged blocks are padded with phantom rows (id >= n_nodes) to one fixed
    shape, so the whole stream takes exactly one trace."""
    g = gen.gnp(64, 0.5, seed=6)
    blocks = [g.edges[i:i + 37] for i in range(0, g.n_edges, 37)]
    assert len(blocks[-1]) < 37  # genuinely ragged tail
    before = streaming.ingest_trace_count()
    assert streaming.count_stream(64, blocks) == count_triangles_brute(g)
    assert streaming.ingest_trace_count() - before == 1


def test_counter_count_stream_contract():
    g = gen.gnp(80, 0.3, seed=2)
    blocks = [g.edges[i:i + 29] for i in range(0, g.n_edges, 29)]
    res = TriangleCounter().count_stream(80, blocks)
    assert res.item() == count_triangles_brute(g)
    assert res.plan.method == "stream"
    assert res.stats["ingest_traces"] <= 1  # 0 if this shape was traced already


def test_graph_stream_pipeline_blocked_generation():
    """Satellite: edge_stream yields per-block (seeded per block index) and
    the union of blocks is exactly the gnp graph — never materialized whole."""
    from repro.data.pipeline import GraphStreamPipeline

    pipe = GraphStreamPipeline(n_nodes=200, density=0.2, seed=3)
    blocks = list(pipe.edge_stream(block_size=500))
    g = gen.gnp(200, 0.2, seed=3)
    assert all(len(b) <= 500 for b in blocks)
    got = np.concatenate(blocks)
    assert got.shape == g.edges.shape
    # same edge multiset, locally shuffled
    assert np.array_equal(np.unique(got, axis=0), np.unique(g.edges, axis=0))
    assert streaming.count_stream(200, pipe.edge_stream(block_size=500)) == \
        count_triangles_brute(g)


def test_triangle_server_batches_small_dense_requests():
    from repro.serve.serve_loop import TriangleServeConfig, TriangleServer

    server = TriangleServer(serve_cfg=TriangleServeConfig(max_batch=4))
    graphs = [gen.gnp(n, 0.5, seed=n) for n in (24, 30, 36, 42, 48, 54)]
    results = server.serve(graphs)
    assert len(results) == len(graphs)
    for g, r in zip(graphs, results):
        assert r.item() == count_triangles_brute(g)
    assert any(r.stats.get("batched") for r in results)


def test_scale_20_stream_admits_one_hybrid_shape_for_every_session():
    """One v5e (15.75 GiB): a 2**20-vertex stream gets the hybrid state with
    512-neighbour tail buffers, 8,192 hub rows and promotion at degree 512
    (a Graph500 scale-20 graph has 6,196 vertices of degree >= 512), and the
    second and third concurrent sessions get the same shapes, so one
    compiled ingest serves them all. The charge is the allocation."""
    from repro.api import admit_session

    res = Resources(memory_bytes=int(15.75 * 2**30), backend="tpu")
    first = admit_session(2**20, res)
    assert first.action == "admit-hybrid"
    p = first.plan
    assert (p.hub_slots, p.tail_capacity, p.hub_threshold, p.block_size) == (
        8192, 512, 512, 2048)
    assert first.state_bytes == p.predicted_bytes == streaming.hybrid_state_nbytes(
        2**20, 8192, 512) < 3.3e9
    for k in (1, 2):
        more = admit_session(2**20, res, bytes_in_use=k * first.state_bytes)
        assert more.action == "admit-hybrid"
        assert more.plan.cache_key() == p.cache_key()
        assert more.state_bytes == first.state_bytes
    # past what the whole-device shapes fit, the remainder sizes the state
    last = admit_session(2**20, res, bytes_in_use=res.memory_bytes - (1 << 30))
    assert last.action == "admit-hybrid" and last.state_bytes <= 1 << 30
    assert last.plan.hub_slots < p.hub_slots


def test_hybrid_state_nbytes_equals_the_allocation_with_counters():
    for n, h, cap in [(97, 8, 4), (4096, 256, 16), (2**14, 512, 64)]:
        state = streaming.init_hybrid_state(n, h, cap)
        assert streaming.state_nbytes(streaming.snapshot_state(state)) \
            == streaming.hybrid_state_nbytes(n, h, cap)
        assert set(streaming.HYBRID_COUNTERS) <= set(state)
