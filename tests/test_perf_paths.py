"""Differential tests for the perf-optimized counting paths (PR: dead-block
elimination + blocked streaming).

Covers, in interpret mode on CPU:
- live-grid dense kernel vs the XLA ``count_triangles_dense`` path,
- blocked bitset kernel vs ``bitset_ring_spec``'s pure-JAX process fn,
- the live-grid size law Σ_{i≤j}(j−i+1) = C(nb+2, 3),
- the scanned ``run_sequential`` vs the seed Python-loop emulation,
across Erdős–Rényi, complete, and star graphs — complete graphs at
n = 3·block make every boundary block of the i ≤ k ≤ j wedge live, star
graphs make almost all of them dead.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dynamic_pipeline import run_sequential, run_sequential_python
from repro.core.triangle_pipeline import (
    bitset_ring_spec,
    build_bitset_ring_operands,
    build_dense_ring_operands,
    count_triangles_bitset_ring,
    count_triangles_dense,
    count_triangles_ring,
    dense_ring_spec,
)
from repro.core.triangle_ref import count_triangles_brute
from repro.graphs.formats import Graph, forward_adjacency_dense
from repro.graphs import generators as gen
from repro.kernels.bitset_count.ops import bitset_edge_count, bitset_grid_steps
from repro.kernels.triangle_count.ops import triangle_count, triangle_count_grid_steps
from repro.kernels.triangle_count.triangle_count import live_grid_indices, live_grid_size


def star(n: int) -> Graph:
    """Hub-and-spokes: zero triangles, maximally skewed degrees."""
    edges = np.stack([np.zeros(n - 1, np.int32), np.arange(1, n, dtype=np.int32)], 1)
    return Graph(edges=edges, n_nodes=n)


def complete(n: int) -> Graph:
    iu = np.triu_indices(n, k=1)
    return Graph(edges=np.stack(iu, 1).astype(np.int32), n_nodes=n)


GRAPHS = [
    ("er", gen.gnp(150, 0.35, seed=11)),
    # 3x3 blocks at block=64: every boundary block of the i ≤ k ≤ j wedge live
    ("complete", complete(192)),
    ("star", star(200)),
]


# --------------------------------------------------------------------------
# Live-grid dense kernel
# --------------------------------------------------------------------------
@pytest.mark.parametrize("name,g", GRAPHS, ids=[n for n, _ in GRAPHS])
def test_live_grid_kernel_matches_dense_path(name, g):
    u = jnp.asarray(forward_adjacency_dense(g))
    want = int(count_triangles_dense(u))
    got = int(triangle_count(u, block=64, interpret=True, live_grid=True))
    assert got == want == count_triangles_brute(g)


@pytest.mark.parametrize("nb", [1, 2, 3, 5])
def test_live_grid_enumeration_law(nb):
    idx = live_grid_indices(nb)
    # the compacted grid is exactly Σ_{i≤j} (j−i+1) steps...
    want = sum(j - i + 1 for i in range(nb) for j in range(i, nb))
    assert idx.shape[0] == want == live_grid_size(nb)
    # ...every triple is a live wedge block, k innermost within each (i, j)
    i, j, k = idx[:, 0], idx[:, 1], idx[:, 2]
    assert np.all((i <= k) & (k <= j))
    assert idx.shape[0] == len({tuple(t) for t in idx.tolist()})


def test_grid_steps_accounting():
    # n=192, block=64 → nb=3: full grid 27 steps, live grid C(5,3)=10
    assert triangle_count_grid_steps(192, block=64, live_grid=False) == 27
    assert triangle_count_grid_steps(192, block=64, live_grid=True) == 10
    # the live grid never exceeds the full grid and wins ~6x asymptotically
    assert live_grid_size(16) == 816 < 16**3


def test_live_grid_boundary_blocks():
    """U supported only on the extreme blocks: (0, nb-1) off-diagonal corner
    plus the diagonal blocks — catches index-map transposition errors."""
    block, nb = 64, 3
    n = block * nb
    rng = np.random.default_rng(0)
    u = np.zeros((n, n), np.float32)
    iu = np.triu_indices(n, k=1)
    dense = (rng.random(len(iu[0])) < 0.3).astype(np.float32)
    full = np.zeros((n, n), np.float32)
    full[iu] = dense
    # keep only rows/cols touching block-row 0 and block-col nb-1
    u[:block, :] = full[:block, :]
    u[:, -block:] = full[:, -block:]
    want = int(count_triangles_dense(jnp.asarray(u)))
    got = int(triangle_count(jnp.asarray(u), block=block, interpret=True))
    assert got == want


# --------------------------------------------------------------------------
# Blocked bitset kernel
# --------------------------------------------------------------------------
@pytest.mark.parametrize("name,g", GRAPHS, ids=[n for n, _ in GRAPHS])
@pytest.mark.parametrize("n_stages", [1, 4])
def test_blocked_bitset_kernel_matches_pure_jax(name, g, n_stages):
    _, masks, edge_blocks = build_bitset_ring_operands(g, n_stages)
    spec = bitset_ring_spec(use_kernel=False)
    for s in range(n_stages):
        mask = jnp.asarray(masks[s])
        for t in range(n_stages):
            eb = jnp.asarray(edge_blocks[t])
            _, want = spec.process(spec.init(mask), eb, jnp.int32(t))
            got = bitset_edge_count(mask, eb, interpret=True)
            assert int(got) == int(want)


def test_blocked_kernel_matches_seed_per_edge_kernel():
    """The reinstated seed baseline and the blocked kernel agree bit-for-bit
    (they are benchmarked against each other in BENCH_kernels.json)."""
    from repro.kernels.bitset_count.bitset_count import bitset_edge_count_per_edge_kernel

    g = gen.gnp(100, 0.4, seed=8)
    _, masks, edge_blocks = build_bitset_ring_operands(g, 2)
    for s in range(2):
        mask = jnp.asarray(masks[s])
        for t in range(2):
            eb = jnp.asarray(edge_blocks[t])
            seed = bitset_edge_count_per_edge_kernel(mask, eb, interpret=True)
            blocked = bitset_edge_count(mask, eb, interpret=True)
            assert int(seed) == int(blocked)


def test_blocked_bitset_tile_occupancy():
    """≥128 edges per grid step: a 1000-edge block runs ceil(1000/128)=8
    steps, not 1000."""
    assert bitset_grid_steps(1000) == 8
    assert bitset_grid_steps(1, edge_tile=128) == 1
    g = gen.gnp(80, 0.5, seed=4)
    _, masks, edge_blocks = build_bitset_ring_operands(g, 1)
    b = edge_blocks.shape[1]
    got = bitset_edge_count(jnp.asarray(masks[0]), jnp.asarray(edge_blocks[0]),
                            interpret=True)
    assert int(got) == count_triangles_brute(g)
    assert bitset_grid_steps(b) == -(-b // 128) < b


def test_bitset_ring_use_kernel_end_to_end():
    """The satellite fix: use_kernel must actually reach the kernel and agree."""
    g = gen.gnp(96, 0.4, seed=5)
    want = count_triangles_brute(g)
    assert count_triangles_bitset_ring(g, n_stages=3, sequential=True,
                                       use_kernel=True) == want


# --------------------------------------------------------------------------
# Scanned runtime + uint8 streaming
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n_stages", [1, 3])
def test_scanned_sequential_matches_python_loop(n_stages):
    g = gen.gnp(90, 0.3, seed=7)
    part, blocks = build_dense_ring_operands(g, n_stages)
    spec = dense_ring_spec(part.rows_per_stage)
    blocks = jnp.asarray(blocks)
    scanned = run_sequential(spec, blocks, blocks, n_stages)
    eager = run_sequential_python(spec, blocks, blocks, n_stages)
    assert int(scanned) == int(eager) == count_triangles_brute(g)

    _, masks, edges = build_bitset_ring_operands(g, n_stages)
    bspec = bitset_ring_spec()
    masks, edges = jnp.asarray(masks), jnp.asarray(edges)
    assert int(run_sequential(bspec, masks, edges, n_stages)) == \
        int(run_sequential_python(bspec, masks, edges, n_stages))


def test_dense_ring_streams_uint8_by_default():
    g = gen.gnp(64, 0.5, seed=2)
    _, blocks = build_dense_ring_operands(g, 2)
    assert blocks.dtype == np.uint8
    want = count_triangles_brute(g)
    assert count_triangles_ring(g, n_stages=2, sequential=True) == want
    assert count_triangles_ring(g, n_stages=2, sequential=True, use_kernel=True) == want
    # seed layout still reachable
    _, f32_blocks = build_dense_ring_operands(g, 2, dtype=np.float32)
    assert f32_blocks.dtype == np.float32
