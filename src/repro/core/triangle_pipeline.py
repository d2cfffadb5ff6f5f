"""Dynamic-pipeline triangle counting (the paper's contribution, TPU-native).

Counting semantics (provably equal to Aráoz–Zoltan's filter semantics, see
DESIGN.md §2): fix any total order on nodes; the filter responsible for rank
r counts streamed edges (u, v) with u, v ∈ fwd_adj(r); each triangle is
counted exactly once, at its min-rank vertex. Three execution paths:

- dense:   Δ = sum(U ⊙ (U @ U)) with U the strictly-upper-triangular
           rank-permuted adjacency — the MXU path (Pallas kernel available).
- ring:    row blocks of U are the stage-resident filters; the blocks
           themselves stream around the device ring (``dynamic_pipeline``).
- sparse:  padded sorted forward-adjacency + per-edge sorted intersection —
           the memory-bound path for huge sparse graphs (NY road network).
- bitset:  stage-resident membership bitmasks; *edge blocks* stream through
           the ring and are closed against each stage's responsible set —
           the most literal rendering of the paper's edge streaming.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import count_dtype
from repro.core.dynamic_pipeline import DynamicPipeline, FilterSpec, run_sequential
from repro.core.partition import RingPartition, ring_partition
from repro.graphs.formats import Graph


# --------------------------------------------------------------------------
# Dense single-device path
# --------------------------------------------------------------------------
def count_triangles_dense(u: jax.Array, *, use_kernel: bool = False) -> jax.Array:
    """sum(U ⊙ (U @ U)) — U strictly upper triangular 0/1, any float dtype.

    The matmul is exact in f32 (entries ≤ n < 2²⁴) but the REDUCTION must be
    integer: an f32 sum silently loses exactness past 2²⁴ total triangles
    (caught by the benchmark's pipeline-vs-MapReduce cross-check on DSJC.5,
    Δ = 20.8M)."""
    if use_kernel:
        from repro.kernels.triangle_count.ops import triangle_count as tc_kernel

        return tc_kernel(u)
    prod = jax.lax.dot(u, u, preferred_element_type=jnp.float32)
    masked = (prod * u.astype(jnp.float32)).astype(jnp.int32)
    return jnp.sum(masked, dtype=count_dtype())


# --------------------------------------------------------------------------
# Sparse single-device path (per-edge sorted intersection)
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("edge_batch",))
def count_triangles_sparse(
    nbrs: jax.Array, edges: jax.Array, *, edge_batch: int = 4096
) -> jax.Array:
    """Forward-edge intersection count.

    nbrs:  (n_pad, md) int32 — sorted forward neighbors in rank space, padded
           with a sentinel larger than any real rank (use n_pad).
    edges: (m_pad, 2) int32 ranks (lo, hi), lo < hi; padding rows must use the
           sentinel so they contribute zero.
    """
    n_pad, md = nbrs.shape
    sentinel = n_pad

    def edge_tri(uv):
        u = jnp.minimum(uv[0], n_pad - 1)
        v = jnp.minimum(uv[1], n_pad - 1)
        fu = nbrs[u]
        fv = nbrs[v]
        pos = jnp.clip(jnp.searchsorted(fv, fu), 0, md - 1)
        hit = (fv[pos] == fu) & (fu < sentinel)
        return jnp.sum(hit.astype(jnp.int32)) * (uv[0] < sentinel)

    m = edges.shape[0]
    pad = (-m) % edge_batch
    edges = jnp.pad(edges, ((0, pad), (0, 0)), constant_values=sentinel)
    batches = edges.reshape(-1, edge_batch, 2)
    per_batch = jax.lax.map(lambda eb: jnp.sum(jax.vmap(edge_tri)(eb), dtype=count_dtype()), batches)
    return jnp.sum(per_batch, dtype=count_dtype())


# --------------------------------------------------------------------------
# Ring (dense row-block streaming) — the distributed dynamic pipeline
# --------------------------------------------------------------------------
@lru_cache(maxsize=None)
def dense_ring_spec(rows_per_stage: int, *, use_kernel: bool = False) -> FilterSpec:
    """FilterSpec for the dense ring. Resident = this stage's row block U_s
    (R, n_pad); streamed blocks are the row blocks of every stage; block from
    stage k covers ranks [k*R, (k+1)*R) (the k-slice of the contraction).

    Works for f32/bf16/uint8 blocks: the contraction always accumulates in a
    wide type (preferred_element_type), so the 0/1 adjacency streams at
    1 byte/entry by default — 4x less ring traffic than f32 (see
    EXPERIMENTS.md §Perf iteration 1)."""
    R = rows_per_stage

    def init(u_s):
        return (u_s, jnp.zeros((), count_dtype()))

    def process(state, u_k, src):
        u_s, acc = state
        cols = jax.lax.dynamic_slice_in_dim(u_s, src * R, R, axis=1)
        if use_kernel:
            from repro.kernels.triangle_count.ops import masked_matmul_sum

            partial_ = masked_matmul_sum(cols, u_k, u_s)
        else:
            wide = jnp.int32 if jnp.issubdtype(u_s.dtype, jnp.integer) else jnp.float32
            prod = jax.lax.dot(cols, u_k, preferred_element_type=wide)
            # integer reduction — f32 sums lose exactness past 2^24
            partial_ = jnp.sum((prod * u_s.astype(wide)).astype(jnp.int32),
                               dtype=count_dtype())
        return (u_s, acc + partial_.astype(count_dtype()))

    def finalize(state):
        return state[1]

    return FilterSpec(init=init, process=process, finalize=finalize)


def build_dense_ring_operands(
    g: Graph, n_stages: int, *, balance: bool = True, pad_to: int = 8, dtype=np.uint8
) -> tuple[RingPartition, np.ndarray]:
    """Stage row blocks of the rank-permuted U. Default dtype is uint8: the
    0/1 adjacency streams around the ring at 1 byte/entry (4x less ring
    traffic than f32) while the contraction still accumulates wide — see
    ``dense_ring_spec``. Pass dtype=np.float32 to reproduce the seed layout."""
    part = ring_partition(g, n_stages, balance=balance, pad_to=pad_to)
    n_pad = part.n_pad
    ru = part.rank[g.edges[:, 0]]
    rv = part.rank[g.edges[:, 1]]
    lo = np.minimum(ru, rv)
    hi = np.maximum(ru, rv)
    u = np.zeros((n_pad, n_pad), dtype=dtype)
    u[lo, hi] = 1
    blocks = u.reshape(n_stages, part.rows_per_stage, n_pad)
    return part, blocks


def count_triangles_ring(
    g: Graph,
    *,
    mesh=None,
    n_stages: int | None = None,
    balance: bool = True,
    use_kernel: bool = False,
    sequential: bool = False,
    dtype=np.uint8,
) -> int:
    """Distributed dense count. With ``sequential=True`` (or a 1-device mesh)
    runs the paper-faithful chain emulation instead of shard_map. Blocks
    stream as uint8 by default (see ``build_dense_ring_operands``)."""
    if mesh is not None and n_stages is None:
        n_stages = mesh.devices.size
    n_stages = n_stages or 1
    part, blocks = build_dense_ring_operands(g, n_stages, balance=balance, dtype=dtype)
    spec = dense_ring_spec(part.rows_per_stage, use_kernel=use_kernel)
    blocks = jnp.asarray(blocks)
    if sequential or mesh is None or mesh.devices.size == 1:
        out = run_sequential(spec, blocks, blocks, n_stages)
    else:
        out = DynamicPipeline(mesh, mesh.axis_names[0]).run(spec, blocks, blocks)
    return int(out)


# --------------------------------------------------------------------------
# Bitset ring (edge-block streaming) — the literal edge stream
# --------------------------------------------------------------------------
# The blocked kernel holds the full (n_pad, W) uint32 mask table VMEM-resident
# (~8 MB leaves headroom in a 16 MB VMEM) and the (B, 2) int32 edge table as a
# scalar-prefetch operand in SMEM — both must fit or we fall back to pure JAX.
_MASK_VMEM_BUDGET = 8 * 1024 * 1024
_EDGE_SMEM_BUDGET = 256 * 1024
# The XLA closure sweep gathers two (B, W) uint32 row tables; it walks the
# edge block in chunks that keep them near this many bytes, whatever B is.
_XLA_GATHER_BUDGET = 64 * 1024 * 1024


def _bitset_closures_xla(mask: jax.Array, edge_block: jax.Array) -> jax.Array:
    """Σ_e popcount(mask[u_e] & mask[v_e]) over a (B, 2) edge block
    (phantom rows: id >= n_pad), in ``count_dtype``. Halving stops at an
    odd chunk; the counter's power-of-two blocks always reach the budget."""
    n_pad, w = mask.shape
    b = edge_block.shape[0]
    chunk = max(b, 1)
    while chunk % 2 == 0 and chunk > 128 and 8 * chunk * w > _XLA_GATHER_BUDGET:
        chunk //= 2

    def closures(acc, eb):
        u = jnp.minimum(eb[:, 0], n_pad - 1)
        v = jnp.minimum(eb[:, 1], n_pad - 1)
        pc = jax.lax.population_count(jnp.bitwise_and(mask[u], mask[v])).sum(axis=-1)
        return acc + jnp.sum(jnp.where(eb[:, 0] < n_pad, pc, 0),
                             dtype=count_dtype()), None

    acc, _ = jax.lax.scan(closures, jnp.zeros((), count_dtype()),
                          edge_block.reshape(b // chunk, chunk, 2))
    return acc


@lru_cache(maxsize=None)
def bitset_ring_spec(*, use_kernel: bool = False) -> FilterSpec:
    """Resident = (n_pad, W) uint32 membership bitmask over this stage's
    responsible ranks; streamed = (B, 2) int32 edge blocks in rank space.

    ``use_kernel=True`` closes each streamed edge block with the blocked
    Pallas kernel (edge tiles gathered against the VMEM-resident mask table)
    instead of the pure-JAX take/popcount path — mirroring the dense ring's
    ``use_kernel`` switch. The kernel keeps the whole mask table in one VMEM
    block and the edge endpoints in SMEM, so stages whose mask table exceeds
    ``_MASK_VMEM_BUDGET`` or whose edge block exceeds ``_EDGE_SMEM_BUDGET``
    fall back to the pure-JAX path (which the seed per-row-DMA kernel also
    handled) rather than fail allocation."""

    def init(mask):
        return (mask, jnp.zeros((), count_dtype()))

    def process(state, edge_block, src):
        mask, acc = state
        if (use_kernel and mask.size * 4 <= _MASK_VMEM_BUDGET
                and edge_block.size * 4 <= _EDGE_SMEM_BUDGET):
            from repro.kernels.bitset_count.ops import bitset_edge_count

            partial_ = bitset_edge_count(mask, edge_block)
        else:
            partial_ = _bitset_closures_xla(mask, edge_block)
        return (mask, acc + partial_.astype(count_dtype()))

    def finalize(state):
        return state[1]

    return FilterSpec(init=init, process=process, finalize=finalize)


def build_bitset_ring_operands(
    g: Graph, n_stages: int, *, balance: bool = True, edge_block: int | None = None,
    pad_to: int = 1
) -> tuple[RingPartition, np.ndarray, np.ndarray]:
    part = ring_partition(g, n_stages, balance=balance, pad_to=pad_to)
    R, n_pad = part.rows_per_stage, part.n_pad
    W = -(-R // 32)
    ru = part.rank[g.edges[:, 0]]
    rv = part.rank[g.edges[:, 1]]
    lo = np.minimum(ru, rv)
    hi = np.maximum(ru, rv)
    # masks[s, x, w] bit j: x ∈ fwd_adj(rank s*R + w*32 + j)
    masks = np.zeros((n_stages, n_pad, W), dtype=np.uint32)
    s = lo // R
    local = lo - s * R
    np.bitwise_or.at(masks, (s, hi, local // 32), np.uint32(1) << (local % 32).astype(np.uint32))
    # edge stream blocks (padded with sentinel n_pad)
    m = len(lo)
    if edge_block is None:
        edge_block = -(-m // n_stages)
    m_pad = n_stages * edge_block
    edges = np.full((m_pad, 2), n_pad, dtype=np.int32)
    edges[:m, 0] = lo
    edges[:m, 1] = hi
    return part, masks, edges.reshape(n_stages, edge_block, 2)


def count_triangles_bitset_ring(
    g: Graph, *, mesh=None, n_stages: int | None = None, balance: bool = True,
    use_kernel: bool = False, sequential: bool = False
) -> int:
    if mesh is not None and n_stages is None:
        n_stages = mesh.devices.size
    n_stages = n_stages or 1
    part, masks, edges = build_bitset_ring_operands(g, n_stages, balance=balance)
    spec = bitset_ring_spec(use_kernel=use_kernel)
    masks, edges = jnp.asarray(masks), jnp.asarray(edges)
    if sequential or mesh is None or mesh.devices.size == 1:
        out = run_sequential(spec, masks, edges, n_stages)
    else:
        out = DynamicPipeline(mesh, mesh.axis_names[0]).run(spec, masks, edges)
    return int(out)


# --------------------------------------------------------------------------
# Host conveniences
# --------------------------------------------------------------------------
def count_triangles(g: Graph, *, method: str = "dense", **kw) -> int:
    """DEPRECATED front door — now a thin shim over ``repro.api``.

    Routes through the shared planner-driven ``TriangleCounter`` (compile
    cache, ``CountResult`` contract); ``method="auto"`` lets the planner
    choose. New code should use ``repro.api.TriangleCounter`` directly.
    """
    from repro.api import count_triangles as _api_count_triangles

    return _api_count_triangles(g, method=method, **kw)
