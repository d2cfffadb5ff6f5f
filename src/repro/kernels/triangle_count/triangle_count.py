"""Blocked masked-matmul-reduce Pallas kernels: sum((A @ B) ⊙ M).

This is the counting phase of the dynamic pipeline on the MXU. A is (R, K),
B is (K, N), M is (R, N); all blocks are VMEM-resident tiles, the contraction
accumulates into an f32 VMEM scratch, and the masked reduction folds into an
(8, block_n) int32 output block that stays resident across the whole grid
(the wrapper sums it).

Two grid strategies:

``masked_matmul_sum_kernel`` — the general rectangular kernel. Grid =
(R/bm, N/bn, K/bk), k fastest-varying (Pallas iterates the last grid axis
innermost) so the accumulator pattern is the canonical matmul one.
``upper_triangular=True`` adds the structural skip for the single-matrix
triangle count U@U⊙U: the M block (i, j) is all-zero when j < i, and the
k-th contraction slice is all-zero unless i ≤ k ≤ j (U is strictly upper
triangular: U[i,k] needs k > i-block-start, U[k,j] needs k < j-block-end).
Skipped blocks cost no MXU work (`pl.when`) but STILL cost three VMEM
fetches per dead triple — the full grid is ~6x larger than the live set.

``triangle_count_live_kernel`` — the live-grid kernel. The host enumerates
exactly the live triples {(i, j, k) : i ≤ j, i ≤ k ≤ j} once
(``live_grid_indices``), and the kernel runs a compacted 1-D grid over them
with the triple table scalar-prefetched (``pltpu.PrefetchScalarGridSpec``)
driving the BlockSpec index maps. Dead blocks are never part of the grid, so
they cost neither MXU work *nor* VMEM fetches: C(nb+2, 3) grid steps instead
of nb³ — the paper's "useful work only" claim rendered in the memory system,
not just in occupancy (see EXPERIMENTS.md §Perf for recorded counts).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _widen(x: jax.Array) -> jax.Array:
    """MXU operand dtype: integer 0/1 adjacency (uint8 ring streaming) is
    exact in f32 for per-block contractions (entries ≤ block_k < 2^24).
    Mosaic has no direct uint8 -> f32 cast, so it goes through int32."""
    if jnp.issubdtype(x.dtype, jnp.integer):
        return x.astype(jnp.int32).astype(jnp.float32)
    return x


def _fold_block(out_ref, acc: jax.Array, m: jax.Array) -> None:
    """Add the masked block sum Σ acc ⊙ M into the (8, bn) int32 output.

    Each product entry is an exact integer in f32 (≤ n < 2^24), so it is
    cast to int32 BEFORE any sum (an f32 block sum loses exactness past
    2^24); the rows fold by sublane groups, which keeps the accumulator a
    vector block — Mosaic stores no scalars to VMEM. The wrapper sums the
    (8, bn) partials."""
    prod = (acc * _widen(m)).astype(jnp.int32)
    bm, bn = prod.shape
    out_ref[...] += prod.reshape(bm // 8, 8, bn).sum(axis=0, dtype=jnp.int32)


def _kernel(a_ref, b_ref, m_ref, out_ref, acc_ref, *, n_k: int, upper_triangular: bool):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (j == 0) & (k == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if upper_triangular:
        live = (j >= i) & (k >= i) & (k <= j)
    else:
        live = (i >= 0)  # always true, keeps a traced bool

    @pl.when(live)
    def _accumulate():
        acc_ref[...] += jnp.dot(
            _widen(a_ref[...]), _widen(b_ref[...]), preferred_element_type=jnp.float32
        )

    @pl.when(k == n_k - 1)
    def _reduce():
        _fold_block(out_ref, acc_ref[...], m_ref[...])


def masked_matmul_sum_kernel(
    a: jax.Array,
    b: jax.Array,
    m: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    upper_triangular: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """sum((A @ B) ⊙ M) with (R, K) @ (K, N) against mask (R, N).

    Shapes must be multiples of the block sizes (ops.py pads). Returns the
    (8, block_n) int32 partials; the caller sums them.
    """
    R, K = a.shape
    K2, N = b.shape
    assert K == K2 and m.shape == (R, N), (a.shape, b.shape, m.shape)
    grid = (R // block_m, N // block_n, K // block_k)

    out = pl.pallas_call(
        functools.partial(_kernel, n_k=grid[2], upper_triangular=upper_triangular),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        ],
        out_specs=pl.BlockSpec((8, block_n), lambda i, j, k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, block_n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(a, b, m)
    return out


# --------------------------------------------------------------------------
# Live-grid kernel: dead upper-triangular blocks are not in the grid at all
# --------------------------------------------------------------------------
def live_grid_indices(n_blocks: int) -> np.ndarray:
    """Enumerate the live triples of the U@U⊙U block grid.

    Returns (n_live, 3) int32 rows (i, j, k) with i ≤ j and i ≤ k ≤ j, k
    innermost per (i, j) run so the accumulator lifecycle is init at k == i,
    flush at k == j. n_live = Σ_{i≤j} (j−i+1) = C(nb+2, 3), vs nb³ for the
    full grid (~6x at large nb).
    """
    triples = [
        (i, j, k)
        for i in range(n_blocks)
        for j in range(i, n_blocks)
        for k in range(i, j + 1)
    ]
    return np.asarray(triples, dtype=np.int32).reshape(-1, 3)


def live_grid_size(n_blocks: int) -> int:
    """C(nb+2, 3) — closed form of ``len(live_grid_indices(nb))``."""
    return n_blocks * (n_blocks + 1) * (n_blocks + 2) // 6


def _live_kernel(idx_ref, a_ref, b_ref, m_ref, out_ref, acc_ref):
    g = pl.program_id(0)
    i, j, k = idx_ref[3 * g], idx_ref[3 * g + 1], idx_ref[3 * g + 2]

    @pl.when(g == 0)
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(k == i)  # first contraction step of this (i, j) block run
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        _widen(a_ref[...]), _widen(b_ref[...]), preferred_element_type=jnp.float32
    )

    @pl.when(k == j)  # last contraction step: fold the masked block sum
    def _reduce():
        _fold_block(out_ref, acc_ref[...], m_ref[...])


def triangle_count_live_kernel(
    u: jax.Array,
    *,
    block: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """sum(U ⊙ (U @ U)) over the compacted live grid.

    U must be square, strictly upper triangular, and padded to a multiple of
    ``block`` (ops.py pads). The flattened triple table is scalar-prefetched
    and drives every BlockSpec index map, so each grid step DMAs exactly the
    three live tiles it needs. Returns the (8, block) int32 partials; the
    caller sums them.
    """
    n, n2 = u.shape
    assert n == n2 and n % block == 0, u.shape
    nb = n // block
    # flattened (i, j, k) triples: a 1-D SMEM table is packed, where an
    # (n_live, 3) one pads its minor dimension
    idx = jnp.asarray(live_grid_indices(nb).reshape(-1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(idx.shape[0] // 3,),
        in_specs=[
            pl.BlockSpec((block, block), lambda g, t: (t[3 * g], t[3 * g + 2])),      # A(i, k)
            pl.BlockSpec((block, block), lambda g, t: (t[3 * g + 2], t[3 * g + 1])),  # B(k, j)
            pl.BlockSpec((block, block), lambda g, t: (t[3 * g], t[3 * g + 1])),      # M(i, j)
        ],
        out_specs=pl.BlockSpec((8, block), lambda g, t: (0, 0)),
        scratch_shapes=[pltpu.VMEM((block, block), jnp.float32)],
    )
    return pl.pallas_call(
        _live_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((8, block), jnp.int32),
        interpret=interpret,
    )(idx, u, u, u)
