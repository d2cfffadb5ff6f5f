"""Jit'd public wrappers for the triangle-count kernel (pads + dispatches)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.triangle_count.triangle_count import (
    live_grid_size,
    masked_matmul_sum_kernel,
    triangle_count_live_kernel,
)
from repro.kernels import note_kernel_trace
from repro.utils import count_dtype, interpret_mode


# Scalar-prefetch operands live in SMEM; cap the live-triple table well below
# typical SMEM capacity so the compacted grid never fails to compile.
_SMEM_TABLE_BUDGET = 384 * 1024


def _pad2(x: jax.Array, bm: int, bn: int) -> jax.Array:
    pm = (-x.shape[0]) % bm
    pn = (-x.shape[1]) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


@partial(jax.jit, static_argnames=("block_m", "block_n", "block_k", "upper_triangular", "interpret"))
def masked_matmul_sum(
    a: jax.Array,
    b: jax.Array,
    m: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    upper_triangular: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """sum((A @ B) ⊙ M) in ``count_dtype`` for 0/1 operands. Pads to block
    multiples (zero pad is count-neutral)."""
    interpret = interpret_mode(interpret)
    note_kernel_trace("masked_matmul_sum", interpret)
    a = _pad2(a, block_m, block_k)
    b = _pad2(b, block_k, block_n)
    m = _pad2(m, block_m, block_n)
    partials = masked_matmul_sum_kernel(
        a,
        b,
        m,
        block_m=block_m,
        block_n=block_n,
        block_k=block_k,
        upper_triangular=upper_triangular,
        interpret=interpret,
    )
    return jnp.sum(partials, dtype=count_dtype())


@partial(jax.jit, static_argnames=("block", "interpret", "live_grid"))
def triangle_count(u: jax.Array, *, block: int = 128, interpret: bool | None = None,
                   live_grid: bool = True) -> jax.Array:
    """sum(U ⊙ (U@U)) for strictly-upper-triangular U.

    ``live_grid=True`` (default) runs the compacted grid over only the live
    triples {i ≤ k ≤ j} — C(nb+2, 3) steps, no dead-block fetches.
    ``live_grid=False`` keeps the seed full-grid kernel (nb³ steps, dead
    blocks fetched but MXU-skipped) as the comparison baseline.

    The live triple table is a scalar-prefetch operand (SMEM-resident), so
    very large grids fall back to the full-grid kernel rather than blow the
    SMEM budget: 12 bytes/triple against ``_SMEM_TABLE_BUDGET`` (nb ≤ ~56 at
    block 128, i.e. n ≤ ~7k — beyond that the count is ring-partitioned
    anyway).
    """
    interpret = interpret_mode(interpret)
    u = _pad2(u, block, block)
    nb = u.shape[0] // block
    if live_grid and live_grid_size(nb) * 12 > _SMEM_TABLE_BUDGET:
        live_grid = False
    note_kernel_trace("triangle_count_live" if live_grid
                      else "triangle_count_full_grid", interpret)
    if live_grid:
        out = triangle_count_live_kernel(u, block=block, interpret=interpret)
    else:
        out = masked_matmul_sum_kernel(
            u, u, u, block_m=block, block_n=block, block_k=block,
            upper_triangular=True, interpret=interpret,
        )
    return jnp.sum(out, dtype=count_dtype())


def triangle_count_grid_steps(n: int, *, block: int = 128, live_grid: bool = True) -> int:
    """Grid steps ``triangle_count`` executes for an (n, n) input — the unit
    the BENCH_kernels.json trajectory tracks. Mirrors the SMEM fallback."""
    nb = -(-n // block)
    if live_grid and live_grid_size(nb) * 12 > _SMEM_TABLE_BUDGET:
        live_grid = False
    return live_grid_size(nb) if live_grid else nb**3
