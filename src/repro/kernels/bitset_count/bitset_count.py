"""Bitset edge-closure Pallas kernel — the counting phase of the BITSET ring.

The paper's filter closes a streamed edge (u, v) against its responsible
adjacency set; the bitset form packs "u ∈ fwd_adj(r)" into 32 responsible
nodes per word, so one edge costs W AND+popcount lane ops (VPU, not MXU).

The seed kernel issued one grid step — two (1, W) row DMAs — per single
edge: at W of a few words those DMAs are far below the sublane granule and
the kernel is pure DMA-issue overhead. This kernel instead processes an
*edge tile* of ``edge_tile`` edges per grid step: the mask table is a
VMEM-resident block (fetched once, revisited across all grid steps because
its index map is constant), the tile's endpoints arrive via scalar prefetch
(SMEM), and the kernel gathers the (1, W) mask rows for all edges of the
tile in-kernel, reducing the AND+popcount in registers and flushing a
(1, W) int32 per-lane accumulator once per tile — E edges per grid step instead of
one, grid length m/E instead of m (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import count_dtype


def _tile_closures(edges_ref, a_ref, b_ref, out_ref, *, n_pad: int,
                   edge_tile: int):
    """Close one tile of ``edge_tile`` edges: Σ_e popcount(a[u_e] & b[v_e])
    kept per word lane in a (1, W) int32 vector and flushed once per tile.

    The accumulator is a vector block, never a scalar: Mosaic stores no
    scalars to VMEM and reduces no unsigned integers, so the popcounts are
    widened to int32 (each is at most 32) and the cross-lane sum is left to
    the wrapper."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def closure(e, acc):
        # edges_ref lives in SMEM (scalar prefetch, flattened (u, v) pairs):
        # scalar loads drive the VMEM row gathers — the whole tile reduces
        # without touching HBM.
        base = 2 * (t * edge_tile + e)
        u = edges_ref[base]
        v = edges_ref[base + 1]
        uc = jnp.minimum(u, n_pad - 1)
        vc = jnp.minimum(v, n_pad - 1)
        both = jnp.bitwise_and(a_ref[pl.ds(uc, 1), :], b_ref[pl.ds(vc, 1), :])
        pc = jax.lax.population_count(both).astype(jnp.int32)
        return acc + jnp.where(u < n_pad, pc, 0)

    acc = jax.lax.fori_loop(jnp.int32(0), jnp.int32(edge_tile), closure,
                            jnp.zeros(out_ref.shape, jnp.int32))
    out_ref[...] += acc


def _kernel(edges_ref, masks_ref, out_ref, *, n_pad: int, edge_tile: int):
    _tile_closures(edges_ref, masks_ref, masks_ref, out_ref, n_pad=n_pad,
                   edge_tile=edge_tile)


def _pair_kernel(edges_ref, a_ref, b_ref, out_ref, *, n_pad: int, edge_tile: int):
    _tile_closures(edges_ref, a_ref, b_ref, out_ref, n_pad=n_pad,
                   edge_tile=edge_tile)


def _per_edge_kernel(edges_ref, mu_ref, mv_ref, out_ref, *, n_pad: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    both = jnp.bitwise_and(mu_ref[...], mv_ref[...])
    pc = jax.lax.population_count(both).astype(jnp.int32)
    out_ref[...] += jnp.where(edges_ref[2 * i] < n_pad, pc, 0)


def bitset_edge_count_per_edge_kernel(masks: jax.Array, edges: jax.Array, *,
                                      interpret: bool = False) -> jax.Array:
    """The seed kernel: one grid step — two scalar-prefetch-driven (1, W) row
    DMAs — per single edge. Kept as the recorded baseline the blocked kernel
    is benchmarked against (BENCH_kernels.json ``per_edge_seed`` rows).
    Same (1, W) per-lane accumulator and flattened endpoint table as the
    blocked kernels; returns the summed count."""
    n_pad, w = masks.shape
    b = edges.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, w), lambda i, e: (jnp.minimum(e[2 * i], n_pad - 1), 0)),
            pl.BlockSpec((1, w), lambda i, e: (jnp.minimum(e[2 * i + 1], n_pad - 1), 0)),
        ],
        out_specs=pl.BlockSpec((1, w), lambda i, e: (0, 0)),
    )
    lanes = pl.pallas_call(
        functools.partial(_per_edge_kernel, n_pad=n_pad),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, w), jnp.int32),
        interpret=interpret,
    )(edges.astype(jnp.int32).reshape(-1), masks, masks)
    return jnp.sum(lanes, dtype=count_dtype())


def bitset_pair_count_kernel(masks_a: jax.Array, masks_b: jax.Array,
                             edges: jax.Array, *, edge_tile: int = 128,
                             interpret: bool = False) -> jax.Array:
    """Two-table variant of the blocked kernel: Σ_e popcount(a[u_e] & b[v_e])
    with u gathered from ``masks_a`` and v from ``masks_b`` — the mixed
    (pre-block × in-block) closure term of the streaming two-phase ingest.
    Both tables are VMEM-resident (constant index maps), so callers must
    budget for two tables, not one. Returns the (1, W) int32 per-lane
    partials; the caller sums them."""
    n_pad, w = masks_a.shape
    assert masks_b.shape == (n_pad, w), (masks_a.shape, masks_b.shape)
    b = edges.shape[0]
    assert b % edge_tile == 0, (b, edge_tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b // edge_tile,),
        in_specs=[
            pl.BlockSpec((n_pad, w), lambda t, e: (0, 0)),
            pl.BlockSpec((n_pad, w), lambda t, e: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, w), lambda t, e: (0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_pair_kernel, n_pad=n_pad, edge_tile=edge_tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, w), jnp.int32),
        interpret=interpret,
    )(edges.reshape(-1), masks_a, masks_b)


def bitset_edge_count_kernel(masks: jax.Array, edges: jax.Array, *,
                             edge_tile: int = 128,
                             interpret: bool = False) -> jax.Array:
    """masks: (n_pad, W) uint32; edges: (B, 2) int32 (phantom id >= n_pad).

    B must be a multiple of ``edge_tile`` (ops.py pads with phantom edges,
    which contribute zero). The endpoints are scalar-prefetched flattened to
    (2B,): a 1-D SMEM array is packed, where a (B, 2) one pads its minor
    dimension. Returns the (1, W) int32 per-lane partials; the caller sums
    them.
    """
    n_pad, w = masks.shape
    b = edges.shape[0]
    assert b % edge_tile == 0, (b, edge_tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b // edge_tile,),
        in_specs=[
            # Constant index map: the mask table is fetched into VMEM once
            # and revisited across every tile step.
            pl.BlockSpec((n_pad, w), lambda t, e: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, w), lambda t, e: (0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_pad=n_pad, edge_tile=edge_tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, w), jnp.int32),
        interpret=interpret,
    )(edges.reshape(-1), masks)
