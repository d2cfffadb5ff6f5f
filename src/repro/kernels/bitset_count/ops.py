"""Public wrapper for the bitset edge-closure kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.bitset_count.bitset_count import (
    bitset_edge_count_kernel,
    bitset_pair_count_kernel,
)
from repro.kernels import note_kernel_trace
from repro.utils import count_dtype, interpret_mode


@partial(jax.jit, static_argnames=("edge_tile", "interpret"))
def bitset_edge_count(masks: jax.Array, edges: jax.Array, *,
                      edge_tile: int = 128,
                      interpret: bool | None = None) -> jax.Array:
    """Σ_e popcount(masks[u_e] & masks[v_e]) — the bitset ring's per-stage
    counting step. masks: (n_pad, W) uint32; edges: (B, 2) int32.

    Edges are padded up to a multiple of ``edge_tile`` with phantom rows
    (id = n_pad ≥ any real rank), which the kernel masks out, so any B is
    accepted while every grid step still closes a full tile.
    """
    n_pad = masks.shape[0]
    edges = edges.astype(jnp.int32)
    pad = (-edges.shape[0]) % edge_tile
    if pad:
        edges = jnp.pad(edges, ((0, pad), (0, 0)), constant_values=n_pad)
    interpret = interpret_mode(interpret)
    note_kernel_trace("bitset_edge_count", interpret)
    lanes = bitset_edge_count_kernel(masks, edges, edge_tile=edge_tile,
                                     interpret=interpret)
    return jnp.sum(lanes, dtype=count_dtype())


@partial(jax.jit, static_argnames=("edge_tile", "interpret"))
def bitset_pair_count(masks_a: jax.Array, masks_b: jax.Array, edges: jax.Array,
                      *, edge_tile: int = 128,
                      interpret: bool | None = None) -> jax.Array:
    """Σ_e popcount(masks_a[u_e] & masks_b[v_e]) — the two-table closure used
    by the streaming ingest's intra-block correction (u rows from the
    pre-block adjacency, v rows from the block delta, or vice versa). Same
    phantom/padding contract as :func:`bitset_edge_count`."""
    n_pad = masks_a.shape[0]
    edges = edges.astype(jnp.int32)
    pad = (-edges.shape[0]) % edge_tile
    if pad:
        edges = jnp.pad(edges, ((0, pad), (0, 0)), constant_values=n_pad)
    interpret = interpret_mode(interpret)
    note_kernel_trace("bitset_pair_count", interpret)
    lanes = bitset_pair_count_kernel(masks_a, masks_b, edges,
                                     edge_tile=edge_tile, interpret=interpret)
    return jnp.sum(lanes, dtype=count_dtype())


def bitset_grid_steps(n_edges: int, *, edge_tile: int = 128) -> int:
    """Grid steps ``bitset_edge_count`` executes for a B-edge block (the seed
    kernel ran B steps — one DMA pair per edge)."""
    return -(-n_edges // edge_tile)
