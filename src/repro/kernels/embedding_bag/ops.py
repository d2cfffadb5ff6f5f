"""Public EmbeddingBag wrapper."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.embedding_bag.embedding_bag import embedding_bag_kernel
from repro.utils import interpret_mode


@partial(jax.jit, static_argnames=("interpret",))
def embedding_bag(table: jax.Array, indices: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Sum-mode EmbeddingBag. table: (V, D); indices: (B, L) with sentinel >= V
    rows meaning padding. Returns (B, D) in the table dtype."""
    interpret = interpret_mode(interpret)
    out = embedding_bag_kernel(table, indices.astype(jnp.int32), interpret=interpret)
    return out.astype(table.dtype)
