"""Graph500 Kronecker (R-MAT) edge tuples, drawn on the device from a seed.

Law: the Graph500 specification's reference generator (graph500.org,
"Graph 500 Benchmark 1", section 3.1, ``kronecker_generator.m``). For each of
``scale`` levels, one uniform draw picks the row bit (1 with probability
1 - A - B) and a second the column bit (1 with probability C / (C + D) when
the row bit is set, B / (A + B) otherwise). Vertex labels are then permuted
at random, one permutation per stream. The specification also permutes the
order of the tuples; the tuples are independent draws, so that step changes
nothing in law and is left out.

The tuples are raw: self-loops and repeated edges stay in, as the
specification emits them.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _key(*entropy: int):
    words = np.random.SeedSequence([int(e) for e in entropy]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


@partial(jax.jit, static_argnames=("scale", "shape"))
def kronecker_bits(key, a, b, c, *, scale: int, shape: tuple):
    """(u, v) int32 endpoints of ``shape`` before relabelling: level l
    sets bit l of u and of v by the initiator's quadrant probabilities."""
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    u = jnp.zeros(shape, jnp.int32)
    v = jnp.zeros(shape, jnp.int32)
    for level, k in enumerate(jax.random.split(key, scale)):
        ki, kj = jax.random.split(k)
        ii = jax.random.uniform(ki, shape, jnp.float32) > ab
        jj = jax.random.uniform(kj, shape, jnp.float32) > jnp.where(ii, c_norm, a_norm)
        u = u | (ii.astype(jnp.int32) << level)
        v = v | (jj.astype(jnp.int32) << level)
    return u, v


@partial(jax.jit, static_argnames=("scale", "shape"))
def _kronecker(key, a, b, c, *, scale: int, shape: tuple):
    k_bits, k_perm = jax.random.split(key)
    u, v = kronecker_bits(k_bits, a, b, c, scale=scale, shape=shape)
    perms = jax.vmap(lambda k: jax.random.permutation(k, 1 << scale))(
        jax.random.split(k_perm, shape[0]))
    relabel = jax.vmap(lambda p, x: p[x])
    return jnp.stack([relabel(perms, u), relabel(perms, v)], axis=-1)


def n_nodes(cfg: dict) -> int:
    return 1 << int(cfg["scale"])


def tuples_per_graph(cfg: dict) -> int:
    return int(cfg["edge_factor"]) << int(cfg["scale"])


def draw(cfg: dict, seed: int, *, n_streams: int, parts: int,
         tuples: int) -> np.ndarray:
    """(n_streams, parts, tuples, 2) int32 endpoints: ``n_streams``
    independent streams, each with its own vertex relabelling, cut into
    ``parts`` consecutive parts of ``tuples`` raw tuples."""
    a, b, c = (np.float32(cfg[k]) for k in ("a", "b", "c"))
    out = _kronecker(_key(seed), a, b, c, scale=int(cfg["scale"]),
                     shape=(n_streams, parts, tuples))
    return np.asarray(out)
