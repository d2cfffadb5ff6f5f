"""Uniform random graphs G(n, m): exactly m distinct undirected edges,
every m-subset of the n(n-1)/2 vertex pairs equally likely.

This is the law of the FNA family in the paper's Table 1 (arXiv:1701.03318),
drawn on the device from a seed. Candidate pairs are drawn with
replacement, keyed ``lo * n + hi`` as one integer, sorted, and cleared of
self-loops and repeats; m of the distinct keys are then kept, chosen by
independent random priorities. Given its size, the set of distinct
candidates is a uniform subset, so the m kept are a uniform m-subset.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _key(*entropy: int):
    words = np.random.SeedSequence([int(e) for e in entropy]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


@partial(jax.jit, static_argnames=("n", "m", "k"))
def _gnm(key, *, n: int, m: int, k: int):
    kdt = jnp.int64 if n * n >= 2 ** 31 else jnp.int32
    ku, kv, kp = jax.random.split(key, 3)
    u = jax.random.randint(ku, (k,), 0, n, dtype=jnp.int32).astype(kdt)
    v = jax.random.randint(kv, (k,), 0, n, dtype=jnp.int32).astype(kdt)
    none = jnp.asarray(n * n, kdt)  # above every real key
    keys = jnp.sort(jnp.where(u == v, none, jnp.minimum(u, v) * n + jnp.maximum(u, v)))
    repeat = jnp.concatenate([jnp.zeros((1,), bool), keys[1:] == keys[:-1]])
    keys = jnp.where(repeat, none, keys)
    valid = keys != none
    prio = jnp.where(valid, jax.random.uniform(kp, (k,), jnp.float32), 2.0)
    kept = jnp.sort(keys[jnp.argsort(prio)[:m]])
    return jnp.stack([kept // n, kept % n], axis=-1).astype(jnp.int32), valid.sum()


def n_nodes(cfg: dict) -> int:
    return int(cfg["n_nodes"])


def tuples_per_graph(cfg: dict) -> int:
    return int(cfg["n_edges"])


def candidates(n: int, m: int) -> int:
    """How many pairs to draw so that at least m are distinct with room to
    spare: the expected distinct count N(1 - exp(-k/N)) set to 1.01 m plus
    six standard deviations, and self-loops made up for."""
    pairs = n * (n - 1) // 2
    want = 1.01 * m + 6 * math.sqrt(m)
    if want >= pairs:
        raise ValueError(f"m={m} is too close to the {pairs} pairs of n={n}")
    return int(-pairs * math.log1p(-want / pairs) * n / (n - 1)) + 1


def draw(cfg: dict, seed: int, *, n_streams: int = 1, parts: int = 1,
         tuples: int | None = None) -> np.ndarray:
    """(n_streams, parts, m, 2) int32 canonical edges (lo < hi), sorted:
    one independent G(n, m) graph per (stream, part)."""
    n = n_nodes(cfg)
    m = int(tuples or tuples_per_graph(cfg))
    k = candidates(n, m)
    graphs = []
    for i in range(n_streams * parts):
        edges, distinct = _gnm(_key(seed, i), n=n, m=m, k=k)
        if int(distinct) < m:
            raise RuntimeError(f"drew {int(distinct)} distinct pairs, need {m}")
        graphs.append(np.asarray(edges))
    return np.stack(graphs).reshape(n_streams, parts, m, 2)
