"""The plain reference: exact triangle counts of simple graphs and of
sliding windows over edge streams, and the controls that break one of the
guarantees a configuration states.

Past 65,536 vertices in the 2-core, U no longer fits a chip, and the count
is taken as the sum over edges i < j of |N+(i) & N+(j)|, N+(v) being v's
neighbours above v, held as one bitset row per vertex (k**2 / 8 bytes):
each triangle i < j < l is counted once, at edge (i, j). Each edge's
popcount is summed in int32, each chunk of 4,096 edges in int32, the
chunks in int64 on the host.

It shares no code with the counter under test. The graph is reduced to its
simple form on the host (self-loops and repeated edges dropped, each edge
once as lo < hi) and then to its 2-core (vertices of degree below 2, which
lie on no triangle, are dropped with their edges until none is left); the
vertices that remain are renumbered 0..k-1, and the count is the sum over
the strictly upper adjacency U of (U @ U) * U: every triangle i < j < l is counted once, at entry (i, l)
through j. U is held on the device in bfloat16 (its entries are 0 and 1,
exact), the products accumulate in float32 (every entry of U @ U is at most
k < 2**24, exact), and each row is summed in int32 (a row's sum is at most
C(k, 2) < 2**31 for k < 65,536), the rows in int64 on the host. Blocks of
2,048 rows keep the device memory at k**2 * 2 bytes plus one block of
products.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_BLOCK = 2048  # rows per product block
_PAD = 8192  # k is padded to a multiple of this, so few shapes compile
_DENSE_MAX = 65_536  # vertices past which U (k**2 * 2 bytes) is not held
_CHUNK = 4096  # edges per gather chunk of the bitset count


def simple_pairs(n: int, tuples) -> tuple[np.ndarray, np.ndarray]:
    """(lo, hi) int64 of the distinct non-loop pairs among raw ``tuples``."""
    e = np.asarray(tuples, np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    key = np.unique(np.minimum(e[:, 0], e[:, 1]) * n + np.maximum(e[:, 0], e[:, 1]))
    return key // n, key % n


def multi_pairs(n: int, tuples) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(lo, hi, multiplicity) of the non-loop pairs, repeats kept as a
    multiplicity: the multigraph a count that skipped deduplication sees."""
    e = np.asarray(tuples, np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    key, mult = np.unique(np.minimum(e[:, 0], e[:, 1]) * n
                          + np.maximum(e[:, 0], e[:, 1]), return_counts=True)
    return key // n, key % n, mult


@partial(jax.jit, static_argnames=("k_pad",))
def _upper(lo, hi, weight, *, k_pad: int):
    return jnp.zeros((k_pad, k_pad), jnp.bfloat16).at[lo, hi].add(weight)


@partial(jax.jit, static_argnames=("exact",))
def _block_sums(u, *, exact: bool):
    """Per block of rows, the sum over its rows of (U @ U) * U: int32 per
    row when ``exact`` (returned whole, summed on the host in int64), else
    float32 per block."""
    k = u.shape[0]

    def closed(r0):
        rows = jax.lax.dynamic_slice_in_dim(u, r0, _BLOCK, axis=0)
        paths = jnp.dot(rows, u, preferred_element_type=jnp.float32)
        return paths * rows.astype(jnp.float32)

    if exact:
        def body(i, acc):
            part = closed(i * _BLOCK).astype(jnp.int32).sum(axis=1, dtype=jnp.int32)
            return jax.lax.dynamic_update_slice_in_dim(acc, part, i * _BLOCK, 0)
        return jax.lax.fori_loop(0, k // _BLOCK, body, jnp.zeros((k,), jnp.int32))

    def body(i, acc):
        return acc.at[i].set(closed(i * _BLOCK).sum(dtype=jnp.float32))
    return jax.lax.fori_loop(0, k // _BLOCK, body, jnp.zeros((k // _BLOCK,), jnp.float32))


@partial(jax.jit, static_argnames=("k_pad",))
def _rows(lo, hi, live, *, k_pad: int):
    bit = jnp.where(live, jnp.uint32(1) << (hi % 32).astype(jnp.uint32), jnp.uint32(0))
    # each (lo, hi) appears once, so adding the bits ORs them
    return jnp.zeros((k_pad, k_pad // 32), jnp.uint32).at[lo, hi // 32].add(bit)


@partial(jax.jit, static_argnames=("exact",))
def _edge_sums(rows, lo, hi, *, exact: bool):
    """Per chunk of edges, the sum of |N+(lo) & N+(hi)|: int32 when
    ``exact``, else float32."""
    n_chunks = lo.shape[0] // _CHUNK
    dtype = jnp.int32 if exact else jnp.float32

    def body(i, acc):
        a = rows[jax.lax.dynamic_slice_in_dim(lo, i * _CHUNK, _CHUNK)]
        b = rows[jax.lax.dynamic_slice_in_dim(hi, i * _CHUNK, _CHUNK)]
        per_edge = jax.lax.population_count(a & b).astype(jnp.int32).sum(axis=1, dtype=jnp.int32)
        return acc.at[i].set(per_edge.astype(dtype).sum(dtype=dtype))
    return jax.lax.fori_loop(0, n_chunks, body, jnp.zeros((n_chunks,), dtype))


def two_core(lo, hi, weight):
    """The edges of the 2-core: drop edges at a vertex of degree 1 until
    every vertex left has degree 2 or more. No triangle is lost."""
    while len(lo):
        size = int(max(lo.max(), hi.max())) + 1
        deg = np.bincount(lo, minlength=size) + np.bincount(hi, minlength=size)
        keep = (deg[lo] >= 2) & (deg[hi] >= 2)
        if keep.all():
            break
        lo, hi, weight = lo[keep], hi[keep], weight[keep]
    return lo, hi, weight


def _count(lo, hi, weight, *, exact: bool):
    lo, hi, weight = two_core(lo, hi, weight)
    if len(lo) == 0:
        return 0
    present = np.zeros(int(max(lo.max(), hi.max())) + 1, bool)
    present[lo] = True
    present[hi] = True
    renumber = np.cumsum(present) - 1  # keeps the order, so lo < hi stays
    k = int(present.sum())
    # the edge list is padded to a power of two with entries that add
    # nothing, so graphs of one size share the compiled programs
    m_pad = 1 << max(len(lo) - 1, 0).bit_length()
    if k + 1 > _DENSE_MAX:
        return _count_bitset(renumber[lo], renumber[hi], weight, k, m_pad, exact=exact)
    k_pad = max(_PAD, -(-k // _PAD) * _PAD)
    clo, chi, w = (np.zeros(m_pad, np.int32), np.zeros(m_pad, np.int32),
                   np.zeros(m_pad, np.float32))
    clo[:len(lo)], chi[:len(lo)], w[:len(lo)] = renumber[lo], renumber[hi], weight
    u = _upper(jnp.asarray(clo), jnp.asarray(chi), jnp.asarray(w, jnp.bfloat16),
               k_pad=k_pad)
    return _total(np.asarray(_block_sums(u, exact=exact)), exact)


def _count_bitset(clo, chi, weight, k: int, m_pad: int, *, exact: bool):
    if not np.all(weight == 1):
        raise ValueError("the bitset count takes a simple graph only")
    k_pad = -(-(k + 1) // _PAD) * _PAD  # row k stays empty: padding points there
    m_pad = max(m_pad, _CHUNK)
    lo, hi = np.full(m_pad, k, np.int32), np.full(m_pad, k, np.int32)
    lo[:len(clo)], hi[:len(chi)] = clo, chi
    live = np.arange(m_pad) < len(clo)
    rows = _rows(jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(live), k_pad=k_pad)
    return _total(np.asarray(_edge_sums(rows, jnp.asarray(lo), jnp.asarray(hi), exact=exact)), exact)


def _total(sums: np.ndarray, exact: bool) -> int:
    if exact:
        return int(sums.sum(dtype=np.int64))
    total = np.float32(0)
    for s in sums:  # block by block, as a float32 accumulator would
        total = np.float32(total + s)
    return int(total)


def triangles(n: int, tuples) -> int:
    """Exact triangle count of the simple graph that raw ``tuples`` span."""
    lo, hi = simple_pairs(n, tuples)
    if len(lo) == 0:
        return 0
    return _count(lo, hi, np.ones(len(lo), np.float32), exact=True)


def window_pairs(n: int, epochs, window: int) -> tuple[np.ndarray, np.ndarray]:
    """(lo, hi) of the edges live after the last of ``epochs`` (raw tuple
    arrays, oldest first) in a window of ``window`` epochs, under
    first-arrival semantics: a tuple whose edge arrived in one of the last
    ``window`` epochs (its own included) is ignored, so an edge lives for
    ``window`` epochs from its kept arrival and then expires, and a later
    tuple of it after expiry is a new arrival."""
    keyed = []
    for ep in epochs:
        lo, hi = simple_pairs(n, ep)
        keyed.append(lo * n + hi)
    every = np.unique(np.concatenate(keyed)) if keyed else np.zeros(0, np.int64)
    arrival = np.full(len(every), -window - 1, np.int64)
    for t, keys in enumerate(keyed):
        idx = np.searchsorted(every, keys)
        gone = arrival[idx] <= t - window
        arrival[idx[gone]] = t
    live = every[arrival > len(epochs) - 1 - window]
    return live // n, live % n


def window_triangles(n: int, epochs, window: int) -> int:
    """Exact triangle count of the live window (see :func:`window_pairs`)."""
    lo, hi = window_pairs(n, epochs, window)
    if len(lo) == 0:
        return 0
    return _count(lo, hi, np.ones(len(lo), np.float32), exact=True)


def exact(n: int, item: dict) -> int:
    """The reference count of one compared item: ``{"graph": tuples}`` or
    ``{"epochs": [tuples, ...], "window": E}``."""
    if "graph" in item:
        return triangles(n, item["graph"])
    return window_triangles(n, item["epochs"], item["window"])


# -- controls: each breaks one guarantee a configuration states -----------
def triangles_float32(n: int, tuples) -> int:
    """The count accumulated in float32, the precision below the exact
    integer count: breaks exactness once the sum passes 2**24."""
    lo, hi = simple_pairs(n, tuples)
    return _count(lo, hi, np.ones(len(lo), np.float32), exact=False)


def triangles_multigraph(n: int, tuples) -> int:
    """Repeated tuples kept as parallel edges: breaks the simple-graph
    semantics (a stream's repeats are one edge)."""
    lo, hi, mult = multi_pairs(n, tuples)
    return _count(lo, hi, mult.astype(np.float32), exact=True)


def control(name: str, n: int, item: dict) -> int:
    """The count of ``item`` by control ``name``, in the program's place:
    ``float32`` accumulates a graph's exact sum in float32; ``multigraph``
    keeps a stream's repeated tuples (of the whole graph, or of the
    window's last epochs) as parallel edges."""
    if name == "float32":
        return triangles_float32(n, item["graph"])
    if name == "multigraph":
        tuples = (item["graph"] if "graph" in item
                  else np.concatenate(list(item["epochs"])[-item["window"]:]))
        return triangles_multigraph(n, tuples)
    raise ValueError(f"unknown control {name!r}")
