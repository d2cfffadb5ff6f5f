"""The plain reference: exact triangle counts of simple graphs and of
sliding windows over edge streams, and the controls that break one of the
guarantees a configuration states.

It shares no code with the counter under test. The graph is reduced to its
simple form on the host (self-loops and repeated edges dropped, each edge
once as lo < hi) and then to its 2-core (vertices of degree below 2, which
lie on no triangle, are dropped with their edges until none is left); the
k vertices that remain are renumbered 0..k-1 and counted on the device by
one of two paths.

Dense, while k + 1 <= 65,536: the count is the sum over the strictly upper
adjacency U of (U @ U) * U: every triangle i < j < l is counted once, at
entry (i, l) through j. U is held on the device in bfloat16 (its entries
are 0 and 1, exact), the products accumulate in float32 (every entry of
U @ U is at most k < 2**24, exact), and each row is summed in int32 (a
row's sum is at most C(k, 2) < 2**31 for k < 65,536), the rows in int64 on
the host. Blocks of 2,048 rows keep the device memory at k**2 * 2 bytes
(8.6 GB at the limit) plus one block of products; the work is 2 k**3
multiply-adds on the matrix unit, whatever the edges.

Forward lists, past that, where U no longer fits a chip: the vertices are
ranked by (degree, id), each edge is oriented from its lower-ranked end to
its higher, and N+(v) is v's out-neighbours, sorted; in this order no N+(v)
is longer than sqrt(2 m). The count is the sum over oriented edges (p, q)
of |N+(p) & N+(q)|: each triangle is counted once, at its two lowest-ranked
vertices. Each N+(v) is a row of the table of its width (a power of two
from 8 to 128, past that a multiple of 128), padded with a value that
matches no vertex; with each table's rows rounded up to a power of two,
the tables hold about 2 m int32 entries (sum(|N+(v)|) = m). The edges are
taken in chunks of one pair of row widths (w_p, w_q); each entry of row p
is compared with every entry of row q, so the work is sum over edges of
w_p * w_q comparisons on the vector unit, and the only gathers are the two
rows. Each edge's count is an int32 (at most w_p), the edges' counts are
summed in int64 on the host. The host work (ranking, sorting the edges,
filling the tables) is O(m log m).

With weights (the multigraph control's multiplicities), both paths count a
triangle as w_ab * w_ac * w_bc. The float32 control sums each block of rows,
or each chunk of edges, in float32, and the blocks or chunks in float32 on
the host, in order: past 2**24 the sum comes out wrong.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_BLOCK = 2048  # rows per product block
_PAD = 8192  # k is padded to a multiple of this, so few shapes compile
_DENSE_MAX = 65_536  # vertices past which U (k**2 * 2 bytes) is not held
_NONE = np.iinfo(np.int32).max  # pads a forward list: sorts last, matches no vertex
_STEP = 1 << 25  # vertex comparisons per loop step of the forward-list count
_CHUNK_MAX = 1 << 14  # edges per loop step, at most
_STEPS = 64  # loop steps per call of the forward-list count


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


@partial(jax.jit, static_argnames=("chunk", "exact"))
def _pair_sums(tp, tq, wtp, wtq, p, q, w, steps, *, chunk: int, exact: bool):
    """For the first ``steps`` chunks of ``chunk`` edges (p, q), the sum
    over c in N+(p) & N+(q) of w_pq * w_pc * w_qc, N+(p) being row p of
    ``tp`` and N+(q) row q of ``tq``: each entry of the one row compared
    with every entry of the other. Int32 per edge when ``exact``, else
    float32 per chunk. ``wtp``, ``wtq`` (the rows' weights) and ``w`` None:
    every weight is 1."""
    dtype = jnp.int32 if exact else jnp.float32

    def per_edge(i):
        pi = jax.lax.dynamic_slice_in_dim(p, i * chunk, chunk)
        qi = jax.lax.dynamic_slice_in_dim(q, i * chunk, chunk)
        a = tp[pi][:, :, None]
        hit = (a == tq[qi][:, None, :]) & (a != _NONE)
        if wtp is None:
            return hit.sum(axis=(1, 2), dtype=jnp.int32)
        both = wtp[pi][:, :, None] * wtq[qi][:, None, :]
        return (jnp.where(hit, both, 0).sum(axis=(1, 2), dtype=jnp.int32)
                * jax.lax.dynamic_slice_in_dim(w, i * chunk, chunk))

    if exact:
        def body(i, acc):
            return jax.lax.dynamic_update_slice_in_dim(acc, per_edge(i), i * chunk, 0)
        return jax.lax.fori_loop(0, steps, body, jnp.zeros(p.shape, dtype))

    def body(i, acc):
        return acc.at[i].set(per_edge(i).astype(dtype).sum(dtype=dtype))
    return jax.lax.fori_loop(0, steps, body, jnp.zeros((p.shape[0] // chunk,), dtype))


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
    if k + 1 > _DENSE_MAX:
        return _count_forward(renumber[lo], renumber[hi], weight, k, exact=exact)
    # the edge list is padded to a power of two with entries that add
    # nothing, so graphs of one size share the compiled programs
    m_pad = 1 << max(len(lo) - 1, 0).bit_length()
    k_pad = max(_PAD, -(-k // _PAD) * _PAD)
    clo, chi, w = (np.zeros(m_pad, np.int32), np.zeros(m_pad, np.int32),
                   np.zeros(m_pad, np.float32))
    clo[:len(lo)], chi[:len(lo)], w[:len(lo)] = renumber[lo], renumber[hi], weight
    u = _upper(jnp.asarray(clo), jnp.asarray(chi), jnp.asarray(w, jnp.bfloat16),
               k_pad=k_pad)
    return _total(np.asarray(_block_sums(u, exact=exact)), exact)


def _width(d: np.ndarray) -> np.ndarray:
    """The row width a forward list of ``d`` entries is compared in: a
    power of two from 8 to 128, past that a multiple of 128, so few shapes
    compile and a row past 8 entries is less than twice its list."""
    d = np.maximum(np.asarray(d, np.int64), 1)
    return np.where(d <= 128, np.maximum(8, 2 ** np.ceil(np.log2(d))).astype(np.int64),
                    -(-d // 128) * 128)


def _count_forward(clo, chi, weight, k: int, *, exact: bool):
    # rank by (degree, id) and orient each edge upwards: N+(v) is then at
    # most sqrt(2 m) long, and each triangle p < q < r is counted once, at
    # its edge (p, q), through r
    deg = np.bincount(clo, minlength=k) + np.bincount(chi, minlength=k)
    rank = np.empty(k, np.int64)
    rank[np.argsort(deg, kind="stable")] = np.arange(k)
    a, b = rank[clo], rank[chi]
    key = np.minimum(a, b) * k + np.maximum(a, b)
    order = np.argsort(key)
    a, b, w = key[order] // k, key[order] % k, weight[order].astype(np.int64)
    out = np.bincount(a, minlength=k)
    pos = np.arange(len(a)) - (np.cumsum(out) - out)[a]
    weighted = not np.all(w == 1)  # weights are multiplicities: whole numbers
    if weighted:
        w_out, w_max = np.bincount(a, weights=w, minlength=k), np.zeros(k, np.int64)
        np.maximum.at(w_max, a, w)
        if (w * w_out[a] * w_max[b]).max() >= 2**31:
            raise OverflowError("an edge's weighted sum may pass int32")
    # each N+(v) is row row[v] of the table of its width, padded with
    # _NONE; row len(vs) of each table stays empty, for padding
    width, row = _width(out), np.zeros(k, np.int64)
    widths = np.unique(width[out > 0])
    tables = []
    for wd in widths.tolist():
        vs = np.flatnonzero((width == wd) & (out > 0))
        row[vs] = np.arange(len(vs))
        on = width[a] == wd
        t = np.full((1 << len(vs).bit_length(), wd), _NONE, np.int32)
        t[row[a[on]], pos[on]] = b[on]
        wt = None
        if weighted:
            wt = np.zeros(t.shape, np.int32)
            wt[row[a[on]], pos[on]] = w[on]
            wt = jnp.asarray(wt)
        tables.append((jnp.asarray(t), wt, len(vs)))
    live = out[b] > 0  # an edge whose upper end has no N+ closes nothing
    a, b, w = a[live], b[live], w[live]
    # the edges by pair of width classes, the narrower end first
    ca, cb = np.searchsorted(widths, width[a]), np.searchsorted(widths, width[b])
    p, q = row[np.where(ca <= cb, a, b)], row[np.where(ca <= cb, b, a)]
    pair = np.minimum(ca, cb) * len(widths) + np.maximum(ca, cb)
    order = np.argsort(pair.astype(np.min_scalar_type(len(widths) ** 2)), kind="stable")
    p, q, w, pair = p[order], q[order], w[order], pair[order]
    counts = np.bincount(pair, minlength=len(widths) ** 2)
    sums = []
    for code in np.flatnonzero(counts).tolist():
        i, j = divmod(code, len(widths))
        (tp, wtp, ep), (tq, wtq, eq) = tables[i], tables[j]
        # one program per pair of widths: _STEPS chunks of about _STEP
        # comparisons, of which a call runs as many as it has edges for
        chunk = max(1, min(_CHUNK_MAX, 1 << ((_STEP // (tp.shape[1] * tq.shape[1])).bit_length() - 1)))
        size = chunk * _STEPS
        start, end = counts[:code].sum(), counts[:code + 1].sum()
        for s in range(start, end, size):
            n = min(size, end - s)
            cp, cq, cw = np.full(size, ep, np.int32), np.full(size, eq, np.int32), np.zeros(size, np.int32)
            cp[:n], cq[:n], cw[:n] = p[s:s + n], q[s:s + n], w[s:s + n]
            sums.append(_pair_sums(tp, tq, wtp, wtq, cp, cq, cw if weighted else None,
                                   np.int32(-(-n // chunk)), chunk=chunk, exact=exact))
    if not sums:
        return 0
    return _total(np.concatenate([np.asarray(s) for s in sums]), exact)


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
