"""The hybrid stream ingest on Kronecker-skewed graphs, against the plain
reference and against a numpy model of the layout.

Graph500's Kronecker law (A, B, C = 0.57, 0.19, 0.19) at scales 9–11, with
hub slots, tail capacity and promotion threshold chosen so that every edge
class occurs (hub–hub, hub–tail, tail–tail) and both kinds of promotion
fire inside blocks: mandatory (a tail buffer would overflow) and at the
threshold. Each count must equal the brute-force reference and the dense
layout, bit for bit, and every counter of the state must equal what a
numpy replay of the same blocks predicts.
"""
from functools import partial

import numpy as np
import pytest

from repro.core.streaming import (
    count_stream,
    count_stream_hybrid,
    hybrid_stats,
    ingest_block_hybrid,
    init_hybrid_state,
    padded_blocks,
)
from repro.core.triangle_ref import count_triangles_brute
from repro.graphs.formats import canonical_edges


def kronecker(scale: int, edge_factor: int, seed: int) -> np.ndarray:
    """Raw Graph500 tuples (self-loops and repeats kept), labels permuted."""
    rng = np.random.default_rng(seed)
    m = edge_factor << scale
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    for level in range(scale):
        ii = rng.random(m) > 0.76
        jj = rng.random(m) > np.where(ii, 0.19 / 0.24, 0.19 / 0.76)
        u |= ii.astype(np.int64) << level
        v |= jj.astype(np.int64) << level
    perm = rng.permutation(1 << scale)
    return np.stack([perm[u], perm[v]], 1).astype(np.int32)


def replay(n, blocks, hubs, cap, threshold):
    """What the hybrid layout's counters should read after ``blocks``: a
    plain replay of its rules. Classes and fills are taken before the
    block; at most min(64, 2B) promotions a block, buffer overflows first,
    each group by vertex id, slots in order; an overflowing tail end that
    gets no slot loses its endpoints."""
    is_hub = np.zeros(n, bool)
    deg = np.zeros(n, np.int64)
    seen = set()
    out = dict(promoted=0, pairs_hub=0, pairs_mixed=0, pairs_tail=0,
               fill_mixed=0, fill_tail=0, lost=0, mandatory=0, at_threshold=0)
    for blk in blocks:
        p_cap = min(64, 2 * len(blk))
        live = []
        for a, b in blk.tolist():
            lo, hi = min(a, b), max(a, b)
            if lo == hi or hi >= n or (lo, hi) in seen:
                continue
            seen.add((lo, hi))
            live.append((lo, hi))
        fill = np.where(is_hub, 0, np.minimum(deg, cap))
        d = np.zeros(n, np.int64)
        for lo, hi in live:
            kind = int(is_hub[lo]) + int(is_hub[hi])
            out[("pairs_tail", "pairs_mixed", "pairs_hub")[kind]] += 1
            if kind == 1:
                out["fill_mixed"] += fill[hi] if is_hub[lo] else fill[lo]
            elif kind == 0:
                out["fill_tail"] += fill[lo] + fill[hi]
            d[lo] += 1
            d[hi] += 1
        touched = np.flatnonzero((d > 0) & ~is_hub)
        new = deg[touched] + d[touched]
        must = touched[new > cap]
        want = touched[(new >= threshold) & (new <= cap)]
        room = min(hubs - int(is_hub.sum()), p_cap)
        got = np.concatenate([must, want])[:room]
        out["mandatory"] += int(np.isin(got, must).sum())
        out["at_threshold"] += int(np.isin(got, want).sum())
        is_hub[got] = True
        out["promoted"] += len(got)
        for v in touched[~is_hub[touched]]:  # tail ends: appends past C drop
            out["lost"] += int(max(0, d[v] - max(0, cap - deg[v])))
        deg += d
    out["hub_slots_used"] = int(is_hub.sum())
    return out


def _ingest(n, blocks, hubs, cap, threshold):
    step = partial(ingest_block_hybrid, hub_threshold=threshold)
    state = init_hybrid_state(n, hubs, cap)
    for b in blocks:
        state = step(state, b)
    return state


# (scale, seed, hub slots, tail capacity, threshold, block)
CASES = [
    (9, 1, 512, 16, 8, 1024),
    (10, 2, 512, 32, 24, 512),
    (11, 3, 1024, 64, 64, 2048),
]


@pytest.mark.parametrize("scale,seed,hubs,cap,threshold,block", CASES)
def test_kronecker_counts_and_counters_match(scale, seed, hubs, cap, threshold, block):
    n = 1 << scale
    raw = kronecker(scale, 16, seed)
    blocks = list(padded_blocks([raw], n, block))
    state = _ingest(n, blocks, hubs, cap, threshold)
    want = count_triangles_brute(canonical_edges(raw, n_nodes=n))
    assert int(state["count"]) == want == count_stream(n, [raw], block_size=block)
    got = hybrid_stats(state)
    model = replay(n, blocks, hubs, cap, threshold)
    assert got == {k: model[k] for k in got}, (got, model)
    # every class occurs, and both kinds of promotion fired mid-stream
    assert min(got["pairs_hub"], got["pairs_mixed"], got["pairs_tail"]) > 0
    assert model["mandatory"] > 0 and model["at_threshold"] > 0
    assert got["lost"] == 0 and got["fill_mixed"] > 0 and got["fill_tail"] > 0


def test_exhausted_hub_slots_lose_endpoints_and_raise():
    n, block = 512, 512
    raw = kronecker(9, 16, 4)
    blocks = list(padded_blocks([raw], n, block))
    state = _ingest(n, blocks, 8, 16, 16)
    got = hybrid_stats(state)
    # once endpoints are dropped the lists no longer see every repeat, so
    # only the loss itself is pinned
    assert got["lost"] > 0 and got["hub_slots_used"] == got["promoted"] == 8
    with pytest.raises(RuntimeError, match="dropped .* endpoint"):
        count_stream_hybrid(n, [raw], hub_slots=8, tail_capacity=16,
                            hub_threshold=16, block_size=block)


def test_a_star_inside_one_block_takes_the_gathered_window():
    """A vertex with 300 edges in one block is past the shifted windows
    (block-local degree 64): phase 2 reads every other member of its group
    instead, and the count stays exact."""
    rng = np.random.default_rng(5)
    n = 600
    spokes = np.stack([np.zeros(300, np.int32), np.arange(1, 301, dtype=np.int32)], 1)
    chords = rng.integers(1, 301, size=(400, 2)).astype(np.int32)
    raw = np.concatenate([chords[:200], spokes, chords[200:]])
    want = count_triangles_brute(canonical_edges(raw, n_nodes=n))
    assert want > 0
    got = count_stream_hybrid(n, [raw], hub_slots=64, tail_capacity=8,
                              hub_threshold=8, block_size=1024)
    assert got == want == count_stream(n, [raw], block_size=1024)


def test_deferred_threshold_promotions_stay_exact():
    """More than 64 vertices reach the threshold in one block: the cap
    defers the rest, whose buffers still hold every neighbour, and the
    count and counters stay exact."""
    rng = np.random.default_rng(6)
    n, block = 400, 1024
    raw = rng.integers(0, n, size=(3 * block, 2)).astype(np.int32)
    blocks = list(padded_blocks([raw], n, block))
    state = _ingest(n, blocks, 400, 32, 6)
    got = hybrid_stats(state)
    assert got == {k: v for k, v in replay(n, blocks, 400, 32, 6).items() if k in got}
    assert got["lost"] == 0 and got["promoted"] > 64
    assert int(state["count"]) == count_triangles_brute(canonical_edges(raw, n_nodes=n))
