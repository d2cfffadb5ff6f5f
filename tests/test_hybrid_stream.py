"""Randomized differential harness for the degree-aware hybrid stream state.

The hybrid layout (``core.streaming.init_hybrid_state``) keeps full bitset
rows only for promoted hubs and fixed-capacity buffers for the tail,
so its exactness is a real claim that needs adversarial inputs: power-law
degree skew (promotion under pressure), dense G(n,p) (everything wants to be
a hub), star graphs (one mandatory promotion), plus the stream-shape hazards
every ingest already guards (duplicate edges, self-loops, reversed
orientation, ragged blocks). Every case is DIFFERENTIAL — the hybrid count
must be BIT-IDENTICAL to the dense bitset fold on the same stream — and
seeded, so a failure replays from its parametrized seed.

Capacity policy under test: a tail vertex whose streamed degree would
overflow its buffer must PROMOTE to a hub row (never silently drop), and
when promotion is impossible (hub slots exhausted) the stream must fail
LOUDLY via the ``lost`` counter — an inexact count is never returned.

The hypothesis-powered twin of this module is
``test_hybrid_stream_properties.py`` (skipped when hypothesis is absent);
this file is hypothesis-free so the differential harness always runs.
"""
from functools import partial

import numpy as np
import pytest

from repro.api.counter import TriangleCounter
from repro.api.planner import Plan
from repro.core.streaming import (
    count_stream,
    count_stream_hybrid,
    count_windowed_stream,
    hybrid_lost,
    hybrid_state_nbytes,
    ingest_block_hybrid,
    init_hybrid_state,
    padded_blocks,
    restore_state,
    snapshot_state,
    state_nbytes,
)

_BLOCK = 128  # one block shape for the whole module: one trace per config


# ---------------------------------------------------------------------------
# seeded topology generators (numpy only, no hypothesis)
# ---------------------------------------------------------------------------
def _gnp_edges(rng, n, p):
    iu = np.triu_indices(n, 1)
    keep = rng.random(len(iu[0])) < p
    return np.stack([iu[0][keep], iu[1][keep]], 1).astype(np.int32)


def _powerlaw_edges(rng, n, m, alpha=0.85):
    w = np.arange(1, n + 1, dtype=np.float64) ** -alpha
    w /= w.sum()
    return np.stack([rng.choice(n, m, p=w), rng.choice(n, m, p=w)],
                    1).astype(np.int32)


def _star_edges(rng, n):
    # one mandatory hub plus random chords that close triangles through it
    spokes = np.stack([np.zeros(n - 1, np.int32),
                       np.arange(1, n, dtype=np.int32)], 1)
    chords = _gnp_edges(rng, n, 8.0 / n)
    return np.concatenate([spokes, chords])


# (name, n, edge maker) — n fixed per topology so the whole module compiles
# one hybrid ingest per (n, config), not one per seed
_TOPOLOGIES = [
    ("powerlaw", 300, lambda rng: _powerlaw_edges(rng, 300, 1800)),
    ("gnp_sparse", 256, lambda rng: _gnp_edges(rng, 256, 0.04)),
    ("gnp_dense", 96, lambda rng: _gnp_edges(rng, 96, 0.5)),
    ("star_hub", 200, lambda rng: _star_edges(rng, 200)),
]


def _mangle(rng, edges, n):
    """Stream hazards: duplicates, self-loops, reversed orientation, shuffle
    — none may change the count (dedup + canonicalization are per-ingest)."""
    dups = edges[rng.integers(0, len(edges), size=len(edges) // 4)]
    loops = np.stack([rng.integers(0, n, 7, dtype=np.int32)] * 2, 1)
    e = np.concatenate([edges, dups, loops])
    flip = rng.random(len(e)) < 0.5
    e[flip] = e[flip][:, ::-1]
    rng.shuffle(e)
    return e


def _ragged_blocks(rng, edges):
    cuts = np.sort(rng.integers(0, len(edges),
                                size=rng.integers(3, 9)))
    return [b for b in np.split(edges, cuts) if len(b)]


def _case(seed):
    rng = np.random.default_rng(seed)
    name, n, make = _TOPOLOGIES[seed % len(_TOPOLOGIES)]
    edges = _mangle(rng, make(rng), n)
    return name, n, edges, _ragged_blocks(rng, edges)


# Generous-but-pressured config: threshold 16 promotes eagerly, capacity 32
# forces mandatory promotion on dense cases, 256 slots keep loss impossible
# for these sizes (at most 2m/32 < 256 vertices can reach degree 32).
_H, _C, _T = 256, 32, 16


# ---------------------------------------------------------------------------
# the differential core: hybrid == dense, bit-identical
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", range(12))
def test_hybrid_matches_dense_bit_identical(seed):
    name, n, _, blocks = _case(seed)
    want = count_stream(n, blocks, block_size=_BLOCK)
    got = count_stream_hybrid(n, blocks, hub_slots=_H, tail_capacity=_C,
                              hub_threshold=_T, block_size=_BLOCK)
    assert got == want, f"{name} seed={seed}: hybrid {got} != dense {want}"


@pytest.mark.parametrize("seed", (0, 1, 2, 3))
def test_hybrid_matches_every_dense_regime(seed):
    """One stream, five regimes, one number: plain dense, emulated-sharded
    dense, windowed dense (window covering the whole stream), plain hybrid,
    and hybrid interrupted by a checkpoint→restore round-trip mid-stream."""
    name, n, edges, blocks = _case(seed)
    plain = count_stream(n, blocks, block_size=_BLOCK)
    sharded = count_stream(n, blocks, block_size=_BLOCK, n_stages=3)
    windowed = int(np.asarray(count_windowed_stream(
        n, [blocks], window_epochs=2, block_size=_BLOCK)))
    hybrid = count_stream_hybrid(n, blocks, hub_slots=_H, tail_capacity=_C,
                                 hub_threshold=_T, block_size=_BLOCK)

    step = partial(ingest_block_hybrid, hub_threshold=_T)
    state = init_hybrid_state(n, _H, _C)
    fixed = list(padded_blocks(blocks, n, _BLOCK))
    for i, b in enumerate(fixed):
        state = step(state, b)
        if i == len(fixed) // 2:  # snapshot + rehydrate mid-stream
            state = restore_state(snapshot_state(state))
    resumed = int(state["count"])

    assert plain == sharded == windowed == hybrid == resumed, (
        f"{name} seed={seed}: plain={plain} sharded={sharded} "
        f"windowed={windowed} hybrid={hybrid} resumed={resumed}")
    assert hybrid_lost(state) == 0


# ---------------------------------------------------------------------------
# promotion paths: overflow promotes, exhaustion fails loudly
# ---------------------------------------------------------------------------
def test_tail_overflow_promotes_instead_of_dropping():
    """A vertex whose degree blows straight past a tiny tail buffer must be
    promoted to a hub bitset row — the count stays exact and lost == 0."""
    rng = np.random.default_rng(99)
    n = 200
    # spokes give vertex 0 degree ~199; sparse chords (avg ~2 per vertex)
    # close triangles through it while keeping most tails under capacity 4
    spokes = np.stack([np.zeros(n - 1, np.int32),
                       np.arange(1, n, dtype=np.int32)], 1)
    edges = np.concatenate([spokes, _gnp_edges(rng, n, 2.0 / n)])
    want = count_stream(n, [edges], block_size=_BLOCK)
    step = partial(ingest_block_hybrid, hub_threshold=64)
    state = init_hybrid_state(n, 64, 4)
    for b in padded_blocks([edges], n, _BLOCK):
        state = step(state, b)
    assert int(state["count"]) == want
    assert hybrid_lost(state) == 0
    assert int(state["hub_slot"][0]) >= 0, "overflowing hub was not promoted"


def test_hub_slot_exhaustion_raises_instead_of_undercounting():
    """When every hub slot is taken AND a tail buffer overflows, the stream
    must refuse to produce a count — a RuntimeError naming the loss, never a
    silently smaller number."""
    rng = np.random.default_rng(7)
    edges = _gnp_edges(rng, 96, 0.5)  # avg degree ~47 >> capacity 4
    with pytest.raises(RuntimeError, match="dropped .* endpoint"):
        count_stream_hybrid(96, [edges], hub_slots=2, tail_capacity=4,
                            hub_threshold=4, block_size=_BLOCK)


# ---------------------------------------------------------------------------
# the counter/session surface: forced hybrid plans behave like any stream
# ---------------------------------------------------------------------------
def _hybrid_plan():
    return Plan(method="stream", n_stages=1, block_size=_BLOCK,
                state_layout="hybrid", hub_slots=_H, tail_capacity=_C,
                hub_threshold=_T, reason="forced hybrid (test)")


def test_counter_checkpoint_restore_finalize_bit_identical():
    name, n, edges, blocks = _case(1)
    want = count_stream(n, blocks, block_size=_BLOCK)
    c = TriangleCounter()
    s = c.open_stream(n, plan=_hybrid_plan())
    half = len(edges) // 2
    s.feed(edges[:half])
    ck = s.checkpoint()
    # the checkpoint charges exactly the allocation formula
    assert ck.nbytes == hybrid_state_nbytes(n, _H, _C) == state_nbytes(
        snapshot_state(s.state))
    s2 = c.restore_stream(ck)
    s2.feed(edges[half:])
    assert s2.finalize().item() == want
    # zero-device finalize of a fully-fed checkpoint agrees too
    s3 = c.open_stream(n, plan=_hybrid_plan())
    s3.feed(edges)
    assert s3.checkpoint().finalize_result().item() == want


def test_counter_finalize_refuses_lossy_hybrid_session():
    rng = np.random.default_rng(13)
    edges = _gnp_edges(rng, 96, 0.5)
    p = Plan(method="stream", n_stages=1, block_size=_BLOCK,
             state_layout="hybrid", hub_slots=2, tail_capacity=4,
             hub_threshold=4, reason="undersized hybrid (test)")
    s = TriangleCounter().open_stream(96, plan=p)
    s.feed(edges)
    with pytest.raises(RuntimeError, match="dropped"):
        s.finalize()


def test_open_stream_rejects_hybrid_windowed_or_sharded_plans():
    c = TriangleCounter()
    bad = Plan(method="stream", state_layout="hybrid", hub_slots=8,
               tail_capacity=8, hub_threshold=8, window_epochs=2,
               reason="invalid")
    with pytest.raises(ValueError, match="hybrid"):
        c.open_stream(64, plan=bad)
    bad2 = Plan(method="stream", n_stages=2, state_layout="hybrid",
                hub_slots=8, tail_capacity=8, hub_threshold=8,
                reason="invalid")
    with pytest.raises(ValueError, match="hybrid"):
        c.open_stream(64, plan=bad2)


def test_hybrid_state_nbytes_formula_is_exact():
    for n, h, cap in [(97, 8, 4), (256, 64, 32), (1025, 128, 16)]:
        assert (state_nbytes(init_hybrid_state(n, h, cap))
                == hybrid_state_nbytes(n, h, cap))
