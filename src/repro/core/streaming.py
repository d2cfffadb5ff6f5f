"""Streaming triangle counting — the paper's "graph dynamically generated /
does not fit in memory" regime, as an incremental API.

A triangle is counted exactly once: when its LAST edge arrives. The state is
the adjacency-so-far bitset (n, W) uint32 (n²/8 bytes — 8× under a dense f32
matrix and independent of the stream length).

Two ingest implementations share that contract:

- ``ingest_block`` — the production path: a TWO-PHASE blocked ingest. Phase 1
  closes every edge of the block against the PRE-BLOCK adjacency A in one
  vectorized gather+popcount sweep (``kernels/bitset_count`` when
  ``use_kernel``). Phase 2 adds the exact intra-block correction — triangles
  whose last two edges share the block — from the block's own delta-adjacency
  D: Σ_e pc(A[u]&D[v]) + pc(D[u]&A[v]) counts each (block, block, A) triangle
  twice and Σ_e pc(D[u]&D[v]) counts each all-in-block triangle three times,
  so the block's contribution is ``pre + mixed//2 + dd//3`` (A and D are
  disjoint by dedup, so the terms never overlap). The block is written into
  the donated state IN PLACE: A's rows are gathered, the 2·B live bits are
  added as one scatter of one-hot 128-word windows (``_block_bits``), and
  the same rows gathered again give ``D[u] = new[u] ^ A[u]``. No (n, W)
  delta table is built, so a block moves ~16·B·W bytes of rows whatever n
  is. No per-edge sequential dependency remains.
- ``ingest_block_per_edge`` — the seed per-edge ``lax.scan`` fold, RETAINED AS
  THE DIFFERENTIAL ORACLE (and the BENCH_kernels.json ``stream_bench``
  baseline): O(B) sequential steps per block, trivially correct.

``init_sharded_state``/``ingest_block_sharded`` are the ring-sharded variant:
the adjacency bitset is COLUMN-sharded over S pipeline stages (words
[s·Ws, (s+1)·Ws) of every row live on stage s — n²/8/S bytes per device), so
streamed graphs larger than one device's memory stay countable. Every
popcount term above is a sum over words, so each stage computes its word
shard's partial and the block total is psum-reduced; on a real mesh the step
runs under shard_map via ``dynamic_pipeline.ShardedStateStream``
(``make_mesh_ingest``), on a single host it is emulated with a vmap over the
stage axis.

DEGREE-AWARE HYBRID STATE (``init_hybrid_state``/``ingest_block_hybrid``)
escapes the n²/8 wall for sparse streams: full bitset rows only for
high-degree hubs (promoted when their streamed degree crosses a threshold
or their buffer would overflow), compacted sorted-adjacency buffers of C
neighbor slots for the long tail — ``4·(H·W + n·(C+2))`` bytes, linear in
n. The two-phase blocked contract is preserved exactly: phase 1 gathers
full-width rows for the block's endpoints only, phase 2 runs in a packed
block-local vertex space, and ``pre + mixed//2 + dd//3`` is bit-identical
to the dense state (pinned by tests/test_hybrid_stream.py's randomized
differential harness). Capacity exhaustion is counted in ``lost`` and
raises at finalize — never a silent undercount.

SLIDING WINDOWS (``init_windowed_state``/``ingest_block_windowed``/
``expire_epoch``) extend the same contract with deletions: the state is a
ring of E epoch bitsets (E·n²/8 bytes; ``/S`` per stage when ring-sharded)
whose OR is the LIVE adjacency — the edges of the most recent E epochs.
``expire_epoch`` slides the window by rotating the ring head and clearing
ONE epoch slot (no per-edge deletes). Exactness with cheap expiry comes from
attribution: a live triangle dies exactly when its OLDEST edge's epoch
leaves the window, so per-slot counters ``counts[r]`` hold the triangles
whose oldest edge sits in slot r and the window total is ``counts.sum()``.
The blocked two-phase ingest is reused per epoch — phase 1 sweeps the block
against the E age-cumulative OR tables (newest-first prefix ORs of the ring)
and adjacent differences attribute each closure to the age of its oldest
wedge edge; phase 2's ``pre + mixed//2 + dd//3`` correction is unchanged,
with the mixed term likewise differenced per age. See docs/STREAMING.md for
the derivation and the window-semantics contract (re-arrivals of a
still-live edge are duplicates; an edge re-inserted after expiry is new).
"""
from __future__ import annotations

import threading
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.tracing import INGEST_AGE_CUM, INGEST_LIVE, INGEST_TERMS, INGEST_UPDATE
from repro.utils import count_dtype

# The blocked-kernel path keeps the whole mask table VMEM-resident and the
# edge endpoints in SMEM (see kernels/bitset_count); states that exceed the
# budgets fall back to the pure-JAX gather+popcount sweep instead of failing
# allocation. Mirrors triangle_pipeline's bitset-ring gating.
_MASK_VMEM_BUDGET = 8 * 1024 * 1024
_EDGE_SMEM_BUDGET = 256 * 1024

# uint32 words in one row of an (8, 128) tile, the TPU's layout of a table
_LANES = 128


def init_state(n_nodes: int) -> dict:
    """Unbounded stream state: the adjacency-so-far bitset.

    State bytes: ``4·n·ceil(n/32) ≈ n²/8`` for ``adj`` plus one scalar
    ``count`` — independent of the stream length. Allocation only; traces
    nothing."""
    w = -(-n_nodes // 32)
    return {
        "adj": jnp.zeros((n_nodes, w), jnp.uint32),
        "count": jnp.zeros((), count_dtype()),
    }


def _stage_zeros(shape: tuple, mesh=None) -> jax.Array:
    """A zero uint32 array whose leading axis is the stage axis: on ``mesh``
    it is allocated as one shard per device along the mesh's first axis —
    never whole on one chip first — and on one device otherwise."""
    if mesh is None:
        return jnp.zeros(shape, jnp.uint32)
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
    return jax.jit(lambda: jnp.zeros(shape, jnp.uint32),
                   out_shardings=sharding)()


def init_sharded_state(n_nodes: int, n_stages: int, mesh=None) -> dict:
    """Column-sharded state: stage s owns words [s·Ws, (s+1)·Ws) of every
    row — n·Ws·4 ≈ n²/8/S bytes PER STAGE (S·n·Ws·4 total when the sharding
    is host-emulated on one device; ``mesh`` places each stage's shard on
    its own device). The trailing pad words (W rounded up to S·Ws) map to
    no node and stay zero forever. Allocation only."""
    w = -(-n_nodes // 32)
    ws = -(-w // n_stages)
    return {
        "adj": _stage_zeros((n_stages, n_nodes, ws), mesh),
        "count": jnp.zeros((), count_dtype()),
    }


def init_windowed_state(n_nodes: int, window_epochs: int) -> dict:
    """Sliding-window state: a ring of E = ``window_epochs`` epoch bitsets.

    ``epochs[r]`` holds the edges that arrived while ring slot r was the
    current epoch; the LIVE adjacency is the OR over slots. ``counts[r]``
    holds the live triangles whose OLDEST edge sits in slot r (so clearing a
    slot deletes exactly the triangles that die with it — see
    ``expire_epoch``); the window's triangle count is ``counts.sum()``
    (``window_count``). ``head`` is the slot of the CURRENT epoch; slot age
    is ``(head - r) mod E``.

    State bytes: ``E·4·n·ceil(n/32) ≈ E·n²/8`` for the ring plus E count
    slots — E× the unbounded state, still independent of the stream length.
    Allocation only; traces nothing."""
    if window_epochs < 1:
        raise ValueError(f"window_epochs must be >= 1, got {window_epochs}")
    w = -(-n_nodes // 32)
    return {
        "epochs": jnp.zeros((window_epochs, n_nodes, w), jnp.uint32),
        "counts": jnp.zeros((window_epochs,), count_dtype()),
        "head": jnp.zeros((), jnp.int32),
    }


def init_windowed_sharded_state(n_nodes: int, window_epochs: int,
                                n_stages: int, mesh=None) -> dict:
    """Ring-sharded windowed state: ``init_windowed_state`` with every epoch
    bitset column-sharded over S stages exactly like ``init_sharded_state``
    — ``E·n·Ws·4 ≈ E·n²/8/S`` bytes per stage (all S shards on one device
    when host-emulated, one per device on ``mesh``). ``counts``/``head`` are
    replicated scalars. Allocation only."""
    if window_epochs < 1:
        raise ValueError(f"window_epochs must be >= 1, got {window_epochs}")
    w = -(-n_nodes // 32)
    ws = -(-w // n_stages)
    return {
        "epochs": _stage_zeros((n_stages, window_epochs, n_nodes, ws), mesh),
        "counts": jnp.zeros((window_epochs,), count_dtype()),
        "head": jnp.zeros((), jnp.int32),
    }


def validate_edges(edges, n_nodes: int) -> np.ndarray:
    """Front-door edge validation: the (B, 2) int array contract, enforced.

    The ingest paths treat ids >= n as phantoms (silently dropped) and a
    NEGATIVE id would gather/scatter at a wrapped index — silent corruption
    of the bitset. So the serving front door (``StreamSession.feed`` and the
    multiplexer/server above it) rejects anything outside the contract with
    a clear ``ValueError`` instead: non-integer dtypes, shapes that are not
    (B, 2), and vertex ids outside ``[0, n_nodes)``. Returns the validated
    int32 (B, 2) array (zero-copy when already conforming); empty inputs of
    any shape normalize to (0, 2)."""
    arr = np.asarray(edges)
    if arr.size == 0:
        return np.zeros((0, 2), np.int32)
    if not np.issubdtype(arr.dtype, np.integer):
        raise ValueError(
            f"edges must be an integer array, got dtype {arr.dtype} — vertex "
            f"ids are indices, not floats")
    if arr.ndim != 2 or arr.shape[1] != 2:
        raise ValueError(
            f"edges must have shape (B, 2) (one (u, v) pair per row), got "
            f"{arr.shape}")
    lo, hi = int(arr.min()), int(arr.max())
    if lo < 0 or hi >= n_nodes:
        raise ValueError(
            f"vertex ids must lie in [0, {n_nodes}), got range [{lo}, {hi}] "
            f"— out-of-range ids would silently scatter outside the bitset")
    return arr.astype(np.int32, copy=False)


def snapshot_state(state: dict) -> dict:
    """Bit-exact HOST copy of any streaming state (dense, sharded, windowed,
    on-mesh): the checkpoint half of checkpoint/restore. Blocks until every
    in-flight ingest into ``state`` has completed (the snapshot boundary),
    then copies each array to host numpy — a mesh-sharded state is gathered
    to one host array, which restores onto any layout (the emulated and mesh
    shardings share the (S, ...) shape). Traces nothing."""
    state = jax.block_until_ready(state)
    return {k: np.asarray(v) for k, v in state.items()}


_STAGE_KEYS = ("adj", "epochs")  # state arrays whose leading axis is the stage


def restore_state(snap: dict, mesh=None) -> dict:
    """Device rehydration of a :func:`snapshot_state` copy — the restore
    half. Dtype and bits are preserved exactly, so a restored stream
    continues bit-identically to one that was never interrupted. On
    ``mesh`` the stage-axis arrays land one shard per device, as
    ``init_sharded_state`` allocates them, so the session's compiled step
    takes them as they are. Traces nothing."""
    if mesh is None:
        return {k: jnp.asarray(v) for k, v in snap.items()}
    from jax.sharding import NamedSharding, PartitionSpec as P

    stage = NamedSharding(mesh, P(mesh.axis_names[0]))
    return {k: jax.device_put(v, stage) if k in _STAGE_KEYS else jnp.asarray(v)
            for k, v in snap.items()}


def state_nbytes(state: dict) -> int:
    """Total bytes of a state dict or host snapshot — what a checkpoint
    charges against the host/disk budgets."""
    return int(sum(v.nbytes for v in state.values()))


# Retrace telemetry: the traced-function body runs once per (shape, dtype)
# specialization, so this counts compiles, not calls. With ``padded_blocks``
# feeding fixed-shape blocks, one stream takes exactly one trace.
_INGEST_TRACES = [0]


def ingest_trace_count() -> int:
    """Process-wide ingest-compile telemetry: how many times any ingest body
    (blocked, sharded, windowed, per-edge, mesh) has been TRACED — compiles,
    not calls. The contract every test pins: one fixed block shape → one
    trace per ingest family, shared across streams, sessions and (for the
    windowed path) epochs."""
    return _INGEST_TRACES[0]


# --------------------------------------------------------------------------
# Shared per-block math (unsharded = the off=0, full-width special case)
# --------------------------------------------------------------------------
def _canonical_live(edges: jax.Array, n: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(keep, lo, hi): canonicalized endpoints with self-loops/phantoms
    invalidated (lo = hi = n) and within-block duplicates reduced to their
    first occurrence. ``keep`` still needs the not-already-in-A check."""
    with jax.named_scope(INGEST_LIVE):
        e = edges.astype(jnp.int32)
        u, v = e[:, 0], e[:, 1]
        valid = (u < n) & (v < n) & (u != v)
        lo = jnp.where(valid, jnp.minimum(u, v), n)
        hi = jnp.where(valid, jnp.maximum(u, v), n)
        order = jnp.lexsort((hi, lo))  # stable: first occurrence keeps block order
        ls, hs = lo[order], hi[order]
        dup = jnp.concatenate(
            [jnp.zeros((1,), bool), (ls[1:] == ls[:-1]) & (hs[1:] == hs[:-1])])
        first = jnp.zeros(e.shape[0], bool).at[order].set(~dup)
        return valid & first, lo, hi


def _stage_seen(adj_s: jax.Array, lo: jax.Array, hi: jax.Array, off) -> jax.Array:
    """Per-edge already-in-A bit, restricted to this stage's word shard
    (exactly one stage owns word hi//32, so summing over stages recovers
    the global bit)."""
    n, ws = adj_s.shape
    with jax.named_scope(INGEST_LIVE):
        wl = hi // 32 - off
        owned = (wl >= 0) & (wl < ws) & (lo < n)
        word = adj_s[jnp.clip(lo, 0, n - 1), jnp.clip(wl, 0, ws - 1)]
        bit = (word >> (hi % 32).astype(jnp.uint32)) & jnp.uint32(1)
        return jnp.where(owned, bit, jnp.uint32(0))


def _block_bits(n: int, ws: int, lo: jax.Array, hi: jax.Array,
                live: jax.Array, off) -> tuple[jax.Array, jax.Array]:
    """The block's delta-adjacency on this stage's word shard as 2B one-hot
    windows for :func:`_write_bits`: ``(at, win)`` with ``win[i]`` the L
    words holding one edge's bit and ``at[i]`` the lane row it is added to.
    A table of whole (8, 128) tiles, the TPU's layout for it, has L = 128
    and lane rows in the order it is stored in: tile by tile, then sublane
    by sublane. Any other table has its whole rows as lane rows (L = W_s).
    The ``lo→hi`` bits come first, then ``hi→lo``. Dead edges and bits of
    words another stage owns get ``at`` = n·W_s/L, one past the last lane
    row, so the scatter drops them. The windows are a broadcast compare: no
    (n, W_s) table is built."""
    with jax.named_scope(INGEST_UPDATE):
        row = jnp.concatenate([lo, hi])
        col = jnp.concatenate([hi, lo])
        wl = col // 32 - off
        ok = jnp.concatenate([live, live]) & (wl >= 0) & (wl < ws)
        lanes = _LANES if n % 8 == 0 and ws % _LANES == 0 else ws
        # tile (row // 8, wl // lanes), sublane row % 8; whole rows: at = row
        at = ((row // 8) * (ws // lanes) + wl // lanes) * 8 + row % 8
        bit = jnp.uint32(1) << (col % 32).astype(jnp.uint32)
        win = jnp.where(
            ok[:, None] & (jnp.arange(lanes, dtype=wl.dtype)[None, :]
                           == (wl % lanes)[:, None]),
            bit[:, None], jnp.uint32(0))
        return jnp.where(ok, at, n * ws // lanes), win


def _write_bits(table: jax.Array, at: jax.Array, win: jax.Array) -> jax.Array:
    """Add :func:`_block_bits`' windows into ``table`` (..., n, W_s) in one
    scatter over its lane rows, dropping rows out of bounds. On the TPU the
    lane-row view of a tiled table is a bitcast, so the write is in place
    and moves 512 bytes an update. The bits are distinct and unset in
    ``table``: ``add`` is ``or``."""
    *lead, n, ws = table.shape
    lanes = win.shape[1]
    with jax.named_scope(INGEST_UPDATE):
        if lanes == ws:
            rows = table.reshape(-1, ws).at[at].add(win, mode="drop")
            return rows.reshape(table.shape)
        tiles = table.reshape(*lead, n // 8, 8, ws // lanes, lanes)
        rows = jnp.swapaxes(tiles, -3, -2).reshape(-1, lanes)
        rows = rows.at[at].add(win, mode="drop")
        tiles = rows.reshape(*lead, n // 8, ws // lanes, 8, lanes)
        return jnp.swapaxes(tiles, -3, -2).reshape(table.shape)


def _kernel_fits(use_kernel: bool, table_bytes: int, n_edges: int) -> bool:
    """THE gate for routing a closure sweep through ``kernels/bitset_count``:
    the mask table(s) must fit the VMEM budget and the edge list SMEM — one
    definition so the unbounded and windowed paths cannot drift."""
    return (use_kernel and table_bytes <= _MASK_VMEM_BUDGET
            and n_edges * 8 <= _EDGE_SMEM_BUDGET)


def _phantom_edges(lo: jax.Array, hi: jax.Array, live: jax.Array, n: int) -> jax.Array:
    """Dead edges become phantoms (id = n) so the kernel's validity mask
    doubles as the live mask."""
    return jnp.where(live[:, None], jnp.stack([lo, hi], axis=1), n)


def _stage_update(adj_s: jax.Array, lo: jax.Array, hi: jax.Array,
                  live: jax.Array, off, *,
                  use_kernel: bool = False) -> tuple[jax.Array, jax.Array]:
    """One stage's share of the two-phase block ingest.

    Returns (new word shard, (pre, mixed, dd) partials). The caller combines
    shards (psum / sum over the stage axis) BEFORE dividing: mixed counts
    every (block, block, pre-block) triangle twice and dd every all-in-block
    triangle three times, and those multiplicities only hold for the
    full-width sums."""
    n, ws = adj_s.shape

    def masked_sum(words):
        pc = jax.lax.population_count(words).sum(axis=-1)
        return jnp.sum(jnp.where(live, pc, 0), dtype=count_dtype())

    with jax.named_scope(INGEST_TERMS):
        glo = jnp.clip(lo, 0, n - 1)
        ghi = jnp.clip(hi, 0, n - 1)
        au, av = adj_s[glo], adj_s[ghi]
    at, win = _block_bits(n, ws, lo, hi, live, off)
    new = _write_bits(adj_s, at, win)  # live bits are unset in A (seen)

    with jax.named_scope(INGEST_TERMS):
        table_bytes = n * ws * 4
        kernel = _kernel_fits(use_kernel, table_bytes, lo.shape[0])
        if kernel and 2 * table_bytes <= _MASK_VMEM_BUDGET:
            # the pair kernel holds two whole tables: give it D as one
            from repro.kernels.bitset_count.ops import bitset_edge_count, bitset_pair_count

            delta = _write_bits(jnp.zeros_like(adj_s), at, win)
            ek = _phantom_edges(lo, hi, live, n)
            pre = bitset_edge_count(adj_s, ek)
            mixed = (bitset_pair_count(adj_s, delta, ek)
                     + bitset_pair_count(delta, adj_s, ek))
            dd = bitset_edge_count(delta, ek)
        else:
            # A and D are disjoint, so the written rows XOR the old are D's
            du, dv = new[glo] ^ au, new[ghi] ^ av
            if kernel:
                from repro.kernels.bitset_count.ops import bitset_edge_count

                pre = bitset_edge_count(adj_s, _phantom_edges(lo, hi, live, n))
            else:
                pre = masked_sum(au & av)
            mixed = masked_sum(au & dv) + masked_sum(du & av)
            dd = masked_sum(du & dv)
        return new, jnp.stack([pre, mixed, dd])


def _combine(count, terms):
    # terms = full-width (pre, mixed, dd); integer divisions are exact (see
    # the multiplicities in the module docstring)
    with jax.named_scope(INGEST_UPDATE):
        return count + terms[0] + terms[1] // 2 + terms[2] // 3


# --------------------------------------------------------------------------
# Sliding-window math (shared by the dense / emulated / mesh windowed paths)
# --------------------------------------------------------------------------
def _age_order(head, n_epochs: int) -> jax.Array:
    """Ring slots in AGE order, newest first: ``order[t]`` is the slot whose
    epoch is t epochs old (order[0] = head = the current epoch)."""
    return (head - jnp.arange(n_epochs, dtype=jnp.int32)) % n_epochs


def _age_cum(epochs_s: jax.Array, head) -> jax.Array:
    """Age-cumulative OR tables on this stage's word shard: ``cum[t]`` is
    the OR of the t+1 NEWEST epoch bitsets, so ``cum[-1]`` is the live
    adjacency. Computed once per block and shared between the dedup check
    and the phase sweeps."""
    n_epochs = epochs_s.shape[0]
    with jax.named_scope(INGEST_AGE_CUM):
        return jax.lax.associative_scan(
            jnp.bitwise_or, epochs_s[_age_order(head, n_epochs)], axis=0)


def _windowed_stage_update(epochs_s: jax.Array, cum: jax.Array,
                           lo: jax.Array, hi: jax.Array,
                           live: jax.Array, off, head, *,
                           use_kernel: bool = False
                           ) -> tuple[jax.Array, jax.Array]:
    """One stage's share of the windowed two-phase block ingest.

    The unbounded ingest's phase-1 sweep is reused PER EPOCH: ``cum`` is
    this shard's ``_age_cum`` table stack (the caller already built it for
    the dedup check), and each table gets the same gather+popcount closure
    sweep — ``P[t] = Σ_e pc(cum_t[u] & cum_t[v])`` counts the wedges both
    of whose edges are at age ≤ t, once each. Phase 2's mixed term is swept
    against the same tables
    (``M[t] = Σ_e pc(cum_t[u] & D[v]) + pc(D[u] & cum_t[v])``, each
    (block, block, age ≤ t) triangle twice) and ``dd`` is unchanged.

    Returns ``(new word shard, terms)`` with ``terms`` the (2E+1,) stack
    ``[P (E,), M (E,), dd]``. The caller psums/sums shards over the stage
    axis BEFORE differencing adjacent ages and dividing
    (``_windowed_combine``) — multiplicities only hold for full-width sums,
    exactly like the unbounded path."""
    n_epochs, n, ws = epochs_s.shape

    def masked_sum(words):
        # words: (..., B, ws) -> (...,) masked popcount over live edges
        pc = jax.lax.population_count(words).sum(axis=-1)
        return jnp.sum(jnp.where(live, pc, 0), axis=-1, dtype=count_dtype())

    with jax.named_scope(INGEST_TERMS):
        glo = jnp.clip(lo, 0, n - 1)
        ghi = jnp.clip(hi, 0, n - 1)
    at, win = _block_bits(n, ws, lo, hi, live, off)
    # into slot head; live bits are unset in every slot (seen is the live OR)
    per_slot = n * ws // win.shape[1]
    new = _write_bits(epochs_s, jnp.where(at < per_slot, head * per_slot + at,
                                          n_epochs * per_slot), win)

    with jax.named_scope(INGEST_TERMS):
        table_bytes = n * ws * 4
        if _kernel_fits(use_kernel, table_bytes, lo.shape[0]):
            from repro.kernels.bitset_count.ops import bitset_edge_count, bitset_pair_count

            ek = _phantom_edges(lo, hi, live, n)
            pair_ok = 2 * table_bytes <= _MASK_VMEM_BUDGET
            if pair_ok:  # the pair kernel holds two whole tables: D as one
                delta = _write_bits(jnp.zeros((n, ws), jnp.uint32), at, win)
            else:  # cum[0] is the head slot before the write
                du = new[head, glo] ^ cum[0][glo]
                dv = new[head, ghi] ^ cum[0][ghi]
            ps, ms = [], []
            for t in range(n_epochs):  # the unbounded kernels, once per epoch age
                ps.append(bitset_edge_count(cum[t], ek))
                if pair_ok:
                    ms.append(bitset_pair_count(cum[t], delta, ek)
                              + bitset_pair_count(delta, cum[t], ek))
                else:
                    cu, cv = cum[t][glo], cum[t][ghi]
                    ms.append(masked_sum(cu & dv) + masked_sum(du & cv))
            p_terms = jnp.stack(ps)
            m_terms = jnp.stack(ms)
            dd = bitset_edge_count(delta, ek) if pair_ok else masked_sum(du & dv)
        else:
            cu, cv = cum[:, glo], cum[:, ghi]       # (E, B, ws)
            # the head slot and D are disjoint, and cu[0] / cv[0] are the
            # head rows before the write: after XOR before is D's rows
            du, dv = new[head, glo] ^ cu[0], new[head, ghi] ^ cv[0]
            p_terms = masked_sum(cu & cv)           # (E,)
            m_terms = masked_sum(cu & dv[None]) + masked_sum(du[None] & cv)
            dd = masked_sum(du & dv)
        return new, jnp.concatenate([p_terms, m_terms, dd[None]])


def _windowed_combine(counts: jax.Array, terms: jax.Array, head) -> jax.Array:
    """Attribute the block's full-width (P, M, dd) sums to per-slot counts.

    ``P[t] - P[t-1]`` is the number of closures whose OLDEST wedge edge is
    exactly t epochs old (once each); ``(M[t] - M[t-1]) // 2`` the mixed
    triangles whose third edge is exactly t old (M counts them twice);
    ``dd // 3`` the all-in-block triangles (all three edges current). Each
    lands on the slot that is t epochs old, so ``expire_epoch``'s slot clear
    deletes exactly the triangles whose oldest edge leaves the window. The
    integer divisions are exact for full-width sums only — callers must
    psum/sum shards before calling this."""
    n_epochs = counts.shape[0]
    with jax.named_scope(INGEST_UPDATE):
        p_terms, m_terms, dd = terms[:n_epochs], terms[n_epochs:2 * n_epochs], terms[-1]
        pre_t = jnp.diff(p_terms, prepend=jnp.zeros((1,), p_terms.dtype))
        mixed_t = jnp.diff(m_terms, prepend=jnp.zeros((1,), m_terms.dtype)) // 2
        contrib = (pre_t + mixed_t).at[0].add(dd // 3)
        return counts.at[_age_order(head, n_epochs)].add(contrib)


def window_count(state: dict):
    """The live window's triangle count (device scalar, ``count_dtype``):
    the sum over per-slot attribution counters. Traces nothing (plain
    reduction)."""
    return state["counts"].sum(dtype=state["counts"].dtype)


def _ingest_block_impl(state: dict, edges: jax.Array, *,
                       use_kernel: bool = False) -> dict:
    """Fold one (B, 2) int32 edge block (phantom rows: id >= n_nodes) with the
    two-phase blocked ingest. Duplicate edges are ignored (the paper's
    simple-graph precondition); self-loops contribute nothing.

    State bytes: the n²/8 ``adj`` bitset, written in place when donated
    (transient block working set: four gathered word-rows and two one-hot
    128-word windows per edge). Trace contract: one
    trace per (block shape, n, backend flags) — module-level jit, so every
    stream and session sharing a block shape shares ONE trace
    (``ingest_trace_count`` telemetry). ``ingest_block_donated`` is the same
    body jitted with ``donate_argnums=(0,)``: the input state's buffers are
    aliased into the output, so steady-state ingest allocates NOTHING — the
    caller must rebind (``state = fn(state, block)``) and never touch the
    old dict again. The donated and plain jits are separate compiled
    objects; a session path must pick ONE to keep the one-trace pins."""
    _INGEST_TRACES[0] += 1
    adj = state["adj"]
    n = adj.shape[0]
    keep, lo, hi = _canonical_live(edges, n)
    live = keep & (_stage_seen(adj, lo, hi, 0) == 0)
    adj, terms = _stage_update(adj, lo, hi, live, 0, use_kernel=use_kernel)
    return {"adj": adj, "count": _combine(state["count"], terms)}


_INGEST_STATICS = ("use_kernel",)
ingest_block = partial(jax.jit, static_argnames=_INGEST_STATICS)(
    _ingest_block_impl)
ingest_block_donated = partial(jax.jit, static_argnames=_INGEST_STATICS,
                               donate_argnums=(0,))(_ingest_block_impl)


def _ingest_block_sharded_impl(state: dict, edges: jax.Array) -> dict:
    """Ring-sharded ingest, single-host emulation: vmap over the stage axis
    stands in for the device ring, sum over stages for the psum. Exercises
    the exact word-shard decomposition the mesh path runs under shard_map
    (``make_mesh_ingest``); the Pallas kernel stays off here because the
    emulation vmaps the stage axis.

    State bytes: all S column shards live on THIS device — n²/8 total (the
    n²/8/S-per-stage saving needs the real mesh path). Trace contract: one
    trace per (block shape, S, n), shared across streams and epochs."""
    _INGEST_TRACES[0] += 1
    adj = state["adj"]  # (S, n, Ws)
    s, n, ws = adj.shape
    keep, lo, hi = _canonical_live(edges, n)
    offs = jnp.arange(s, dtype=jnp.int32) * ws
    seen = jax.vmap(lambda a, o: _stage_seen(a, lo, hi, o))(adj, offs)
    with jax.named_scope(INGEST_LIVE):
        seen = seen.sum(0)
    live = keep & (seen == 0)
    adj, terms = jax.vmap(lambda a, o: _stage_update(a, lo, hi, live, o))(adj, offs)
    with jax.named_scope(INGEST_TERMS):
        terms = terms.sum(0)
    return {"adj": adj, "count": _combine(state["count"], terms)}


ingest_block_sharded = jax.jit(_ingest_block_sharded_impl)
ingest_block_sharded_donated = jax.jit(_ingest_block_sharded_impl,
                                       donate_argnums=(0,))


@lru_cache(maxsize=32)
def make_mesh_ingest(mesh, axis_name: str | None = None, *,
                     use_kernel: bool = False):
    """Jitted ring-sharded ingest step over a real device mesh: the state's
    stage axis is laid out along ``axis_name`` (one word shard per device)
    via ``dynamic_pipeline.ShardedStateStream``; ``seen`` and the
    (pre, mixed, dd) partials are psum-reduced per block. Memoized (and the
    runtime shared per mesh) so every block of every stream — including
    interleaved serving sessions — on one mesh reuses one compiled
    executable: one trace per (block shape, mesh, backend flags). State
    bytes: n²/8/S per device — the real per-stage discount the admission
    accounting may charge. The state is DONATED, so each shard is written
    in place: the caller rebinds (``state = ingest(state, block)``) and
    never touches the old dict again."""
    from repro.core.dynamic_pipeline import ShardedStateStream

    runtime = ShardedStateStream.shared(mesh, axis_name or mesh.axis_names[0])
    ax = runtime.axis_name

    def ingest_block_mesh(adj_s, carry, edges):
        _INGEST_TRACES[0] += 1
        n, ws = adj_s.shape
        off = jax.lax.axis_index(ax) * ws
        keep, lo, hi = _canonical_live(edges, n)
        seen = _stage_seen(adj_s, lo, hi, off)
        with jax.named_scope(INGEST_LIVE):
            seen = jax.lax.psum(seen, ax)
        live = keep & (seen == 0)
        adj_s, terms = _stage_update(adj_s, lo, hi, live, off,
                                     use_kernel=use_kernel)
        with jax.named_scope(INGEST_TERMS):
            terms = jax.lax.psum(terms, ax)
        return adj_s, _combine(carry, terms)

    fn = runtime.jit_step(ingest_block_mesh)

    def ingest(state: dict, edges: jax.Array) -> dict:
        adj, count = fn(state["adj"], state["count"], edges)
        return {"adj": adj, "count": count}

    return ingest


# --------------------------------------------------------------------------
# Sliding-window ingest: the epoch ring (dense / emulated-sharded / mesh)
# --------------------------------------------------------------------------
def _ingest_block_windowed_impl(state: dict, edges: jax.Array, *,
                                use_kernel: bool = False) -> dict:
    """Fold one (B, 2) int32 edge block into the CURRENT epoch of a windowed
    state (``init_windowed_state``; phantom rows: id >= n_nodes).

    Duplicates of a STILL-LIVE edge are ignored wherever that edge's epoch
    sits (the window keeps each live edge's first arrival — the unbounded
    path's simple-graph precondition applied per window); an edge whose
    earlier arrival has expired is genuinely new and lands in the current
    epoch. Per-slot triangle attribution is exact (see
    ``_windowed_combine``), so ``window_count`` equals a from-scratch
    recount of the live window after every block.

    State bytes: unchanged E·n²/8 (the ring is updated in place-shape); the
    sweep builds E age-cumulative tables, so transient memory is ~2× the
    ring. Trace contract: one trace per (block shape, E, n) — ``head`` is a
    traced scalar, so epoch advances NEVER retrace (pinned by
    ``tests/test_windowed_stream.py``)."""
    _INGEST_TRACES[0] += 1
    epochs = state["epochs"]
    n = epochs.shape[1]
    keep, lo, hi = _canonical_live(edges, n)
    cum = _age_cum(epochs, state["head"])  # cum[-1] = live adjacency
    live = keep & (_stage_seen(cum[-1], lo, hi, 0) == 0)
    epochs, terms = _windowed_stage_update(
        epochs, cum, lo, hi, live, 0, state["head"], use_kernel=use_kernel)
    return {"epochs": epochs,
            "counts": _windowed_combine(state["counts"], terms, state["head"]),
            "head": state["head"]}


ingest_block_windowed = partial(jax.jit, static_argnames=_INGEST_STATICS)(
    _ingest_block_windowed_impl)
ingest_block_windowed_donated = partial(
    jax.jit, static_argnames=_INGEST_STATICS,
    donate_argnums=(0,))(_ingest_block_windowed_impl)


def _ingest_block_windowed_sharded_impl(state: dict, edges: jax.Array) -> dict:
    """Ring-sharded windowed ingest, single-host emulation: vmap over the
    stage axis stands in for the device ring (all S shards on this device —
    E·n²/8 bytes total, not per stage), sum over stages for the psum. The
    (P, M, dd) partials are summed over shards BEFORE ``_windowed_combine``
    differences and divides — the multiplicities only hold full-width.
    Trace contract: one trace per (block shape, E, S, n), shared across
    epochs and sessions."""
    _INGEST_TRACES[0] += 1
    epochs = state["epochs"]  # (S, E, n, Ws)
    s, _, n, ws = epochs.shape
    head = state["head"]
    keep, lo, hi = _canonical_live(edges, n)
    offs = jnp.arange(s, dtype=jnp.int32) * ws
    cums = jax.vmap(lambda e: _age_cum(e, head))(epochs)  # (S, E, n, Ws)
    seen = jax.vmap(lambda c, o: _stage_seen(c[-1], lo, hi, o))(cums, offs)
    with jax.named_scope(INGEST_LIVE):
        seen = seen.sum(0)
    live = keep & (seen == 0)
    epochs, terms = jax.vmap(
        lambda e, c, o: _windowed_stage_update(e, c, lo, hi, live, o, head))(
        epochs, cums, offs)
    with jax.named_scope(INGEST_TERMS):
        terms = terms.sum(0)
    return {"epochs": epochs,
            "counts": _windowed_combine(state["counts"], terms, head),
            "head": head}


ingest_block_windowed_sharded = jax.jit(_ingest_block_windowed_sharded_impl)
ingest_block_windowed_sharded_donated = jax.jit(
    _ingest_block_windowed_sharded_impl, donate_argnums=(0,))


@lru_cache(maxsize=32)
def make_mesh_ingest_windowed(mesh, axis_name: str | None = None, *,
                              use_kernel: bool = False):
    """Jitted ring-sharded WINDOWED ingest step over a real device mesh: the
    epoch ring's stage axis is laid out along ``axis_name`` (E·n²/8/S bytes
    per device) via the same ``dynamic_pipeline.ShardedStateStream`` runtime
    the unbounded mesh ingest uses — sharded and dense windows share one
    code path (``_windowed_stage_update``). ``counts``/``head`` ride the
    replicated carry; ``seen`` and the (P, M, dd) partials are psum-reduced
    per block before ``_windowed_combine``. Memoized per
    (mesh, axis, backend flags): every windowed stream on one mesh reuses
    one compiled executable per block shape. The ring is DONATED and
    written in place, as in ``make_mesh_ingest``."""
    from repro.core.dynamic_pipeline import ShardedStateStream

    runtime = ShardedStateStream.shared(mesh, axis_name or mesh.axis_names[0])
    ax = runtime.axis_name

    def ingest_block_windowed_mesh(epochs_s, carry, edges):
        _INGEST_TRACES[0] += 1
        counts, head = carry
        _, n, ws = epochs_s.shape
        off = jax.lax.axis_index(ax) * ws
        keep, lo, hi = _canonical_live(edges, n)
        cum = _age_cum(epochs_s, head)  # cum[-1] = this shard's live words
        seen = _stage_seen(cum[-1], lo, hi, off)
        with jax.named_scope(INGEST_LIVE):
            seen = jax.lax.psum(seen, ax)
        live = keep & (seen == 0)
        epochs_s, terms = _windowed_stage_update(
            epochs_s, cum, lo, hi, live, off, head, use_kernel=use_kernel)
        with jax.named_scope(INGEST_TERMS):
            terms = jax.lax.psum(terms, ax)
        counts = _windowed_combine(counts, terms, head)
        return epochs_s, (counts, head)

    fn = runtime.jit_step(ingest_block_windowed_mesh)

    def ingest(state: dict, edges: jax.Array) -> dict:
        epochs, (counts, head) = fn(
            state["epochs"], (state["counts"], state["head"]), edges)
        return {"epochs": epochs, "counts": counts, "head": head}

    return ingest


@partial(jax.jit, donate_argnums=(0, 1))
def _expire(epochs, counts, head):
    # the ring and counters are donated: the slide aliases the input buffers
    # (one slot actually written) instead of copying the whole E-slot ring
    n_epochs = counts.shape[0]
    new_head = (head + 1) % n_epochs
    if epochs.ndim == 4:  # sharded: (S, E, n, Ws)
        epochs = epochs.at[:, new_head].set(jnp.uint32(0))
    else:  # dense: (E, n, W)
        epochs = epochs.at[new_head].set(jnp.uint32(0))
    return epochs, counts.at[new_head].set(0), new_head


def expire_epoch(state: dict) -> dict:
    """Slide the window by one epoch: rotate the ring head onto the OLDEST
    slot and clear it (bitset + count slot).

    This is the whole deletion story — a single epoch-slot clear, no
    per-edge deletes: the cleared slot held exactly the edges older than the
    new window, and ``counts`` attribution (oldest-edge epoch) guarantees
    its count slot held exactly the triangles those edges supported. The new
    current epoch starts empty. The ring and counters are DONATED to the
    jit — the caller must rebind (``state = expire_epoch(state)``) and drop
    the old dict — so a slide writes O(n²/8) bytes (one slot) regardless of
    how many edges die, instead of copying the E-slot ring. Works on dense
    and sharded windowed states; one trace per state shape (``head`` is
    traced, so repeated slides never retrace)."""
    epochs, counts, head = _expire(state["epochs"], state["counts"], state["head"])
    return {"epochs": epochs, "counts": counts, "head": head}


def count_windowed_stream(n_nodes: int, epochs, window_epochs: int, *,
                          block_size: int | None = None, n_stages: int = 1,
                          mesh=None, use_kernel: bool = False) -> int:
    """Consume an iterable of EPOCHS — each an iterable of (B, 2) numpy edge
    blocks — and return the triangle count of the final window (the last
    ``window_epochs`` epochs), host-synced. The core-level twin of
    ``TriangleCounter.count_windowed`` for differential tests and benches.

    Blocks are coalesced/padded to one fixed shape through a single
    :class:`BlockBuffer` shared across epochs (epoch tails flush at every
    boundary; the tail shape is sticky), so a stream of same-sized epochs
    costs one ingest trace TOTAL — ``expire_epoch`` between epochs rotates
    a traced head and never retraces. ``n_stages > 1`` ring-shards every
    epoch bitset (E·n²/8/S bytes per stage on ``mesh`` when its size
    matches, else host-emulated)."""
    if n_stages > 1:
        on_mesh = mesh is not None and mesh.devices.size == n_stages
        state = init_windowed_sharded_state(n_nodes, window_epochs, n_stages,
                                            mesh=mesh if on_mesh else None)
        if on_mesh:
            step = make_mesh_ingest_windowed(mesh, use_kernel=use_kernel)
        else:
            step = ingest_block_windowed_sharded
    else:
        state = init_windowed_state(n_nodes, window_epochs)
        step = partial(ingest_block_windowed, use_kernel=use_kernel)
    buf = BlockBuffer(n_nodes, block_size)

    def _drain(blocks):
        nonlocal state
        for b in blocks:
            state = step(state, b)

    first = True
    for epoch_blocks in epochs:
        if not first:  # close the previous epoch: flush its tail, slide
            tail = buf.flush()
            if tail is not None:
                _drain([tail])
            state = expire_epoch(state)
        first = False
        for block in epoch_blocks:
            _drain(buf.push(block))
    tail = buf.flush()
    if tail is not None:
        _drain([tail])
    return int(window_count(state))


# --------------------------------------------------------------------------
# Per-edge scan — the seed implementation, retained as the oracle
# --------------------------------------------------------------------------
@jax.jit
def ingest_block_per_edge(state: dict, edges: jax.Array) -> dict:
    """The seed per-edge ``lax.scan`` fold: O(B) sequential steps per block.
    Retained as the differential-testing ORACLE for ``ingest_block`` /
    ``ingest_block_sharded`` and as the ``stream_bench`` baseline — it is
    trivially correct (each edge sees exactly the adjacency before it) but
    neither parallel nor pipelined. Same n²/8 state bytes and one-trace-per-
    block-shape contract as ``ingest_block``."""
    _INGEST_TRACES[0] += 1
    n = state["adj"].shape[0]

    def one(carry, uv):
        adj, count = carry
        u = jnp.minimum(uv[0], n - 1)
        v = jnp.minimum(uv[1], n - 1)
        valid = (uv[0] < n) & (uv[1] < n) & (uv[0] != uv[1])
        seen = (adj[u, v // 32] >> (v % 32)) & 1  # dedup: already present?
        live = valid & (seen == 0)
        closures = jax.lax.population_count(
            jnp.bitwise_and(adj[u], adj[v])
        ).sum().astype(count_dtype())
        count = count + jnp.where(live, closures, 0)
        bit_v = jnp.where(live, jnp.uint32(1) << (v % 32).astype(jnp.uint32), jnp.uint32(0))
        bit_u = jnp.where(live, jnp.uint32(1) << (u % 32).astype(jnp.uint32), jnp.uint32(0))
        adj = adj.at[u, v // 32].set(adj[u, v // 32] | bit_v)
        adj = adj.at[v, u // 32].set(adj[v, u // 32] | bit_u)
        return (adj, count), None

    (adj, count), _ = jax.lax.scan(one, (state["adj"], state["count"]),
                                   edges.astype(jnp.int32))
    return {"adj": adj, "count": count}


# --------------------------------------------------------------------------
# Degree-aware hybrid state: bitset rows for hubs, fixed-capacity sorted
# adjacency buffers for the long tail — the escape from the n²/8 wall
# --------------------------------------------------------------------------
def init_hybrid_state(n_nodes: int, hub_slots: int, tail_capacity: int) -> dict:
    """Hybrid streaming state: ``hub_slots`` full bitset rows reserved for
    high-degree vertices plus a compacted sorted-adjacency buffer of
    ``tail_capacity`` neighbor slots per vertex for the long tail.

    Layout (all int32/uint32):

    - ``hub_adj``  (H, W)  — one full-width bitset row per hub slot
    - ``hub_ids``  (H,)    — vertex owning each slot (sentinel n = free)
    - ``hub_slot`` (n,)    — slot index per vertex (-1 = tail vertex)
    - ``tail_nbr`` (n, C)  — sorted neighbor ids, sentinel n past the fill
    - ``deg``      (n,)    — streamed degree so far (the promotion sketch)
    - ``count``            — running triangle total; ``lost`` — edge
      endpoints DROPPED on capacity exhaustion (must stay 0; the serving
      tier raises loudly otherwise — never a silent undercount)

    State bytes: ``4·(H·W + H + n·(C+2)) + O(1)`` (:func:`hybrid_state_nbytes`
    is the exact planner-side formula) — linear in n instead of the dense
    n²/8 whenever C ≪ n/8. Allocation only; traces nothing."""
    if hub_slots < 1:
        raise ValueError(f"hub_slots must be >= 1, got {hub_slots}")
    if tail_capacity < 1:
        raise ValueError(f"tail_capacity must be >= 1, got {tail_capacity}")
    w = -(-n_nodes // 32)
    return {
        "hub_adj": jnp.zeros((hub_slots, w), jnp.uint32),
        "hub_ids": jnp.full((hub_slots,), n_nodes, jnp.int32),
        "hub_slot": jnp.full((n_nodes,), -1, jnp.int32),
        "tail_nbr": jnp.full((n_nodes, tail_capacity), n_nodes, jnp.int32),
        "deg": jnp.zeros((n_nodes,), jnp.int32),
        "count": jnp.zeros((), count_dtype()),
        "lost": jnp.zeros((), jnp.int32),
    }


def hybrid_state_nbytes(n_nodes: int, hub_slots: int, tail_capacity: int) -> int:
    """EXACT device bytes of :func:`init_hybrid_state` — the formula the
    planner charges at admission, asserted equal to the real allocation by
    the planner test suite (a drifting estimate would corrupt every
    admission ledger above it)."""
    w = -(-n_nodes // 32)
    scalar = int(np.dtype(count_dtype()).itemsize)
    return 4 * (hub_slots * w + hub_slots + n_nodes * (tail_capacity + 2)) \
        + scalar + 4


def _tail_rows(nbrs: jax.Array, n: int, w: int) -> jax.Array:
    """(R, C) sorted tail neighbor buffers -> (R, W) full-width bitset rows.

    The sentinel column is mapped to word W EXPLICITLY (scatter drop): the
    naive ``n // 32`` is a REAL word index whenever ``n % 32 != 0``, so
    relying on the id itself being out of range would corrupt bit n%32 of
    the last word."""
    r = nbrs.shape[0]
    real = nbrs < n
    col = jnp.where(real, nbrs // 32, w)
    bit = jnp.where(real, jnp.uint32(1) << (nbrs % 32).astype(jnp.uint32),
                    jnp.uint32(0))
    # buffer entries are distinct neighbors, so add == bitwise-or
    return jnp.zeros((r, w), jnp.uint32).at[
        jnp.arange(r)[:, None], col].add(bit)


def _ingest_block_hybrid_impl(state: dict, edges: jax.Array, *,
                              hub_threshold: int) -> dict:
    """Fold one (B, 2) int32 edge block into the HYBRID state — the same
    two-phase ``pre + mixed//2 + dd//3`` contract as ``ingest_block``, bit
    for bit, without ever materializing an (n, W) table.

    Phase 1 gathers full-width pre-block rows for the 2B endpoints only
    (hub rows verbatim, tail buffers expanded via :func:`_tail_rows`) and
    popcounts closures. Phase 2 works in a BLOCK-LOCAL vertex space: the
    block delta D only ever touches block endpoints, so D and the
    restriction of A to block-vertex columns are packed into (2B, ceil(2B/32))
    words and the exact dense multiplicities carry over unchanged (mixed
    counts each (block, block, pre-block) triangle twice, dd each
    all-in-block triangle three times).

    PROMOTION runs before insertion: a tail vertex whose streamed degree
    would exceed its buffer (mandatory) or reaches ``hub_threshold``
    (policy) claims a free hub slot — its buffer is expanded into the slot's
    bitset row and cleared — with mandatory promotions outranking policy
    ones when slots are scarce. Only when every slot is taken AND a buffer
    still overflows are edge endpoints dropped, counted in ``lost`` (the
    serving tier refuses to finalize a lossy session).

    Transient working set: ~8 full-width row-gathers of B edges (32·B·W
    bytes) plus the (2B)² local bit matrix — the planner's hybrid block
    sizing keeps both inside the memory budget. Trace contract: one trace
    per (block shape, n, H, C, threshold) — module-level jit, shared across
    sessions; promotion and degree updates are data, never a retrace."""
    _INGEST_TRACES[0] += 1
    hub_adj, hub_ids = state["hub_adj"], state["hub_ids"]
    hub_slot, tail_nbr, deg = state["hub_slot"], state["tail_nbr"], state["deg"]
    n = hub_slot.shape[0]
    h, w = hub_adj.shape
    c = tail_nbr.shape[1]
    b = edges.shape[0]

    keep, lo, hi = _canonical_live(edges, n)

    def full_rows(v):
        # (B, W) pre-block adjacency rows (phantom id n -> zero row)
        gv = jnp.clip(v, 0, n - 1)
        slot = jnp.where(v < n, hub_slot[gv], -1)
        hubrow = hub_adj[jnp.clip(slot, 0, h - 1)]
        tailrow = _tail_rows(tail_nbr[gv], n, w)
        rows = jnp.where((slot >= 0)[:, None], hubrow, tailrow)
        return jnp.where((v < n)[:, None], rows, jnp.uint32(0))

    with jax.named_scope(INGEST_TERMS):
        rows_lo = full_rows(lo)
        rows_hi = full_rows(hi)

    with jax.named_scope(INGEST_LIVE):
        # dedup against A: bit hi of lo's row (rows are symmetric by insertion)
        word = rows_lo[jnp.arange(b), jnp.clip(hi // 32, 0, w - 1)]
        seen = (word >> (hi % 32).astype(jnp.uint32)) & jnp.uint32(1)
        live = keep & (seen == 0)

    def masked_sum(words, mask):
        pc = jax.lax.population_count(words).sum(axis=-1)
        return jnp.sum(jnp.where(mask, pc, 0), dtype=count_dtype())

    with jax.named_scope(INGEST_TERMS):
        pre = masked_sum(rows_lo & rows_hi, live)

        # ---- block-local vertex space for the intra-block correction ----
        big = 2 * b
        wl = -(-big // 32)
        rlo = jnp.where(live, lo, n)
        rhi = jnp.where(live, hi, n)
        verts = jnp.concatenate([rlo, rhi])      # one occurrence per endpoint
        others = jnp.concatenate([rhi, rlo])     # the occurrence's neighbor
        liveo = jnp.concatenate([live, live])
        order = jnp.argsort(verts, stable=True)
        sv = verts[order]
        firsts = jnp.concatenate([jnp.ones((1,), bool), sv[1:] != sv[:-1]])
        lid_sorted = (jnp.cumsum(firsts) - 1).astype(jnp.int32)
        lid = jnp.zeros((big,), jnp.int32).at[order].set(lid_sorted)
        # global vertex per local id (dead occurrences share the id of value n)
        gvert = jnp.full((big,), n, jnp.int32).at[lid_sorted].set(sv)

        # D in local space: each live edge's two bits, one scatter each way
        l_lo, l_hi = lid[:b], lid[b:]

        def dscat(dst, row, cvert):
            rr = jnp.where(live, row, big)  # dead edges scatter out of bounds
            bit = jnp.where(live, jnp.uint32(1) << (cvert % 32).astype(jnp.uint32),
                            jnp.uint32(0))
            return dst.at[rr, cvert // 32].add(bit)

        dloc = dscat(dscat(jnp.zeros((big, wl), jnp.uint32), l_lo, l_hi), l_hi, l_lo)

        # A restricted to block-vertex columns, per occurrence, packed to words
        rows_cat = jnp.concatenate([rows_lo, rows_hi])          # (2B, W)
        gw = jnp.clip(gvert // 32, 0, w - 1)
        abit = (rows_cat[:, gw] >> (gvert % 32).astype(jnp.uint32)[None, :]) \
            & jnp.uint32(1)                                      # (2B, L)
        abit = jnp.where((gvert < n)[None, :], abit, jnp.uint32(0))
        abit = jnp.pad(abit, ((0, 0), (0, wl * 32 - big)))
        aloc = (abit.reshape(big, wl, 32)
                << jnp.arange(32, dtype=jnp.uint32)[None, None, :]).sum(
            axis=-1, dtype=jnp.uint32)                           # (2B, Wl)

        d_lo = dloc[jnp.clip(l_lo, 0, big - 1)]
        d_hi = dloc[jnp.clip(l_hi, 0, big - 1)]
        mixed = masked_sum(aloc[:b] & d_hi, live) + masked_sum(d_lo & aloc[b:], live)
        dd = masked_sum(d_lo & d_hi, live)
    count = _combine(state["count"], jnp.stack([pre, mixed, dd]))

    with jax.named_scope(INGEST_UPDATE):
        # ---- promotion (BEFORE insertion, on pre-block buffers) ----
        occ = jnp.zeros((big,), jnp.int32).at[jnp.where(liveo, lid, big)].add(1)
        real = gvert < n
        gv_ok = jnp.clip(gvert, 0, n - 1)
        is_tail = jnp.where(real, hub_slot[gv_ok] < 0, False)
        newdeg = jnp.where(real, deg[gv_ok], 0) + occ
        touched = is_tail & (occ > 0)
        must = touched & (newdeg > c)            # buffer would overflow
        want = touched & (newdeg >= hub_threshold)
        cand = must | want
        free = hub_ids == n
        n_free = jnp.sum(free.astype(jnp.int32))
        # mandatory promotions claim free slots before policy ones
        mrank = jnp.cumsum(must.astype(jnp.int32)) - 1
        wrank = jnp.sum(must.astype(jnp.int32)) \
            + jnp.cumsum((cand & ~must).astype(jnp.int32)) - 1
        prank = jnp.where(must, mrank, wrank)
        slot_for = jnp.argsort(~free, stable=True)[jnp.clip(prank, 0, h - 1)]
        ok = cand & (prank < n_free) & (prank < h)

        s_ok = jnp.where(ok, slot_for, h)        # out-of-bounds scatter -> drop
        v_ok = jnp.where(ok, gvert, n)
        promo_rows = jnp.where(real[:, None], _tail_rows(tail_nbr[gv_ok], n, w),
                               jnp.uint32(0))
        hub_adj = hub_adj.at[s_ok].set(promo_rows)   # free slots hold zero rows
        hub_ids = hub_ids.at[s_ok].set(v_ok)
        hub_slot = hub_slot.at[v_ok].set(jnp.where(ok, slot_for, 0).astype(jnp.int32))
        tail_nbr = tail_nbr.at[v_ok].set(jnp.int32(n))

        # ---- insertion (hub rows get bits, tail buffers get sorted ids) ----
        slot_now = jnp.where(liveo, hub_slot[jnp.clip(verts, 0, n - 1)], -1)
        to_hub = liveo & (slot_now >= 0)
        hbit = jnp.where(to_hub, jnp.uint32(1) << (others % 32).astype(jnp.uint32),
                         jnp.uint32(0))
        # live edges are deduped and absent from A, so the added bits are
        # distinct and unset: add == bitwise-or (promoted rows included)
        hub_adj = hub_adj.at[jnp.where(to_hub, slot_now, h),
                             jnp.clip(others // 32, 0, w - 1)].add(hbit)

        to_tail = liveo & (slot_now < 0)
        # arrival rank of each occurrence within its vertex's block segment
        first_pos = jnp.full((big,), big, jnp.int32).at[lid_sorted].min(
            jnp.arange(big, dtype=jnp.int32))
        rank = jnp.zeros((big,), jnp.int32).at[order].set(
            jnp.arange(big, dtype=jnp.int32) - first_pos[lid_sorted])
        pos = jnp.where(liveo, deg[jnp.clip(verts, 0, n - 1)], 0) + rank
        over = to_tail & (pos >= c)              # slot exhausted AND buffer full
        tail_nbr = tail_nbr.at[jnp.where(to_tail & (pos < c), verts, n),
                               jnp.clip(pos, 0, c - 1)].set(
            jnp.where(to_tail, others, n))
        lost = state["lost"] + jnp.sum(over.astype(jnp.int32))

        # keep touched tail buffers sorted (sentinel n sorts past the fill):
        # canonical layout -> bit-identical checkpoints regardless of feed order
        still_tail = real & (hub_slot[gv_ok] < 0) & touched
        resorted = jnp.sort(tail_nbr[gv_ok], axis=1)
        tail_nbr = tail_nbr.at[jnp.where(still_tail, gvert, n)].set(resorted)

        deg = deg.at[jnp.where(liveo, verts, n)].add(1)
    return {"hub_adj": hub_adj, "hub_ids": hub_ids, "hub_slot": hub_slot,
            "tail_nbr": tail_nbr, "deg": deg, "count": count, "lost": lost}


ingest_block_hybrid = partial(jax.jit, static_argnames=("hub_threshold",))(
    _ingest_block_hybrid_impl)
ingest_block_hybrid_donated = partial(
    jax.jit, static_argnames=("hub_threshold",),
    donate_argnums=(0,))(_ingest_block_hybrid_impl)


def hybrid_lost(state: dict) -> int:
    """Host-synced dropped-endpoint counter of a hybrid state — must be 0
    for the count to be exact; every finalize/checkpoint path raises when it
    is not (capacity exhaustion is a sizing bug, never a silent
    undercount)."""
    return int(np.asarray(state["lost"]))


def count_stream_hybrid(n_nodes: int, blocks, *, hub_slots: int,
                        tail_capacity: int, hub_threshold: int | None = None,
                        block_size: int | None = None) -> int:
    """Consume an iterable of (B, 2) numpy edge blocks through the HYBRID
    state — the differential twin of :func:`count_stream` for the fuzz
    harness and benches. Raises if any edge endpoint was dropped (hub slots
    exhausted while a tail buffer overflowed) instead of returning an
    undercount. ``hub_threshold`` defaults to ``tail_capacity`` (promote
    exactly when the buffer fills)."""
    state = init_hybrid_state(n_nodes, hub_slots, tail_capacity)
    step = partial(ingest_block_hybrid, hub_threshold=int(
        tail_capacity if hub_threshold is None else hub_threshold))
    for block in padded_blocks(blocks, n_nodes, block_size):
        state = step(state, block)
    lost = hybrid_lost(state)
    if lost:
        raise RuntimeError(
            f"hybrid stream dropped {lost} edge endpoint(s): {hub_slots} hub "
            f"slots exhausted while tail buffers of {tail_capacity} "
            f"overflowed — resize hub_slots/tail_capacity")
    return int(state["count"])


class BlockBuffer:
    """Incremental re-blocking: push ragged edge arrays in, pop fixed-shape
    blocks out — ``padded_blocks`` as a handle instead of a generator, so a
    serving session can interleave with other sessions (push a block, yield
    control, push more) without holding a suspended generator per stream.

    The shape policy is exactly ``padded_blocks``'s: every full block has
    ``block_size`` rows; the trailing remainder is padded with phantom edges
    (id = n_nodes, which every ingest treats as invalid); a stream that ends
    before ever filling one block is padded to the next power of two instead
    (still a single shape for the stream — a 100-edge stream under a
    planner-sized 1M block must not scan 1M phantom rows).
    ``block_size=None`` adopts the first non-empty push's row count.

    Host-side cost: at most ``block_size - 1`` buffered edges (numpy); the
    device state is whoever consumes the emitted blocks. Emitting one fixed
    shape is what holds the one-ingest-trace-per-stream contract — every
    shape this buffer emits is one (shared, module-level) ingest trace.

    OWNERSHIP (single producer, single consumer — enforced): at any moment
    exactly ONE thread may be inside a mutating call (``push`` / ``flush`` /
    ``set_block_size``). The async prefetch driver transfers ownership at
    its quiesce barrier: the producer thread owns the buffer while prefetch
    is live, the drive thread reclaims it after the barrier (checkpoint /
    finalize / advance flush the tail from the drive thread). Overlapping
    mutators used to corrupt the sticky tail SILENTLY (two flushes racing on
    ``_buf``/``_tail_target``); now any mutating call that finds another one
    in flight raises ``RuntimeError`` immediately — the guard is a
    non-blocking try-lock, never a wait, so it cannot deadlock.
    """

    def __init__(self, n_nodes: int, block_size: int | None = None):
        self.n_nodes = n_nodes
        self.block_size = block_size
        self._buf: list[np.ndarray] = []
        self._buffered = 0
        self._emitted_full = False
        self._tail_target = 0  # sticky pow2 tail shape across repeated flushes
        self._owner = threading.Lock()  # SPSC guard: held only DURING a call

    def _acquire(self, op: str):
        if not self._owner.acquire(blocking=False):
            raise RuntimeError(
                f"BlockBuffer.{op}() while another mutating call is in "
                f"flight — the buffer is single-producer/single-consumer; "
                f"concurrent push/flush silently corrupts the sticky tail "
                f"(quiesce the prefetch driver before touching the buffer "
                f"from another thread)")

    def export_shape_state(self) -> dict:
        """The re-blocking continuity a session checkpoint must carry: the
        adopted ``block_size`` plus the sticky tail-shape state. A restored
        buffer that imports this emits exactly the shapes the original would
        have — the no-retrace-on-restore half of the checkpoint contract.
        (The buffered edges themselves are NOT exported: ``checkpoint()``
        flushes the tail first, so the buffer is empty at the snapshot
        boundary.)"""
        return {"block_size": self.block_size,
                "tail_target": self._tail_target,
                "emitted_full": self._emitted_full}

    def import_shape_state(self, shape_state: dict) -> None:
        """Adopt a checkpointed buffer's shape continuity (see
        :meth:`export_shape_state`)."""
        self.block_size = shape_state["block_size"]
        self._tail_target = shape_state["tail_target"]
        self._emitted_full = shape_state["emitted_full"]

    def _drain(self) -> list[jax.Array]:
        out: list[jax.Array] = []
        while self._buffered >= self.block_size:
            flat = np.concatenate(self._buf) if len(self._buf) > 1 else self._buf[0]
            chunk, rest = flat[: self.block_size], flat[self.block_size:]
            self._buf, self._buffered = ([rest], len(rest)) if len(rest) else ([], 0)
            self._emitted_full = True
            out.append(jnp.asarray(chunk))
        return out

    def push(self, block) -> list[jax.Array]:
        """Buffer ``block``; return every full ``block_size`` block it
        completed (possibly none). Raises ``RuntimeError`` when another
        mutating call is in flight (SPSC ownership — see the class
        docstring)."""
        self._acquire("push")
        try:
            b = np.asarray(block, dtype=np.int32).reshape(-1, 2)
            if len(b) == 0:
                return []
            if self.block_size is None:
                self.block_size = len(b)
            self._buf.append(b)
            self._buffered += len(b)
            return self._drain()
        finally:
            self._owner.release()

    def set_block_size(self, block_size: int) -> list[jax.Array]:
        """Adaptive re-blocking: switch the emitted full-block shape from
        the NEXT block on (already-emitted blocks keep their shape; counts
        are invariant to re-blocking, so this never changes a result). The
        buffered remainder re-chunks immediately — any blocks the new size
        completes are returned just like :meth:`push`. Each distinct size is
        one (module-level, shared) ingest trace; callers bound the sizes to
        pow2 steps of one bucket (``AdaptiveBlockSizer``), so the trace cost
        is log2-bounded."""
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self._acquire("set_block_size")
        try:
            self.block_size = int(block_size)
            self._emitted_full = False  # let a small tail keep its pow2 shape
            return self._drain()
        finally:
            self._owner.release()

    def flush(self) -> jax.Array | None:
        """The padded tail block (None if nothing is buffered). Call at end
        of stream — or at every epoch boundary for a windowed session: the
        power-of-two tail shape is STICKY (remembered and only ever grown),
        so repeated flushes of similar-size tails reuse one shape, hence one
        ingest trace (distinct shapes only when a tail outgrows every
        earlier one — log2-bounded). Raises ``RuntimeError`` when another
        mutating call is in flight (SPSC ownership)."""
        self._acquire("flush")
        try:
            if not self._buffered:
                return None
            flat = np.concatenate(self._buf) if len(self._buf) > 1 else self._buf[0]
            self._buf, self._buffered = [], 0
            if self._emitted_full:
                target = self.block_size
            else:  # never filled a block: one power-of-two shape, not block_size
                target = max(self._tail_target, 8)
                while target < min(len(flat), self.block_size):
                    target *= 2
                target = min(target, self.block_size)
                self._tail_target = target
            pad = np.full((target - len(flat), 2), self.n_nodes, np.int32)
            return jnp.asarray(np.concatenate([flat, pad]))
        finally:
            self._owner.release()


class AdaptiveBlockSizer:
    """Grow/shrink the ingest block size from observed wall-clock — the
    paper's dynamic-pipeline "growing and shrinking" analogue, applied to
    re-blocking: a block that dispatches too fast is dominated by per-call
    overhead (grow ×2 to amortize it), one that runs too long hurts latency
    and working-set (shrink ÷2).

    Sizes move in POWER-OF-TWO steps inside ``[lo, hi]`` where ``hi`` is the
    plan's block size (never exceed what the planner budgeted for the block
    working set) and ``lo`` defaults to ``max(hi // 8, 256)`` — so at most
    ``log2(hi/lo) + 1`` distinct shapes can ever be proposed, keeping the
    trace cost bounded. ``observe(n_edges, wall_s)`` feeds one measured
    ingest; a resize is proposed only after ``patience`` consecutive
    observations agree (hysteresis — one slow GC pause must not thrash the
    shape). Returns the new size when a change is due, else None. Pure host
    arithmetic; traces nothing, thread-free (the caller serializes calls)."""

    def __init__(self, plan_block_size: int, *, lo: int | None = None,
                 low_s: float = 2e-3, high_s: float = 20e-3,
                 patience: int = 3):
        hi = 1 << max(int(plan_block_size) - 1, 0).bit_length()  # pow2 >= plan
        self.hi = max(hi, 1)
        self.lo = max(1, min(lo if lo is not None else max(hi // 8, 256),
                             self.hi))
        self.low_s = low_s
        self.high_s = high_s
        self.patience = patience
        self.size = self.hi
        self._streak = 0  # +k fast observations in a row, -k slow

    def observe(self, n_edges: int, wall_s: float) -> int | None:
        """One measured ingest of ``n_edges`` rows in ``wall_s`` seconds.
        Returns the NEW block size when ``patience`` consecutive
        observations agree a resize helps (caller applies it via
        ``BlockBuffer.set_block_size``), else None."""
        if n_edges <= 0:
            return None
        if wall_s < self.low_s and self.size * 2 <= self.hi:
            self._streak = self._streak + 1 if self._streak > 0 else 1
            if self._streak >= self.patience:
                self._streak = 0
                self.size *= 2
                return self.size
        elif wall_s > self.high_s and self.size // 2 >= self.lo:
            self._streak = self._streak - 1 if self._streak < 0 else -1
            if -self._streak >= self.patience:
                self._streak = 0
                self.size //= 2
                return self.size
        else:
            self._streak = 0
        return None


def padded_blocks(blocks, n_nodes: int, block_size: int | None = None):
    """Normalize an iterable of (B, 2) edge blocks to ONE fixed block shape.

    The ingest functions retrace per distinct block shape, so a producer that
    emits ragged blocks pays an extra compile per shape. This coalesces and
    splits the incoming blocks to exactly ``block_size`` rows (the pull-based
    rendering of :class:`BlockBuffer` — see it for the shape policy). The
    count is invariant to the re-blocking: triangle totals do not depend on
    edge order, and coalescing preserves order anyway.
    """
    buf = BlockBuffer(n_nodes, block_size)
    for block in blocks:
        yield from buf.push(block)
    tail = buf.flush()
    if tail is not None:
        yield tail


def count_stream(n_nodes: int, blocks, *, block_size: int | None = None,
                 n_stages: int = 1, mesh=None, use_kernel: bool = False) -> int:
    """Consume an iterable of (B, 2) numpy edge blocks; returns the exact
    triangle count without ever materializing the full edge list. Blocks are
    coalesced/padded to one fixed shape (see ``padded_blocks``) so the whole
    stream compiles once.

    ``n_stages > 1`` column-shards the adjacency state over the ring
    (n²/8/S bytes per stage): on ``mesh`` (when its size matches) each shard
    lives on its own device under shard_map, otherwise the sharding is
    emulated on host. ``use_kernel`` routes the phase-1 closure sweep through
    ``kernels/bitset_count`` where the state fits its VMEM/SMEM budgets."""
    if n_stages > 1:
        on_mesh = mesh is not None and mesh.devices.size == n_stages
        state = init_sharded_state(n_nodes, n_stages,
                                   mesh=mesh if on_mesh else None)
        if on_mesh:
            step = make_mesh_ingest(mesh, use_kernel=use_kernel)
        else:
            step = ingest_block_sharded
    else:
        state = init_state(n_nodes)
        step = partial(ingest_block, use_kernel=use_kernel)
    for block in padded_blocks(blocks, n_nodes, block_size):
        state = step(state, block)
    return int(state["count"])


def count_stream_per_edge(n_nodes: int, blocks, *,
                          block_size: int | None = None) -> int:
    """The seed streaming fold (per-edge scan) — the oracle twin of
    ``count_stream`` for differential tests and ``stream_bench``. Same
    n²/8 state bytes and one-trace-per-fixed-shape-stream contract; the
    cost difference is the O(B) sequential scan per block."""
    state = init_state(n_nodes)
    for block in padded_blocks(blocks, n_nodes, block_size):
        state = ingest_block_per_edge(state, block)
    return int(state["count"])
