"""Sliding-window streaming with deletions: the epoch-rotated bitset ring.

No hypothesis dependency — this module always runs in tier-1.

THE acceptance pin for the windowed PR lives here: on every tested stream
(dense, sharded, mesh, post-expiry re-insertion, epoch-straddling
duplicates) the windowed count must be BIT-IDENTICAL to
``windowed_oracle`` — a from-scratch python recount of the live window —
with exactly one ingest trace per block shape across all epochs.

Window-semantics contract (documented in docs/STREAMING.md): the window
keeps each live edge's FIRST arrival — a duplicate of a still-live edge is
ignored wherever its epoch sits (the unbounded path's simple-graph
precondition applied per window); an edge whose earlier arrival has expired
is genuinely new and lands in the current epoch. The oracle replays exactly
that rule."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.api import Plan, Resources, TriangleCounter, admit_session
from repro.core import streaming
from repro.graphs import generators as gen
from repro.serve.serve_loop import TriangleServer
from repro.serve.sessions import StreamMultiplexer


def windowed_oracle(n_nodes: int, epoch_edges: list, window: int) -> int:
    """From-scratch recount of the live window: replay the stream keeping
    each live edge's first arrival epoch, then brute-count triangles among
    the edges whose epoch is within the final ``window`` epochs."""
    arrival: dict = {}
    n_epochs = len(epoch_edges)
    for t, edges in enumerate(epoch_edges):
        for u, v in np.asarray(edges, dtype=np.int64).reshape(-1, 2):
            u, v = int(u), int(v)
            if u == v or u >= n_nodes or v >= n_nodes or u < 0 or v < 0:
                continue
            e = (min(u, v), max(u, v))
            if e in arrival and arrival[e] > t - window:
                continue  # duplicate of a still-live edge: first arrival wins
            arrival[e] = t
    live = {e for e, a in arrival.items() if a > n_epochs - 1 - window}
    adj: dict = {i: set() for i in range(n_nodes)}
    for u, v in live:
        adj[u].add(v)
        adj[v].add(u)
    return sum(len(adj[u] & adj[v]) for u, v in live) // 3


def _noisy_epochs(n, n_epochs, m, *, seed=0, dups=4, self_loops=2):
    """Random epoch edge arrays with duplicate/self-loop noise baked in
    (np.random integers already produce repeats; add explicit ones too)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_epochs):
        e = rng.integers(0, n, size=(m, 2)).astype(np.int32)
        if self_loops:
            loops = rng.integers(0, n, size=self_loops)
            e = np.concatenate([e, np.stack([loops, loops], axis=1).astype(np.int32)])
        if dups:
            e = np.concatenate([e, e[rng.integers(0, len(e), size=dups)]])
        out.append(e[rng.permutation(len(e))])
    return out


# --------------------------------------------------------------------------
# Differential: windowed fold vs the from-scratch recount oracle
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,window,n_epochs,m,seed", [
    (30, 3, 8, 40, 0),    # window slides well past its width
    (25, 2, 10, 60, 1),   # dense-ish, short window
    (40, 5, 12, 30, 2),   # long window, sparse epochs
    (20, 1, 6, 50, 3),    # width-1 window: only the current epoch lives
])
def test_windowed_matches_recount_oracle(n, window, n_epochs, m, seed):
    epochs = _noisy_epochs(n, n_epochs, m, seed=seed)
    want = windowed_oracle(n, epochs, window)
    got = streaming.count_windowed_stream(n, [[e] for e in epochs], window,
                                          block_size=16)
    assert got == want
    # kernel-routed phase sweeps are bit-identical too
    got_k = streaming.count_windowed_stream(n, [[e] for e in epochs], window,
                                            block_size=16, use_kernel=True)
    assert got_k == want


@pytest.mark.parametrize("n_stages", [2, 3, 5])
def test_sharded_window_matches_dense_window(n_stages):
    """Sharded-vs-dense window parity: the column-sharded epoch ring is the
    same count, term by term (psum before the //2, //3 divisions)."""
    epochs = _noisy_epochs(52, 9, 45, seed=7)
    want = windowed_oracle(52, epochs, 3)
    dense = streaming.count_windowed_stream(52, [[e] for e in epochs], 3,
                                            block_size=16)
    sharded = streaming.count_windowed_stream(52, [[e] for e in epochs], 3,
                                              block_size=16, n_stages=n_stages)
    assert dense == sharded == want


def test_window_covering_whole_stream_equals_unbounded():
    """A window at least as long as the stream deletes nothing: the windowed
    count must equal the unbounded streaming count."""
    g = gen.gnp(48, 0.4, seed=11)
    blocks = [g.edges[i:i + 16] for i in range(0, g.n_edges, 16)]
    want = streaming.count_stream(48, blocks, block_size=16)
    got = streaming.count_windowed_stream(
        48, [[b] for b in blocks], len(blocks), block_size=16)
    assert got == want


# --------------------------------------------------------------------------
# The windowed edge cases the satellite names
# --------------------------------------------------------------------------
def test_window_shorter_than_one_block():
    """Window of 1 epoch, whole epoch in one block: after every advance only
    the current epoch's edges live."""
    tri = np.array([[0, 1], [1, 2], [0, 2]], np.int32)
    other = np.array([[3, 4], [4, 5], [3, 5]], np.int32)
    # epoch 0: triangle 0-1-2; epoch 1: triangle 3-4-5 — with window=1 only
    # the second lives at the end
    got = streaming.count_windowed_stream(6, [[tri], [other]], 1)
    assert got == 1
    assert windowed_oracle(6, [tri, other], 1) == 1


def test_edge_reinserted_after_expiry():
    """An edge that expired and re-arrives is genuinely new: it lands in the
    current epoch and completes triangles again."""
    e01 = np.array([[0, 1]], np.int32)
    e12 = np.array([[1, 2]], np.int32)
    e02 = np.array([[0, 2]], np.int32)
    # window=2: epoch0 {0-1}, epoch1 {1-2}, epoch2 {0-2}: 0-1 expired -> no
    # triangle; epoch3 re-inserts {0-1} while {1-2} has expired -> still none
    epochs = [e01, e12, e02, e01]
    assert windowed_oracle(3, epochs, 2) == 0
    assert streaming.count_windowed_stream(3, [[e] for e in epochs], 2) == 0
    # but a window of 3 keeps all three edges live at epoch2 -> triangle
    # exists in the window ending there; at epoch3 the re-insert + live
    # {0-2} gives no triangle ({1-2} gone)
    assert windowed_oracle(3, epochs[:3], 3) == 1
    assert streaming.count_windowed_stream(3, [[e] for e in epochs[:3]], 3) == 1
    # re-insertion that COMPLETES a triangle again: all three re-arrive
    epochs = [e01, e12, e02, e01, e12, e02]
    assert windowed_oracle(3, epochs, 3) == 1
    assert streaming.count_windowed_stream(3, [[e] for e in epochs], 3) == 1


def test_duplicate_straddling_epoch_boundary_keeps_first_arrival():
    """The contract: a duplicate of a STILL-LIVE edge is ignored — the edge
    keeps its first-arrival epoch and expires with it, even if the duplicate
    arrived one epoch before the expiry."""
    e01 = np.array([[0, 1]], np.int32)
    e12 = np.array([[1, 2]], np.int32)
    e02 = np.array([[0, 2]], np.int32)
    empty = np.zeros((0, 2), np.int32)
    # window=2. epoch0: {0-1}; epoch1: {1-2} + DUPLICATE {0-1} (still live,
    # ignored); epoch2: {0-2}. 0-1's first arrival (epoch0) has left the
    # window -> NO triangle, even though its duplicate straddled into epoch1.
    epochs = [e01, np.concatenate([e12, e01]), e02]
    assert windowed_oracle(3, epochs, 2) == 0
    assert streaming.count_windowed_stream(3, [[e] for e in epochs], 2) == 0
    # the reversed orientation of a duplicate straddling the boundary is
    # still the same edge
    epochs = [e01, np.concatenate([e12, e01[:, ::-1]]), e02]
    assert streaming.count_windowed_stream(3, [[e] for e in epochs], 2) == 0
    # control: with window=3 nothing has expired and the triangle lives
    epochs = [e01, np.concatenate([e12, e01]), e02, empty]
    assert windowed_oracle(3, epochs[:3], 3) == 1
    assert streaming.count_windowed_stream(3, [[e] for e in epochs[:3]], 3) == 1


def test_empty_epochs_slide_the_window():
    """Epochs with no edges still advance the window: enough of them expire
    everything."""
    g = gen.gnp(30, 0.5, seed=5)
    full = [[g.edges]]
    silence = [[np.zeros((0, 2), np.int32)] for _ in range(3)]
    # window=3: the populated epoch is pushed out by three silent ones
    got = streaming.count_windowed_stream(30, full + silence, 3)
    assert got == 0
    # one silent epoch fewer: the populated epoch is still (just) live
    got = streaming.count_windowed_stream(30, full + silence[:2], 3)
    assert got == windowed_oracle(30, [g.edges] + [np.zeros((0, 2), np.int32)] * 2, 3)
    assert got > 0


def test_degenerate_windowed_streams():
    assert streaming.count_windowed_stream(10, [], 3) == 0
    assert streaming.count_windowed_stream(10, [[]], 3) == 0
    assert streaming.count_windowed_stream(
        10, [[np.array([[3, 3], [4, 4]], np.int32)]], 2) == 0
    with pytest.raises(ValueError, match="window_epochs"):
        streaming.init_windowed_state(10, 0)
    with pytest.raises(ValueError, match="window_epochs"):
        streaming.init_windowed_sharded_state(10, 0, 2)


def test_windowed_state_shapes_and_bytes():
    """State-size contract: E·n²/8 dense, E·n·ceil(W/S)·4 per stage shard."""
    st = streaming.init_windowed_state(1000, 4)
    w = -(-1000 // 32)
    assert st["epochs"].shape == (4, 1000, w)
    assert st["epochs"].nbytes == 4 * streaming.init_state(1000)["adj"].nbytes
    sh = streaming.init_windowed_sharded_state(1000, 4, 8)
    assert sh["epochs"].shape == (8, 4, 1000, -(-w // 8))
    assert sh["counts"].shape == (4,)


# --------------------------------------------------------------------------
# Trace contract: one ingest trace per block shape ACROSS epochs
# --------------------------------------------------------------------------
def test_windowed_one_trace_across_epochs():
    """Epoch advances rotate a traced head — they never retrace. n/block are
    unique to this test so the process-wide jit cache cannot hide a trace."""
    rng = np.random.default_rng(41)
    epochs = [[rng.integers(0, 111, size=(29, 2)).astype(np.int32)]
              for _ in range(9)]
    before = streaming.ingest_trace_count()
    got = streaming.count_windowed_stream(111, epochs, 4, block_size=29)
    assert streaming.ingest_trace_count() - before == 1
    assert got == windowed_oracle(111, [e[0] for e in epochs], 4)
    # the same shapes again: zero new traces
    before = streaming.ingest_trace_count()
    streaming.count_windowed_stream(111, epochs, 4, block_size=29)
    assert streaming.ingest_trace_count() - before == 0


def test_ragged_epoch_tails_share_sticky_shape():
    """Regression: epochs smaller than one block flush pow2-padded tails at
    every advance; the tail shape must be STICKY (grow-only) so similar-size
    ragged epochs reuse one trace instead of one per distinct pow2."""
    rng = np.random.default_rng(47)
    sizes = [5, 20, 9, 14, 6]  # naive pow2s: 8, 32, 16, 16, 8 -> sticky: 8, 32×4
    epochs = [[rng.integers(0, 109, size=(m, 2)).astype(np.int32)]
              for m in sizes]
    before = streaming.ingest_trace_count()
    got = streaming.count_windowed_stream(109, epochs, 3, block_size=4096)
    assert got == windowed_oracle(109, [e[0] for e in epochs], 3)
    # shapes seen: 8 (first tail) and 32 (sticky once grown) — never 16
    assert streaming.ingest_trace_count() - before == 2


def test_windowed_sharded_one_trace_across_epochs():
    rng = np.random.default_rng(43)
    epochs = [[rng.integers(0, 113, size=(31, 2)).astype(np.int32)]
              for _ in range(7)]
    before = streaming.ingest_trace_count()
    got = streaming.count_windowed_stream(113, epochs, 3, block_size=31,
                                          n_stages=3)
    assert streaming.ingest_trace_count() - before == 1
    assert got == windowed_oracle(113, [e[0] for e in epochs], 3)


# --------------------------------------------------------------------------
# API layer: count_windowed / StreamSession window mode
# --------------------------------------------------------------------------
def test_count_windowed_matches_oracle_and_carries_stats():
    epochs = _noisy_epochs(35, 7, 40, seed=13)
    want = windowed_oracle(35, epochs, 3)
    res = TriangleCounter().count_windowed(35, [[e] for e in epochs],
                                           window=3, block_size=16)
    assert res.item() == want
    assert res.plan.method == "stream" and res.plan.window_epochs == 3
    assert res.stats["window_epochs"] == 3
    assert res.stats["epochs_advanced"] == 6
    assert res.stats["cache"]["key"][0] == res.plan.cache_key()


def test_session_window_mode_feed_advance_finalize():
    epochs = _noisy_epochs(40, 6, 30, seed=17)
    s = TriangleCounter().open_stream(40, window=2, block_size=16)
    assert s.plan.window_epochs == 2
    for t, e in enumerate(epochs):
        if t:
            s.advance()
        s.feed(e)
    res = s.finalize()
    assert res.item() == windowed_oracle(40, epochs, 2)
    # idempotent finalize; feed/advance after close raise
    assert s.finalize() is res
    with pytest.raises(RuntimeError, match="finalized"):
        s.feed(epochs[0])
    with pytest.raises(RuntimeError, match="finalized"):
        s.advance()


def test_advance_requires_windowed_session():
    s = TriangleCounter().open_stream(20)
    with pytest.raises(RuntimeError, match="windowed"):
        s.advance()


def test_count_windowed_requires_window():
    c = TriangleCounter()
    with pytest.raises(ValueError, match="window"):
        c.count_windowed(20, [[np.array([[0, 1]], np.int32)]])
    # ...and validates BEFORE allocating session state / a cache entry
    assert len(c._cache) == 0
    # an unbounded stream plan is rejected the same way (window=0 == none)
    with pytest.raises(ValueError, match="window"):
        c.count_windowed(20, [[np.array([[0, 1]], np.int32)]],
                         plan=Plan(method="stream"), window=0)
    assert len(c._cache) == 0


def test_negative_window_rejected_at_planning():
    from repro.api import GraphStats, plan, stream_sizing

    stats = GraphStats(n_nodes=100, n_edges=0, replication_factor=0,
                       max_degree=0, max_fwd_degree=0, edges_in_memory=False)
    with pytest.raises(ValueError, match="window_epochs"):
        plan(stats, window_epochs=-5)
    with pytest.raises(ValueError, match="window_epochs"):
        stream_sizing(stats, Resources(), window_epochs=-5)
    with pytest.raises(ValueError, match="window_epochs"):
        admit_session(100, Resources(), window_epochs=-5)


def test_open_stream_window_plan_conflict_raises():
    c = TriangleCounter()
    with pytest.raises(ValueError, match="window"):
        c.open_stream(20, plan=Plan(method="stream", window_epochs=2), window=3)
    # agreeing values are fine
    s = c.open_stream(20, plan=Plan(method="stream", window_epochs=2,
                                    block_size=8), window=2)
    assert s.plan.window_epochs == 2


def test_windowed_plan_cache_key_distinct_from_unbounded():
    """A windowed and an unbounded stream plan must not share a compile-cache
    entry (their ingest jits differ)."""
    assert Plan(method="stream").cache_key() != \
        Plan(method="stream", window_epochs=4).cache_key()
    assert Plan.from_dict(Plan(method="stream", window_epochs=4).to_dict()) \
        == Plan(method="stream", window_epochs=4)


def test_sharded_session_window_parity():
    epochs = _noisy_epochs(45, 8, 35, seed=19)
    want = windowed_oracle(45, epochs, 3)
    p = Plan(method="stream", n_stages=3, block_size=16, window_epochs=3)
    res = TriangleCounter(plan=p).count_windowed(45, [[e] for e in epochs])
    assert res.item() == want
    assert res.stats["sharded"] is True and res.stats["window_epochs"] == 3


# --------------------------------------------------------------------------
# Planner: window-aware sizing and admission
# --------------------------------------------------------------------------
def test_windowed_admission_charges_e_times_state():
    res = Resources(memory_bytes=20480)
    dense = admit_session(256, res)
    win = admit_session(256, res, window_epochs=2)
    assert dense.state_bytes == 8192
    assert win.state_bytes == 2 * dense.state_bytes
    assert win.action == "admit-dense" and win.plan.window_epochs == 2
    # E=4 exceeds the budget entirely -> queue
    assert admit_session(256, res, window_epochs=4).action == "queue"
    # windowed state counts against bytes_in_use like any other
    assert admit_session(256, res, bytes_in_use=win.state_bytes,
                         window_epochs=2).action == "queue"


def test_windowed_admission_shards_when_the_ring_helps():
    # 4 epochs × 1.25 GB on 8 × 1 GB devices: only a column shard fits
    adm = admit_session(100_000, Resources(n_devices=8, memory_bytes=1 << 30),
                        window_epochs=4)
    assert adm.action == "admit-sharded"
    assert adm.plan.n_stages > 1 and adm.plan.window_epochs == 4
    assert adm.state_bytes <= 1 << 30


def test_plan_rejects_window_for_resident_stats():
    from repro.api import GraphStats, plan

    stats = GraphStats(n_nodes=100, n_edges=200, replication_factor=10,
                       max_degree=5, max_fwd_degree=3)
    with pytest.raises(ValueError, match="window"):
        plan(stats, window_epochs=3)


# --------------------------------------------------------------------------
# Serving: windowed and unbounded sessions on one multiplexer
# --------------------------------------------------------------------------
def test_windowed_and_unbounded_sessions_multiplex():
    """Interleave a windowed and an unbounded session over one server: both
    bit-match their oracles, and the windowed result is independent of the
    neighbour sessions."""
    n = 40
    epochs = _noisy_epochs(n, 6, 30, seed=23)
    g = gen.gnp(n, 0.4, seed=23)
    g_blocks = [g.edges[i:i + 16] for i in range(0, g.n_edges, 16)]
    server = TriangleServer()
    sid_w = server.open_stream(n, window=3, block_size=16)
    sid_u = server.open_stream(n, block_size=16)
    for t, e in enumerate(epochs):
        if t:
            server.advance_stream(sid_w)
        server.feed(sid_w, e)
        if t < len(g_blocks):
            server.feed(sid_u, g_blocks[t])
    for t in range(len(epochs), len(g_blocks)):
        server.feed(sid_u, g_blocks[t])
    rw = server.close_stream(sid_w)
    ru = server.close_stream(sid_u)
    assert rw.item() == windowed_oracle(n, epochs, 3)
    assert ru.item() == streaming.count_stream(n, g_blocks, block_size=16)
    assert rw.stats["window_epochs"] == 3 and "window_epochs" not in ru.stats


def test_queued_windowed_session_replays_epoch_boundaries():
    """A windowed request that queues buffers its feeds AND its epoch
    markers; the replay on admission is bit-identical to an immediate
    admission."""
    res = Resources(memory_bytes=20480)  # two 8 KB unbounded sessions fit
    mux = StreamMultiplexer(TriangleCounter(res), block_size=16)
    blockers = [mux.open(256), mux.open(256)]  # pin the whole budget
    epochs = _noisy_epochs(128, 5, 30, seed=29)
    w = mux.open(128, window=3)  # 3 × 128·4·4 B = 6 KB: fits idle, not the
    assert mux.status(w) == "queued"  # 4 KB remaining right now
    for t, e in enumerate(epochs):
        if t:
            mux.advance(w)
        mux.feed(w, e)
    assert mux.status(w) == "queued"
    mux.close(blockers[0])  # frees budget -> FIFO replay incl. markers
    assert mux.status(w) == "active"
    got = mux.close(w)
    assert got.item() == windowed_oracle(128, epochs, 3)
    assert got.stats["epochs_advanced"] == len(epochs) - 1
    mux.close(blockers[1])
    # advance on an unbounded queued/closed session raises
    with pytest.raises(RuntimeError, match="closed"):
        mux.advance(w)
    with pytest.raises(KeyError, match="unknown"):
        mux.advance(999)


def test_windowed_admission_on_mux_charges_ring_state():
    """open(window=E) must charge E·n²/8 — a window that can never fit is
    rejected at open like any other hopeless stream."""
    res = Resources(memory_bytes=20480)
    mux = StreamMultiplexer(TriangleCounter(res), block_size=16)
    assert mux.open(256, window=2) is not None  # 16 KB: fits
    with pytest.raises(ValueError, match="never"):
        mux.open(256, window=4)  # 32 KB: never fits a 20 KB budget


# --------------------------------------------------------------------------
# Mesh-sharded windows (subprocess, 8 forced host devices)
# --------------------------------------------------------------------------
MESH_WINDOW_SNIPPET = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro.core import streaming
    from repro.launch.mesh import make_ring_mesh
    from tests.test_windowed_stream import _noisy_epochs, windowed_oracle

    n, window = 200, 3
    epochs = _noisy_epochs(n, 7, 250, seed=31)
    want = windowed_oracle(n, epochs, window)
    mesh = make_ring_mesh(8)
    got = streaming.count_windowed_stream(
        n, [[e] for e in epochs], window, block_size=128, n_stages=8,
        mesh=mesh)
    assert got == want, (got, want)
    emu = streaming.count_windowed_stream(
        n, [[e] for e in epochs], window, block_size=128, n_stages=8)
    assert emu == want, (emu, want)
    print("MESH_WINDOW_OK", want)
    """
)


@pytest.mark.slow
def test_windowed_sharded_on_eight_devices_subprocess():
    env = dict(os.environ)
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + root
    r = subprocess.run([sys.executable, "-c", MESH_WINDOW_SNIPPET], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr
    assert "MESH_WINDOW_OK" in r.stdout
