"""The in-place state write: each block's bits are added into the (donated)
state as one row scatter, and D is recovered from the written rows.

Blocks here are built to collide: a hub star whose 31 edges all write row 0,
a clique inside one block, repeats (same and reversed, within and across
blocks), self-loops, phantoms, and edges on the last row n-1 (the row that
dead edges' clipped gathers read). After every block the count and the
adjacency bitset must equal the per-edge oracle's bit for bit, and a window's
count and epoch slots must equal a from-scratch recount of the live window.
Each stream runs at n = 70, where a table is written in whole rows, and
relabelled into n = 16,384, where every layout's table is whole (8, 128)
tiles and is written in 128-word windows. The mesh runs in a subprocess on
four forced host devices, where the step must also delete its input state
buffer: that pins the in-place write."""
import collections
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import streaming

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
N, B, WINDOW = 70, 32, 2  # W = 3 words: stage shards carry pad words
WIDE = 16_384  # W = 512: tiled whole and in 2 and 4 stage shards
SIZES = (N, WIDE)


def _block(edges, rng):
    """Pad ``edges`` to B rows with random edges (which may repeat)."""
    edges = list(edges)
    while len(edges) < B:
        u, v = rng.integers(0, N, size=2)
        edges.append((int(u), int(v)))
    assert len(edges) == B
    return np.asarray(edges, np.int32)


def _colliding_blocks(n: int = N) -> list[np.ndarray]:
    rng = np.random.default_rng(15)
    star = [(0, v) for v in range(1, 32)] + [(N, 3)]
    cv = [40, 41, 42, 43, 44, 45, 46, N - 1]
    clique = [(a, b) for i, a in enumerate(cv) for b in cv[i + 1:]]
    within = clique + [(41, 40), (N - 1, 45), (7, 7), (N, N)]
    closing = [(v, v + 1) for v in range(1, 31)] + [(5, 0), (0, 5)]
    mixed = ([(0, v) for v in cv] + [(v, N - 1) for v in range(1, 9)]
             + [(N - 1, 1), (3, 3), (N - 1, N - 1), (N, 0), (2, N + 5)])
    blocks = [_block(star, rng), _block(within, rng), _block(closing, rng),
              _block(mixed, rng), _block([], rng)]
    if n == N:
        return blocks
    # spread the vertices over every 128-word window of a WIDE row
    return [np.where(b < N, b * 233, b - N + n).astype(np.int32) for b in blocks]


def _epochs(n: int):
    """Three epochs: the star comes back after its epoch has left the
    window, so its edges are new again in the last one."""
    b = _colliding_blocks(n)
    return [[b[0], b[1]], [b[2]], [b[3], b[0], b[4]]]


def _bits(n: int, edges) -> np.ndarray:
    out = np.zeros((n, -(-n // 32)), np.uint32)
    for u, v in edges:
        out[u, v // 32] |= np.uint32(1 << (v % 32))
        out[v, u // 32] |= np.uint32(1 << (u % 32))
    return out


def _recount(edges) -> int:
    nbr = collections.defaultdict(set)
    for u, v in edges:
        nbr[u].add(v)
        nbr[v].add(u)
    return sum(len(nbr[u] & nbr[v]) for u, v in edges) // 3


def _unshard(adj: np.ndarray) -> np.ndarray:
    """(S, n, Ws) column shards -> (n, W), asserting the pad words stay 0."""
    s, n, ws = adj.shape
    full = adj.transpose(1, 0, 2).reshape(n, s * ws)
    w = -(-n // 32)
    assert not full[:, w:].any(), "a pad word was written"
    return full[:, :w]


def _oracle_states(n: int, blocks):
    """The per-edge oracle's (count, bitset) after each block."""
    state, out = streaming.init_state(n), []
    for b in blocks:
        state = streaming.ingest_block_per_edge(state, jnp.asarray(b))
        out.append((int(state["count"]), np.asarray(state["adj"])))
    return out


MESH_SNIPPET = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import streaming
    from repro.launch.mesh import make_ring_mesh

    mesh = make_ring_mesh(4)
    step = streaming.make_mesh_ingest(mesh)
    deleted = {}
    for path, n in zip(sys.argv[1::2], sys.argv[2::2]):
        blocks, n = list(np.load(path)["blocks"]), int(n)
        state = streaming.init_sharded_state(n, 4, mesh=mesh)
        counts, adjs = [], []
        for b in blocks:
            old = state["adj"]
            state = step(state, jnp.asarray(b))
            jax.block_until_ready(state)
            deleted.setdefault("mesh", old.is_deleted())
            counts.append(int(state["count"]))
            adjs.append(np.asarray(state["adj"]))
        np.savez(path + ".out.npz", counts=np.asarray(counts), adjs=np.stack(adjs))
    wstep = streaming.make_mesh_ingest_windowed(mesh)
    wstate = streaming.init_windowed_sharded_state(n, 2, 4, mesh=mesh)
    old = wstate["epochs"]
    wstate = wstep(wstate, jnp.asarray(blocks[0]))
    jax.block_until_ready(wstate)
    deleted["mesh_windowed"] = old.is_deleted()
    print("RESULT " + json.dumps(deleted))
    """
)


@pytest.fixture(scope="module")
def mesh_run(tmp_path_factory):
    """One subprocess on four forced host devices: the mesh ingest's count
    and shards after every colliding block, and whether each mesh step
    deleted its input state buffer."""
    tmp, args = tmp_path_factory.mktemp("mesh"), []
    for n in SIZES:
        path = str(tmp / f"blocks{n}.npz")
        np.savez(path, blocks=np.stack(_colliding_blocks(n)))
        args += [path, str(n)]
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", MESH_SNIPPET, *args],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    (line,) = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    runs = {}
    for path, n in zip(args[::2], SIZES):
        out = np.load(path + ".out.npz")
        runs[n] = [(int(c), _unshard(a)) for c, a in zip(out["counts"], out["adjs"])]
    return runs, json.loads(line[len("RESULT "):])


def _run_unbounded(layout, n, mesh_run):
    blocks = _colliding_blocks(n)
    if layout == "mesh":
        return mesh_run[0][n]
    if layout == "dense":
        state, step = streaming.init_state(n), streaming.ingest_block_donated
    else:
        state = streaming.init_sharded_state(n, 2)
        step = streaming.ingest_block_sharded_donated
    out = []
    for b in blocks:
        state = step(state, jnp.asarray(b))
        adj = np.asarray(state["adj"])
        out.append((int(state["count"]), adj if adj.ndim == 2 else _unshard(adj)))
    return out


def _check_window(n):
    """Feed ``_epochs`` through the windowed ingest; after every block the
    window count and each epoch slot's bits must match the live window
    replayed from scratch (a live edge keeps its first arrival)."""
    state = streaming.init_windowed_state(n, WINDOW)
    arrival: dict = {}
    for t, epoch in enumerate(_epochs(n)):
        if t:
            state = streaming.expire_epoch(state)
        for b in epoch:
            state = streaming.ingest_block_windowed_donated(state, jnp.asarray(b))
            for u, v in b.tolist():
                if u == v or max(u, v) >= n:
                    continue
                e = (min(u, v), max(u, v))
                if e not in arrival or arrival[e] <= t - WINDOW:
                    arrival[e] = t
            live = {e: a for e, a in arrival.items() if a > t - WINDOW}
            assert int(streaming.window_count(state)) == _recount(live)
            slots = np.asarray(state["epochs"])
            for age in range(WINDOW):
                want = _bits(n, [e for e, a in live.items() if a == t - age])
                np.testing.assert_array_equal(slots[(t - age) % WINDOW], want)


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("layout", ["dense", "sharded", "windowed", "mesh"])
def test_in_place_write_matches_the_per_edge_oracle(layout, n, request):
    if layout == "windowed":
        _check_window(n)
        return
    mesh_run = request.getfixturevalue("mesh_run") if layout == "mesh" else None
    got = _run_unbounded(layout, n, mesh_run)
    want = _oracle_states(n, _colliding_blocks(n))
    assert [c for c, _ in got] == [c for c, _ in want]
    for (_, a), (_, b) in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert want[-1][0] > 0


def test_mesh_steps_delete_their_input_state(mesh_run):
    """Both mesh steps donate the state: after a block the input shard's
    buffer is gone, so the shard was written in place, not copied."""
    assert mesh_run[1] == {"mesh": True, "mesh_windowed": True}
