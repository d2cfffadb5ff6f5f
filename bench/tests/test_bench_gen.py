"""The data laws: G(n, m) has exactly m distinct edges; the Kronecker
generator's quadrant frequencies follow its initiator."""
import importlib.util
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_gen_{name}", os.path.join(HERE, "..", "gen", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


gnm = _load("gnm")
rmat = _load("rmat")


@pytest.mark.parametrize("n, m", [(200, 3_000), (1_000, 250_000)])
def test_gnm_has_exactly_m_distinct_edges(n, m):
    g = gnm.draw({"n_nodes": n, "n_edges": m}, 2**31 + 5, n_streams=2)
    assert g.shape == (2, 1, m, 2)
    for e in g[:, 0]:
        assert (e[:, 0] < e[:, 1]).all() and e.min() >= 0 and e.max() < n
        assert len(np.unique(e[:, 0].astype(np.int64) * n + e[:, 1])) == m
    assert not np.array_equal(g[0, 0], g[1, 0])
    again = gnm.draw({"n_nodes": n, "n_edges": m}, 2**31 + 5, n_streams=2)
    assert np.array_equal(g, again)


def test_gnm_degrees_are_uniform():
    n, m = 400, 20_000
    e = gnm.draw({"n_nodes": n, "n_edges": m}, 7)[0, 0]
    deg = np.bincount(e.ravel(), minlength=n)
    assert abs(deg.mean() - 2 * m / n) < 1e-9
    assert deg.std() < 4 * np.sqrt(2 * m / n)  # binomial spread, no hubs


def test_rmat_quadrant_frequencies_follow_the_initiator():
    import jax

    a, b, c = 0.57, 0.19, 0.19
    scale, k = 8, 20_000
    u, v = rmat.kronecker_bits(jax.random.key(3), np.float32(a), np.float32(b),
                               np.float32(c), scale=scale, shape=(1, 1, k))
    u, v = np.asarray(u).ravel(), np.asarray(v).ravel()
    levels = np.arange(scale)
    ub = (u[:, None] >> levels) & 1
    vb = (v[:, None] >> levels) & 1
    freq = {q: float(((ub == q[0]) & (vb == q[1])).mean())
            for q in [(0, 0), (0, 1), (1, 0), (1, 1)]}
    tol = 0.01  # about 9 standard deviations at 160,000 draws
    assert freq[(0, 0)] == pytest.approx(a, abs=tol)
    assert freq[(0, 1)] == pytest.approx(b, abs=tol)
    assert freq[(1, 0)] == pytest.approx(c, abs=tol)
    assert freq[(1, 1)] == pytest.approx(1 - a - b - c, abs=tol)


def test_rmat_draw_shape_range_and_seed():
    cfg = {"scale": 10, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19}
    assert rmat.n_nodes(cfg) == 1024 and rmat.tuples_per_graph(cfg) == 16_384
    x = rmat.draw(cfg, 2**33 + 1, n_streams=2, parts=3, tuples=500)
    assert x.shape == (2, 3, 500, 2) and x.dtype == np.int32
    assert x.min() >= 0 and x.max() < 1024
    assert np.array_equal(x, rmat.draw(cfg, 2**33 + 1, n_streams=2, parts=3, tuples=500))
    assert not np.array_equal(x, rmat.draw(cfg, 2**33 + 2, n_streams=2, parts=3, tuples=500))
