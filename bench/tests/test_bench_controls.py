"""Each cell's control, the reference put in the program's place with one
of the configuration's guarantees broken, comes out not correct, at a size
a test run holds: ``float32`` (the count accumulated below its exact
integer precision) for the resident cell, ``multigraph`` (a stream's repeats
counted as parallel edges) for the stream cells."""
import numpy as np
import pytest

from cells import load_run

run = load_run()
ref = run.bench_file("reference")
gnm = run.load_module("gen", "gnm")
rmat = run.load_module("gen", "rmat")
RMAT = {"scale": 9, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19}


def _not_correct(n, items, control):
    answers = [(k, ref.exact(n, items[k])) for k in items]
    assert run.passed(run.check(n, answers, items)["checks"])
    got = run.check(n, answers, items, control=control)["checks"]
    assert not run.passed(got)
    return got["count_error_max"]["value"]


def test_float32_control_of_a_dense_gnm_graph():
    # n = 1,500 at density 1/2: about 7e7 triangles, past float32's 2**24
    n, m = 1_500, 562_000
    g = gnm.draw({"n_nodes": n, "n_edges": m}, 2**31 + 41)[0, 0]
    assert _not_correct(n, {0: {"graph": g}}, "float32") > 0


def test_multigraph_control_of_kronecker_sessions():
    pool = rmat.draw(RMAT, 2**31 + 43, n_streams=3, parts=1, tuples=8_192)[:, 0]
    assert _not_correct(512, {i: {"graph": g} for i, g in enumerate(pool)}, "multigraph") > 0


def test_multigraph_control_of_a_kronecker_window():
    epochs = list(rmat.draw(RMAT, 2**31 + 47, n_streams=1, parts=7, tuples=2_048)[0])
    epochs[-1] = epochs[-1][:1_000]  # the window's last epoch cut short
    assert _not_correct(512, {"w": {"epochs": epochs, "window": 4}}, "multigraph") > 0


def test_window_reference_follows_first_arrival():
    # edge (0, 1) arrives in epoch 0, repeats in epoch 2 while live (ignored),
    # and expires after epoch 3 with its first arrival; (1, 2), (0, 2) stay
    e = [np.array([[0, 1]]), np.array([[1, 2]]), np.array([[1, 0]]),
         np.array([[0, 2]]), np.array([[2, 2]])]
    lo, hi = ref.window_pairs(5, e, 4)
    assert sorted(zip(lo.tolist(), hi.tolist())) == [(0, 2), (1, 2)]
    assert ref.window_triangles(5, e[:4], 4) == 1
    assert ref.window_triangles(5, e, 4) == 0


def test_reference_counts_in_64_bit_mode():
    # the resident configuration runs with jax_enable_x64 on
    import jax

    n, m = 300, 5_000
    g = gnm.draw({"n_nodes": n, "n_edges": m}, 2**31 + 53)[0, 0]
    want = ref.triangles(n, g)
    with jax.enable_x64(True):
        assert ref.triangles(n, g) == want
        assert _not_correct(1_500, {0: {"graph": gnm.draw(
            {"n_nodes": 1_500, "n_edges": 562_000}, 2**31 + 41)[0, 0]}}, "float32") > 0


def test_bitset_reference_agrees_with_dense(monkeypatch):
    # past _DENSE_MAX vertices the reference counts by forward lists instead
    pool = rmat.draw(RMAT, 2**31 + 59, n_streams=2, parts=1, tuples=8_192)[:, 0]
    dense = [ref.triangles(512, g) for g in pool]
    monkeypatch.setattr(ref, "_DENSE_MAX", 0)
    assert [ref.triangles(512, g) for g in pool] == dense
    assert all(ref.triangles_float32(512, g) == d for g, d in zip(pool, dense))


def _brute(n, lo, hi, weight):
    """Sum over triangles i < j < l of w_ij * w_jl * w_il, from the dense
    upper adjacency in float64 (exact below 2**53)."""
    u = np.zeros((n, n))
    np.add.at(u, (lo, hi), weight)
    return int(((u @ u) * u).sum())


def _clique(n):
    return np.stack(np.triu_indices(n, 1), axis=1)


def _star_with_rim(n):
    # a hub joined to every other vertex, and a path through them: n - 2
    # triangles, each at the hub, whose degree puts it last
    return np.concatenate([np.stack([np.zeros(n - 1, int), np.arange(1, n)], 1),
                           np.stack([np.arange(1, n - 1), np.arange(2, n)], 1)])


GRAPHS = {
    "gnm": lambda: (300, gnm.draw({"n_nodes": 300, "n_edges": 5_000}, 2**31 + 61)[0, 0]),
    "rmat9": lambda: (512, rmat.draw(RMAT, 2**31 + 67, n_streams=1, parts=1, tuples=8_192)[0, 0]),
    "rmat10": lambda: (1_024, rmat.draw({**RMAT, "scale": 10}, 2**31 + 71, n_streams=1,
                                        parts=1, tuples=16_384)[0, 0]),
    "rmat11": lambda: (2_048, rmat.draw({**RMAT, "scale": 11}, 2**31 + 73, n_streams=1,
                                        parts=1, tuples=32_768)[0, 0]),
    "star": lambda: (200, _star_with_rim(200)),
    "k40": lambda: (40, _clique(40)),
    "empty": lambda: (10, np.zeros((0, 2), np.int64)),
    "isolated": lambda: (10, np.stack([np.arange(10), np.arange(10)], 1)),
    # N+ lengths 0..129 cross every width up to 128 and the step to 256
    "width_steps": lambda: (130, _clique(130)),
}


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_forward_reference_agrees_with_dense_and_brute_force(monkeypatch, name):
    n, g = GRAPHS[name]()
    dense = ref.triangles(n, g)
    lo, hi = ref.simple_pairs(n, g)
    assert dense == _brute(n, lo, hi, np.ones(len(lo)))
    monkeypatch.setattr(ref, "_DENSE_MAX", 0)
    assert ref.triangles(n, g) == dense


def test_forward_reference_weights_each_triangle(monkeypatch):
    # the multigraph control: a triangle counts w_ab * w_ac * w_bc
    g = rmat.draw(RMAT, 2**31 + 79, n_streams=1, parts=1, tuples=8_192)[0, 0]
    lo, hi, mult = ref.multi_pairs(512, g)
    assert mult.max() > 1
    want = _brute(512, lo, hi, mult)
    assert ref.triangles_multigraph(512, g) == want
    monkeypatch.setattr(ref, "_DENSE_MAX", 0)
    assert ref.triangles_multigraph(512, g) == want


def test_forward_float32_control_is_wrong_past_2_24(monkeypatch):
    n = 470  # K_470: 17,193,540 triangles, past 2**24
    monkeypatch.setattr(ref, "_DENSE_MAX", 0)
    exact = ref.triangles(n, _clique(n))
    assert exact == n * (n - 1) * (n - 2) // 6
    assert ref.triangles_float32(n, _clique(n)) != exact
