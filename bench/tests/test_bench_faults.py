"""A run with the timed path broken underneath comes out not correct: the
harness's comparison catches each fault a cell can have. (No cell runs on
several chips, so the fault of a missing exchange between chips has no
cell here.)"""
import numpy as np
import pytest

from cells import tiny_run

PHANTOM = 2**30  # a vertex id past every n: the ingest drops such rows


def _state_unchanged(monkeypatch):
    from repro.api.counter import TriangleCounter

    monkeypatch.setattr(TriangleCounter, "_make_stream",
                        lambda self, entry, p, on_mesh: (lambda state, block: state))


def _half_left_out(monkeypatch):
    from repro.api.counter import TriangleCounter
    from repro.graphs.formats import Graph

    make, count = TriangleCounter._make_stream, TriangleCounter.count

    def make_half(self, entry, p, on_mesh):
        fn = make(self, entry, p, on_mesh)

        def half(state, block):
            b = np.array(block)
            b[len(b) // 2:] = PHANTOM
            return fn(state, b)
        return half

    def count_half(self, g, *, plan=None):
        return count(self, Graph(edges=g.edges[: g.n_edges // 2], n_nodes=g.n_nodes), plan=plan)

    monkeypatch.setattr(TriangleCounter, "_make_stream", make_half)
    monkeypatch.setattr(TriangleCounter, "count", count_half)


def _answer_altered(monkeypatch):
    from repro.api.counter import CountResult

    item = CountResult.item
    monkeypatch.setattr(CountResult, "item", lambda self: item(self) + 1)


FAULTS = {"state_unchanged": _state_unchanged, "half_left_out": _half_left_out,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("cell, fault", [
    ("fna1-count", "half_left_out"),
    ("fna1-count", "answer_altered"),
    ("s16-tenants8", "state_unchanged"),
    ("s16-tenants8", "half_left_out"),
    ("s16-tenants8", "answer_altered"),
    ("s16-window4", "state_unchanged"),
    ("s16-window4", "half_left_out"),
    ("s16-window4", "answer_altered"),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, cell, fault):
    FAULTS[fault](monkeypatch)
    r = tiny_run(monkeypatch, cell, seed=2**31 + 29)
    assert r["correct"] is False
    assert r["checks"]["count_error_max"]["value"] > r["checks"]["count_error_max"]["limit"]
