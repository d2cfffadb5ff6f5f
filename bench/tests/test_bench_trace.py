"""The trace reduction (bench/trace.py): union, idle share and the
attribution of idle gaps to host spans, on a hand-built event list and on
a small trace recorded on the CPU.

``data/cpu_count3.xplane.pb`` was recorded on the CPU backend with
``jax.profiler.start_trace``: inside one ``bench.window`` span, three
``bench.count`` spans each run a jitted 384 x 384 matmul and sum, each
followed by a ``bench.plan`` span that sleeps 20 ms.
"""
import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{name}", os.path.join(HERE, "..", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


trace = _load("trace")
E = trace.Event


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    merged = trace.union([(0, 2), (4, 6)])
    assert trace.covered(merged, 1, 5) == 2
    assert trace.gaps(merged, 0, 10) == [(2, 4), (6, 10)]
    assert trace.gaps(merged, 1, 5) == [(2, 4)]


def _hand_built():
    ops = {"TPU:0": [E("a", 10, 30, "jit_m"), E("b", 25, 40, "jit_m"),
                     E("c", 60, 70, "jit_k")],
           "TPU:1": [E("a", 10, 50, "jit_m")]}
    runs = {"TPU:0": [E("jit_m", 10, 40), E("jit_k", 60, 70)],
            "TPU:1": [E("jit_m", 10, 50)]}
    spans = [E("bench.window", 0, 100), E("bench.feed", 0, 55),
             E("bench.close", 55, 100), E("bench.open", 80, 90)]
    return trace.Trace(ops, runs, spans, (0, 100))


def test_busy_idle_and_spans_are_means_over_chips():
    tr = _hand_built()
    assert tr.window_s() == pytest.approx(100e-9)
    # chip 0 busy 30 + 10 = 40 ns, chip 1 busy 40 ns
    assert tr.busy_s() == pytest.approx(40e-9)
    assert tr.idle_pct() == pytest.approx(60.0)
    (feed,) = tr.spans_named("bench.feed")
    assert tr.busy_in(feed) == pytest.approx((30 + 40) / 2 * 1e-9)
    runs = tr.module_runs("jit_m")
    assert [len(v) for v in runs.values()] == [1, 1]


def test_idle_gaps_go_to_the_innermost_span():
    tr = _hand_built()
    got = dict(tr.idle_by_span())
    # chip 0 gaps: 0-10 (feed), 40-60 (mid 50: feed), 70-100 (mid 85: open)
    # chip 1 gaps: 0-10 (feed), 50-100 (mid 75: close)
    assert got["bench.feed"] == pytest.approx((10 + 20 + 10) / 2 * 1e-9)
    assert got["bench.open"] == pytest.approx(30 / 2 * 1e-9)
    assert got["bench.close"] == pytest.approx(50 / 2 * 1e-9)
    top = tr.top_ops(2)
    assert top[0][0] == "jit_m/a"


def test_recorded_cpu_trace():
    tr = trace.load(os.path.join(HERE, "data", "cpu_count3.xplane.pb"))
    assert tr.devices == ["CPU:0"]
    counts = tr.spans_named("bench.count")
    assert len(counts) == 3
    assert all(tr.busy_in(s) > 0 for s in counts)
    assert 0 < tr.busy_s() < tr.window_s()
    assert 0 < tr.idle_pct() < 100
    labels = dict(tr.idle_by_span())
    # the sleeps inside bench.plan are most of the idle time
    assert max(labels, key=labels.get) == "bench.plan"
    assert set(labels) <= {"bench.plan", "bench.count", trace.OUTSIDE}
    assert tr.top_ops(1)[0][0].startswith("jit__lambda/")


def test_names_are_shortened():
    assert trace.module_name("jit__ingest_block_impl(1109305)") == "jit__ingest_block_impl"
    assert trace.op_name("%fusion.545 = s32[4]{0} fusion(%copy.532), kind=kLoop") == "fusion.545"


def test_a_directory_without_a_trace_is_refused(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace.load(str(tmp_path))
