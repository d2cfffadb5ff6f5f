"""The trace reduction with the program's spans and scopes
(bench/program_trace.py): the XSpace metadata reader, the per-scope split
of the ingest runs, the ``repro.*`` spans, and that a trace without any of
them reduces exactly as bench/trace.py reduces it.

``data/tpu_ingest_n4096.xplane.pb`` was recorded on one TPU v5e: inside a
``bench.window`` span, a dense stream session (n = 4,096, three blocks of
1,024 tuples) and a windowed one (two epochs) were fed and finalized.
"""
import importlib.util
import os
import sys

import pytest

from cells import TINY, load_run

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP_TRACE = os.path.join(HERE, "data", "tpu_ingest_n4096.xplane.pb")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{name}", os.path.join(HERE, "..", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


pt = _load("program_trace")
base = pt.base
E = pt.Event


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(_ingest_block_impl)/ingest.live/jit(lexsort)/sort:", "ingest.live"),
    ("jit(ingest_block_mesh)/shard_map/ingest.terms/psum:", "ingest.terms"),
    ("jit(_ingest_block_windowed_sharded_impl)/vmap(ingest.age_cum)/or:", "ingest.age_cum"),
    ("jit(f)/ingest.live/ingest.update/add:", "ingest.update"),
    ("jit(_ingest_block_impl)/select_n:", ""),
    ("", ""),
])
def test_scope_of_a_tf_op(tf_op, scope):
    assert pt.scope_of(tf_op) == scope


def test_ops_without_metadata_take_their_consumers_phase():
    ops = {(7, "%a = u32[4] iota()"): "jit(f)/ingest.live/iota:",
           (7, "%zero = u32[8] broadcast(u32[] %c)"): "",
           (7, "%fill = u32[8] fusion(u32[8] %zero, u32[4] %a), calls=%fc"): "",
           (7, "%or.3 = u32[8] or(u32[8] %fill, u32[8] %p)"): "jit(f)/ingest.update/or:",
           (7, "%copy-done = u32[8] copy-done(u32[8] %copy-start)"): "",
           (7, "%or_bitcast_fusion = u32[1,8] fusion(u32[8] %or.3), calls=%fc2"):
               "jit(f)/shard_map/broadcast_in_dim:",
           (9, "%zero = u32[8] broadcast(u32[] %c)"): "jit(g)/ingest.terms/x:"}
    got = pt.op_scopes(ops)
    assert got[(7, "%a = u32[4] iota()")] == "ingest.live"
    # zero -> fill -> or.3: the scatter expansion is the update's
    assert got[(7, "%zero = u32[8] broadcast(u32[] %c)")] == "ingest.update"
    assert got[(7, "%fill = u32[8] fusion(u32[8] %zero, u32[4] %a), calls=%fc")] == "ingest.update"
    # no consumer with a phase: the phase of what it reads
    assert got[(7, "%or_bitcast_fusion = u32[1,8] fusion(u32[8] %or.3), calls=%fc2")] \
        == "ingest.update"
    # neither: the unscoped rest
    assert got[(7, "%copy-done = u32[8] copy-done(u32[8] %copy-start)")] == ""
    # programs do not mix
    assert got[(9, "%zero = u32[8] broadcast(u32[] %c)")] == "ingest.terms"


def _hand_built():
    ops = {"TPU:0": [E("a", 10, 20, "jit_ingest", "ingest.live"),
                     E("b", 20, 35, "jit_ingest", "ingest.terms"),
                     E("c", 35, 40, "jit_ingest", ""),
                     E("d", 50, 60, "jit_ingest", "ingest.update"),
                     E("e", 60, 70, "jit_ingest", "ingest.terms"),
                     E("f", 80, 90, "jit_other", "ingest.live")],
           "TPU:1": [E("a", 10, 30, "jit_ingest", "ingest.live"),
                     E("b", 30, 40, "jit_ingest", "ingest.update")]}
    runs = {"TPU:0": [E("jit_ingest", 10, 40), E("jit_ingest", 50, 70),
                      E("jit_other", 80, 90)],
            "TPU:1": [E("jit_ingest", 10, 40)]}
    spans = [E("bench.window", 0, 100), E("bench.count", 0, 100),
             E("repro.count", 5, 95), E("repro.count.operands", 40, 50),
             E("repro.count.operands", 70, 78), E("repro.count.put", 90, 95),
             E("repro.mux.feed", 0, 10, args=(("sid", 4),))]
    return pt.Trace(ops, runs, spans, (0, 100))


def test_scope_split_of_the_ingest_runs():
    got = _hand_built().scope_ms("ingest")
    # chip 0: two runs (30 + 20 ns): live 10, terms 15 + 10, update 10, none 5
    # chip 1: one run of 30 ns: live 20, update 10
    ms = 1e-6
    assert got["run"] == pytest.approx((50 / 2 + 30) / 2 * ms)
    assert got["ingest.live"] == pytest.approx((10 / 2 + 20) / 2 * ms)
    assert got["ingest.terms"] == pytest.approx((25 / 2 + 0) / 2 * ms)
    assert got["ingest.update"] == pytest.approx((10 / 2 + 10) / 2 * ms)
    assert got[""] == pytest.approx((5 / 2) / 2 * ms)
    # the scopes and the unscoped rest add up to the runs' op time
    assert sum(v for k, v in got.items() if k != "run") == pytest.approx(got["run"])
    assert _hand_built().scope_ms("nothing") == {}


def test_program_spans_are_read_and_label_idle_time():
    tr = _hand_built()
    assert tr.span_ms("repro.count.operands") == pytest.approx((10 + 8) / 2 * 1e-6)
    assert tr.span_ms("repro.count.put") == pytest.approx(5e-6)
    assert tr.span_ms("repro.plan") is None
    (feed,) = tr.spans_named("repro.mux.feed")
    assert dict(feed.args) == {"sid": 4}
    idle = dict(tr.idle_by_span())
    # the innermost span of either kind at each gap's middle: chip 0 gaps
    # 0-10 (mid 5: repro.count, which starts there), 40-50 and 70-80
    # (operands), 90-100 (put); chip 1 gaps 0-10 (repro.count) and 40-100
    # (mid 70: the second operands span starts there)
    assert idle == pytest.approx({"repro.count": (10 + 10) / 2 * 1e-9,
                                  "repro.count.operands": (10 + 10 + 60) / 2 * 1e-9,
                                  "repro.count.put": 10 / 2 * 1e-9})


def test_a_trace_without_program_spans_reduces_as_before():
    path = os.path.join(HERE, "data", "cpu_count3.xplane.pb")
    old, new = base.load(path), pt.load(path)
    assert new.window == old.window and new.devices == old.devices
    assert new.busy_s() == old.busy_s() and new.idle_pct() == old.idle_pct()
    assert new.idle_by_span() == old.idle_by_span()
    assert new.top_ops() == old.top_ops()
    assert [(s.name, s.start, s.end) for s in new.spans] == \
        [(s.name, s.start, s.end) for s in old.spans]
    assert [(s.start, s.end) for s in new.spans_named("bench.count")] == \
        [(s.start, s.end) for s in old.spans_named("bench.count")]
    assert {d: [(e.name, e.start, e.end, e.module) for e in evs]
            for d, evs in new.ops.items()} == \
        {d: [(e.name, e.start, e.end, e.module) for e in evs]
         for d, evs in old.ops.items()}
    assert all(e.scope == "" for evs in new.ops.values() for e in evs)
    assert pt.tf_ops(path) == {}


def test_the_chip_trace_names_every_ingest_phase():
    """The tf_op reader on a recorded TPU trace: every ingest executable's
    device time falls under one of the phases, bar a small unscoped rest."""
    assert pt.tf_ops(CHIP_TRACE)["/device:TPU:0"]
    tr = pt.load(CHIP_TRACE)
    assert tr.devices == ["TPU:0"]
    runs = tr.module_runs(pt.INGEST)["TPU:0"]
    assert {r.name for r in runs} == {"jit__ingest_block_impl",
                                      "jit__ingest_block_windowed_impl"}
    for pattern, phases in [("jit__ingest_block_impl", 3), ("windowed", 4)]:
        by = tr.scope_ms(pattern)
        scoped = sum(by.get(s, 0.0) for s in pt.SCOPES)
        assert sum(1 for s in pt.SCOPES if by.get(s)) == phases, by
        assert scoped >= 0.9 * by["run"], by
    ingests = tr.spans_named("repro.session.ingest")
    assert len(ingests) == len(runs)
    assert tr.span_ms("repro.session.feed") > 0


def _tiny_traced(monkeypatch, cell):
    """A tiny ``cell`` on the CPU with a traced window, reduced by
    program_trace: the result line and the reduced trace."""
    import jax

    run = load_run()
    wl, cfg, over = TINY[cell]
    load_json = run.load_json
    monkeypatch.setattr(run, "load_json", lambda kind, name: (
        {**load_json(kind, name), **over} if kind == "traffic" else load_json(kind, name)))
    monkeypatch.setitem(run.bench_file("peaks").PEAKS, "cpu", {"hbm_bytes_per_s": 1e11})
    got = {}

    class Scoped:
        @staticmethod
        def load(path):
            got["trace"] = pt.load(path)
            return got["trace"]

    monkeypatch.setitem(run._FILES, "trace", Scoped)
    r = run.run_cell(wl, cfg, name=cell, seed=2**31 + 29, seconds=2.0, trace=True,
                     devices=jax.devices()[:1], spec=None)
    return r, got["trace"]


def _nested(tr, child, parent) -> bool:
    outer = [s for s in tr.spans if s.name == parent]
    kids = [s for s in tr.spans if s.name == child]
    return bool(kids) and all(any(p.start <= k.start and k.end <= p.end for p in outer)
                              for k in kids)


def test_a_traced_resident_cell_has_the_count_spans(monkeypatch):
    r, tr = _tiny_traced(monkeypatch, "fna1-count")
    assert r["correct"] is True
    for child, parent in [("repro.plan", "bench.plan"), ("repro.plan.stats", "repro.plan"),
                          ("repro.plan.choose", "repro.plan"), ("repro.count", "bench.count"),
                          ("repro.count.operands", "repro.count"),
                          ("repro.count.put", "repro.count"),
                          ("repro.count.dispatch", "repro.count")]:
        assert _nested(tr, child, parent), (child, parent)
    assert tr.span_ms("repro.count.operands") > 0 and tr.span_ms("repro.count.put") > 0
    labels = {name for name, _ in r["breakdown"]["idle_gaps"]}
    assert any(name.startswith("repro.") for name in labels), labels


def test_a_traced_tenant_cell_has_the_session_spans(monkeypatch):
    r, tr = _tiny_traced(monkeypatch, "s16-tenants8")
    assert r["correct"] is True
    for child, parent in [("repro.mux.feed", "bench.feed"),
                          ("repro.session.feed", "repro.mux.feed"),
                          ("repro.mux.close", "bench.close"),
                          ("repro.session.finalize", "repro.mux.close"),
                          ("repro.mux.open", "bench.open")]:
        assert _nested(tr, child, parent), (child, parent)
    # a block is dispatched by the feed that completes it or by the close
    parents = [s for s in tr.spans
               if s.name in ("repro.session.feed", "repro.session.finalize")]
    ingests = tr.spans_named("repro.session.ingest")
    assert ingests and all(any(p.start <= k.start and k.end <= p.end for p in parents)
                           for k in ingests)
    sids = {dict(s.args).get("sid") for s in tr.spans if s.name.startswith("repro.mux.")}
    assert None not in sids and len(sids) >= 2
