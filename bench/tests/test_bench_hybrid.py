"""The hybrid cell's driver and reader at a tiny size on the CPU: Kronecker
graphs of scale 12 on a budget the dense state (2 MiB) does not fit, so
that admission, not the cell, gives each session the hybrid state."""
import pytest

from cells import load_run

CELL = "s20-hybrid-tenants2"
WORKLOAD = {"config": "tiny-rmat-hybrid", "chips": 1, "traffic": "hybrid2-closed",
            "control": "multigraph"}
# edge factor 1: at scale 12 about 80 vertices pass degree 16, under the
# 256 hub slots the budget buys
CONFIG = {"law": "rmat", "scale": 12, "edge_factor": 1, "a": 0.57, "b": 0.19, "c": 0.19,
          "x64": False}
MIX = {"chunk": 1_024, "warm_piece": 512}
BUDGET = 1_750_000  # bytes: tail buffers of 16, 256 hub rows; dense needs 2 MiB


@pytest.fixture
def run(monkeypatch):
    from repro.api import planner

    run = load_run()
    monkeypatch.setattr(planner, "CPU_MEMORY_BYTES", BUDGET)
    load_json = run.load_json
    monkeypatch.setattr(run, "load_json", lambda kind, name: (
        {**load_json(kind, name), **MIX} if kind == "traffic" else load_json(kind, name)))
    monkeypatch.setitem(run.bench_file("peaks").PEAKS, "cpu", {"hbm_bytes_per_s": 1e11})
    return run


def _drive(run, seed, seconds=1.0):
    ctx = run.Context(WORKLOAD, CONFIG, seed)
    driver = run.load_module("traffic", ctx.params["driver"])
    data = driver.make_data(ctx)
    st = driver.setup(ctx, data)
    clock = run.Window(seconds)
    clock.start()
    return ctx, data, driver.window(st, clock)


def test_admission_picks_the_hybrid_and_counts_match(run):
    ctx, data, out = _drive(run, 2**31 + 71)
    s = out["stats"]
    assert s["state_layout"] == "hybrid"
    assert (s["hub_slots"], s["tail_capacity"], s["words"]) == (256, 16, 128)
    h = s["hybrid"]
    assert h["lost"] == 0 and h["blocks"] > 0 and h["promoted"] > 0
    assert min(h["pairs_hub"], h["pairs_mixed"], h["pairs_tail"]) > 0
    verdict = run.check(ctx.n_nodes, out["answers"], data["items"])
    assert run.passed(verdict["checks"]), verdict
    assert verdict["checks"]["counts_compared"]["value"] >= 2
    control = run.check(ctx.n_nodes, out["answers"], data["items"], control="multigraph")
    assert not run.passed(control["checks"])


def test_harness_run_is_correct_and_traced(run):
    import jax

    r = run.run_cell(WORKLOAD, CONFIG, name=CELL, seed=2**31 + 73, seconds=2.0,
                     trace=True, devices=jax.devices()[:1], spec=None)
    assert r["correct"] is True and r["failed"] == 0, r["checks"]
    m = r["metrics"]
    assert 0 <= m["idle_pct.stream"]["value"] < 100
    # the CPU backend records no executable runs, so the block time, and
    # the share that divides by it, read nothing there (and do not raise)
    assert "ingest_ms_per_block" not in m and "hybrid_ingest_roofline" not in m
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]


def test_traced_counters_cover_the_blocks_run_while_recording(run, monkeypatch, tmp_path):
    """The roofline's numerator counts the blocks that ran under the trace:
    the sessions in flight when it starts count only their later blocks, so
    the traced sums lie between nothing and the closed sessions' sums."""
    monkeypatch.setattr(run, "TRACE_FROM", 0.0)
    ctx = run.Context(WORKLOAD, CONFIG, 2**31 + 83)
    driver = run.load_module("traffic", ctx.params["driver"])
    st = driver.setup(ctx, driver.make_data(ctx))
    clock = run.Window(2.0, str(tmp_path))
    clock.start()
    try:
        s = driver.window(st, clock)["stats"]
    finally:
        clock.end_trace()
    whole, traced = s["hybrid"], s["hybrid_traced"]
    assert 0 < traced["blocks"] < whole["blocks"]
    for k in ("pairs_hub", "fill_mixed", "fill_tail"):
        assert 0 <= traced[k] <= whole[k]
    assert traced["pairs_hub"] + traced["fill_mixed"] + traced["fill_tail"] > 0


def test_set_up_guard_stops_a_crawling_warm_session(run, monkeypatch):
    load_json = run.load_json
    monkeypatch.setattr(run, "load_json", lambda kind, name: (
        {**load_json(kind, name), "guard_s": 0, "guard_share": 1.0}
        if kind == "traffic" else load_json(kind, name)))
    with pytest.raises(RuntimeError, match="set-up guard.*a graph would take about"):
        _drive(run, 2**31 + 79)


def test_roofline_bytes_count_what_an_exact_ingest_reads(run):
    work = run.load_module("metrics", "hybrid_ingest_roofline")._hybrid_work()
    # two hub rows per hub–hub edge, 4 + 4 bytes a tested tail entry, 4 a merged one
    assert work.hybrid_ingest_bytes(32_768, 2, 10, 6) == 2 * 8 * 32_768 + 80 + 24
    assert work.hybrid_ingest_bytes(32_768, 0, 0, 0) == 0


class _Ctx:
    def __init__(self, stats, ms):
        self.stats, self._ms = stats, ms
        self.peaks = {"hbm_bytes_per_s": 819e9}

    def value(self, name):
        assert name == "ingest_ms_per_block"
        return self._ms


def test_roofline_reader(run):
    reader = run.load_module("metrics", "hybrid_ingest_roofline")
    h = {"blocks": 4, "pairs_hub": 100, "fill_mixed": 1_000, "fill_tail": 2_000}
    want = 100.0 * (8 * 32_768 * 100 + 8_000 + 8_000) / 4 / (1e-3 * 819e9)
    assert reader.read(_Ctx({"hybrid_traced": h, "words": 32_768}, 1.0)) \
        == pytest.approx(want)
    # cells whose sessions carry no hybrid counters read nothing
    assert reader.read(_Ctx({"words": 32_768}, 1.0)) is None
    assert reader.read(_Ctx({"hybrid_traced": None, "words": 32_768}, 1.0)) is None
