"""Each traffic driver at a tiny size on the CPU, through the harness, with
every count checked against the plain reference."""
import math

import pytest

from cells import tiny_run

E2E = {"fna1-count": {"count_s", "setup_s"},
       "s16-tenants8": {"edges_per_s", "session_p75_s", "setup_s"},
       "s16-window4": {"edges_per_s", "setup_s"}}


@pytest.mark.parametrize("cell", sorted(E2E))
def test_driver_counts_match_the_reference(monkeypatch, cell):
    r = tiny_run(monkeypatch, cell)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert r["checks"]["count_error_max"] == {"value": 0, "limit": 0}
    assert r["checks"]["counts_compared"]["value"] >= 1
    assert set(r["metrics"]) == E2E[cell]
    assert all(math.isfinite(m["value"]) and m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    assert list(r)[-1] == "checks"


def test_traced_run_reports_per_layer_metrics(monkeypatch):
    r = tiny_run(monkeypatch, "fna1-count", seconds=2.0, trace=True)
    assert r["correct"] is True
    m = r["metrics"]
    assert m["plan_ms"]["value"] > 0 and m["resident_device_ms"]["value"] > 0
    assert 0 < m["idle_pct.count"]["value"] < 100
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    assert r["breakdown"]["device_ops"] and r["breakdown"]["idle_gaps"]
    assert len(r["breakdown"]["device_ops"]) <= 10
