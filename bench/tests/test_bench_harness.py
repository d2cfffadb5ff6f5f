"""The harness (bench/run.py) as a program: it refuses to run without a
TPU or without the program beside it, and it finds a cell's files by name."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def _run(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fna1-count", "--seed",
         str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _load_run(bench_dir):
    spec = importlib.util.spec_from_file_location(
        "bench_run_copy", os.path.join(bench_dir, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def test_a_new_cell_is_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (bench / "workloads" / "tiny-extra.json").write_text(json.dumps(
        {"config": "tiny-config", "chips": 1, "traffic": "tiny-mix",
         "control": "multigraph", "why": "a cell a later change adds"}))
    (bench / "configs" / "tiny-config.json").write_text(json.dumps(
        {"law": "rmat", "scale": 8, "edge_factor": 4, "a": 0.57, "b": 0.19, "c": 0.19}))
    (bench / "traffic" / "tiny-mix.json").write_text(json.dumps(
        {"driver": "tenant_sessions", "tenants": 2, "graphs": 2, "chunk": 256}))
    (bench / "metrics" / "extra_metric.py").write_text("def read(ctx):\n    return 1.0\n")
    run = _load_run(str(bench))
    wl, cfg = run.load_cell("tiny-extra")
    assert wl["traffic"] == "tiny-mix" and cfg["scale"] == 8
    assert run.load_json("traffic", wl["traffic"])["driver"] == "tenant_sessions"
    assert run.load_module("traffic", "tenant_sessions").make_data
    assert run.load_module("metrics", "extra_metric").read(None) == 1.0
    assert run.metrics_of({"per_layer": [{"name": "x", "workloads": ["a"]},
                                         {"name": "y"}]}, "per_layer", "tiny-extra") == [{"name": "y"}]


def test_every_cell_of_the_benchmark_has_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for cell in spec["workloads"]:
        with open(os.path.join(BENCH, "workloads", f"{cell['name']}.json")) as f:
            wl = json.load(f)
        assert wl["config"] == cell["config"] and wl["traffic"] == cell["traffic"]
        assert wl["chips"] == cell["chips"] and wl["why"] == cell["why"]
        with open(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json")) as f:
            mix = json.load(f)
        assert os.path.isfile(os.path.join(BENCH, "traffic", f"{mix['driver']}.py"))
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        assert os.path.isfile(os.path.join(BENCH, "gen", f"{cfg['law']}.py"))
    for m in spec["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics", f"{m['name']}.py"))
