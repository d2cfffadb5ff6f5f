"""Tiny versions of the benchmark's cells for the CPU tests: the same
drivers, data laws and reference, at sizes a test run holds."""
import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
SRC = os.path.join(os.path.dirname(BENCH), "src")
if SRC not in sys.path:  # the program under test, as bench/run.py finds it
    sys.path.insert(0, SRC)

# cell -> (workload, configuration, traffic parameters laid over its mix)
TINY = {
    "fna1-count": (
        {"config": "tiny-gnm", "chips": 1, "traffic": "analyst-loop3", "control": "float32"},
        {"law": "gnm", "n_nodes": 300, "n_edges": 5_000},
        {}),
    "s16-tenants8": (
        {"config": "tiny-rmat", "chips": 1, "traffic": "tenants8-closed", "control": "multigraph"},
        {"law": "rmat", "scale": 9, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19},
        {"tenants": 3, "graphs": 3, "chunk": 1_024}),
    "s18-ring4-sessions": (
        {"config": "tiny-rmat", "chips": 4, "traffic": "ring4-closed", "control": "float32"},
        {"law": "rmat", "scale": 9, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19},
        {"graphs": 2, "chunk": 1_024, "block_size": 1_024}),
    "s16-window4": (
        {"config": "tiny-rmat", "chips": 1, "traffic": "window4-epochs", "control": "multigraph"},
        {"law": "rmat", "scale": 9, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19},
        {"epoch_tuples": 2_048, "epochs": 6, "chunk": 512}),
}


def load_run():
    spec = importlib.util.spec_from_file_location("bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def tiny_run(monkeypatch, cell, *, seed=2**31 + 17, seconds=1.0, trace=False):
    """One run of the tiny ``cell`` on the CPU, as ``bench/run.py`` makes it
    past its look for a chip."""
    import jax

    run = load_run()
    wl, cfg, over = TINY[cell]
    load_json = run.load_json

    def tiny_json(kind, name):
        d = load_json(kind, name)
        return {**d, **over} if kind == "traffic" else d

    monkeypatch.setattr(run, "load_json", tiny_json)
    # a CPU has no published peaks: a made-up entry, for the arithmetic only
    monkeypatch.setitem(run.bench_file("peaks").PEAKS, "cpu", {"hbm_bytes_per_s": 1e11})
    return run.run_cell(wl, cfg, name=cell, seed=seed, seconds=seconds, trace=trace,
                        devices=jax.devices()[:wl["chips"]], spec=None)
