#!/usr/bin/env python3
"""The controls of the benchmark's correctness check, at a cell's own size.

    python bench/control.py --workload <cell> --seeds <n>[,<n>...]

For each seed, draws the cell's data as a run does, takes the items a run
compares (each pool graph; for a window, the epochs a run of the cell's
length feeds, the last one cut short), and puts the cell's control (its
workload file's ``control``: ``float32`` or ``multigraph``, see
``bench/reference.py``) in the program's place. It prints, per seed, the
reading the harness would compare, ``count_error_max``, which has to be
above its limit (0) for the control to come out not correct. The
benchmark's own runs never run this; it runs on the machine it is started
on, a TPU where the cell's size needs one.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run as harness  # noqa: E402

# A window run of the cell's length feeds the 4 filled epochs and a few
# more; the control's window takes this many epochs, the last cut in half.
WINDOW_EPOCHS_FED = 10


def items_of(ctx, data: dict) -> dict:
    """The items a run of the cell compares."""
    if data["items"]:
        return data["items"]
    prm = ctx.params
    pool = data["pool"]
    epochs = [pool[i % len(pool)] for i in range(WINDOW_EPOCHS_FED)]
    epochs[-1] = epochs[-1][: len(epochs[-1]) // 2]
    return {"window": {"epochs": epochs, "window": int(prm["window"])}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    wl, cfg = harness.load_cell(args.workload)
    sys.path.insert(0, harness.SRC)
    import jax

    jax.config.update("jax_compilation_cache_dir", harness.compile_cache_dir())
    if cfg.get("x64"):
        jax.config.update("jax_enable_x64", True)
    devices = jax.devices()
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.Context(wl, cfg, seed)
        driver = harness.load_module("traffic", ctx.params["driver"])
        items = items_of(ctx, driver.make_data(ctx))
        ref = harness.bench_file("reference")
        answers = [(k, ref.exact(ctx.n_nodes, items[k])) for k in items]
        t0 = time.perf_counter()
        got = harness.check(ctx.n_nodes, answers, items, control=wl["control"])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": wl["control"],
                          "count_error_max": got["checks"]["count_error_max"]["value"],
                          "correct": harness.passed(got["checks"]),
                          "reference": {str(k): v for k, v in got["reference"].items()},
                          "seconds": time.perf_counter() - t0,
                          "device": devices[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
