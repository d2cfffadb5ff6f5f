# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV
# at the end (harness contract) plus human-readable sections per figure.
#
# This process never touches JAX: every suite that computes runs in a child
# process of its own, one after another, so on a TPU host each child in turn
# is the only holder of the chip.
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

from benchmarks import fig10_11_et_vm, fig12_13_cores  # noqa: E402

# Table 1 and the kernel micro-benchmarks compute in-process; they run here,
# in one child, and hand their CSV rows back on a tagged stdout line.
_LOCAL_SUITES = """
import json
from benchmarks import kernel_bench, roofline, table1_suite
from repro.utils import enable_compile_cache

enable_compile_cache()
print("== Table 1: graph benchmark (scaled instantiation + exactness) ==")
table1_suite.run()
print("\\n== Kernel/path micro-benchmarks ==")
rows = kernel_bench.run()
print("\\n== Roofline (from dry-run artifacts, if present) ==")
roofline.print_table("pod_16x16")
print("CSV_ROWS " + json.dumps(rows, default=str), flush=True)
"""


def _run_local_suites() -> list[dict]:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", _LOCAL_SUITES], env=env,
                          cwd=ROOT, stdout=subprocess.PIPE, text=True, check=True)
    rows: list[dict] = []
    for line in proc.stdout.splitlines():
        if line.startswith("CSV_ROWS "):
            rows = json.loads(line[len("CSV_ROWS "):])
        else:
            print(line)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="skip the subprocess ET/VM suites")
    ap.add_argument("--timeout", type=float, default=150.0)
    args = ap.parse_args()

    csv_rows: list[dict] = _run_local_suites()

    results = {}
    if not args.fast:
        print("\n== Fig 10/11: ET + VM, Pipeline vs MapReduce ==")
        results["fig10_11"] = fig10_11_et_vm.run(timeout_s=args.timeout)
        for r in results["fig10_11"]:
            nm = f"fig10_{r['graph']}_{r['method']}"
            if r.get("timeout"):
                csv_rows.append({"name": nm, "us_per_call": "", "derived": "TIMEOUT"})
            elif "wall_s" in r:
                csv_rows.append({"name": nm, "us_per_call": r["wall_s"] * 1e6,
                                 "derived": f"vm_mb={r['maxrss_mb']:.0f}"})

        print("\n== Fig 12/13: core scaling ==")
        results["fig12_13"] = fig12_13_cores.run(timeout_s=max(args.timeout, 300.0))
        for r in results["fig12_13"]:
            if "wall_s" in r:
                csv_rows.append({"name": f"fig12_{r['graph']}_{r['method']}_x{r['devices']}",
                                 "us_per_call": r["wall_s"] * 1e6, "derived": ""})

    out_dir = os.path.join(ROOT, "results")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "bench_results.json"), "w") as f:
        json.dump(results, f, indent=1, default=str)

    print("\nname,us_per_call,derived")
    for r in csv_rows:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")


if __name__ == "__main__":
    main()
