#!/usr/bin/env python3
"""Chip benchmark of the triangle counter: one cell of ``BENCHMARK.json``.

Run from the root of a checkout, on a machine that holds the cell's chips:

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name, so a new cell is new files:

- ``bench/workloads/<cell>.json``: its configuration, traffic mix, chips,
  control and why;
- ``bench/traffic/<mix>.json``: the mix's parameters and the driver that
  reads them;
- ``bench/configs/<config>.json``: the deployment's sizes, source, data law,
  guarantees and whether it counts in 64 bits;
- ``bench/traffic/<driver>.py``: drives the program with the traffic;
- ``bench/gen/<law>.py``: draws the data from the seed;
- ``bench/metrics/<metric>.py``: reads one per-layer metric of
  ``BENCHMARK.json`` from the traced run.

One run, in one process: configure JAX's persistent compilation cache
(``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache`` in the checkout); fail
when JAX finds no TPU or fewer chips than the cell asks for; draw the data
from ``--seed``; let the driver build the program's objects and warm the
cell's own shapes (all of that is ``setup_s``); drive the traffic for
``--seconds``; read the device's peak memory and free the program's state;
then compare every count the window produced with the plain reference
(``bench/reference.py``). With ``--trace 1`` the same traffic runs with a
profiler trace of a few seconds in the middle of the window, and the
per-layer metrics are read from it.

Log lines go to stderr; its last lines are the numbers compared, each with
its limit. The last line of stdout is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``. The run exits non-zero without that line
when the program (``src/repro``) is not in the checkout, or when JAX finds
no TPU or fewer chips than the cell needs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")

# Where, in a traced run, the profiler records: from this share of the
# window, for at most this many seconds (and never past the window's end).
TRACE_FROM = 0.3
TRACE_SECONDS = 8.0


class BenchError(RuntimeError):
    """A run that cannot produce a result: no exit code 0, no result line."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold ``.`` and
    ``-``, so it is loaded by path, not imported)."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(kind: str, name: str) -> dict:
    path = os.path.join(BENCH, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind} entry {name!r} at {path}")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict]:
    wl = load_json("workloads", name)
    return wl, load_json("configs", wl["config"])


def benchmark_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics_of(spec: dict, section: str, cell: str) -> list[dict]:
    """The entries of ``spec[section]`` that ``cell`` reports: those that
    list it, and those with no list."""
    return [m for m in spec[section]
            if "workloads" not in m or cell in m["workloads"]]


def compile_cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")


def tpu_devices(n_chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n_chips:
        raise BenchError(f"needs {n_chips} TPU chip(s); JAX finds "
                         f"{len(devices)} {devices[0].platform} device(s)")
    return devices


class Compiles:
    """Counts XLA backend compiles (cache misses) as JAX reports them."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1


class Window:
    """The measured window. Drivers ask :meth:`running` between calls into
    the program; it says whether the window is still open and, in a traced
    run, starts and stops the profiler between those calls, so the traced
    part holds whole calls."""

    def __init__(self, seconds: float, trace_dir: str | None = None):
        self.seconds = float(seconds)
        self.trace_dir = trace_dir
        self.trace_from = TRACE_FROM * self.seconds
        self.trace_to = min(self.trace_from + TRACE_SECONDS, self.seconds)
        self.t0 = None
        self._span = None
        self.traced = False

    def start(self) -> float:
        self.t0 = time.perf_counter()
        return self.t0

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def running(self) -> bool:
        t = self.elapsed()
        if self.trace_dir is not None:
            if not self.traced and self._span is None and t >= self.trace_from:
                self._begin_trace()
            elif self._span is not None and t >= self.trace_to:
                self.end_trace()
        return t < self.seconds

    def _begin_trace(self) -> None:
        import jax

        jax.profiler.start_trace(self.trace_dir)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()

    def end_trace(self) -> None:
        if self._span is None:
            return
        import jax

        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()
        self.traced = True


class Context:
    """What a traffic driver gets: the configuration, the traffic mix's
    parameters, the data law and the seed."""

    def __init__(self, wl: dict, cfg: dict, seed: int):
        self.cfg = cfg
        self.params = load_json("traffic", wl["traffic"])
        self.seed = int(seed)
        self.law = load_module("gen", cfg["law"])
        self.n_nodes = self.law.n_nodes(cfg)


def check(n_nodes: int, answers: list, items: dict, *, control: str | None = None) -> dict:
    """Compare every answer ``(item key, count or None)`` with the plain
    reference of its item, computed once per distinct item. ``control``
    puts that control's count in place of each answer (a control has to
    come out not correct). Returns the compared numbers with their limits
    and the reference's time."""
    ref = bench_file("reference")
    t0 = time.perf_counter()
    want = {k: ref.exact(n_nodes, items[k]) for k in sorted({k for k, _ in answers})}
    if control is not None:
        got = {k: ref.control(control, n_nodes, items[k]) for k in want}
        answers = [(k, got[k]) for k, _ in answers]
    errors = [abs(int(c) - want[k]) for k, c in answers if c is not None]
    missing = sum(c is None for _, c in answers)
    return {
        "checks": {
            "count_error_max": {"value": max(errors, default=0), "limit": 0},
            "counts_missing": {"value": missing, "limit": 0},
            "counts_compared": {"value": len(errors), "limit": 1,
                                "at_least": True},
        },
        "reference": {k: want[k] for k in want},
        "reference_s": time.perf_counter() - t0,
    }


_FILES: dict = {}


def bench_file(name: str):
    """``bench/<name>.py`` (reference, trace, peaks, work), loaded once by
    path: ``trace`` would otherwise meet the standard library's module."""
    if name not in _FILES:
        spec = importlib.util.spec_from_file_location(
            f"bench_{name}", os.path.join(BENCH, f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        _FILES[name] = mod
    return _FILES[name]


def passed(checks: dict) -> bool:
    for c in checks.values():
        if c.get("at_least"):
            if c["value"] < c["limit"]:
                return False
        elif c["value"] > c["limit"]:
            return False
    return True


class MetricContext:
    """What a per-layer metric reader gets: the reduced trace, the driver's
    own sizes and host-clock samples, the chip's peaks, and ``work``
    (``bench/work.py``). :meth:`value` reads another metric by name."""

    def __init__(self, trace, stats: dict, peaks: dict):
        self.trace = trace
        self.work = bench_file("work")
        self.stats = stats
        self.peaks = peaks
        self._cache: dict = {}

    def value(self, name: str):
        if name not in self._cache:
            self._cache[name] = load_module("metrics", name).read(self)
        return self._cache[name]


def run_cell(wl: dict, cfg: dict, *, name: str, seed: int, seconds: float,
             trace: bool, devices: list, spec: dict | None,
             t_start: float = T_START) -> dict:
    """One run of a cell on ``devices``; returns the result line's dict.
    ``spec`` (``BENCHMARK.json``) selects the metrics reported; None
    reports every metric the driver and the traced run give."""
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    ctx = Context(wl, cfg, seed)
    driver = load_module("traffic", ctx.params["driver"])
    compiles = Compiles()
    data = driver.make_data(ctx)
    state = driver.setup(ctx, data)
    compiles_setup = compiles.n
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    window = Window(seconds, trace_dir)
    setup_s = window.start() - t_start
    try:
        out = driver.window(state, window)
    finally:
        window.end_trace()
    compiles_window = compiles.n - compiles_setup
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)
    log(f"window: {out['log']}")
    log(f"compiles: {compiles_setup} in set-up, {compiles_window} inside the window")
    driver.free(state)
    del state
    verdict = check(ctx.n_nodes, out["answers"], data["items"])
    log(f"reference: {len(verdict['reference'])} distinct item(s) in "
        f"{verdict['reference_s']:.3f} s: {verdict['reference']}")
    checks = verdict["checks"]
    correct = passed(checks)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    metrics: dict = {}
    breakdown = None
    if not trace:
        values = dict(out["metrics"], setup_s=setup_s)
        wanted = (metrics_of(spec, "end_to_end", name) if spec is not None
                  else [{"name": k, "unit": ""} for k in values])
        for m in wanted:
            if m["name"] not in values:
                raise BenchError(f"driver gave no {m['name']!r}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        tr = bench_file("trace").load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        mctx = MetricContext(tr, out["stats"],
                             bench_file("peaks").peaks(devices[0].device_kind))
        wanted = (metrics_of(spec, "per_layer", name) if spec is not None
                  else [{"name": os.path.splitext(f)[0], "unit": ""}
                        for f in sorted(os.listdir(os.path.join(BENCH, "metrics")))
                        if f.endswith(".py")])
        for m in wanted:
            v = mctx.value(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        breakdown = {"device_ops": tr.top_ops(10), "idle_gaps": tr.idle_by_span(10)}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace part of the window, report per-layer metrics")
    args = ap.parse_args(argv)
    try:
        if not os.path.isdir(os.path.join(SRC, "repro")):
            raise BenchError(f"no program under {SRC}: run from a checkout")
        wl, cfg = load_cell(args.workload)
        spec = benchmark_spec()
        # libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes
        # only inside its checkout, HOME, XDG_CACHE_HOME and TMPDIR
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        import jax

        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        if cfg.get("x64"):
            jax.config.update("jax_enable_x64", True)
        devices = tpu_devices(int(wl["chips"]))[:int(wl["chips"])]
        log(f"cell {args.workload}: config {wl['config']}, seed {args.seed}, "
            f"{args.seconds} s, trace {args.trace}, cache {compile_cache_dir()}, "
            f"device {devices[0].device_kind} x{len(devices)}")
        result = run_cell(wl, cfg, name=args.workload, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          devices=devices, spec=spec)
    except BenchError as e:
        log(f"FAIL: {e}")
        return 2
    for k, c in result["checks"].items():
        log(f"check {k}: {c['value']} (limit {c['limit']}"
            f"{', at least' if c.get('at_least') else ''})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
