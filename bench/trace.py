"""Reduction of a JAX profiler trace to what the per-layer metrics read.

A traced run records with ``jax.profiler.start_trace``; the harness opens a
host span ``bench.window`` over the traced part, and the traffic drivers
wrap each call into the program in a ``bench.*`` span. :func:`load` reads
the ``.xplane.pb`` the profiler wrote and keeps:

- the device operations of each chip: on a TPU the events of the ``XLA
  Ops`` line of each ``/device:TPU:<i>`` plane, with their executable from
  the ``XLA Modules`` line; on the CPU backend (the tests) the host events
  that carry an ``hlo_op`` stat;
- the executable runs of each chip (the ``XLA Modules`` line);
- the ``bench.*`` host spans.

Busy time is the union of a chip's operation intervals; idle is the rest of
the window; each idle gap is put down to the innermost ``bench.*`` span the
host was in at the gap's middle. Every number here is a mean over the
chips the trace holds.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE_ID = re.compile(r"\(\d+\)$")
WINDOW_SPAN = "bench.window"
OUTSIDE = "outside bench spans"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int  # ns
    end: int  # ns
    module: str = ""


def union(intervals) -> list[tuple[int, int]]:
    """Sorted, disjoint cover of ``(start, end)`` intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: int, hi: int) -> int:
    """Length of ``[lo, hi]`` that the disjoint ``merged`` intervals cover."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


def gaps(merged, lo: int, hi: int) -> list[tuple[int, int]]:
    """The parts of ``[lo, hi]`` that ``merged`` leaves uncovered."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


class Trace:
    """The reduced trace: ``ops`` and ``runs`` per chip, host ``spans``,
    and the traced ``window`` (the ``bench.window`` span)."""

    def __init__(self, ops: dict, runs: dict, spans: list, window: tuple[int, int]):
        self.window = window
        lo, hi = window
        self.ops = {d: [e for e in evs if e.end > lo and e.start < hi]
                    for d, evs in ops.items()}
        self.runs = {d: [e for e in evs if e.end > lo and e.start < hi]
                     for d, evs in runs.items()}
        self.spans = [s for s in spans if s.name != WINDOW_SPAN]
        self._busy = {d: union((e.start, e.end) for e in evs)
                      for d, evs in self.ops.items()}

    @property
    def devices(self) -> list[str]:
        return sorted(self.ops)

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _mean(self, per_device) -> float:
        vals = [per_device(d) for d in self.devices]
        return sum(vals) / len(vals) if vals else 0.0

    def busy_s(self) -> float:
        """Seconds of the window in which an operation ran, per chip."""
        lo, hi = self.window
        return self._mean(lambda d: covered(self._busy[d], lo, hi)) / 1e9

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s() / self.window_s())

    def spans_named(self, name: str) -> list[Event]:
        """The ``name`` spans that lie wholly inside the window."""
        lo, hi = self.window
        return [s for s in self.spans if s.name == name and s.start >= lo and s.end <= hi]

    def busy_in(self, span: Event) -> float:
        """Seconds of ``span`` in which an operation ran, per chip."""
        return self._mean(lambda d: covered(self._busy[d], span.start, span.end)) / 1e9

    def busy_pct_of_ops(self, pattern: str) -> float:
        """Share of the window, in percent and per chip, covered by the
        operations whose name matches ``pattern`` (searched)."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return 100.0 * self._mean(lambda d: covered(
            union((e.start, e.end) for e in self.ops[d] if rx.search(e.name)),
            lo, hi)) / (hi - lo)

    def module_runs(self, pattern: str) -> dict[str, list[Event]]:
        """Per chip, the executable runs whose name matches ``pattern``
        (a regular expression, searched) and that lie inside the window."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return {d: [e for e in evs if rx.search(e.name) and e.start >= lo and e.end <= hi]
                for d, evs in self.runs.items()}

    def top_ops(self, k: int = 10) -> list[list]:
        """The ``k`` operations (``executable/op``) that took most device
        time in the window, in seconds per chip."""
        lo, hi = self.window
        total: dict[str, float] = {}
        for evs in self.ops.values():
            for e in evs:
                key = f"{e.module}/{e.name}" if e.module else e.name
                total[key] = total.get(key, 0) + (min(e.end, hi) - max(e.start, lo))
        n = max(len(self.ops), 1)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / n / 1e9] for name, ns in top]

    def idle_by_span(self, k: int = 10) -> list[list]:
        """Idle seconds per chip, put down to what the host was doing: the
        innermost ``bench.*`` span at each gap's middle. The ``k`` largest."""
        lo, hi = self.window
        spans = sorted(self.spans, key=lambda s: s.start)
        starts = [s.start for s in spans]
        total: dict[str, float] = {}
        for d in self.devices:
            for g0, g1 in gaps(self._busy[d], lo, hi):
                mid = (g0 + g1) // 2
                label = OUTSIDE
                for s in reversed(spans[:bisect.bisect_right(starts, mid)]):
                    if s.end >= mid:
                        label = s.name
                        break
                total[label] = total.get(label, 0) + (g1 - g0)
        n = max(len(self.devices), 1)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / n / 1e9] for name, ns in top]


def module_name(name: str) -> str:
    """``jit_f(1234)`` -> ``jit_f``: the executable without its program id."""
    return _MODULE_ID.sub("", name)


def op_name(name: str) -> str:
    """``%fusion.5 = s32[4] fusion(...)`` -> ``fusion.5``: the HLO
    instruction's name without its text."""
    return name.split(" = ", 1)[0].lstrip("%")


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def from_profile(profile) -> Trace:
    """Reduce a ``jax.profiler.ProfileData``."""
    ops: dict[str, list[Event]] = {}
    runs: dict[str, list[Event]] = {}
    spans: list[Event] = []
    for plane in profile.planes:
        m = _TPU_PLANE.match(plane.name)
        if m:
            dev = f"TPU:{m.group(1)}"
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = [Event(module_name(e.name), int(e.start_ns), int(e.end_ns))
                    for e in lines.get("XLA Modules", [])]
            runs[dev] = mods
            starts = [r.start for r in mods]
            evs = []
            for e in lines.get("XLA Ops", []):
                s = int(e.start_ns)
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i].name if i >= 0 and mods[i].end >= s else ""
                evs.append(Event(op_name(e.name), s, int(e.end_ns), mod))
            ops[dev] = evs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append(Event(e.name, int(e.start_ns), int(e.end_ns)))
                        continue
                    st = _stats(e)
                    if "hlo_op" in st:  # the CPU backend runs ops on host threads
                        dev = f"CPU:{st.get('device_ordinal', 0)}"
                        ops.setdefault(dev, []).append(Event(
                            e.name, int(e.start_ns), int(e.end_ns),
                            str(st.get("hlo_module", ""))))
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    w = max(windows, key=lambda s: s.end - s.start)
    return Trace(ops, runs, spans, (w.start, w.end))


def xplane_path(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    """Reduce the trace at ``path``: an ``.xplane.pb`` file, or the
    directory a ``jax.profiler.start_trace`` wrote into."""
    import jax

    if os.path.isdir(path):
        path = xplane_path(path)
    return from_profile(jax.profiler.ProfileData.from_file(path))
