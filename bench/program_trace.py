"""The trace reduction of ``bench/trace.py``, with the program's own spans
and scopes.

The program (``src/repro/tracing.py``) opens host spans named ``repro.*``
and puts the stream ingest's phases under ``jax.named_scope``s
(``ingest.live``, ``ingest.age_cum``, ``ingest.update``, ``ingest.terms``).
:func:`load` reduces a trace as ``bench/trace.py`` does and besides:

- keeps the ``repro.*`` host spans, with their arguments (a mux session's
  ``sid``), next to the ``bench.*`` spans; the idle attribution then names
  the innermost span of either kind;
- gives each TPU operation the ``scope`` of its phase, read from the
  ``tf_op`` stat of the operation's XSpace event *metadata*. The profiler's
  Python API exposes only per-event stats, so :func:`tf_ops` reads the
  ``.xplane.pb`` wire format itself: no tensorflow is needed. An op with
  no phase of its own takes its nearest consumer's or, failing that, its
  nearest operand's (:func:`op_scopes`).

On a trace with no ``repro.*`` spans and no scopes every number reads as
``bench/trace.py`` gives it.

Run on a trace directory (``jax.profiler.trace(dir)``) or ``.xplane.pb``:

    python bench/program_trace.py <path> [--runs REGEX]

to print the device time per ingest run by scope and the mean of each
``repro.*`` span.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import importlib.util
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def _base():
    """``bench/trace.py``, loaded by path once (``trace`` would otherwise
    meet the standard library's module)."""
    name = "bench_trace"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(HERE, "trace.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


base = _base()

PROGRAM_SPAN = "repro."
SCOPES = ("ingest.live", "ingest.age_cum", "ingest.update", "ingest.terms")
# the ingest executables' runs, as ``bench/metrics/ingest_ms_per_block.py``
INGEST = r"ingest|^jit_stage_fn$"
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


@dataclasses.dataclass(frozen=True)
class Event(base.Event):
    scope: str = ""  # the op's ingest phase; "" when it has none
    args: tuple = ()  # a span's arguments, as sorted (name, value) pairs


# --------------------------------------------------------------------------
# XSpace wire format: just what the event metadata needs
# --------------------------------------------------------------------------
def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: int | None = None):
    """``(field number, wire type, value)`` of a message in ``buf[lo:hi]``:
    an int for varints, ``(start, end)`` for length-delimited fields, None
    for fixed-width ones (skipped)."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        tag, i = _varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"unsupported wire type {wire} at byte {i}")
        yield field, wire, v


def _text(buf: bytes, span: tuple[int, int]) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf: bytes, span: tuple[int, int]):
    """A map entry's ``(key, value span)``."""
    key = val = None
    for f, _, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane_tf_ops(buf: bytes, lo: int, hi: int) -> tuple[str, dict]:
    """A plane's name and ``{(program id, op name): tf_op}``."""
    name, stat_names, metas = "", {}, []
    for f, _, v in _fields(buf, lo, hi):
        if f == 2:
            name = _text(buf, v)
        elif f == 4:  # event_metadata: map<int64, XEventMetadata>
            metas.append(_map_value(buf, v)[1])
        elif f == 5:  # stat_metadata: map<int64, XStatMetadata>
            key, val = _map_value(buf, v)
            for g, _, w in _fields(buf, *val):
                if g == 2:
                    stat_names[key] = _text(buf, w)
    out = {}
    for span in metas:
        op, pid, tf_op = "", 0, ""
        for f, _, v in _fields(buf, *span):
            if f == 2:
                op = _text(buf, v)
            elif f == 5:  # XStat
                sid = val = None
                for g, wire, w in _fields(buf, *v):
                    if g == 1:
                        sid = w
                    elif g in (3, 4, 7) or (g == 5 and wire == 2):
                        val = w
                stat = stat_names.get(sid)
                if stat == "program_id" and isinstance(val, int):
                    pid = val
                elif stat == "tf_op":
                    # a string, or a reference to a stat name holding it
                    tf_op = _text(buf, val) if isinstance(val, tuple) else stat_names.get(val, "")
        if tf_op or " = " in op:  # an HLO instruction, with its text
            out[(pid, op)] = tf_op
    return name, out


def tf_ops(path: str) -> dict[str, dict]:
    """Per plane name, ``{(program id, op name): tf_op}`` from the event
    metadata of an ``.xplane.pb`` file; ``tf_op`` is "" for an op that XLA
    made without metadata. A CPU trace holds none."""
    with open(path, "rb") as f:
        buf = f.read()
    out = {}
    for field, _, v in _fields(buf):
        if field == 1:  # XSpace.planes
            name, ops = _plane_tf_ops(buf, *v)
            if ops:
                out[name] = ops
    return out


def scope_of(tf_op: str) -> str:
    """The innermost ingest phase in a ``tf_op`` such as
    ``jit(_ingest_block_impl)/ingest.live/jit(lexsort)/sort:`` (under a
    transform the phase reads ``vmap(ingest.live)``), else ""."""
    for part in reversed(tf_op.rstrip(":").split("/")):
        part = part.rstrip(")").rsplit("(", 1)[-1]
        if part in SCOPES:
            return part
    return ""


_OPERAND = re.compile(r"%([\w.\-]+)")


def _nearest(start, links: dict, own: dict) -> str:
    """The phase of the nearest op reachable from ``start`` through
    ``links``, breadth first; "" when none has one."""
    seen, frontier = {start}, [start]
    while frontier:
        nxt = []
        for op in frontier:
            for other in sorted(links.get(op, ())):
                if other in seen or other not in own:
                    continue
                if own[other][1]:
                    return own[other][1]
                seen.add(other)
                nxt.append(other)
        frontier = nxt
    return ""


def op_scopes(ops: dict) -> dict:
    """``{(program id, op name): phase}`` for one plane's :func:`tf_ops`.
    An op with a phase in its ``tf_op`` has that one. An op without one
    (XLA made it without metadata, as the zero-fill and the scatter fusions
    a scatter is expanded into, or named it after a reshape outside the
    phases, as the state write fused with the mesh step's leading-axis
    bitcast) is charged to the phase of the nearest op that consumes its
    result, else of the nearest op it reads, breadth first over the
    operands in each op's HLO text; "" when none has a phase."""
    own, users, operands = {}, {}, {}
    for pid, text in ops:
        names = _OPERAND.findall(text)
        if " = " not in text or not names:
            continue
        me = (pid, names[0])
        own[me] = (text, scope_of(ops[(pid, text)]))
        operands[me] = {(pid, name) for name in names[1:]}
        for operand in operands[me]:
            users.setdefault(operand, []).append(me)
    return {(me[0], text): scope or _nearest(me, users, own) or _nearest(me, operands, own)
            for me, (text, scope) in own.items()}


# --------------------------------------------------------------------------
# The reduced trace
# --------------------------------------------------------------------------
class Trace(base.Trace):
    """``bench/trace.py``'s ``Trace`` whose ``spans`` include the program's
    and whose ops carry their ``scope``."""

    def span_ms(self, name: str) -> float | None:
        """Mean milliseconds of the ``name`` spans that lie wholly inside
        the window; None when there is none."""
        spans = self.spans_named(name)
        if not spans:
            return None
        return sum(s.end - s.start for s in spans) / len(spans) / 1e6

    def scope_ms(self, pattern: str = INGEST) -> dict[str, float]:
        """Device milliseconds per run of the executables matching
        ``pattern`` (searched; the runs of ``ingest_ms_per_block``), by
        scope: each operation's time inside its run goes to its ``scope``
        (``""``: none), and ``"run"`` is the runs' own time. A mean over the
        runs of each chip, then over the chips that ran one. Empty when no
        run lies inside the window."""
        per_chip = []
        for d, runs in self.module_runs(pattern).items():
            if not runs:
                continue
            runs = sorted(runs, key=lambda r: r.start)
            starts = [r.start for r in runs]
            acc = {"run": float(sum(r.end - r.start for r in runs))}
            for e in self.ops.get(d, []):
                i = bisect.bisect_right(starts, e.start) - 1
                if i < 0 or e.start >= runs[i].end:
                    continue
                scope = getattr(e, "scope", "")
                acc[scope] = acc.get(scope, 0.0) + min(e.end, runs[i].end) - e.start
            per_chip.append({k: v / len(runs) / 1e6 for k, v in acc.items()})
        keys = {k for c in per_chip for k in c}
        return {k: sum(c.get(k, 0.0) for c in per_chip) / len(per_chip) for k in keys}


def from_profile(profile, tf_op: dict | None = None) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` as ``bench/trace.py`` does,
    keeping ``repro.*`` spans and the ops' phases (``tf_op``: the
    :func:`tf_ops` of the same file, read through :func:`op_scopes`).
    Without a ``bench.window`` span the window is the extent of what the
    trace holds."""
    phases = {plane: op_scopes(ops) for plane, ops in (tf_op or {}).items()}
    ops: dict[str, list] = {}
    runs: dict[str, list] = {}
    spans: list[Event] = []
    for plane in profile.planes:
        m = base._TPU_PLANE.match(plane.name)
        if m:
            dev = f"TPU:{m.group(1)}"
            phase = phases.get(plane.name, {})
            lines = {line.name: list(line.events) for line in plane.lines}
            mods, pids = [], []
            for e in lines.get("XLA Modules", []):
                mods.append(Event(base.module_name(e.name), int(e.start_ns), int(e.end_ns)))
                pid = _PROGRAM_ID.search(e.name)
                pids.append(int(pid.group(1)) if pid else 0)
            runs[dev] = mods
            starts = [r.start for r in mods]
            evs = []
            for e in lines.get("XLA Ops", []):
                s = int(e.start_ns)
                i = bisect.bisect_right(starts, s) - 1
                inside = i >= 0 and mods[i].end >= s
                evs.append(Event(base.op_name(e.name), s, int(e.end_ns),
                                 mods[i].name if inside else "",
                                 phase.get((pids[i] if inside else 0, e.name), "")))
            ops[dev] = evs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("bench.", PROGRAM_SPAN)):
                        spans.append(Event(e.name, int(e.start_ns), int(e.end_ns),
                                           args=tuple(sorted(base._stats(e).items()))))
                        continue
                    st = base._stats(e)
                    if "hlo_op" in st:  # the CPU backend runs ops on host threads
                        dev = f"CPU:{st.get('device_ordinal', 0)}"
                        ops.setdefault(dev, []).append(Event(
                            e.name, int(e.start_ns), int(e.end_ns),
                            str(st.get("hlo_module", ""))))
    windows = [s for s in spans if s.name == base.WINDOW_SPAN]
    if windows:
        w = max(windows, key=lambda s: s.end - s.start)
        window = (w.start, w.end)
    else:
        every = spans + [e for evs in ops.values() for e in evs]
        if not every:
            raise ValueError("the trace holds no span and no device operation")
        window = (min(e.start for e in every), max(e.end for e in every))
    return Trace(ops, runs, spans, window)


def load(path: str) -> Trace:
    """Reduce the trace at ``path``: an ``.xplane.pb`` file, or the
    directory a ``jax.profiler`` trace wrote into."""
    import jax

    if os.path.isdir(path):
        path = base.xplane_path(path)
    return from_profile(jax.profiler.ProfileData.from_file(path), tf_ops(path))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="a trace directory or .xplane.pb file")
    ap.add_argument("--runs", default=INGEST,
                    help="regular expression of the executables to split by scope")
    args = ap.parse_args(argv)
    tr = load(args.path)
    by_scope = tr.scope_ms(args.runs)
    print(f"window {tr.window_s():.6f} s, busy {tr.busy_s():.6f} s per chip")
    for k in sorted(by_scope, key=lambda k: (k == "run", k == "", k)):
        print(f"  {k or '(no scope)'}: {by_scope[k]:.4f} ms per run")
    names = sorted({s.name for s in tr.spans if s.name.startswith(PROGRAM_SPAN)})
    for name in names:
        print(f"  {name}: {len(tr.spans_named(name))} x {tr.span_ms(name) or 0.0:.4f} ms")
    for label, s in tr.idle_by_span(10):
        print(f"  idle in {label}: {s:.6f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
