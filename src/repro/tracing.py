"""Spans and named scopes on the profiler's own clock.

Host spans are ``jax.profiler.TraceAnnotation`` events named ``repro.<name>``:
they land in the same trace as the device operations and share their
clock, and they cost next to nothing while no profiler trace is running.
Nesting on one thread gives the causal parent; keyword arguments (a mux
session's ``sid``) become the event's stats.

The ingest phases are ``jax.named_scope``s inside the jitted ingest
bodies (``core/streaming.py``). A scope changes only the operations'
metadata (their ``op_name``, the trace's ``tf_op``), never the compiled
program.

Capture with ``jax.profiler.trace(dir)`` around the calls of interest;
``docs/ARCHITECTURE.md`` ("Tracing") lists what each span and scope covers.
"""
from __future__ import annotations

import jax

# ingest phases (``jax.named_scope`` names, one per phase)
INGEST_LIVE = "ingest.live"  # canonical endpoints, block dedup, already-seen bit
INGEST_AGE_CUM = "ingest.age_cum"  # the windowed age-cumulative OR tables
INGEST_UPDATE = "ingest.update"  # block delta, state write, count update
INGEST_PROMOTE = "ingest.promote"  # hybrid: tail vertices given hub rows
INGEST_TERMS = "ingest.terms"  # row gathers, popcount sums, their reduction


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``repro.<name>`` with ``args`` as its stats."""
    return jax.profiler.TraceAnnotation(f"repro.{name}", **args)
