"""Bytes that an exact block ingest into the degree-aware hybrid stream
state has to read, counted from the layout's counters: the numerator of
``hybrid_ingest_roofline``. Like ``work.py`` it counts what the algorithm
needs, not what an implementation moves: no padding, no capacity, no
phase-2 or state-write traffic.
"""
from __future__ import annotations


def hybrid_ingest_bytes(words: int, pairs_hub: int, fill_mixed: int,
                        fill_tail: int) -> int:
    """Bytes read to close the live edges of a stream into the hybrid
    state: each hub–hub edge reads its two hub rows of ``words`` 4-byte
    words (8·W); each hub–tail edge reads the filled entries of the tail
    end's list and the hub word each entry tests (4 + 4 bytes an entry,
    ``fill_mixed`` entries in all); each tail–tail edge reads the filled
    entries of both lists (4 bytes an entry, ``fill_tail`` in all)."""
    return 8 * int(words) * int(pairs_hub) + 8 * int(fill_mixed) + 4 * int(fill_tail)
