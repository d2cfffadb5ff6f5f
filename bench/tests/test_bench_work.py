"""Byte counts (bench/work.py) at the cells' shapes, and the peak table
(bench/peaks.py)."""
import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{name}", os.path.join(HERE, "..", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


work = _load("work")
peaks = _load("peaks")


def test_bitset_count_bytes_fna1():
    # FNA.1: n = 10,000 (313 words a row), m = 10M edges
    assert work.words(10_000) == 313
    assert work.bitset_count_bytes(10_000, 10_000_000) == 2 * 10_000_000 * 313 * 4 + 80_000_000
    assert work.bitset_count_bytes(10_000, 10_000_000) == 25_120_000_000


@pytest.mark.parametrize("block, n, stages, epochs, want", [
    (16_384, 65_536, 1, 1, 536_870_912),   # s16-tenants8: 16 * 16384 * 2048
    (4_096, 65_536, 1, 4, 536_870_912),    # s16-window4: 16 * 4096 * 2048 * 4
    (16_384, 262_144, 4, 1, 536_870_912),  # a 4-stage scale-18 ring: 2048-word shards
])
def test_ingest_bytes_at_the_cells_shapes(block, n, stages, epochs, want):
    assert work.ingest_bytes(block, n, stages, epochs) == want


def test_peaks_known_and_unknown():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
