"""CPU tests of what running on a chip needs: devices detected, not assumed;
interpret mode decided in one place; one chip per worker; a fixed compile
cache path; counts that never wrap; and a smoke script that refuses to
report without a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from repro.api import Plan, Resources, TriangleCounter
from repro.api.planner import CPU_MEMORY_BYTES
from repro.core import triangle_pipeline
from repro.core.triangle_ref import count_triangles_brute
from repro.graphs import generators as gen
from repro.graphs.formats import Graph
from repro.launch.mesh import make_ring_mesh
from repro.serve.cluster import protocol, worker
from repro.serve.cluster.client import WorkerClient, host_tpu_chips
from repro.utils import (compile_cache_dir, interpret_mode, require_count_capacity,
                         triangle_bound)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_device(platform: str, bytes_limit: int | None, kind: str = "fake"):
    stats = None if bytes_limit is None else {"bytes_limit": bytes_limit}
    return types.SimpleNamespace(platform=platform, device_kind=kind,
                                 memory_stats=lambda: stats)


# -- devices, resources, interpret mode --------------------------------------
def test_make_ring_mesh_raises_past_the_device_count():
    n = len(jax.devices())
    assert make_ring_mesh(n).devices.size == n
    with pytest.raises(ValueError, match="needs"):
        make_ring_mesh(n + 1)


def test_default_resources_report_the_live_backend():
    dev = jax.devices()[0]
    for res in (TriangleCounter().resources, Resources.detect()):
        assert res.backend == dev.platform
        assert res.n_devices == len(jax.devices())
        assert res.memory_bytes == CPU_MEMORY_BYTES  # the CPU reports none


def test_detect_reads_bytes_limit_and_refuses_a_silent_accelerator(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda: [_fake_device("tpu", 15 << 30)])
    res = Resources.detect()
    assert (res.backend, res.memory_bytes, res.n_devices) == ("tpu", 15 << 30, 1)
    assert Resources.detect(memory_bytes=1 << 20).memory_bytes == 1 << 20
    monkeypatch.setattr(jax, "devices", lambda: [_fake_device("tpu", None)])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        Resources.detect()


def test_interpret_mode_follows_the_backend_and_kernels_record_it():
    from repro.core.triangle_pipeline import count_triangles_dense
    from repro.graphs.formats import forward_adjacency_dense
    from repro.kernels import kernel_traces

    assert interpret_mode() is (jax.default_backend() != "tpu")
    assert interpret_mode(False) is False
    g = gen.gnp(70, 0.4, seed=11)
    k0 = len(kernel_traces())
    got = count_triangles_dense(np.asarray(forward_adjacency_dense(g), np.float32),
                                use_kernel=True)
    assert int(got) == count_triangles_brute(g)
    assert ("triangle_count_live", "interpret") in kernel_traces()[k0:]


def test_plan_carries_no_interpret_switch():
    assert "interpret" not in Plan(method="dense").to_dict()
    assert TriangleCounter(Resources(backend="tpu")).batch_plan().use_kernel


# -- compile cache ------------------------------------------------------------
def test_compile_cache_dir_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache_dir() == os.path.join(ROOT, ".jax_cache")
    assert compile_cache_dir() == compile_cache_dir()  # fixed, not per call


# -- counts never wrap ---------------------------------------------------------
def test_capacity_guard_raises_before_counting():
    assert triangle_bound(4, 6) == 4  # K4
    assert triangle_bound(3000, 3000 * 2999 // 2) == 3000 * 2999 * 2998 // 6
    require_count_capacity(2**31 - 1, "fits")
    if jax.config.jax_enable_x64:
        pytest.skip("64-bit counts hold every bound")
    with pytest.raises(OverflowError, match="x64"):
        require_count_capacity(2**31, "too many")


@pytest.mark.skipif(jax.config.jax_enable_x64, reason="64-bit counts hold it")
def test_counter_refuses_a_graph_whose_count_can_wrap():
    n = 3000  # K_3000 has 4.5e9 triangles
    iu = np.triu_indices(n, 1)
    g = Graph(n_nodes=n, edges=np.stack(iu, 1).astype(np.int32))
    counter = TriangleCounter()
    for method in ("dense", "sparse", "ring"):
        with pytest.raises(OverflowError):
            counter.count(g, plan=Plan(method=method))
    session = counter.open_stream(n, block_size=4096)
    with pytest.raises(OverflowError):
        session.feed(g.edges)
    assert session.edges_fed == [0]  # refused before any ingest


def test_windowed_guard_counts_only_live_epochs():
    """A window holds E epochs: a long windowed stream is bounded by the
    edges of its live epochs, not by every edge it was ever fed."""
    counter = TriangleCounter()
    session = counter.open_stream(50, window=2, block_size=64)
    for t in range(4):
        if t:
            session.advance()
        session.feed(np.array([[t, t + 1], [t, t + 2]], np.int32))
    assert session.edges_fed == [2, 2]
    restored = counter.restore_stream(session.checkpoint())
    assert restored.edges_fed == [2, 2]


def test_bitset_ring_xla_sweep_chunks_to_the_same_count(monkeypatch):
    g = gen.gnp(90, 0.5, seed=2)
    want = count_triangles_brute(g)
    assert triangle_pipeline.count_triangles_bitset_ring(g, n_stages=1) == want
    triangle_pipeline.bitset_ring_spec.cache_clear()
    monkeypatch.setattr(triangle_pipeline, "_XLA_GATHER_BUDGET", 8 * 128 * 4)
    try:
        assert triangle_pipeline.count_triangles_bitset_ring(g, n_stages=1) == want
    finally:
        triangle_pipeline.bitset_ring_spec.cache_clear()


# -- one chip per worker --------------------------------------------------------
def test_worker_device_checks():
    args = types.SimpleNamespace(chip=None, devices=1)
    cpu, tpu = _fake_device("cpu", None), _fake_device("tpu", 16 << 30)
    worker._check_devices([cpu], args)
    worker._check_devices([tpu], args)
    with pytest.raises(RuntimeError, match="exactly one chip"):
        worker._check_devices([tpu] * 4, args)
    with pytest.raises(RuntimeError, match="no free TPU chip"):
        worker._check_devices([cpu], types.SimpleNamespace(chip=1, devices=1))
    with pytest.raises(RuntimeError, match="forces CPU host devices"):
        worker._check_devices([tpu], types.SimpleNamespace(chip=0, devices=2))


def test_chip_pinned_worker_fails_at_spawn_without_a_chip():
    assert host_tpu_chips() == 0  # JAX_PLATFORMS rules the TPU out here
    with pytest.raises(protocol.WorkerDied, match="no free TPU chip"):
        WorkerClient.spawn(chip=0, startup_timeout_s=120.0)


# -- the chip smoke script --------------------------------------------------------
def test_chip_smoke_refuses_to_report_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert "TPU" in r.stdout
    last = (r.stdout.strip().splitlines() or [""])[-1]
    assert not last.startswith("{")
    with pytest.raises(json.JSONDecodeError):
        json.loads(last)
