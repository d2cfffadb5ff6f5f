"""The main path's kernels and stream steps, compiled for a described TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
``v5e:2x2`` topology that is described but not attached. A compile that the
chip's compiler refuses (an unsupported cast, a scalar store to VMEM, too
much HBM) fails here, at no chip time. Nothing runs, so no count is checked.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import streaming
from repro.kernels.bitset_count.ops import bitset_edge_count, bitset_pair_count
from repro.kernels.triangle_count.ops import triangle_count

HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["edge", "pair"])
def test_bitset_kernels_compile_at_the_gate(one_chip, kernel):
    """n = 8,192 (W = 256: an 8 MiB table, the VMEM gate) and B = 32,768
    (256 KiB of flattened endpoints, the SMEM gate)."""
    n, b = 8192, 32768
    masks = jax.ShapeDtypeStruct((n, n // 32), jnp.uint32, sharding=one_chip)
    edges = jax.ShapeDtypeStruct((b, 2), jnp.int32, sharding=one_chip)
    assert streaming._kernel_fits(True, n * (n // 32) * 4, b)
    if kernel == "edge":
        lowered = bitset_edge_count.lower(masks, edges, interpret=False)
    else:
        lowered = bitset_pair_count.lower(masks, masks, edges, interpret=False)
    assert _has_kernel(lowered.compile())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.uint8])
def test_live_grid_triangle_kernel_compiles(one_chip, dtype):
    u = jax.ShapeDtypeStruct((1024, 1024), dtype, sharding=one_chip)
    compiled = triangle_count.lower(u, block=128, interpret=False,
                                    live_grid=True).compile()
    assert _has_kernel(compiled)


def test_stream_ingest_fits_one_chip(one_chip):
    """The dense-state ingest at n = 100,000 (1.25 GB of state) and
    B = 65,536 fits one chip's HBM."""
    state = _shapes(jax.eval_shape(lambda: streaming.init_state(100_000)),
                    one_chip)
    edges = jax.ShapeDtypeStruct((65_536, 2), jnp.int32, sharding=one_chip)
    compiled = streaming.ingest_block_donated.lower(
        state, edges, use_kernel=True).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes >= 100_000 * 3125 * 4
    assert used <= HBM_BYTES, used


def test_mesh_ingest_compiles_on_four_chips(topo):
    """The ring-sharded ingest at n = 262,144 (8.6 GB of state, 2.15 GB per
    chip) over the four described devices, with its cross-stage psums."""
    mesh = Mesh(np.asarray(topo.devices), ("stage",))
    n = 262_144
    shape = jax.eval_shape(lambda: streaming.init_sharded_state(n, 4))
    state = {
        "adj": jax.ShapeDtypeStruct(shape["adj"].shape, shape["adj"].dtype,
                                    sharding=NamedSharding(mesh, P("stage"))),
        "count": jax.ShapeDtypeStruct((), shape["count"].dtype,
                                      sharding=NamedSharding(mesh, P())),
    }
    edges = jax.ShapeDtypeStruct((4096, 2), jnp.int32,
                                 sharding=NamedSharding(mesh, P()))
    ingest = streaming.make_mesh_ingest(mesh, use_kernel=True)
    compiled = jax.jit(ingest).lower(state, edges).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 2.2e9  # one shard, not the whole state
    assert "all-reduce" in compiled.as_text()


# The delta-table ingest (a full (n, W_s) table zero-filled, scattered into
# and ORed into the state every block) needed this much temp for the
# windowed cell; the in-place write must come in under it less one table.
_WINDOWED_DELTA_TABLE_TEMP = 6_039_549_440


def _in_place_cell(layout, topo):
    """(compiled donated step, n, W_s, state bytes, temp limit) at the size
    of the benchmark cell that runs ``layout``."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    if layout == "dense":  # s16-tenants8
        n, b = 65_536, 16_384
        state = _shapes(jax.eval_shape(lambda: streaming.init_state(n)), one_chip)
        edges = jax.ShapeDtypeStruct((b, 2), jnp.int32, sharding=one_chip)
        compiled = streaming.ingest_block_donated.lower(state, edges).compile()
        ws = n // 32
        return compiled, n, ws, n * ws * 4, 16 * b * ws + 64 * 2**20
    if layout == "windowed":  # s16-window4
        n, b, e = 65_536, 4_096, 4
        state = _shapes(jax.eval_shape(
            lambda: streaming.init_windowed_state(n, e)), one_chip)
        edges = jax.ShapeDtypeStruct((b, 2), jnp.int32, sharding=one_chip)
        compiled = streaming.ingest_block_windowed_donated.lower(
            state, edges).compile()
        ws = n // 32
        return compiled, n, ws, e * n * ws * 4, \
            _WINDOWED_DELTA_TABLE_TEMP - n * ws * 4
    # mesh, s18-ring4-sessions: one 2.15 GB shard a chip
    mesh = Mesh(np.asarray(topo.devices), ("stage",))
    n, b = 262_144, 16_384
    shape = jax.eval_shape(lambda: streaming.init_sharded_state(n, 4))
    ws = shape["adj"].shape[2]
    state = {
        "adj": jax.ShapeDtypeStruct(shape["adj"].shape, jnp.uint32,
                                    sharding=NamedSharding(mesh, P("stage"))),
        "count": jax.ShapeDtypeStruct((), shape["count"].dtype,
                                      sharding=NamedSharding(mesh, P())),
    }
    edges = jax.ShapeDtypeStruct((b, 2), jnp.int32,
                                 sharding=NamedSharding(mesh, P()))
    # the step donates its shard; an outer donating jit compiles the same
    # program (tests/test_state_write.py pins the step's own donation)
    ingest = streaming.make_mesh_ingest(mesh)
    compiled = jax.jit(ingest, donate_argnums=(0,)).lower(state, edges).compile()
    return compiled, n, ws, n * ws * 4, 16 * b * ws + 64 * 2**20


@pytest.mark.parametrize("layout", ["dense", "mesh", "windowed"])
def test_ingest_writes_the_state_in_place(topo, layout):
    """At each stream cell's size the block is written into the donated
    state: the whole state aliases the output, the temp holds the row
    gathers and no (n, W_s) delta table, and no op lays a table out as a
    flat u32[n·W_s] array (the relayout the element scatter needed)."""
    compiled, n, ws, state_bytes, limit = _in_place_cell(layout, topo)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes, mem
    assert mem.temp_size_in_bytes <= limit, (mem.temp_size_in_bytes, limit)
    assert f"u32[{n * ws}]" not in compiled.as_text()


def test_hybrid_ingest_at_the_scale_20_cell(one_chip):
    """The shapes admission gives a 2**20-vertex stream on one v5e (the
    s20-hybrid-tenants2 cell), in 64 bits: both tables alias the output,
    neither is copied, the temp stays within the working set the planner
    sizes the block by (690 MB; under 1 GiB), and the program has no loop
    (every data-dependent size is a switch over static sizes)."""
    from repro.api.planner import Resources, admit_session, hybrid_block_bytes

    p = admit_session(2**20, Resources(memory_bytes=int(15.75 * 2**30),
                                       backend="tpu")).plan
    assert (p.state_layout, p.hub_slots, p.tail_capacity, p.block_size) == (
        "hybrid", 8192, 512, 2048)
    with jax.enable_x64(True):
        state = _shapes(jax.eval_shape(lambda: streaming.init_hybrid_state(
            2**20, p.hub_slots, p.tail_capacity)), one_chip)
        edges = jax.ShapeDtypeStruct((p.block_size, 2), jnp.int32, sharding=one_chip)
        compiled = streaming.ingest_block_hybrid_donated.lower(
            state, edges, hub_threshold=p.hub_threshold).compile()
        nbytes = streaming.hybrid_state_nbytes(2**20, p.hub_slots, p.tail_capacity)
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    assert mem.alias_size_in_bytes >= nbytes > 3e9
    limit = hybrid_block_bytes(p.block_size, 2**20, p.tail_capacity)
    assert mem.temp_size_in_bytes <= limit < 1 << 30, (mem.temp_size_in_bytes, limit)
    assert "while" not in text
    tables = ("u32[8192,32768]", "s32[1048576,512]", "u32[2097152,128]",
              "s32[4194304,128]")  # the tables and their lane-row views
    copies = [line for line in text.splitlines() if " copy(" in line
              and line.split(" = ", 1)[1].startswith(tables)]
    assert not copies, copies[:2]
