"""Generic dynamic-pipeline runtime: ring streaming under shard_map.

The paper's dynamic pipeline is a chain of stateful filters through which the
input *streams*; each filter consumes what it is responsible for and forwards
the rest. The TPU-native realization (DESIGN.md §2) fixes the chain into a
ring of SPMD stages (one per device along a mesh axis) and rotates the data
blocks instead of the processes: after S ring steps every stage has seen every
block. Double buffering (the ppermute of block t+1 is issued before the
compute on block t) turns the pipeline's asynchrony into compute/comm overlap
— XLA's latency-hiding scheduler overlaps the collective-permute with the
block computation.

Used by: triangle counting (dense + bitset rings), ring attention for the
500k-token LM shapes, and edge-block streaming for full-graph GNNs.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def ring_stream(
    process: Callable[[Any, Any, jax.Array], Any],
    carry0: Any,
    block0: Any,
    *,
    axis_name: str,
    n_stages: int,
) -> Any:
    """Rotate ``block0`` around the ring, folding each visit into the carry.

    Must be called inside shard_map (an SPMD context where ``axis_name`` is a
    physical mesh axis). ``process(carry, block, src)`` sees every stage's
    original block exactly once; ``src`` is the stage index the block
    originated from (the streamed block's identity — the dynamic pipeline's
    "responsible node" tag).
    """
    me = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(state, _):
        carry, block, src = state
        # Issue the permute BEFORE consuming the block: XLA can overlap the
        # collective-permute with process() (double buffering).
        nxt = jax.lax.ppermute(block, axis_name, perm)
        nsrc = jax.lax.ppermute(src, axis_name, perm)
        carry = process(carry, block, src)
        return (carry, nxt, nsrc), None

    (carry, _, _), _ = jax.lax.scan(body, (carry0, block0, me), None, length=n_stages)
    return carry


@dataclasses.dataclass(frozen=True)
class FilterSpec:
    """A dynamic-pipeline filter, lifted to a stage over a rank partition.

    init(resident)                      -> state       (filter specialization)
    process(state, block, src_stage)    -> state       (consume one streamed block)
    finalize(state)                     -> partial      (the filter's output)

    ``partial`` is psum-reduced over the ring — the paper's aggregation phase
    where partial counts flow down the pipe to a collector.
    """

    init: Callable[[Any], Any]
    process: Callable[[Any, Any, jax.Array], Any]
    finalize: Callable[[Any], Any]


class DynamicPipeline:
    """Execute a FilterSpec over a 1-D ring mesh.

    resident: pytree with leading axis n_stages — stage-local state source
              (the filter's adjacency partition).
    stream:   pytree with leading axis n_stages — the blocks that flow through
              every stage (the edge stream).
    """

    def __init__(self, mesh: Mesh, axis_name: str = "stage"):
        if axis_name not in mesh.axis_names:
            raise ValueError(f"mesh has no axis {axis_name!r}")
        self.mesh = mesh
        self.axis_name = axis_name
        self.n_stages = mesh.shape[axis_name]
        self._jit_cache: dict[FilterSpec, Any] = {}

    def run(self, spec: FilterSpec, resident: Any, stream: Any) -> Any:
        ax = self.axis_name
        n = self.n_stages

        def stage_fn(resident_local, stream_local):
            # shard_map gives block-local views with leading axis 1; drop it.
            resident_local = jax.tree.map(lambda x: x[0], resident_local)
            stream_local = jax.tree.map(lambda x: x[0], stream_local)
            state = spec.init(resident_local)
            state = ring_stream(spec.process, state, stream_local, axis_name=ax, n_stages=n)
            out = spec.finalize(state)
            return jax.tree.map(lambda x: jax.lax.psum(x, ax), out)

        sharded = jax.shard_map(
            stage_fn,
            mesh=self.mesh,
            in_specs=(P(ax), P(ax)),
            out_specs=P(),
            check_vma=False,
        )
        return sharded(resident, stream)

    def jit(self, spec: FilterSpec):
        """Jit the ring for ``spec``, memoized so repeated pipeline runs over
        the same filter reuse one compiled executable. Only effective when
        callers reuse spec objects — the spec constructors in
        triangle_pipeline are lru_cached for exactly this reason."""
        if spec not in self._jit_cache:
            self._jit_cache[spec] = jax.jit(partial(self.run, spec))
        return self._jit_cache[spec]


class ShardedStateStream:
    """Persistent sharded-state stream fold: the pipeline's stage axis reused
    to shard a stream consumer's STATE instead of its input.

    ``ring_stream`` rotates resident blocks through the stages; here the state
    stays put — each stage owns one leading-axis shard of it — and every
    streamed block is broadcast to all stages, which fold it into their shard
    concurrently. Cross-shard terms are the step function's responsibility
    (psum over ``axis_name``). Used by ``core.streaming`` for the
    column-sharded adjacency bitset (n²/8/S bytes per device).
    """

    _shared: dict[tuple, "ShardedStateStream"] = {}

    def __init__(self, mesh: Mesh, axis_name: str = "stage"):
        if axis_name not in mesh.axis_names:
            raise ValueError(f"mesh has no axis {axis_name!r}")
        self.mesh = mesh
        self.axis_name = axis_name
        self.n_stages = mesh.shape[axis_name]
        self._jit_cache: dict[Any, Any] = {}

    @classmethod
    def shared(cls, mesh: Mesh, axis_name: str = "stage") -> "ShardedStateStream":
        """One runtime — hence one shard_map jit cache — per (mesh, axis):
        every consumer (each stream session's mesh ingest, any future
        sharded-state fold) lands its step in the same cache, so concurrent
        serving sessions on one mesh never duplicate a compiled step."""
        key = (mesh, axis_name)
        if key not in cls._shared:
            cls._shared[key] = cls(mesh, axis_name)
        return cls._shared[key]

    def jit_step(self, step_fn: Callable[[Any, Any, Any], tuple[Any, Any]]):
        """Jit ``step_fn(state_local, carry, block) -> (state_local, carry)``
        under shard_map: every ``state`` leaf is sharded on its leading axis
        (which must equal the ring width); ``carry`` and ``block`` are
        replicated, and the returned carry must already be identical across
        stages (psum inside the step). Memoized per step function so repeated
        blocks of one stream reuse one compiled executable."""
        if step_fn not in self._jit_cache:
            ax = self.axis_name

            def stage_fn(state_local, carry, block):
                # shard_map gives block-local views with leading axis 1; drop
                # it for the step and restore it for the out_spec.
                state_local = jax.tree.map(lambda x: x[0], state_local)
                state_local, carry = step_fn(state_local, carry, block)
                return jax.tree.map(lambda x: x[None], state_local), carry

            sharded = jax.shard_map(
                stage_fn,
                mesh=self.mesh,
                in_specs=(P(ax), P(), P()),
                out_specs=(P(ax), P()),
                check_vma=False,
            )
            self._jit_cache[step_fn] = jax.jit(sharded)
        return self._jit_cache[step_fn]


# Bounded: FilterSpecs from the memoized constructors recur (cache hits), but
# hand-built specs are new keys per call and must not pin compiled
# executables forever.
@lru_cache(maxsize=64)
def _sequential_fn(spec: FilterSpec, n_stages: int):
    """Compiled chain emulation: a single trace, scanned over stages.

    The naive emulation retraces spec.process S² times and pays a Python
    dispatch per (stage, block) visit; here each of init/process/finalize is
    traced once and the double loop becomes a scan-of-scans, so small graphs
    stop being dominated by retrace/dispatch overhead.
    """

    def run(resident, stream):
        ts = jnp.arange(n_stages, dtype=jnp.int32)

        def stage_fn(s):
            state0 = spec.init(jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(x, s, keepdims=False), resident))

            def fold(state, t):
                block = jax.tree.map(
                    lambda x: jax.lax.dynamic_index_in_dim(x, t, keepdims=False), stream)
                return spec.process(state, block, t), None

            state, _ = jax.lax.scan(fold, state0, ts)
            return spec.finalize(state)

        out_sds = jax.eval_shape(stage_fn, jax.ShapeDtypeStruct((), jnp.int32))
        total0 = jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype), out_sds)

        def outer(total, s):
            return jax.tree.map(jnp.add, total, stage_fn(s)), None

        total, _ = jax.lax.scan(outer, total0, ts)
        return total

    return jax.jit(run)


def run_sequential(spec: FilterSpec, resident: Any, stream: Any, n_stages: int) -> Any:
    """Paper-faithful single-process pipeline: stages visited in chain order.

    Semantically identical to the ring (every stage sees every block); used on
    hosts without a device ring and as the differential-testing oracle for
    DynamicPipeline. Traced once and executed as a jitted scan-of-scans —
    see ``run_sequential_python`` for the unjitted original (kept as the
    benchmark baseline and trace-free oracle).
    """
    return _sequential_fn(spec, n_stages)(resident, stream)


def run_sequential_python(spec: FilterSpec, resident: Any, stream: Any, n_stages: int) -> Any:
    """Original eager chain emulation: O(S²) Python dispatches, one retrace of
    spec.process per visit when process itself jits. Kept as the seed baseline
    for BENCH_kernels.json and as a differential oracle for ``run_sequential``."""
    partials = []
    for s in range(n_stages):
        state = spec.init(jax.tree.map(lambda x: x[s], resident))
        for t in range(n_stages):
            block = jax.tree.map(lambda x: x[t], stream)
            state = spec.process(state, block, jnp.int32(t))
        partials.append(spec.finalize(state))
    total = partials[0]
    for p in partials[1:]:
        total = jax.tree.map(jnp.add, total, p)
    return total
