"""``TriangleCounter`` — the planned, compile-cached execution engine.

One object owns one compile cache, keyed by ``(plan.cache_key(), shape
bucket)``: operands are padded up to power-of-two buckets with the phantom
convention each path already understands (zero rows for the dense matmul,
sentinel ids >= n_pad for sparse/mapreduce/stream), so repeated calls on
same-bucket graphs reuse one traced executable instead of retracing per
shape. Every entry point returns a :class:`CountResult` whose ``count`` stays
a device array until ``.item()`` — callers that feed the count onward (batch
aggregation, the serve loop) never pay a host sync per call.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.planner import GraphStats, Plan, Resources, plan as plan_fn
from repro.tracing import span
from repro.utils import require_count_capacity, triangle_bound


def bucket(x: int, minimum: int = 64) -> int:
    """Next power of two >= x (>= minimum) — the shape-bucketing policy."""
    b = minimum
    while b < x:
        b *= 2
    return b


@dataclasses.dataclass
class CountResult:
    """The single result contract for every counting path.

    count:  device array — scalar for ``count``/``count_stream``, a vector of
            per-graph counts for ``count_batch``. Stays on device until
            ``.item()`` / ``np.asarray`` so hot loops avoid per-call syncs.
    plan:   the executed :class:`Plan` (method, predicted bytes, reason).
    wall_s: host wall time of build+dispatch (async dispatch: excludes device
            completion unless the path is synchronous anyway).
    stats:  per-run details — cache key/hit/trace count, stage costs for ring
            plans, block counts for streams.
    """

    count: Any
    plan: Plan
    wall_s: float
    stats: dict = dataclasses.field(default_factory=dict)

    def item(self) -> int:
        return int(np.asarray(self.count).item())

    def __int__(self) -> int:
        return self.item()


@dataclasses.dataclass
class SessionCheckpoint:
    """A host-side, bit-exact snapshot of one :class:`StreamSession` — the
    unit of preemption, spill, and (future) cross-worker migration.

    Taken by :meth:`StreamSession.checkpoint` (which first flushes the
    buffered tail so the snapshot boundary is exactly "every edge fed so
    far") and consumed by :meth:`TriangleCounter.restore_stream`, which
    resumes the stream BIT-IDENTICALLY: same state arrays, same compile-cache
    key (so restore never retraces an already-traced block shape), same
    sticky re-blocking shapes (``buffer_shape``), same running stats.

    ``arrays`` is the numpy rendering of the session's state dict —
    ``{adj, count}`` unbounded, ``{epochs, counts, head}`` windowed, with the
    leading stage axis kept for sharded states (the emulated and mesh
    layouts share it, so a checkpoint taken on either restores onto either).
    ``nbytes`` is what the snapshot charges against a host checkpoint budget;
    ``state_bytes`` is the per-stage device footprint the session pins when
    restored (what admission re-charges on readmission). ``spill``/``load``
    round-trip the checkpoint through one COMPRESSED ``.npz`` file for
    storage beyond the host budget — ``arrays`` is None while spilled, and
    ``disk_bytes`` is the file's actual on-disk size (sparse bitset rows
    deflate heavily, so disk budgets charge compressed bytes, not
    ``nbytes``).
    """

    n_nodes: int
    plan: Plan
    block_size: int
    state_bytes: int
    nbytes: int
    arrays: dict | None
    buffer_shape: dict
    n_blocks: int
    n_epochs_advanced: int
    wall_s: float
    path: str | None = None
    disk_bytes: int | None = None
    # edges fed per epoch the state still holds (one entry for an unbounded
    # stream): carries the count-capacity guard across restore
    edges_fed: tuple = (0,)

    @property
    def spilled(self) -> bool:
        return self.arrays is None

    def spill(self, path: str) -> None:
        """Move the snapshot arrays from host memory to one COMPRESSED
        ``.npz`` at ``path`` (everything else — plan, shapes, stats — stays
        in the object). Bitset state is mostly zero words for sparse
        streams, so deflate routinely shrinks the snapshot by an order of
        magnitude; ``disk_bytes`` records the real file size for disk-budget
        accounting. Idempotent on an already-spilled checkpoint."""
        if self.arrays is None:
            return
        meta = json.dumps({
            "n_nodes": self.n_nodes, "plan": self.plan.to_dict(),
            "block_size": self.block_size, "state_bytes": self.state_bytes,
            "nbytes": self.nbytes, "buffer_shape": self.buffer_shape,
            "n_blocks": self.n_blocks,
            "n_epochs_advanced": self.n_epochs_advanced,
            "edges_fed": list(self.edges_fed),
            "wall_s": self.wall_s})
        np.savez_compressed(path, __meta__=np.array(meta), **self.arrays)
        self.arrays, self.path = None, path
        self.disk_bytes = int(os.path.getsize(path))

    def load_arrays(self) -> dict:
        """The snapshot arrays, loading (and deleting) the spill file if the
        checkpoint was spilled."""
        if self.arrays is None:
            with np.load(self.path) as z:
                self.arrays = {k: z[k] for k in z.files if k != "__meta__"}
            os.remove(self.path)
            self.path, self.disk_bytes = None, None
        return self.arrays

    def discard(self) -> None:
        """Drop the snapshot (and its spill file, if any) — a cancelled
        session's state is not coming back."""
        if self.path is not None and os.path.exists(self.path):
            os.remove(self.path)
        self.arrays, self.path = None, None

    def finalize_result(self) -> "CountResult":
        """Finalize WITHOUT touching the device: ``checkpoint()`` flushed the
        buffered tail, so the snapshot already covers every edge fed and the
        count is simply read out of the host arrays — the running total for
        unbounded sessions, the sum over the epoch ring's per-slot counters
        for windowed ones. Value and dtype are bit-identical to restoring
        and finalizing; the scheduler uses this as the zero-cost close for a
        parked session nobody fed since its checkpoint."""
        arrays = self.load_arrays()
        p = self.plan
        if p.window_epochs:
            count = jnp.asarray(arrays["counts"].sum(
                dtype=arrays["counts"].dtype))
        else:
            if int(arrays.get("lost", 0)):
                raise RuntimeError(
                    f"hybrid stream checkpoint recorded "
                    f"{int(arrays['lost'])} dropped edge endpoint(s) — its "
                    f"count is not exact and cannot be finalized")
            count = jnp.asarray(arrays["count"])
        stats = {"n_blocks": self.n_blocks, "block_size": self.block_size,
                 "n_stages": p.n_stages, "sharded": p.n_stages > 1,
                 "session": True, "from_checkpoint": True,
                 "state_bytes": self.nbytes}
        if p.window_epochs:
            stats["window_epochs"] = p.window_epochs
            stats["epochs_advanced"] = self.n_epochs_advanced
        if p.state_layout == "hybrid":
            from repro.core.streaming import hybrid_stats

            stats["hybrid"] = hybrid_stats(arrays)
        return CountResult(count=count, plan=p, wall_s=self.wall_s,
                           stats=stats)

    @classmethod
    def from_file(cls, path: str) -> "SessionCheckpoint":
        """Rehydrate a checkpoint something else spilled/shipped — the
        migration entry point (checkpoint on worker A, ``from_file`` +
        ``restore_stream`` on worker B)."""
        with np.load(path) as z:
            meta = json.loads(str(z["__meta__"][()]))
            arrays = {k: z[k] for k in z.files if k != "__meta__"}
        return cls(n_nodes=meta["n_nodes"], plan=Plan.from_dict(meta["plan"]),
                   block_size=meta["block_size"],
                   state_bytes=meta["state_bytes"], nbytes=meta["nbytes"],
                   arrays=arrays, buffer_shape=meta["buffer_shape"],
                   n_blocks=meta["n_blocks"],
                   n_epochs_advanced=meta["n_epochs_advanced"],
                   edges_fed=tuple(meta.get("edges_fed", (0,))),
                   wall_s=meta["wall_s"],
                   disk_bytes=int(os.path.getsize(path)))


class _Entry:
    __slots__ = ("fn", "traces", "hits")

    def __init__(self, fn):
        self.fn = fn
        self.traces = 0
        self.hits = -1  # first use is the miss


class TriangleCounter:
    """The front door: plan (or accept a plan), execute, cache the compile.

    ``mesh`` routes ring plans through ``DynamicPipeline``; without one they
    run the paper-faithful sequential chain emulation.
    """

    def __init__(self, resources: Resources | None = None, *,
                 plan: Plan | None = None, mesh=None):
        self.resources = resources or Resources.detect()
        self.fixed_plan = plan
        self.mesh = mesh
        self._cache: dict[tuple, _Entry] = {}

    # -- planning ----------------------------------------------------------
    def plan_for(self, g, *, allow: set[str] | None = None) -> Plan:
        if self.fixed_plan is not None:
            return self.fixed_plan
        with span("plan"):
            with span("plan.stats"):
                stats = GraphStats.from_graph(g)
            with span("plan.choose"):
                return plan_fn(stats, self.resources, allow=allow)

    # -- compile cache -----------------------------------------------------
    def _entry(self, key: tuple, make) -> _Entry:
        entry = self._cache.get(key)
        if entry is None:
            entry = _Entry(None)
            entry.fn = make(entry)
            self._cache[key] = entry
        entry.hits += 1
        return entry

    @property
    def cache_info(self) -> dict:
        return {
            "entries": len(self._cache),
            "traces": sum(e.traces for e in self._cache.values()),
            "hits": sum(max(e.hits, 0) for e in self._cache.values()),
        }

    # -- entry points ------------------------------------------------------
    def count(self, g, *, plan: Plan | None = None) -> CountResult:
        """Count triangles in a memory-resident graph.

        Plan resolution order: the ``plan`` argument, else the counter's
        fixed plan, else the planner on ``GraphStats.from_graph(g)`` — every
        execution knob comes from the resolved plan, never from defaults.
        The executable is cached under ``(plan.cache_key(), shape bucket)``:
        operands pad to power-of-two buckets, so same-bucket graphs reuse one
        trace across calls (``stats["cache"]`` records key/hit/traces).
        Traced as ``repro.count``, with the executor's
        ``repro.count.operands`` (host operand build), ``repro.count.put``
        (host-to-device copies) and ``repro.count.dispatch`` inside."""
        with span("count"):
            p = plan or self.plan_for(g)
            t0 = time.perf_counter()
            executor = getattr(self, f"_run_{p.method}", None)
            if executor is None:
                raise ValueError(f"plan method {p.method!r} not executable here")
            if p.method != "stream":  # a stream session guards its own feeds
                require_count_capacity(triangle_bound(g.n_nodes, g.n_edges),
                                       f"{p.method} count of a {g.n_nodes}-node, "
                                       f"{g.n_edges}-edge graph")
            count, stats = executor(g, p)
            return CountResult(count=count, plan=p,
                               wall_s=time.perf_counter() - t0, stats=stats)

    def open_stream(self, n_nodes: int, *, plan: Plan | None = None,
                    block_size: int | None = None,
                    window: int | None = None) -> "StreamSession":
        """Open a :class:`StreamSession` — the handle behind every streaming
        entry point (``count_stream`` is open → feed → finalize in one call;
        the serve loop's ``StreamMultiplexer`` interleaves many).

        Plan resolution order (identical to ``count_stream``): the ``plan``
        argument, else the counter's fixed plan, else the planner on
        not-memory-resident stats — resolved BEFORE the block size, so the
        planner's ``block_size``/``n_stages`` actually apply; an explicit
        ``block_size`` argument still overrides the plan's. Plans whose
        method is not ``"stream"`` are rejected — silently streaming under a
        dense/ring plan would ignore every knob the caller thought they set.

        ``window = E`` opens a SLIDING-WINDOW session (state: a ring of E
        epoch bitsets, E·n²/8 bytes, /S per stage — see
        ``core.streaming.init_windowed_state``): ``feed`` lands edges in the
        current epoch, :meth:`StreamSession.advance` slides the window, and
        ``finalize`` returns the live window's count. When a plan is also
        resolved, its ``window_epochs`` must agree with ``window`` (pass one
        or the other); with no plan the planner is asked for a windowed
        stream plan (E-scaled sizing).

        The session's jitted ingest step registers in THIS counter's compile
        cache under ``(plan.cache_key(), ("stream", n_nodes, block_size,
        on_mesh))`` — ``cache_key`` includes ``window_epochs`` — and the
        underlying ingest functions are module-level jits keyed by block
        shape, so S concurrent sessions feeding one block shape cost exactly
        one trace, shared across all of them AND across every epoch of a
        windowed session (epoch advances rotate a traced head).
        """
        p = plan or self.fixed_plan
        if p is None:
            stats = GraphStats(n_nodes=n_nodes, n_edges=0, replication_factor=0,
                               max_degree=0, max_fwd_degree=0, edges_in_memory=False)
            p = plan_fn(stats, self.resources, window_epochs=window or 0)
        elif window is not None and p.window_epochs != window:
            raise ValueError(
                f"window={window} conflicts with the resolved plan's "
                f"window_epochs={p.window_epochs} — pass the window through "
                f"the plan OR the argument, not both")
        if p.method != "stream":
            raise ValueError(
                f"count_stream requires a plan with method='stream', got "
                f"{p.method!r} — use count()/count_batch() for memory-resident "
                f"plans, or drop the plan to let the planner size the stream")
        if p.state_layout == "hybrid" and (p.window_epochs or p.n_stages > 1):
            # the planner never emits these combinations; reject hand-built
            # plans before they allocate a state no ingest path understands
            raise ValueError(
                "state_layout='hybrid' supports only unbounded single-stage "
                f"streams (got window_epochs={p.window_epochs}, "
                f"n_stages={p.n_stages}) — the windowed epoch ring and the "
                "mesh stage axis stay bitset")
        if block_size is None:
            block_size = p.block_size
        return StreamSession(self, n_nodes, p, block_size,
                             self.mesh_matches(p.n_stages))

    def restore_stream(self, ckpt: SessionCheckpoint) -> "StreamSession":
        """Resume a checkpointed stream session — the other half of
        :meth:`StreamSession.checkpoint` and the primitive under the
        scheduler's preemption (and a future multi-host router's migration).

        The restored session continues BIT-IDENTICALLY to one that was never
        interrupted: the state arrays are rehydrated exactly
        (``core.streaming.restore_state``), the session registers under the
        SAME compile-cache key as the original — so restoring onto a counter
        that has already traced the stream's block shapes retraces nothing —
        and the re-blocking buffer resumes the checkpoint's sticky shapes.
        The checkpoint's plan must be a stream plan (it always is when the
        checkpoint came from ``checkpoint()``); restoring a ring-sharded
        checkpoint works on mesh and emulated counters alike (the layouts
        share the stage-major shape). The session re-pins its
        ``state_bytes`` on device the moment it is constructed — callers
        budgeting admission charge it exactly like a fresh open."""
        from repro.core import streaming

        on_mesh = self.mesh_matches(ckpt.plan.n_stages)
        session = StreamSession(
            self, ckpt.n_nodes, ckpt.plan, ckpt.block_size, on_mesh,
            state=streaming.restore_state(ckpt.load_arrays(),
                                          mesh=self.mesh if on_mesh else None))
        session._buffer.import_shape_state(ckpt.buffer_shape)
        session.n_blocks = ckpt.n_blocks
        session.n_epochs_advanced = ckpt.n_epochs_advanced
        session.edges_fed = list(ckpt.edges_fed)
        session._wall = ckpt.wall_s
        session.restored = True
        return session

    def count_stream(self, n_nodes: int, blocks: Iterable, *,
                     plan: Plan | None = None,
                     block_size: int | None = None) -> CountResult:
        """Fold an iterable of (B, 2) edge blocks — ``core.streaming`` behind
        the same result contract, as a one-session wrapper over
        :meth:`open_stream` (see it for the plan-resolution order, the
        stream-plan requirement, and the cache-keying contract).

        ``n_stages > 1`` runs the ring-sharded ingest (column-sharded
        adjacency, n²/8/S bytes per stage) — on ``self.mesh`` when its size
        matches, else host-emulated. The ingest step lives in this counter's
        compile cache, so e.g. serve-loop streams share it across requests."""
        session = self.open_stream(n_nodes, plan=plan, block_size=block_size)
        for b in blocks:
            session.feed(b)
        return session.finalize()

    def count_windowed(self, n_nodes: int, epochs: Iterable, *,
                       window: int | None = None, plan: Plan | None = None,
                       block_size: int | None = None) -> CountResult:
        """Count triangles over a SLIDING WINDOW of an edge stream: consume
        an iterable of EPOCHS — each itself an iterable of (B, 2) edge
        blocks — and return the triangle count of the final window (the last
        ``window`` epochs). A one-session wrapper over :meth:`open_stream`
        with ``window=``: each epoch is fed, the window advances between
        epochs (``StreamSession.advance`` — a single epoch-slot clear, no
        per-edge deletes), and ``finalize`` reads the live count.

        Plan resolution and cache keying follow :meth:`open_stream`; the
        session pins E·n²/8 bytes (E epoch bitsets; /S per stage when the
        plan ring-shards) and the whole stream costs one ingest trace per
        block shape regardless of how many epochs it spans."""
        p = plan or self.fixed_plan
        if not window and (p is None or not p.window_epochs):
            # validate BEFORE open_stream allocates state and registers a
            # compile-cache entry for a session that would never run
            raise ValueError(
                "count_windowed needs a windowed session — pass window=E or "
                "a plan with window_epochs > 0")
        session = self.open_stream(n_nodes, plan=plan, block_size=block_size,
                                   window=window)
        first = True
        for epoch_blocks in epochs:
            if not first:
                session.advance()
            first = False
            for b in epoch_blocks:
                session.feed(b)
        return session.finalize()

    def _make_stream(self, entry: _Entry, p: Plan, on_mesh: bool):
        from functools import partial as _partial

        from repro.core import streaming

        # The ingest fns are module-level jits (shared across counters); a
        # fresh cache entry stands for at most one trace per fixed-shape
        # stream (see streaming.ingest_trace_count for the exact telemetry).
        # Every non-mesh session path picks the DONATED twin uniformly: the
        # session rebinds its state on every ingest, so the input buffers
        # alias into the output and steady-state feeds allocate nothing.
        # Uniform selection is what keeps the one-trace pins valid — the
        # donated and plain jits trace separately, so mixing them per
        # session would double the trace count per shape.
        entry.traces += 1
        if p.state_layout == "hybrid":
            # degree-aware hybrid state: hub bitset rows + tail buffers;
            # hub_threshold is the jit-static promotion knob (in cache_key)
            return _partial(streaming.ingest_block_hybrid_donated,
                            hub_threshold=p.hub_threshold)
        if p.window_epochs:
            if p.n_stages > 1:
                if on_mesh:
                    return streaming.make_mesh_ingest_windowed(
                        self.mesh, use_kernel=p.use_kernel)
                return streaming.ingest_block_windowed_sharded_donated
            return _partial(streaming.ingest_block_windowed_donated,
                            use_kernel=p.use_kernel)
        if p.n_stages > 1:
            if on_mesh:
                return streaming.make_mesh_ingest(
                    self.mesh, use_kernel=p.use_kernel)
            return streaming.ingest_block_sharded_donated
        return _partial(streaming.ingest_block_donated, use_kernel=p.use_kernel)

    def batch_plan(self) -> Plan:
        """The dense plan ``count_batch`` runs when none is given: derived
        from ``self.resources`` so the backend decision (the Pallas kernels
        on TPU, the XLA formulation elsewhere) carries into batched serving
        instead of silently reverting to the Plan defaults."""
        from repro.api.planner import backend_exec_flags

        res = self.resources
        return Plan(method="dense", **backend_exec_flags(res),
                    reason=f"batched dense path ({res.backend} backend)")

    def count_batch(self, graphs: list, *, plan: Plan | None = None) -> CountResult:
        """Vmapped dense path over many small graphs: one compiled executable
        per (batch bucket, node bucket) counts the whole batch in one call.
        ``count`` is the (len(graphs),) per-graph vector.

        Plan resolution: the ``plan`` argument, else :meth:`batch_plan`
        (derived from ``self.resources`` so the backend kernel switch
        survives batching). NOTE: the counter's fixed plan is deliberately
        NOT consulted — a fixed single-graph plan rarely describes a batch;
        pass ``plan=`` explicitly to force one. Non-``dense`` plans are
        rejected. Cached under ``(("batch_dense",) + plan.cache_key(),
        (batch bucket, node bucket))``, both buckets power-of-two padded."""
        from repro.graphs.formats import forward_adjacency_dense

        if not graphs:
            raise ValueError("empty batch")
        p = plan or self.batch_plan()
        if p.method != "dense":
            raise ValueError(
                f"count_batch is the vmapped dense path; got a plan with "
                f"method={p.method!r}")
        t0 = time.perf_counter()
        require_count_capacity(
            max(triangle_bound(g.n_nodes, g.n_edges) for g in graphs),
            "batched dense count")
        n_b = bucket(max(g.n_nodes for g in graphs))
        b_b = bucket(len(graphs), minimum=8)
        us = np.zeros((b_b, n_b, n_b), np.float32)
        for i, g in enumerate(graphs):
            us[i, :g.n_nodes, :g.n_nodes] = forward_adjacency_dense(g)
        key = (("batch_dense",) + p.cache_key(), (b_b, n_b))
        entry = self._entry(key, lambda e: self._make_batch_dense(e, p))
        counts = entry.fn(jnp.asarray(us))[: len(graphs)]
        return CountResult(
            count=counts, plan=p, wall_s=time.perf_counter() - t0,
            stats={"cache": self._cache_stats(key, entry),
                   "batch_size": len(graphs), "bucket": (b_b, n_b)},
        )

    def _cache_stats(self, key: tuple, entry: _Entry) -> dict:
        return {"key": key, "hit": entry.hits > 0, "traces": entry.traces}

    # -- executors (one per plan method) -----------------------------------
    def _run_dense(self, g, p: Plan):
        from repro.graphs.formats import forward_adjacency_dense

        with span("count.operands"):
            n_b = bucket(g.n_nodes)
            u = np.zeros((n_b, n_b), np.float32)
            u[: g.n_nodes, : g.n_nodes] = forward_adjacency_dense(g)
        key = (p.cache_key(), (n_b,))
        entry = self._entry(key, lambda e: self._make_dense(e, p))
        with span("count.put"):
            u = jnp.asarray(u)
        with span("count.dispatch"):
            out = entry.fn(u)
        return out, {"cache": self._cache_stats(key, entry)}

    def _make_dense(self, entry: _Entry, p: Plan):
        from repro.core.triangle_pipeline import count_triangles_dense

        def body(u):
            entry.traces += 1
            return count_triangles_dense(u, use_kernel=p.use_kernel)

        return jax.jit(body)

    def _make_batch_dense(self, entry: _Entry, p: Plan):
        from repro.core.triangle_pipeline import count_triangles_dense

        def body(us):
            entry.traces += 1
            return jax.vmap(lambda u: count_triangles_dense(
                u, use_kernel=p.use_kernel))(us)

        return jax.jit(body)

    def _run_sparse(self, g, p: Plan):
        from repro.graphs.formats import degree_order, forward_adjacency_padded

        with span("count.operands"):
            rank = degree_order(g)
            nbrs, _ = forward_adjacency_padded(g, rank)
            n, md = nbrs.shape
            n_b = bucket(n)
            md_b = bucket(max(md, 1), minimum=8)
            # re-sentinel into bucket space: padding value must equal n_pad = n_b
            nb = np.full((n_b, md_b), n_b, np.int32)
            nb[:n, :md] = np.where(nbrs == n, n_b, nbrs)
            ru = rank[g.edges[:, 0]]
            rv = rank[g.edges[:, 1]]
            edges = np.stack([np.minimum(ru, rv), np.maximum(ru, rv)], axis=1)
            m_b = bucket(max(g.n_edges, 1), minimum=256)
            ed = np.full((m_b, 2), n_b, np.int32)
            ed[: g.n_edges] = edges
        key = (p.cache_key(), (n_b, md_b, m_b))
        entry = self._entry(key, lambda e: self._make_sparse(e, p))
        with span("count.put"):
            nb, ed = jnp.asarray(nb), jnp.asarray(ed)
        with span("count.dispatch"):
            out = entry.fn(nb, ed)
        return out, {"cache": self._cache_stats(key, entry)}

    def _make_sparse(self, entry: _Entry, p: Plan):
        from repro.core.triangle_pipeline import count_triangles_sparse

        def body(nbrs, edges):
            entry.traces += 1
            return count_triangles_sparse(nbrs, edges, edge_batch=p.edge_batch)

        return jax.jit(body)

    def _run_ring(self, g, p: Plan):
        from repro.core.dynamic_pipeline import DynamicPipeline, run_sequential
        from repro.core.triangle_pipeline import build_dense_ring_operands, dense_ring_spec

        # pad_to a power-of-two per-stage row count: same-bucket graphs share
        # the block shapes, hence the compiled ring
        pad_to = bucket(max(-(-g.n_nodes // p.n_stages), 1), minimum=8)
        with span("count.operands"):
            part, blocks = build_dense_ring_operands(g, p.n_stages, balance=p.balance,
                                                     pad_to=pad_to)
        spec = dense_ring_spec(part.rows_per_stage, use_kernel=p.use_kernel)
        with span("count.put"):
            blocks = jnp.asarray(blocks)
        key = (p.cache_key(), ("ring", p.n_stages, part.rows_per_stage))
        if self.mesh_matches(p.n_stages):
            entry = self._entry(key, lambda e: self._mark_traced(
                e, DynamicPipeline(self.mesh, self.mesh.axis_names[0]).jit(spec)))
        else:
            entry = self._entry(key, lambda e: self._mark_traced(
                e, lambda r, s: run_sequential(spec, r, s, p.n_stages)))
        with span("count.dispatch"):
            out = entry.fn(blocks, blocks)
        return out, {"cache": self._cache_stats(key, entry)}

    def _run_bitset_ring(self, g, p: Plan):
        from repro.core.dynamic_pipeline import DynamicPipeline, run_sequential
        from repro.core.triangle_pipeline import bitset_ring_spec, build_bitset_ring_operands

        pad_to = bucket(max(-(-g.n_nodes // p.n_stages), 1), minimum=8)
        edge_block = bucket(max(-(-g.n_edges // p.n_stages), 1), minimum=128)
        with span("count.operands"):
            _, masks, edges = build_bitset_ring_operands(
                g, p.n_stages, balance=p.balance, pad_to=pad_to, edge_block=edge_block)
        spec = bitset_ring_spec(use_kernel=p.use_kernel)
        with span("count.put"):
            masks, edges = jnp.asarray(masks), jnp.asarray(edges)
        key = (p.cache_key(), ("bitset", p.n_stages) + tuple(masks.shape) + tuple(edges.shape))
        if self.mesh_matches(p.n_stages):
            entry = self._entry(key, lambda e: self._mark_traced(
                e, DynamicPipeline(self.mesh, self.mesh.axis_names[0]).jit(spec)))
        else:
            entry = self._entry(key, lambda e: self._mark_traced(
                e, lambda r, s: run_sequential(spec, r, s, p.n_stages)))
        with span("count.dispatch"):
            out = entry.fn(masks, edges)
        return out, {"cache": self._cache_stats(key, entry)}

    def mesh_matches(self, n_stages: int) -> bool:
        """True when this counter's mesh actually hosts a ``n_stages``-wide
        ring — shard_map requires leading dim == device count; any mismatch
        (e.g. the planner capped stages below the ring width for a tiny
        graph) falls back to the sequential chain emulation instead of
        failing. Admission logic branches on this: an emulated shard pays
        the FULL bitset, so the per-stage discount only applies on-mesh."""
        return (self.mesh is not None and self.mesh.devices.size > 1
                and self.mesh.devices.size == n_stages)

    @staticmethod
    def _mark_traced(entry: _Entry, fn):
        # The ring runtimes memoize their own trace (run_sequential /
        # DynamicPipeline.jit); a fresh cache entry stands for one trace.
        entry.traces += 1
        return fn

    def _run_mapreduce(self, g, p: Plan):
        from repro.core.triangle_mapreduce import build_mapreduce_operands

        n_b = bucket(g.n_nodes)
        if not jax.config.jax_enable_x64 and n_b * n_b > np.iinfo(np.int32).max:
            # jnp.asarray silently downcasts the int64 keys to int32 without
            # x64, so the u*base+v encoding (and the base² padding key) must
            # stay below 2^31: clamp the bucket to the largest safe base.
            cap = int(np.sqrt(np.iinfo(np.int32).max))  # 46340
            if g.n_nodes > cap:
                raise ValueError(
                    f"mapreduce path needs jax_enable_x64 for n_nodes > {cap} "
                    f"(pair keys overflow int32); got {g.n_nodes}")
            n_b = cap
        with span("count.operands"):
            nbrs, keys, n = build_mapreduce_operands(g, key_base=n_b)
            _, dmax = nbrs.shape
            d_b = bucket(max(dmax, 1), minimum=8)
            # bucket space: sentinel and key base both become n_b
            nb = np.full((n_b, d_b), n_b, np.int64)
            nb[:n, :dmax] = np.where(nbrs == n, n_b, nbrs)
            m_b = bucket(max(g.n_edges, 1), minimum=256)
            ks = np.full(m_b, np.int64(n_b) * n_b, np.int64)  # > any real key
            ks[: g.n_edges] = keys
        key = (p.cache_key(), (n_b, d_b, m_b))
        entry = self._entry(key, lambda e: self._make_mapreduce(e, p, n_b))
        with span("count.put"):
            nb, ks = jnp.asarray(nb), jnp.asarray(ks)
        with span("count.dispatch"):
            out = entry.fn(nb, ks)
        return out, {"cache": self._cache_stats(key, entry)}

    def _make_mapreduce(self, entry: _Entry, p: Plan, n_b: int):
        from repro.core.triangle_mapreduce import _mapreduce_count

        def body(nbrs, keys):
            entry.traces += 1
            return _mapreduce_count(nbrs, keys, n=n_b, node_batch=p.node_batch)

        return jax.jit(body)

    def _run_stream(self, g, p: Plan):
        # A memory-resident graph executed under a stream plan: feed its own
        # edge list as blocks (differential-test path; real streams use
        # count_stream). Shrink the block to the graph so the padded scan
        # does not run 65536 phantom steps on a 100-edge input.
        p_run = dataclasses.replace(
            p, block_size=min(p.block_size, bucket(max(g.n_edges, 1), minimum=256)))
        res = self.count_stream(g.n_nodes, [g.edges], plan=p_run)
        return res.count, res.stats


class StreamSession:
    """One in-flight streaming count: open → ``feed`` blocks → ``finalize``.

    The handle owns this stream's state — the adjacency-so-far bitset
    (n²/8 bytes dense, n²/8/S per stage when the plan is ring-sharded; for a
    windowed plan a ring of E epoch bitsets, E·n²/8 and E·n²/8/S; for a
    hybrid plan the degree-aware hub-row + tail-buffer arrays, linear in
    n — see ``core.streaming.init_hybrid_state``) plus a
    :class:`~repro.core.streaming.BlockBuffer` that re-blocks ragged feeds to
    one fixed shape — and borrows everything compiled from the counter that
    opened it: many sessions over one counter share one compile cache, so S
    concurrent streams feeding one block shape cost exactly one trace.
    Sessions are independent ("concurrent" means interleavable from one
    driver thread, e.g. the serve loop's ``StreamMultiplexer``; the handle
    itself is not thread-safe).

    ``feed`` ingests every full block the new edges completed and buffers the
    remainder host-side (at most ``block_size - 1`` edges). Windowed sessions
    (``plan.window_epochs = E > 0``) add :meth:`advance`: flush the current
    epoch's tail and slide the window one epoch — a single epoch-slot clear,
    no per-edge deletes, never a retrace (the ring head is a traced scalar).
    ``finalize`` flushes the padded tail, returns the :class:`CountResult`
    (the running total for unbounded sessions, the LIVE WINDOW's count for
    windowed ones), and is idempotent — later calls return the same result;
    later ``feed``/``advance`` calls raise. ``state_bytes`` is the per-stage
    device footprint the session pins while open — the number the serve
    loop's admission accounting charges.
    """

    def __init__(self, counter: TriangleCounter, n_nodes: int, plan: Plan,
                 block_size: int, on_mesh: bool, *, state: dict | None = None):
        from repro.core import streaming

        self.counter = counter
        self.n_nodes = n_nodes
        self.plan = plan
        self.block_size = block_size
        self._buffer = streaming.BlockBuffer(n_nodes, block_size)
        self._key = (plan.cache_key(), ("stream", n_nodes, block_size, on_mesh))
        self._entry = counter._entry(
            self._key, lambda e: counter._make_stream(e, plan, on_mesh))
        self._cache_hit = self._entry.hits > 0
        self._on_mesh = on_mesh
        self.restored = False
        if state is not None:
            # restore path (TriangleCounter.restore_stream): adopt the
            # checkpointed arrays instead of allocating zeros
            self.state = state
        elif plan.state_layout == "hybrid":
            self.state = streaming.init_hybrid_state(
                n_nodes, plan.hub_slots, plan.tail_capacity)
        elif plan.window_epochs:
            if plan.n_stages > 1:
                self.state = streaming.init_windowed_sharded_state(
                    n_nodes, plan.window_epochs, plan.n_stages,
                    mesh=counter.mesh if on_mesh else None)
            else:
                self.state = streaming.init_windowed_state(
                    n_nodes, plan.window_epochs)
        elif plan.n_stages > 1:
            self.state = streaming.init_sharded_state(
                n_nodes, plan.n_stages, mesh=counter.mesh if on_mesh else None)
        else:
            self.state = streaming.init_state(n_nodes)
        # per-device footprint: one column shard when a real mesh hosts the
        # stage axis; the WHOLE array when the sharding is host-emulated —
        # emulation keeps all S shards on one device, so admission budgets
        # must charge all of them
        nbytes = self._state_nbytes()
        self.state_bytes = nbytes // plan.n_stages if on_mesh else nbytes
        self.n_blocks = 0
        self.edges_fed = [0]  # per live epoch, newest last (see _admit_edges)
        self.n_epochs_advanced = 0
        self._traces0 = streaming.ingest_trace_count()
        self._wall = 0.0
        self.result: CountResult | None = None

    def _bitset_state(self):
        return self.state["epochs" if self.plan.window_epochs else "adj"]

    def _state_nbytes(self) -> int:
        """Device bytes this session's state pins: the bitset array for the
        dense/sharded/windowed layouts, the SUM over all hybrid arrays (hub
        rows, hub maps, tail buffers, degrees, counters) — exactly
        ``planner.hybrid_sizing``'s prediction, pinned by tests."""
        if self.plan.state_layout == "hybrid":
            return int(sum(v.nbytes for v in self.state.values()))
        return int(self._bitset_state().nbytes)

    @property
    def closed(self) -> bool:
        return self.result is not None

    def feed(self, edges) -> None:
        """Buffer ``edges`` ((B, 2) array-like, any B including ragged);
        ingest every full ``block_size`` block they completed (into the
        CURRENT epoch for windowed sessions). Front-door validation
        (``core.streaming.validate_edges``): non-integer arrays, shapes
        other than (B, 2), and vertex ids outside ``[0, n_nodes)`` raise
        ``ValueError`` — out-of-range ids would otherwise scatter silently
        outside (or wrap around inside) the bitset."""
        if self.result is not None:
            raise RuntimeError("session already finalized")
        with span("session.feed"):
            edges = self._admit_edges(edges)
            t0 = time.perf_counter()
            for b in self._buffer.push(edges):
                self._ingest(b)
            self._wall += time.perf_counter() - t0

    def _ingest(self, block) -> None:
        """Dispatch one fixed-shape block into the state (``repro.session.ingest``)."""
        with span("session.ingest"):
            self.state = self._entry.fn(self.state, block)
        self.n_blocks += 1

    # -- async prefetch surface (serve.sessions._PrefetchDriver) -----------
    # feed() = reblock() + ingest_ready() per emitted block, split so a
    # background producer thread can own the host half (validate + BlockBuffer
    # re-blocking/padding) while the drive thread owns the device half. The
    # split is the public API on purpose: repro_lint R5 forbids serve/ from
    # reaching into self._buffer/self._entry, and BlockBuffer's SPSC guard
    # enforces that only one thread at a time runs the host half.

    def reblock(self, edges) -> list:
        """PRODUCER half of an async ``feed``: validate ``edges`` and push
        them through the re-blocking buffer, returning every device-ready
        fixed-shape block they completed (possibly none). Touches no device
        state and no stats — safe to run on a background thread while the
        drive thread ingests earlier blocks. The caller must route every
        returned block through :meth:`ingest_ready` IN ORDER."""
        if self.result is not None:
            raise RuntimeError("session already finalized")
        return self._buffer.push(self._admit_edges(edges))

    def _admit_edges(self, edges):
        """Front-door validation plus the count-capacity guard: the ingest
        terms reach three times the triangles of the edges the state holds
        (``dd`` counts each all-in-block triangle three times) — every edge
        fed, or for a windowed stream those of its live epochs — so a feed
        that could push them past ``count_dtype`` raises before it is
        ingested instead of wrapping."""
        from repro.core import streaming

        edges = streaming.validate_edges(edges, self.n_nodes)
        held = sum(self.edges_fed) + len(edges)
        require_count_capacity(3 * triangle_bound(self.n_nodes, held),
                               f"stream of {self.n_nodes} nodes holding "
                               f"{held} edges")
        self.edges_fed[-1] += len(edges)
        return edges

    def _next_epoch_tally(self) -> None:
        """Host-half epoch boundary: the guard's tally forgets the epoch the
        window is about to expire."""
        if self.plan.window_epochs:
            self.edges_fed = (self.edges_fed + [0])[-self.plan.window_epochs:]

    def flush_ready(self):
        """PRODUCER half of an async tail flush: the padded tail block
        (None when nothing is buffered), NOT ingested. Used by the prefetch
        producer at an ``advance`` boundary so the epoch's tail enters the
        device-ready queue in order before the expiry marker; the capacity
        guard's tally moves to the next epoch here."""
        if self.result is not None:
            raise RuntimeError("session already finalized")
        self._next_epoch_tally()
        return self._buffer.flush()

    def ingest_ready(self, block) -> None:
        """CONSUMER half of an async ``feed``: dispatch one already-padded
        device-ready block (from :meth:`reblock` / :meth:`flush_ready`) into
        the session state. Must be called from the single drive thread, in
        the order the blocks were produced — then the device-op sequence is
        IDENTICAL to a synchronous ``feed`` of the same edges, which is why
        async counts are bit-identical."""
        if self.result is not None:
            raise RuntimeError("session already finalized")
        t0 = time.perf_counter()
        self._ingest(block)
        self._wall += time.perf_counter() - t0

    def expire_ready(self) -> None:
        """CONSUMER half of an async ``advance``: rotate the window WITHOUT
        flushing the tail (the producer already flushed it through
        :meth:`flush_ready` and queued it ahead of this marker). Same
        single-slot clear as :meth:`advance`."""
        if self.result is not None:
            raise RuntimeError("session already finalized")
        if not self.plan.window_epochs:
            raise RuntimeError(
                "expire_ready() is for windowed sessions — open with "
                "window=E (or a plan with window_epochs > 0)")
        from repro.core import streaming

        with span("session.advance"):
            t0 = time.perf_counter()
            self.state = streaming.expire_epoch(self.state)
            self.n_epochs_advanced += 1
            self._wall += time.perf_counter() - t0

    def set_block_size(self, block_size: int) -> list:
        """Adaptive re-blocking: change the emitted block shape from the
        next block on (``BlockBuffer.set_block_size``; counts are invariant
        to re-blocking). Returns any blocks the buffered remainder completed
        at the new size — route them through :meth:`ingest_ready` in order.
        The session's ``block_size`` follows, so a later checkpoint carries
        the CURRENT shape and restore resumes with it."""
        if self.result is not None:
            raise RuntimeError("session already finalized")
        out = self._buffer.set_block_size(block_size)
        self.block_size = int(block_size)
        return out

    def checkpoint(self) -> SessionCheckpoint:
        """Snapshot this session to host memory — the preemption primitive.

        The buffered tail is flushed and ingested first (the epoch-ring /
        bitset layout makes the boundary well-defined: after the flush the
        device state covers EXACTLY the edges fed so far), then every state
        array is copied to host numpy bit-exactly. The session itself stays
        usable (checkpoint is a snapshot, not a close) — the scheduler that
        wants the device bytes back simply drops its reference after
        checkpointing. ``restore_stream`` on the checkpoint resumes
        bit-identically, with no retrace for block shapes this counter has
        already traced (same cache key, sticky tail shapes carried over).
        Raises after ``finalize`` — a closed session has a result, not
        state."""
        if self.result is not None:
            raise RuntimeError("session already finalized")
        from repro.core import streaming

        with span("session.checkpoint"):
            t0 = time.perf_counter()
            tail = self._buffer.flush()
            if tail is not None:
                self._ingest(tail)
            arrays = streaming.snapshot_state(self.state)
            if int(np.asarray(arrays.get("lost", 0))):
                raise RuntimeError(
                    f"refusing to checkpoint a hybrid session that dropped "
                    f"{int(np.asarray(arrays['lost']))} edge endpoint(s) — the "
                    f"snapshot would persist an inexact count")
            self._wall += time.perf_counter() - t0
        return SessionCheckpoint(
            n_nodes=self.n_nodes, plan=self.plan, block_size=self.block_size,
            state_bytes=self.state_bytes,
            nbytes=streaming.state_nbytes(arrays), arrays=arrays,
            buffer_shape=self._buffer.export_shape_state(),
            n_blocks=self.n_blocks, n_epochs_advanced=self.n_epochs_advanced,
            edges_fed=tuple(self.edges_fed),
            wall_s=self._wall)

    def advance(self) -> None:
        """Slide a WINDOWED session's window by one epoch: the buffered tail
        of the closing epoch is flushed and ingested first (epoch boundaries
        bind edges to the epoch they were fed in), then the ring rotates —
        the oldest epoch's bitset and count slot are cleared in one shot
        (``core.streaming.expire_epoch``; no per-edge deletes). The rotation
        itself never retraces (the ring head is a traced scalar); a flushed
        ragged tail compiles once per distinct tail shape, and the tail
        shape is sticky across epochs (``BlockBuffer.flush``), so uniform
        epochs cost one trace total. Raises on unbounded sessions and after
        ``finalize``."""
        if self.result is not None:
            raise RuntimeError("session already finalized")
        if not self.plan.window_epochs:
            raise RuntimeError(
                "advance() is for windowed sessions — open with window=E "
                "(or a plan with window_epochs > 0)")
        from repro.core import streaming

        with span("session.advance"):
            t0 = time.perf_counter()
            self._next_epoch_tally()
            tail = self._buffer.flush()
            if tail is not None:
                self._ingest(tail)
            self.state = streaming.expire_epoch(self.state)
            self.n_epochs_advanced += 1
            self._wall += time.perf_counter() - t0

    def finalize(self) -> CountResult:
        """Flush the padded tail block and return the stream's
        :class:`CountResult` (idempotent): the running total for unbounded
        sessions, the live window's count (``counts.sum()`` over the epoch
        ring) for windowed ones. ``wall_s`` is the time spent inside
        ``feed``/``advance``/``finalize`` — idle time between interleaved
        feeds is not charged to the session. ``stats["ingest_traces"]``
        counts global ingest traces over the session's lifetime, so with
        interleaved sessions it attributes the one shared trace to whichever
        session fed the shape first."""
        if self.result is not None:
            return self.result
        with span("session.finalize"):
            return self._finalize()

    def _finalize(self) -> CountResult:
        from repro.core import streaming

        t0 = time.perf_counter()
        tail = self._buffer.flush()
        if tail is not None:
            self._ingest(tail)
        self._wall += time.perf_counter() - t0
        p = self.plan
        if p.state_layout == "hybrid":
            # loud, not silent: a hybrid stream that exhausted its hub slots
            # AND overflowed a tail buffer has dropped edge endpoints — its
            # count is a lie, so finalize refuses to return one
            lost = streaming.hybrid_lost(self.state)
            if lost:
                raise RuntimeError(
                    f"hybrid stream dropped {lost} edge endpoint(s): "
                    f"{p.hub_slots} hub slots exhausted while tail buffers "
                    f"of {p.tail_capacity} overflowed — re-plan with larger "
                    f"hub_slots/tail_capacity")
        count = (streaming.window_count(self.state) if p.window_epochs
                 else self.state["count"])
        stats = {"n_blocks": self.n_blocks, "block_size": self.block_size,
                 "n_stages": p.n_stages, "sharded": p.n_stages > 1,
                 "on_mesh": self._on_mesh, "session": True,
                 "state_bytes": self._state_nbytes(),
                 "cache": {"key": self._key, "hit": self._cache_hit,
                           "traces": self._entry.traces},
                 "ingest_traces": streaming.ingest_trace_count() - self._traces0}
        if p.window_epochs:
            stats["window_epochs"] = p.window_epochs
            stats["epochs_advanced"] = self.n_epochs_advanced
        if p.state_layout == "hybrid":
            # lost, hub slots in use, promotions, live edges by class
            stats["hybrid"] = streaming.hybrid_stats(self.state)
        self.result = CountResult(count=count, plan=p, wall_s=self._wall,
                                  stats=stats)
        return self.result


_DEFAULT: TriangleCounter | None = None


def default_counter() -> TriangleCounter:
    """Module-level counter shared by the ``count_triangles`` shim so casual
    callers still get compile caching across calls."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = TriangleCounter()
    return _DEFAULT


_METHOD_ALIASES = {"bitset": "bitset_ring"}
_PLAN_KWARGS = {"n_stages", "use_kernel", "balance",
                "edge_batch", "node_batch", "block_size"}


def count_triangles(g, *, method: str = "auto", counter: TriangleCounter | None = None,
                    **kw) -> int:
    """DEPRECATED thin shim over :class:`TriangleCounter`.

    Kept so existing call sites (`method="dense"|"sparse"|"ring"|"bitset"`)
    keep working; new code should hold a ``TriangleCounter`` and consume
    :class:`CountResult` (no forced host sync, inspectable plan).
    ``method="auto"`` routes through the planner.
    """
    c = counter or default_counter()
    if method == "auto":
        return c.count(g).item()
    method = _METHOD_ALIASES.get(method, method)
    unknown = set(kw) - _PLAN_KWARGS
    if unknown:
        # exotic legacy kwargs (mesh=, sequential=, dtype=...) — fall through
        # to the original per-method entry points untouched
        from repro.core import triangle_pipeline as tp

        legacy = {"ring": tp.count_triangles_ring,
                  "bitset_ring": tp.count_triangles_bitset_ring}
        if method in legacy:
            return int(legacy[method](g, **kw))
        raise TypeError(f"unsupported kwargs {sorted(unknown)} for method {method!r}")
    p = Plan(method=method, reason=f"fixed method={method!r} via count_triangles shim", **kw)
    return c.count(g, plan=p).item()
