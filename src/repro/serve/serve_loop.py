"""Batched serving loops: the LM server (continuous-batching-lite over
prefill + decode) and the triangle-counting server (planner-driven
``repro.api`` front end with one shared compile cache across requests).

Requests (prompt token arrays) are grouped into fixed-size batches (padding
short prompts on the left with a pad id), prefilled once, then decoded
greedily with the KV cache until max_new_tokens. This is the host-side twin
of the decode_* dry-run cells; on the production mesh the same step functions
run under the shardings in launch/sharding.py.

NOTE: left-pads are attended causally (no pad mask in the step functions), so
mixed-length batches are approximate; a production deployment would bucket
requests by length (the data-pipeline bucketing pattern) or add a pad mask.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LMConfig
from repro.models.transformer import decode_step, prefill


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8
    max_new_tokens: int = 32
    pad_id: int = 0


class LMServer:
    def __init__(self, params, cfg: LMConfig, serve_cfg: ServeConfig | None = None):
        self.params = params
        self.cfg = cfg
        self.scfg = serve_cfg or ServeConfig()
        self._decode = jax.jit(
            lambda cache, tok, cur: decode_step(self.params, self.cfg, cache, tok, cur)
        )

    def generate(self, prompts: list[np.ndarray]) -> list[np.ndarray]:
        """Greedy-decode a list of int32 prompt arrays. Returns generated ids."""
        out: list[np.ndarray] = []
        for i in range(0, len(prompts), self.scfg.max_batch):
            out.extend(self._generate_batch(prompts[i : i + self.scfg.max_batch]))
        return out

    def _generate_batch(self, prompts: list[np.ndarray]) -> list[np.ndarray]:
        b = len(prompts)
        plen = max(len(p) for p in prompts)
        s_max = plen + self.scfg.max_new_tokens
        tokens = np.full((b, plen), self.scfg.pad_id, np.int32)
        for i, p in enumerate(prompts):
            tokens[i, plen - len(p):] = p  # left-pad → aligned last positions
        logits, cache = prefill(self.params, self.cfg, jnp.asarray(tokens), s_max,
                                chunk_q=min(512, plen))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        gen = [tok]
        for step in range(self.scfg.max_new_tokens - 1):
            logits, cache = self._decode(cache, tok, jnp.int32(plen + step))
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            gen.append(tok)
        stacked = np.asarray(jnp.concatenate(gen, axis=1))
        return [stacked[i] for i in range(b)]


# --------------------------------------------------------------------------
# Triangle-counting serving loop (the paper's workload, served)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class TriangleServeConfig:
    max_batch: int = 16          # vmapped batch width per executable call
    batch_node_limit: int = 512  # dense-plan graphs up to this ride the batch path


class TriangleServer:
    """Serve triangle-count requests over ``repro.api``.

    One ``TriangleCounter`` (one compile cache) lives for the server's
    lifetime, so steady-state traffic never retraces. Small graphs whose plan
    is the dense path are grouped by padded-shape bucket and counted with ONE
    vmapped executable call per group (``count_batch``, executed under the
    group's planner plan so the backend kernel decision survives batching);
    everything else runs its planner-chosen path individually. Streaming
    requests run as SESSIONS on ``self.streams`` (a ``StreamMultiplexer``
    over the same cache): any number may be open at once —
    ``open_stream``/``feed``/``close_stream`` drive them directly,
    ``serve_streams`` interleaves a whole list of them round-robin, and
    ``serve_stream`` keeps the pre-session one-stream signature. Admission is
    the planner's budget (``admit_session``): sessions whose pinned bitset
    state would overcommit ``Resources.memory_bytes`` queue host-side instead
    of OOMing the server — and the multiplexer's scheduler is PREEMPTIBLE
    (see ``serve.sessions``): per-session ``priority=`` / ``deadline_s=``,
    ``preempt_stream`` to park an active session's state host-side, bounded
    queue/checkpoint budgets that raise ``BackpressureError`` instead of
    buffering toward OOM. Results come back as per-request ``CountResult``s
    in request order — counts stay device arrays, so an aggregating caller
    syncs once, not per request.
    """

    def __init__(self, resources=None, serve_cfg: TriangleServeConfig | None = None,
                 mesh=None, prefetch_depth: int | None = None,
                 adaptive_block: bool = False):
        from repro.api import TriangleCounter
        from repro.serve.sessions import StreamMultiplexer

        self.counter = TriangleCounter(resources, mesh=mesh)
        self.cfg = serve_cfg or TriangleServeConfig()
        # prefetch_depth=K gives every streaming session an async prefetch
        # pipeline (background host re-blocking overlapping device ingest,
        # K-deep device-ready queue — see serve.sessions); None keeps the
        # synchronous drive loop. adaptive_block additionally lets each
        # pipeline grow/shrink its block size from observed ingest wall-clock.
        self.streams = StreamMultiplexer(self.counter,
                                         prefetch_depth=prefetch_depth,
                                         adaptive_block=adaptive_block)

    def serve(self, graphs: list) -> list:
        from repro.api import CountResult, bucket

        cfg = self.cfg
        results: list = [None] * len(graphs)
        # node bucket -> (the group's planner plan, request indices). The
        # plan rides along so count_batch executes the planner's backend
        # decision (use_kernel) instead of Plan defaults — on TPU
        # the batched path must run the compiled kernels too.
        batchable: dict[int, tuple] = {}
        for i, g in enumerate(graphs):
            p = self.counter.plan_for(g)
            if p.method == "dense" and g.n_nodes <= cfg.batch_node_limit:
                batchable.setdefault(bucket(g.n_nodes), (p, []))[1].append(i)
            else:
                results[i] = self.counter.count(g, plan=p)
        for group_plan, idx in batchable.values():
            for j in range(0, len(idx), cfg.max_batch):
                chunk = idx[j:j + cfg.max_batch]
                rb = self.counter.count_batch([graphs[i] for i in chunk],
                                              plan=group_plan)
                for pos, i in enumerate(chunk):
                    # amortized share of the batch call, so summing wall_s
                    # over a response doesn't multiply-count the batch (the
                    # full batch time stays in stats)
                    results[i] = CountResult(
                        count=rb.count[pos], plan=rb.plan,
                        wall_s=rb.wall_s / len(chunk),
                        stats={**rb.stats, "batched": True, "batch_pos": pos,
                               "batch_wall_s": rb.wall_s},
                    )
        return results

    # -- streaming sessions ------------------------------------------------
    def open_stream(self, n_nodes: int, *, block_size: int | None = None,
                    window: int | None = None, priority: int = 0,
                    deadline_s: float | None = None) -> int:
        """Open one streaming session on the server's multiplexer; returns
        its session id (admitted, queued, or admitted by preempting
        strictly-lower-priority actives — see ``serve.sessions``).
        ``window=E`` opens a sliding-window session (admission charges its
        E·n²/8(/S) epoch-ring state); windowed and unbounded sessions
        multiplex over the same compile cache. ``priority`` ranks the
        session for fair-share scheduling; ``deadline_s`` reaps it if idle
        that long (device state parked, then cancelled)."""
        return self.streams.open(n_nodes, block_size=block_size, window=window,
                                 priority=priority, deadline_s=deadline_s)

    def feed(self, sid: int, edges) -> None:
        """Feed one (B, 2) edge block to an open session (the current epoch
        for windowed sessions)."""
        self.streams.feed(sid, edges)

    def advance_stream(self, sid: int) -> None:
        """Slide a windowed session's window one epoch (see
        ``StreamMultiplexer.advance``: a single epoch-slot clear, buffered
        as an epoch marker while the session is queued)."""
        self.streams.advance(sid)

    def preempt_stream(self, sid: int) -> None:
        """Park an ACTIVE session's device state host-side (checkpoint into
        the multiplexer's bounded store) — it readmits transparently when
        budget frees, and ``close_stream`` on it restores first so the count
        is exact (see ``StreamMultiplexer.preempt``)."""
        self.streams.preempt(sid)

    def stream_status(self, sid: int) -> str:
        """``"active"`` / ``"queued"`` / ``"preempted"`` / ``"closed"``."""
        return self.streams.status(sid)

    def close_stream(self, sid: int):
        """Finalize a session; returns its ``CountResult`` (idempotent;
        cancels a never-admitted session, restores a preempted one)."""
        return self.streams.close(sid)

    def serve_streams(self, requests, *, block_size: int | None = None) -> list:
        """Serve many streaming requests CONCURRENTLY: ``requests`` is a list
        of ``(n_nodes, blocks-iterable)`` pairs; block ingest is interleaved
        round-robin across every admitted session in admission order (the
        paper's serving regime: many dynamically-generated graphs in flight
        at once, one compile cache, planner-budgeted admission). Sessions are
        closed in admission order as the interleave finishes, so freed state
        admits any queued requests FIFO. Returns per-request ``CountResult``s
        in request order — bit-identical to running each request through
        ``serve_stream`` sequentially."""
        its = [iter(blocks) for _, blocks in requests]
        sids = [self.streams.open(n, block_size=block_size)
                for n, _ in requests]
        live = set(range(len(requests)))
        while live:
            for i in sorted(live):
                try:
                    block = next(its[i])
                except StopIteration:
                    live.discard(i)
                    continue
                self.streams.feed(sids[i], block)
        return [self.streams.close(sid) for sid in sids]

    def serve_stream(self, n_nodes: int, blocks, *,
                     block_size: int | None = None):
        """Serve ONE streaming request (an iterable of (B, 2) edge blocks —
        the paper's not-memory-resident regime): the pre-session signature,
        kept as a one-session wrapper over the multiplexer. The planner sizes
        ``n_stages``/``block_size`` from the server's resources, and the
        jitted ingest step lands in the server's shared compile cache, so
        repeated (or concurrent) streams with one block shape never
        retrace."""
        return self.serve_streams([(n_nodes, blocks)],
                                  block_size=block_size)[0]


class ClusterServer:
    """The multi-host front door: ``TriangleServer``'s streaming surface
    over a :class:`~repro.serve.cluster.ClusterRouter` of worker PROCESSES.

    Where ``TriangleServer`` multiplexes sessions inside one process (one
    host's ``Resources.memory_bytes`` caps the aggregate state), the
    cluster server places each session on a worker by planner-predicted
    state bytes (``place_session``: least-loaded-by-bytes, never-fits
    rejection at ``open_stream``) and rides the router's durability
    machinery — journaled feeds, checkpoint barriers, live migration, and
    failover that resurrects a dead worker's sessions from their spilled
    checkpoints. Session ids are GLOBAL (router-issued); results are the
    same ``CountResult``s, counts bit-identical to a single-process run.

    ``workers`` is a list of :class:`~repro.serve.cluster.WorkerClient`\\ s
    or spawn-spec dicts (``{"memory_bytes": ..., "devices": ...}``);
    remaining keyword arguments go to the router. Use as a context manager
    (or call ``shutdown()``) so worker subprocesses are reaped."""

    def __init__(self, workers, **router_kwargs):
        from repro.serve.cluster import ClusterRouter

        self.router = ClusterRouter(workers, **router_kwargs)

    # -- TriangleServer's streaming surface, routed ------------------------
    def open_stream(self, n_nodes: int, *, block_size: int | None = None,
                    window: int | None = None, priority: int = 0) -> int:
        """Place one streaming session on the least-loaded fitting worker;
        returns its global session id. ``BackpressureError`` = fits no
        worker at current load (retry after closes); ``ValueError`` = could
        never fit any worker, even idle."""
        return self.router.open(n_nodes, block_size=block_size,
                                window=window, priority=priority)

    def feed(self, sid: int, edges) -> None:
        """Feed one (B, 2) edge block (journaled, then dispatched)."""
        self.router.feed(sid, edges)

    def advance_stream(self, sid: int) -> None:
        """Slide a windowed session's window one epoch."""
        self.router.advance(sid)

    def stream_status(self, sid: int) -> str:
        """``"active"`` / ``"queued"`` / ``"preempted"`` on its worker,
        ``"displaced"`` while failover has no home for it, ``"closed"``."""
        return self.router.status(sid)

    def close_stream(self, sid: int):
        """Finalize a session; returns its ``CountResult`` (idempotent)."""
        return self.router.close(sid)

    def serve_streams(self, requests, *, block_size: int | None = None) -> list:
        """Serve many ``(n_nodes, blocks)`` requests concurrently across
        the cluster, round-robin interleaved — ``TriangleServer``'s
        signature, placement decided per session."""
        its = [iter(blocks) for _, blocks in requests]
        sids = [self.router.open(n, block_size=block_size)
                for n, _ in requests]
        live = set(range(len(requests)))
        while live:
            for i in sorted(live):
                try:
                    block = next(its[i])
                except StopIteration:
                    live.discard(i)
                    continue
                self.router.feed(sids[i], block)
        return [self.router.close(sid) for sid in sids]

    # -- cluster-only controls ---------------------------------------------
    def checkpoint_stream(self, sid: int) -> str | None:
        """Durability barrier: spill the session's state to the checkpoint
        dir and truncate its replay journal."""
        return self.router.checkpoint(sid)

    def migrate_stream(self, sid: int, to: int | None = None) -> int:
        """Move a live session to another worker (checkpoint → evict →
        restore; bit-identical, zero new traces on a warm target)."""
        return self.router.migrate(sid, to=to)

    def rebalance(self, *, threshold_bytes: int = 0) -> int | None:
        """One gap-shrinking migration between the most- and least-loaded
        workers (``None`` when already balanced)."""
        return self.router.rebalance(threshold_bytes=threshold_bytes)

    def stats(self) -> dict:
        """Router counters + per-worker ledger/multiplexer gauges."""
        return self.router.stats()

    def shutdown(self) -> None:
        self.router.shutdown()

    def __enter__(self) -> "ClusterServer":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
