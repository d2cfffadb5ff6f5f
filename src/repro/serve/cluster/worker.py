"""Cluster worker process: one ``StreamMultiplexer`` behind a socket.

Run as a module (``python -m repro.serve.cluster.worker --memory-bytes N
[--devices K] [--port P]``); the process binds a localhost TCP port,
prints ``WORKER_READY <port>`` on stdout (the spawn handshake
:class:`~repro.serve.cluster.client.WorkerClient` waits for), accepts ONE
router connection, and serves length-prefixed requests until the router
sends ``shutdown`` or the connection drops.

The worker plans against the device it finds (``Resources.detect``:
backend and ``bytes_limit`` from JAX); ``--memory-bytes`` overrides the
budget it advertises. On a TPU host a worker owns exactly one chip:
``--chip I`` names the chip its spawner pinned it to
(``client.one_chip_env``), and a worker that finds no TPU chip, or more
than one, exits before ``WORKER_READY`` with a message saying so.

``--devices K`` (> 1) is the CPU harness the mesh tests use: it forces K
host devices via ``XLA_FLAGS`` BEFORE jax is imported and builds the ring
mesh over them, so a cluster can mix meshed workers (per-stage n²/8/S
admission) with plain single-device ones, and the router's
``WorkerLoad.mesh_devices`` model stays honest. It is refused on any other
backend.

Ops (request ``{"op": ...}`` → reply ``{"ok": True, ...}``; failures
reply ``{"ok": False, "etype", "error"}`` and the worker keeps serving):

- ``hello``                        → advertised budget/mesh/pid
- ``open``/``feed``/``advance``    → multiplexer lifecycle; ``feed`` and
  ``advance`` carry a router ``seq`` and are EXACTLY-ONCE: a seq at or
  below the session's high-water mark is acknowledged without re-applying,
  so the router may blindly replay its journal after a failover
- ``checkpoint {sid, path}``       → non-destructive compressed spill of a
  live session (the router's durability barrier)
- ``evict {sid, path}``            → checkpoint + forget (migration send)
- ``restore {path, seq}``          → adopt a spilled checkpoint as a new
  session (migration receive / failover resurrect)
- ``close``                        → finalize; the count returns as a raw
  array buffer so dtype and bits survive the wire
- ``status`` / ``stats`` / ``ping`` / ``shutdown``
"""
from __future__ import annotations

import argparse
import os
import socket
import sys

from repro.serve.cluster import protocol


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--port", type=int, default=0,
                    help="TCP port to bind (0 = ephemeral, printed on stdout)")
    ap.add_argument("--memory-bytes", type=int, default=None,
                    help="device-state budget this worker advertises "
                         "(default: the device's own bytes_limit)")
    ap.add_argument("--devices", type=int, default=1,
                    help="forced CPU host device count (>1 builds a ring "
                         "mesh; CPU backend only)")
    ap.add_argument("--chip", type=int, default=None,
                    help="the TPU chip this worker was pinned to (the "
                         "spawner sets the pinning environment)")
    ap.add_argument("--max-stages", type=int, default=None,
                    help="planner ring-width cap (default: --devices)")
    ap.add_argument("--block-size", type=int, default=0,
                    help="uniform default ingest block size (0 = planner's)")
    ap.add_argument("--prefetch-depth", type=int, default=0,
                    help="async prefetch pipeline depth per session "
                         "(0 = synchronous drive loop)")
    return ap.parse_args(argv)


def _check_devices(devices, args) -> None:
    """A worker owns one device: one TPU chip on a TPU host, or the CPU
    (with ``--devices`` forced host devices for the mesh harness). Anything
    else fails the worker before READY, so its spawner reports why instead
    of planning on the wrong device."""
    platform = devices[0].platform
    if args.chip is not None and platform != "tpu":
        raise RuntimeError(
            f"worker pinned to TPU chip {args.chip} found no free TPU chip "
            f"(JAX found {len(devices)} {platform} device(s))")
    if platform == "cpu":
        return
    if args.devices > 1:
        raise RuntimeError(
            f"--devices {args.devices} forces CPU host devices; on a "
            f"{platform} host a worker owns exactly one chip")
    if len(devices) != 1:
        raise RuntimeError(
            f"worker sees {len(devices)} {platform} devices; a worker owns "
            f"exactly one chip — spawn it with chip=<index>")


def _build_mux(args):
    # imports live HERE, after XLA_FLAGS is set, so the forced device
    # count is visible to jax's first initialization
    import jax

    from repro.api import Resources, TriangleCounter
    from repro.serve.sessions import StreamMultiplexer

    _check_devices(jax.devices(), args)
    mesh = None
    if args.devices > 1:
        from repro.launch.mesh import make_ring_mesh

        mesh = make_ring_mesh(args.devices)
    res = Resources.detect(
        memory_bytes=args.memory_bytes,
        max_stages=(args.max_stages if args.max_stages is not None
                    else args.devices))
    counter = TriangleCounter(res, mesh=mesh)
    mux = StreamMultiplexer(counter, block_size=args.block_size or None,
                            prefetch_depth=args.prefetch_depth or None)
    mesh_devices = int(mesh.devices.size) if mesh is not None else 0
    return mux, res, mesh_devices


def _handle(op, header, arrays, mux, res, mesh_devices, last_seq):
    """Execute one request; returns ``(reply_header, reply_arrays, stop)``."""
    import numpy as np

    from repro.core import streaming

    if op == "hello":
        return ({"ok": True, "pid": os.getpid(),
                 "memory_bytes": res.memory_bytes,
                 "n_devices": res.n_devices, "backend": res.backend,
                 "max_stages": res.max_stages,
                 "mesh_devices": mesh_devices}, None, False)
    if op == "ping":
        return ({"ok": True}, None, False)
    if op == "shutdown":
        return ({"ok": True}, None, True)
    if op == "open":
        sid = mux.open(int(header["n_nodes"]),
                       block_size=header.get("block_size"),
                       window=header.get("window"),
                       priority=int(header.get("priority") or 0))
        return ({"ok": True, "sid": sid, "status": mux.status(sid),
                 "state_bytes": mux.state_bytes_of(sid)}, None, False)
    if op in ("feed", "advance"):
        sid, seq = int(header["sid"]), header.get("seq")
        if seq is not None and seq <= last_seq.get(sid, -1):
            # replayed journal entry the pre-failover worker already
            # applied: acknowledge, don't double-count
            return ({"ok": True, "dedup": True}, None, False)
        if op == "feed":
            mux.feed(sid, arrays["edges"])
        else:
            mux.advance(sid)
        if seq is not None:
            last_seq[sid] = seq
        return ({"ok": True}, None, False)
    if op == "checkpoint":
        ckpt = mux.checkpoint(int(header["sid"]))
        raw = ckpt.nbytes
        ckpt.spill(header["path"])
        return ({"ok": True, "nbytes": raw, "disk_bytes": ckpt.disk_bytes},
                None, False)
    if op == "evict":
        sid = int(header["sid"])
        ckpt = mux.evict(sid)
        last_seq.pop(sid, None)
        raw = ckpt.nbytes
        ckpt.spill(header["path"])
        return ({"ok": True, "nbytes": raw, "disk_bytes": ckpt.disk_bytes,
                 "state_bytes": ckpt.state_bytes}, None, False)
    if op == "restore":
        from repro.api import SessionCheckpoint

        ckpt = SessionCheckpoint.from_file(header["path"])
        sid = mux.adopt(ckpt, priority=int(header.get("priority") or 0))
        if header.get("seq") is not None:
            last_seq[sid] = int(header["seq"])
        return ({"ok": True, "sid": sid,
                 "state_bytes": mux.state_bytes_of(sid)}, None, False)
    if op == "close":
        sid = int(header["sid"])
        result = mux.close(sid)
        last_seq.pop(sid, None)
        return ({"ok": True, "plan": result.plan.to_dict(),
                 "wall_s": result.wall_s,
                 "stats": protocol.jsonable(result.stats)},
                {"count": np.asarray(result.count)}, False)
    if op == "status":
        return ({"ok": True, "status": mux.status(int(header["sid"]))},
                None, False)
    if op == "stats":
        return ({"ok": True, "bytes_in_use": mux.bytes_in_use,
                 "n_active": mux.n_active, "n_queued": mux.n_queued,
                 "n_preempted": mux.n_preempted,
                 "ingest_traces": streaming.ingest_trace_count(),
                 "sched": protocol.jsonable(mux.sched_stats)}, None, False)
    raise ValueError(f"unknown op {op!r}")


def serve(conn, mux, res, mesh_devices) -> None:
    """Request loop over one router connection (returns on shutdown or on
    the router going away — a worker never outlives its router)."""
    last_seq: dict[int, int] = {}  # sid -> exactly-once high-water mark
    while True:
        try:
            header, arrays = protocol.recv_msg(conn)
        except protocol.WorkerDied:
            return
        try:
            reply, out, stop = _handle(header.get("op"), header, arrays,
                                       mux, res, mesh_devices, last_seq)
        except Exception as e:  # noqa: BLE001 — every failure crosses the wire
            protocol.send_msg(conn, {"ok": False, "etype": type(e).__name__,
                                     "error": str(e)})
            continue
        protocol.send_msg(conn, reply, out)
        if stop:
            return


def main(argv=None) -> int:
    from repro.utils import enable_compile_cache

    args = _parse(argv)
    if args.devices > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        forced = f"--xla_force_host_platform_device_count={args.devices}"
        if forced not in flags:
            os.environ["XLA_FLAGS"] = f"{flags} {forced}".strip()
    enable_compile_cache()
    mux, res, mesh_devices = _build_mux(args)
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", args.port))
    srv.listen(1)
    print(f"WORKER_READY {srv.getsockname()[1]}", flush=True)
    conn, _ = srv.accept()
    try:
        serve(conn, mux, res, mesh_devices)
    finally:
        conn.close()
        srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
