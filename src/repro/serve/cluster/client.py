"""Router-side handle to one worker process: spawn, RPC, liveness.

``WorkerClient.spawn`` launches ``python -m repro.serve.cluster.worker``
as a subprocess, waits for its ``WORKER_READY <port>`` handshake, connects
one TCP socket, and performs the ``hello`` exchange that caches the
worker's advertised :class:`~repro.api.Resources` and mesh width — the
inputs to the router's :class:`~repro.api.WorkerLoad` model.

Every RPC failure at the SOCKET level (reset, EOF, broken pipe) marks the
client dead and raises :class:`~repro.serve.cluster.protocol.WorkerDied`;
application-level failures arrive as ``{"ok": False}`` replies and
re-raise as the original exception type (``BackpressureError`` stays a
``BackpressureError`` across the wire).
"""
from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import time

import socket as socket_mod

from repro.serve.cluster import protocol


def host_tpu_chips() -> int:
    """TPU chips attached to this host, found the way JAX finds them (PCI
    ids) without loading the TPU runtime — so a router can pin workers to
    chips while holding none itself. 0 when ``JAX_PLATFORMS`` rules TPUs
    out."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def one_chip_env(chip: int) -> dict:
    """Environment that gives a process chip ``chip`` of its TPU host and
    no other: a 1x1x1 process of one visible chip (libtpu then takes no
    host-wide lock, so sibling workers hold the other chips) with a runtime
    port of its own."""
    return {"TPU_VISIBLE_CHIPS": str(int(chip)),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_PORT": str(8476 + int(chip)),
            "CLOUD_TPU_TASK_ID": "0"}


def _last_error(errf) -> str:
    """The last line of a dead worker's stderr (its exception), if any."""
    errf.seek(0)
    lines = [ln.strip() for ln in errf.read().splitlines() if ln.strip()]
    errf.close()
    return f": {lines[-1]}" if lines else ""


class WorkerClient:
    """One live worker: ``proc`` (subprocess), ``sock`` (its one RPC
    connection), and the budget/mesh facts it advertised at ``hello``."""

    def __init__(self, proc, sock, hello: dict):
        from repro.api import Resources

        self.proc = proc
        self.sock = sock
        self.pid = hello["pid"]
        self.resources = Resources(
            memory_bytes=hello["memory_bytes"],
            n_devices=hello["n_devices"], backend=hello["backend"],
            max_stages=hello["max_stages"])
        self.mesh_devices = int(hello["mesh_devices"])
        self._alive = True

    @classmethod
    def spawn(cls, *, memory_bytes: int | None = None, devices: int = 1,
              chip: int | None = None,
              max_stages: int | None = None, block_size: int | None = None,
              prefetch_depth: int | None = None,
              startup_timeout_s: float = 180.0) -> "WorkerClient":
        """Start a worker subprocess and complete the spawn handshake.

        The child gets ``PYTHONPATH`` pointing at this repro package's
        source root, so spawning works from a test or bench process no
        matter what the caller's cwd is; the worker sets its own
        ``XLA_FLAGS`` for forced device counts before importing jax.
        ``chip`` pins the worker to one TPU chip of this host
        (:func:`one_chip_env`); a worker that cannot have it fails here,
        with its own message, instead of READY. The spawning process must
        not hold the chip itself: it stays off JAX's devices.
        ``memory_bytes`` overrides the budget the worker would detect."""
        import repro

        src_root = os.path.dirname(os.path.dirname(
            os.path.abspath(repro.__file__)))
        env = dict(os.environ)
        env["PYTHONPATH"] = src_root + os.pathsep + env.get("PYTHONPATH", "")
        if chip is not None:
            env.update(one_chip_env(chip))
        if devices > 1:
            # must be in the child's env BEFORE its first jax import (the
            # worker module tree imports jax transitively), so the forced
            # host device count is set here, not in the worker's main()
            flags = env.get("XLA_FLAGS", "")
            forced = f"--xla_force_host_platform_device_count={int(devices)}"
            if forced not in flags:
                env["XLA_FLAGS"] = f"{flags} {forced}".strip()
        cmd = [sys.executable, "-u", "-m", "repro.serve.cluster.worker",
               "--port", "0", "--devices", str(int(devices))]
        if memory_bytes is not None:
            cmd += ["--memory-bytes", str(int(memory_bytes))]
        if chip is not None:
            cmd += ["--chip", str(int(chip))]
        if max_stages is not None:
            cmd += ["--max-stages", str(int(max_stages))]
        if block_size is not None:
            cmd += ["--block-size", str(int(block_size))]
        if prefetch_depth is not None:
            cmd += ["--prefetch-depth", str(int(prefetch_depth))]
        # stderr goes to an anonymous file: read back only when the worker
        # dies before READY, so a chatty worker can never block on a pipe
        errf = tempfile.TemporaryFile(mode="w+")
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=errf,
                                env=env, text=True)
        deadline = time.monotonic() + startup_timeout_s
        port = None
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                if proc.poll() is not None:
                    raise protocol.WorkerDied(
                        f"worker exited with {proc.returncode} before READY"
                        f"{_last_error(errf)}")
                continue
            if line.startswith("WORKER_READY"):
                port = int(line.split()[1])
                break
        if port is None:
            proc.kill()
            proc.wait()
            raise protocol.WorkerDied(
                f"worker not READY within {startup_timeout_s:.0f}s"
                f"{_last_error(errf)}")
        errf.close()
        sock = socket_mod.create_connection(("127.0.0.1", port), timeout=None)
        sock.setsockopt(socket_mod.IPPROTO_TCP, socket_mod.TCP_NODELAY, 1)
        client = cls.__new__(cls)
        client.proc, client.sock, client._alive = proc, sock, True
        hello, _ = client.rpc({"op": "hello"})
        client.__init__(proc, sock, hello)
        return client

    @property
    def alive(self) -> bool:
        return self._alive and (self.proc is None or self.proc.poll() is None)

    def rpc(self, header: dict, arrays: dict | None = None) -> tuple:
        """One request/reply exchange; returns ``(reply_header, arrays)``.
        Socket failure ⇒ client marked dead + :class:`WorkerDied`; a
        ``{"ok": False}`` reply re-raises the worker-side exception."""
        if not self._alive:
            raise protocol.WorkerDied(
                f"worker pid {getattr(self, 'pid', '?')} already dead")
        try:
            protocol.send_msg(self.sock, header, arrays)
            reply, out = protocol.recv_msg(self.sock)
        except protocol.WorkerDied as e:
            self._alive = False
            raise protocol.WorkerDied(
                f"worker pid {getattr(self, 'pid', '?')} lost during "
                f"{header.get('op')!r}: {e}") from None
        if not reply.get("ok", False):
            protocol.raise_remote(reply)
        return reply, out

    def shutdown(self) -> None:
        """Graceful stop: ask, then reap (kill if asking failed)."""
        try:
            self.rpc({"op": "shutdown"})
        except protocol.WorkerDied:
            pass
        self.kill()

    def kill(self) -> None:
        """Hard stop: close the socket, kill and reap the subprocess."""
        self._alive = False
        try:
            self.sock.close()
        except OSError:
            pass
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
        if self.proc is not None:
            self.proc.wait(timeout=30)
            if self.proc.stdout is not None:
                self.proc.stdout.close()
