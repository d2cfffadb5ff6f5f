"""Small shared utilities."""
from __future__ import annotations

import math
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np


def count_dtype():
    """Widest available integer dtype for exact triangle counts."""
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


def triangle_bound(n_nodes: int, n_edges: int) -> int:
    """An upper bound on the triangles of a simple graph with ``n_nodes``
    vertices and ``n_edges`` edges: the smaller of C(n, 3) and Rivin's
    (√2/3)·m^{3/2}."""
    n, m = int(n_nodes), int(n_edges)
    return min(math.comb(n, 3), math.isqrt(2 * m ** 3) // 3 + 1)


def require_count_capacity(bound: int, what: str) -> None:
    """Raise before counting when ``bound`` (the largest value any count or
    partial of ``what`` can reach) does not fit ``count_dtype()``: without
    x64 an int32 count past 2³¹ would wrap silently."""
    limit = int(np.iinfo(count_dtype()).max)
    if bound > limit:
        raise OverflowError(
            f"{what}: counts can reach {bound}, past the {limit} that "
            f"{np.dtype(count_dtype()).name} holds — enable "
            f"jax_enable_x64 to count this input exactly")


def interpret_mode(interpret: bool | None = None) -> bool:
    """THE decision whether Pallas kernels run interpreted: compiled on a
    TPU backend, interpreted everywhere else. ``interpret`` overrides it
    (kernel unit tests force the interpreter explicitly)."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` at the root
    of the checkout this package runs from. The path never depends on a
    temp name, a process id or the time — it is part of the cache's key."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    here = os.path.dirname(os.path.abspath(__file__))  # <repo>/src/repro
    return os.path.join(os.path.dirname(os.path.dirname(here)), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and export the path, so child processes (cluster workers) share it.
    Entry points call this once, before their first compile; nothing else
    in the repo sets a cache. Returns the path."""
    path = compile_cache_dir()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def bytes_of(tree) -> int:
    leaves = jax.tree.leaves(tree)
    return sum(x.size * x.dtype.itemsize for x in leaves if hasattr(x, "dtype"))


class PropagatingThread(threading.Thread):
    """``threading.Thread`` that re-raises the target's exception on
    ``join()`` instead of letting it die with the thread — a bare Thread
    turns a failed async checkpoint write into a silent no-op, which is
    exactly the failure mode repro_lint's R5 exists to catch."""

    def run(self):
        self._exc = None
        try:
            super().run()
        except BaseException as e:  # re-raised on join — nothing is lost
            self._exc = e

    def join(self, timeout=None):
        super().join(timeout)
        exc, self._exc = getattr(self, "_exc", None), None
        if exc is not None:
            raise exc
