"""Production mesh builders.

Everything here is a FUNCTION (never module-level device state) so importing
this module never initializes jax's device backend — required because the
dry-run overrides XLA_FLAGS before first jax init while the smoke tests must
see the single real CPU device.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Target deployment mesh: 16x16 = 256 chips/pod, or 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(*, data: int | None = None, model: int = 1) -> Mesh:
    """Mesh over whatever devices actually exist (tests / CPU benches)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    devs = np.asarray(jax.devices()[: data * model]).reshape(data, model)
    return Mesh(devs, ("data", "model"))


def make_ring_mesh(n_stages: int | None = None) -> Mesh:
    """1-D ring mesh for the dynamic-pipeline runtime ("stage" axis).

    On the production mesh the DP ring is the flattened (data, model) axes of
    a pod; here we build it directly over the first ``n_stages`` devices.
    Asking for more stages than there are devices raises: a narrower mesh
    would quietly run the ring on fewer chips than the caller sized for.
    """
    devs = jax.devices()
    if n_stages is None:
        n_stages = len(devs)
    if n_stages > len(devs):
        raise ValueError(
            f"ring mesh of {n_stages} stages needs {n_stages} devices; "
            f"{len(devs)} {devs[0].platform} device(s) exist")
    return Mesh(np.asarray(devs[:n_stages]), ("stage",))


def data_parallel_axes(mesh: Mesh) -> tuple[str, ...]:
    """Axes that carry batch parallelism (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def named(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))
