"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* the first
jax device query, and smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh

_FORCE_FLAG = "--xla_force_host_platform_device_count"


def _make_mesh(shape, axes, devices=None) -> Mesh:
    if devices is not None:
        return Mesh(np.asarray(devices).reshape(shape), axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (16, 16) = ("data", "model") — 256 chips.
    Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Mesh over whatever devices exist (CPU smoke tests).

    Degrades to a 1-device ``("data", "model")`` mesh when the host has a
    single device (the common un-forced CPU case) instead of assuming a
    multi-device topology — so every mesh-aware code path is importable
    and runnable on a laptop, it just doesn't split work."""
    devs = list(jax.devices())
    n = max(len(devs), 1)
    try:
        return _make_mesh((n, 1), ("data", "model"))
    except Exception:  # ragged/odd device sets: fall back to one device
        return _make_mesh((1, 1), ("data", "model"), devices=devs[:1])


def axis_extents(mesh: Mesh | None) -> dict[str, int]:
    """``{axis name: extent}`` of a mesh, ``{}`` for ``None`` — the form
    engine/benchmark report rows record (JSON-friendly, no device objects).
    """
    if mesh is None:
        return {}
    return {str(name): int(extent)
            for name, extent in zip(mesh.axis_names, mesh.devices.shape)}


def make_test_mesh(n: int = 8, *, model_parallel: int = 1) -> Mesh:
    """Mesh of ``n`` forced host devices for multi-device CPU testing.

    Honors an ``XLA_FLAGS=--xla_force_host_platform_device_count=K``
    already present in the environment (the tier-1 multidevice suite sets
    it on its subprocesses); when absent *and* the backend has not been
    initialized yet, sets it to ``n`` so a bare
    ``make_test_mesh(8)`` works in a fresh process.  If the backend ends
    up with fewer than ``n`` devices (flag set too late — jax reads it at
    first device query), the mesh degrades to the devices that exist
    rather than raising, mirroring :func:`make_host_mesh`.

    ``model_parallel`` splits the trailing ``"model"`` axis: e.g.
    ``make_test_mesh(8, model_parallel=2)`` is a (4, 2) data×model mesh.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if _FORCE_FLAG not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {_FORCE_FLAG}={n}".strip()
    devs = list(jax.devices())
    if len(devs) < n:
        n = len(devs)
    if model_parallel > 1 and n % model_parallel == 0:
        shape = (n // model_parallel, model_parallel)
    else:
        shape = (n, 1)
    return _make_mesh(shape, ("data", "model"), devices=devs[:n])
