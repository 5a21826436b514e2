"""Production mesh construction (assignment MULTI-POD DRY-RUN step 1).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) = ("data", "model") single pod (256 v5e chips), or
    (2, 16, 16) = ("pod", "data", "model") for 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Whatever devices exist, data-parallel (CPU tests / tiny training)."""
    n = len(jax.devices())
    assert n % model == 0
    return _make_mesh((n // model, model), ("data", "model"))


def make_data_mesh(n: int):
    """A 1-D ``("data",)`` mesh over the first ``n`` devices, Auto axes."""
    return _make_mesh((n,), ("data",))


def force_host_devices(n: int) -> int:
    """Ask XLA for ``n`` virtual host (CPU) devices; returns the count
    actually available. Platform/env setup is centralized in
    ``repro.config`` (DESIGN.md §14) — this re-export keeps the historical
    launch-layer call sites working."""
    from repro import config

    return config.force_host_devices(n)


def batch_axes(mesh) -> tuple:
    """The data-parallel axes present in this mesh ((pod,)data)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
