"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init;
smoke tests and benches see the 1 real CPU device.
"""
from __future__ import annotations

import math

import jax
import numpy as np


def _mesh_from(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Version-portable concrete Mesh: explicit (devices-array, axis-names)
    construction — ``jax.sharding.Mesh`` wants an ndarray of devices whose
    shape IS the mesh shape, not bare ints."""
    n = math.prod(shape)
    devices = np.asarray(jax.devices()[:n]).reshape(shape)
    return jax.sharding.Mesh(devices, axes)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh_from(shape, axes)


def make_host_mesh(*, data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (CPU tests)."""
    n = len(jax.devices())
    assert data * model <= n, f"need {data*model} devices, have {n}"
    return _mesh_from((data, model), ("data", "model"))


def make_fleet_mesh(num_clients: int, *, max_data: int | None = None,
                    fsdp: int = 1, tp: int = 1):
    """``('data', 'fsdp', 'tp')`` mesh for the fleet engine.

    The ``data`` axis carries the stacked client axis (the largest size
    that divides ``num_clients`` and fits the devices left after the server
    axes) — the client tier never tensor-parallelizes (DESIGN.md §3), so
    clients only ever shard over ``data``. ``fsdp`` x ``tp`` is the server
    suffix's 2D sub-mesh: the shard_map engines leave those axes to GSPMD
    (``auto``) and constrain the server params/gradients with the
    ``launch.steps.fleet_server_pspecs`` tier specs, mirroring
    ``build_step``'s server-tier rule. Returns None when the layout needs
    more devices than exist or collapses to a single device (data = fsdp =
    tp = 1), so callers can fall back to the unsharded path."""
    navail = len(jax.devices())
    if fsdp * tp > navail:
        return None
    limit = navail // (fsdp * tp)
    if max_data is not None:
        limit = min(limit, max_data)
    data = 1
    for d in range(1, min(limit, num_clients) + 1):
        if num_clients % d == 0:
            data = d
    if data * fsdp * tp <= 1:
        return None
    return _mesh_from((data, fsdp, tp), ("data", "fsdp", "tp"))


def single_device_fleet_mesh():
    """Degenerate (1, 1, 1) fleet mesh: lets the explicit-collective
    shard_map engines compile and train on a one-device host (the
    collectives become no-ops) with the same code path as a real fleet."""
    return _mesh_from((1, 1, 1), ("data", "fsdp", "tp"))


def abstract_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Device-free ``AbstractMesh`` of the given sizes and axis names."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axes))
