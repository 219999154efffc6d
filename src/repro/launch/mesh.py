"""Production mesh builders.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips, the `pod` axis is the
outermost data-parallel axis (DCN between pods; all sharding rules treat
batch as (pod, data)).

Functions, not module constants — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before first jax init). Every mesh
comes from `engine.parallel.device_mesh`, the one builder.
"""
from __future__ import annotations

import jax

from repro.engine.parallel import device_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return device_mesh(shape, axes)


def snap_model(n_devices: int, model: int) -> int:
    """Largest divisor of `n_devices` that is <= the requested `model`
    extent (pure helper, unit-testable without touching jax devices)."""
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    model = max(1, min(int(model), n_devices))
    while n_devices % model:
        model -= 1
    return model


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples).

    `model` is snapped to the largest divisor of the device count at or
    below the request, so every device always lands in the mesh — a
    requested model=4 on a 6-device host yields a (2, 3) mesh over all 6
    devices, not a (1, 4) mesh that silently drops two.
    """
    n = len(jax.devices())
    model = snap_model(n, model)
    return device_mesh((n // model, model), ("data", "model"))
