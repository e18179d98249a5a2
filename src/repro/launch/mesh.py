"""Production mesh builders.

Single-pod: (16, 16) → ("data", "model") — 256 chips (one v5e pod).
Multi-pod:  (2, 16, 16) → ("pod", "data", "model") — 512 chips.

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import)."""

from __future__ import annotations

import jax


def make_auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with Auto axes: the models place activations with
    ``with_sharding_constraint``, which Explicit axes (the default) refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist, as a 1-D 'data' mesh (tests/examples)."""
    return make_auto_mesh((len(jax.devices()),), ("data",))


def make_grid_mesh(n: int | None = None):
    """First ``n`` devices (default: all) as a 1-D ``'grid'`` mesh.

    The sweep engines (:mod:`repro.fleet.shard`) partition their stacked
    grid-case axis over this mesh with ``shard_map``; a submesh over a
    device subset lets one process bench 1/2/4/... device scaling from the
    same pool of (possibly ``--xla_force_host_platform_device_count``
    virtual) devices.
    """
    import numpy as np

    devices = jax.devices()
    n = len(devices) if n is None else int(n)
    if not 1 <= n <= len(devices):
        raise ValueError(f"need 1 <= n <= {len(devices)} devices, got {n}")
    return jax.sharding.Mesh(np.asarray(devices[:n]), ("grid",))
