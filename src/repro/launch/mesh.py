"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first init.
Mesh construction goes through ``distributed.sharding.make_mesh`` (every
axis ``AxisType.Auto``).
"""

from __future__ import annotations

import jax

from repro.distributed.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model); the pod axis is pure DP
    over DCN, data is DP/FSDP over ICI, model is TP/EP over ICI."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(devices=None):
    """(n, 1) data x model over ``devices`` (default: every device this
    host has — tests / examples / training)."""
    devs = list(jax.devices() if devices is None else devices)
    return make_mesh((len(devs), 1), ("data", "model"), devices=devs)


def make_serving_mesh(shards: int, devices=None):
    """1-D ("model",) mesh over the first ``shards`` devices — the mesh
    ``Engine.serve(mesh=...)`` shards attention heads and the paged block
    pool across. Raises (rather
    than letting XLA fail on placement) when the host has too few devices,
    with the simulated-device recipe CI uses."""
    devs = list(jax.devices() if devices is None else devices)
    n = int(shards)
    if n < 1:
        raise ValueError(f"shards must be >= 1, got {n}")
    if n > len(devs):
        raise ValueError(
            f"serving mesh wants {n} shards but only {len(devs)} device(s) "
            "are visible; on CPU hosts simulate devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            "(set it before the first jax import — see README, "
            "'Multi-device serving')")
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.asarray(devs[:n]), ("model",))
