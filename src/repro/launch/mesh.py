"""Production meshes.  Defined as FUNCTIONS so importing this module never
touches jax device state (the dry-run pins the device count before any
jax initialization)."""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types (sharding propagation, as
    the model code expects)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = ("data", "model") — 256 chips (v5e pod).
    Multi-pod: (2, 16, 16) = ("pod", "data", "model") — 512 chips; the
    "pod" axis extends data parallelism across the cross-pod DCN/ICI."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_smoke_mesh(shape=(2, 2), axes=("data", "model")):
    """Tiny host-device mesh for tests (requires
    --xla_force_host_platform_device_count >= prod(shape))."""
    return _make_mesh(shape, axes)


def make_fleet_mesh(n_devices=None, axis="study"):
    """1-D mesh for the fleet ask plane: the study axis is embarrassingly
    parallel, so the fleet shards slot blocks over a single ``"study"``
    dimension spanning ``n_devices`` (default: every visible device).
    A 1-device fleet mesh is valid and bit-for-bit equal to running
    unsharded — the placement-independence invariant."""
    import numpy as np
    from jax.sharding import Mesh
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if not 1 <= n <= len(devs):
        raise ValueError(f"fleet mesh needs 1 <= n_devices <= {len(devs)} "
                         f"visible devices, got {n}")
    return Mesh(np.asarray(devs[:n]), (axis,))


# v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW_PER_LINK = 50e9          # B/s/link
HBM_BYTES = 16 * 1024**3        # 16 GiB
