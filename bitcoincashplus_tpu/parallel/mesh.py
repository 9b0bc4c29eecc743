"""Device mesh construction.

One 1-D mesh axis ('chip',) spanning all local devices — the v5e-8 target is
a single host with 8 chips in a 2x4 ICI ring (SURVEY.md §6.8); a 1-D logical
axis is the right shape because both sharded workloads (nonce sweep, sig
batch) are embarrassingly parallel with a single tiny reduction.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

CHIP_AXIS = "chip"


def local_devices() -> list:
    """Devices for the mesh: JAX's own answer (JAX_PLATFORMS=cpu with
    xla_force_host_platform_device_count=N gives N virtual CPU devices)."""
    return jax.devices()


def device_count() -> int:
    return len(local_devices())


def chip_mesh(n: int | None = None) -> Mesh:
    """Mesh over the first n local devices (default: all)."""
    devs = local_devices()
    if n is not None:
        if n > len(devs):
            raise ValueError(f"requested {n} devices, have {len(devs)}")
        devs = devs[:n]
    return Mesh(np.array(devs), (CHIP_AXIS,))


def shard_map_nocheck(body, mesh: Mesh, in_specs, out_specs):
    """shard_map with the varying-mesh-axes check off: pallas_call's
    out_shape carries no VMA annotation."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
