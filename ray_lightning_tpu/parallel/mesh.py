"""Device-mesh construction over TPU topologies.

The reference's topology unit is "one process per GPU joining a NCCL
group" with rank math derived from node IPs (ray_ddp.py:282-306).  The
TPU-native unit is a ``jax.sharding.Mesh`` over all chips of all hosts;
rank math is subsumed by ``jax.process_index()`` + the mesh's logical
axes.  ``build_device_mesh`` shapes the global device list into named
axes (data / fsdp / tensor / sequence / expert), preferring ICI-contiguous
placement for the innermost (most communication-heavy) axes by putting
them last, which keeps XLA collectives on-slice.
"""

from __future__ import annotations

import math
import threading
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh


def _infer_axis_sizes(n_devices: int, axis_sizes: dict[str, int],
                      axis_names: Sequence[str]) -> list[int]:
    """Fill in at most one -1/None axis so the product equals n_devices."""
    sizes = [axis_sizes.get(name, None) for name in axis_names]
    known = [s for s in sizes if s not in (None, -1)]
    unknown = [i for i, s in enumerate(sizes) if s in (None, -1)]
    prod = math.prod(known) if known else 1
    if len(unknown) > 1:
        raise ValueError(f"At most one axis may be inferred, got {axis_sizes}")
    if unknown:
        if n_devices % prod != 0:
            raise ValueError(
                f"{n_devices} devices not divisible by fixed axes {axis_sizes}")
        sizes[unknown[0]] = n_devices // prod
    elif prod != n_devices:
        raise ValueError(
            f"Mesh axes {dict(zip(axis_names, sizes))} need {prod} devices, "
            f"have {n_devices}")
    return [int(s) for s in sizes]


def build_device_mesh(
    axis_names: Sequence[str] = ("data",),
    axis_sizes: dict[str, int] | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a Mesh over ``devices`` (default: all global devices).

    ``axis_sizes`` maps axis name → size; one axis may be ``-1``/absent to
    absorb the remainder (typically the data axis).  Axis order in
    ``axis_names`` is outermost→innermost: put the heaviest-traffic axis
    (tensor) last so it lands on physically adjacent chips.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    sizes = _infer_axis_sizes(len(devices), dict(axis_sizes or {}), axis_names)
    arr = np.asarray(devices, dtype=object).reshape(sizes)
    return Mesh(arr, tuple(axis_names))


def mesh_axis_size(mesh: Mesh, *names: str) -> int:
    """Product of the sizes of the given axes present in the mesh."""
    total = 1
    for n in names:
        if n in mesh.axis_names:
            total *= mesh.shape[n]
    return total


# The trainer publishes its mesh here so mesh-aware ops traced *inside*
# its jitted step (ring attention's shard_map, parallel/ring.py) can
# reach it without threading a handle through the flax module tree.
# Thread-local because concurrent tune trials each run a Trainer in
# their own thread (tune/runner.py) with distinct meshes.
_MESH_TLS = threading.local()


def data_and_tensor_axes(mesh: Mesh):
    """(data_axes, tensor_axis) present in ``mesh`` — the batch/head
    sharding layout shared by the attention shard_map paths
    (ops/attention.py, parallel/ring.py)."""
    dp = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names) or None
    tensor = "tensor" if "tensor" in mesh.axis_names else None
    return dp, tensor


def set_current_mesh(mesh: Mesh | None) -> None:
    _MESH_TLS.mesh = mesh


def get_current_mesh() -> Mesh | None:
    return getattr(_MESH_TLS, "mesh", None)
