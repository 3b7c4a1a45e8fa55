"""Per-host TPU chip partitioning — the ``_share_cuda_visible_devices``
analog (reference ray_ddp.py:221-265).

The reference unions each node's GPU ids into ``CUDA_VISIBLE_DEVICES``
so co-located workers can address their devices.  TPU inverts the
problem: libtpu assumes one process owns the whole host unless told
otherwise, so when several actors land on ONE TPU host (splitting a
v4-8 into per-chip workers, say) each process must be scoped to its own
chips via the ``TPU_*`` env family *before* libtpu initializes:

- ``TPU_CHIPS_PER_PROCESS_BOUNDS`` — the 3-D topology slab of chips one
  process owns;
- ``TPU_PROCESS_BOUNDS`` — how many such slabs tile the host;
- ``TPU_VISIBLE_CHIPS`` / ``TPU_VISIBLE_DEVICES`` — which local chip
  indices this process may open;
- ``TPU_PROCESS_ADDRESSES`` + ``TPU_PROCESS_PORT`` +
  ``CLOUD_TPU_TASK_ID`` — the co-located processes' local mesh
  rendezvous.

Impossible splits (a chip count that is not a rectangular sub-slab of
the host) raise instead of silently producing a hung libtpu init.
"""

from __future__ import annotations

from typing import Sequence

#: chip-count → 3-D bounds for the host form factors we know how to
#: tile: 1 chip, a chip pair, a v4/v5p host (2×2), a v2/v3/v5e host
#: (2×4).
_BOUNDS: dict[int, tuple[int, int, int]] = {
    1: (1, 1, 1),
    2: (1, 2, 1),
    4: (2, 2, 1),
    8: (2, 4, 1),
}


def process_bounds(devices_per_worker: int,
                   n_colocated: int) -> tuple[str, str]:
    """(chips_per_process_bounds, process_bounds) strings for
    ``n_colocated`` workers each owning ``devices_per_worker`` chips of
    one host.  The split must exactly tile a known host form factor."""
    host_chips = devices_per_worker * n_colocated
    if devices_per_worker not in _BOUNDS or host_chips not in _BOUNDS:
        raise ValueError(
            f"cannot split a TPU host into {n_colocated} workers x "
            f"{devices_per_worker} chips: {host_chips} chips is not a "
            f"known host form factor {sorted(_BOUNDS)} "
            f"(reference analog: _share_cuda_visible_devices, "
            f"ray_ddp.py:221-265)")
    cpb = _BOUNDS[devices_per_worker]
    host = _BOUNDS[host_chips]
    if any(h % c for h, c in zip(host, cpb)):
        raise ValueError(
            f"{devices_per_worker}-chip slab {cpb} does not tile the "
            f"{host_chips}-chip host {host}")
    pb = tuple(h // c for h, c in zip(host, cpb))
    return ",".join(map(str, cpb)), ",".join(map(str, pb))


def partition_env(
    devices_per_worker: int,
    local_rank: int,
    node_ip: str,
    ports: Sequence[int],
) -> dict[str, str]:
    """Env for ONE co-located worker (``local_rank`` of
    ``len(ports)`` on ``node_ip``; ``ports[i]`` is worker i's local
    rendezvous port)."""
    n = len(ports)
    cpb, pb = process_bounds(devices_per_worker, n)
    lo = local_rank * devices_per_worker
    chips = ",".join(str(c) for c in range(lo, lo + devices_per_worker))
    return {
        "TPU_CHIPS_PER_PROCESS_BOUNDS": cpb,
        "TPU_PROCESS_BOUNDS": pb,
        "TPU_VISIBLE_CHIPS": chips,
        # libtpu 0.0.34 lists this name beside TPU_VISIBLE_CHIPS
        "TPU_VISIBLE_DEVICES": chips,
        "TPU_PROCESS_ADDRESSES": ",".join(
            f"{node_ip}:{p}" for p in ports),
        "TPU_PROCESS_PORT": str(ports[local_rank]),
        "CLOUD_TPU_TASK_ID": str(local_rank),
    }
