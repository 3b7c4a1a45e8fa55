"""Slot-indexed, device-resident KV cache for continuous batching.

The cache is two arrays ``[n_layer, S, L, C]`` (keys / values): ``S``
batch slots x ``L`` max context x ``C = n_head * head_dim``, living on
device for the whole life of the serve fleet and sharded through the
training strategies (``ShardingStrategy.kv_cache_spec`` — slots ride
the data axes like a batch dim, ``C`` rides ``tensor`` under SPMD: whole
heads, as long as ``n_head`` divides).

There is ONE layout, the one the decode kernel reads: a row is a
token's heads side by side on the lane axis, which is how the qkv
projection makes it (ops/attention.py) and how ops/flash_decode.py
computes on it.  Every program takes the two arrays whole, donated, and
touches of them only the rows it writes and the rows attention reads;
none slices a layer out, unpacks the heads or stacks layers back (on
the TPU's tiled layouts ``[.., H, D] -> [.., H*D]`` is a copy of the
whole cache, not a view).  In-flight request insertion and eviction are
SLOT INDEX operations:

- insert  = the bucket prefill program ``dynamic_update_slice``-writes a
  prompt's K/V block at its slot (core/steps.py build_prefill_step);
- advance = the decode program scatter-writes one row per slot and
  layer at ``[layer, slot, position]`` (ops/attention.py
  MultiHeadAttention);
- evict   = the driver frees the slot index — NO device work.  Stale
  K/V beyond a slot's position bound are unreachable by construction
  (the per-slot position mask), so a freed slot is reusable the moment
  the next prefill overwrites its prefix.

Shapes are static whatever the live-request mix, so the decode loop
never re-traces — the property the serve acceptance pins with trace
counters (serve/engine.py).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class KVCacheSpec:
    """Host-side description of the device cache (picklable; shipped to
    workers inside the serve payload).  ``width`` is a row's length
    ``C = n_head * head_dim``: the cache never sees heads apart."""

    n_layer: int
    slots: int
    max_seq_len: int
    width: int

    @property
    def shape(self) -> tuple[int, int, int, int]:
        """``[n_layer, S, L, C]`` — THE shape every serve program and
        the decode kernel share."""
        return (self.n_layer, self.slots, self.max_seq_len, self.width)

    def nbytes(self, itemsize: int = 2) -> int:
        """Device residency of BOTH cache arrays (k and v) at the given
        element size (bf16 default)."""
        return 2 * int(np.prod(self.shape, dtype=np.int64)) * itemsize

    @classmethod
    def from_capture(cls, kv_shapes, slots: int,
                     max_seq_len: int) -> "KVCacheSpec":
        """Derive the cache geometry from a prefill ``eval_shape``
        capture: ``kv_shapes`` is any per-layer K aval list with entries
        shaped ``[B, T, C]`` (core/steps.py _stacked_kv order)."""
        n_layer = len(kv_shapes)
        if n_layer == 0:
            raise ValueError("model captured no kv_cache entries; does "
                             "its attention sow the 'kv_cache' "
                             "collection? (ops/attention.py)")
        _, _, width = kv_shapes[0].shape
        return cls(n_layer=n_layer, slots=slots, max_seq_len=max_seq_len,
                   width=int(width))


class SlotAllocator:
    """Driver-side free-list of cache slots (the host half of
    insert/evict; the device half is the index writes above)."""

    def __init__(self, slots: int):
        if slots < 1:
            raise ValueError(f"need >= 1 slot, got {slots}")
        self.slots = slots
        self._free = list(range(slots))
        self._used: set[int] = set()

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return len(self._used)

    def acquire(self) -> "int | None":
        if not self._free:
            return None
        slot = self._free.pop(0)
        self._used.add(slot)
        return slot

    def release(self, slot: int) -> None:
        if slot not in self._used:
            raise ValueError(f"slot {slot} is not in use")
        self._used.remove(slot)
        self._free.append(slot)

    def in_use(self) -> tuple[int, ...]:
        return tuple(sorted(self._used))


__all__ = ["KVCacheSpec", "SlotAllocator"]
