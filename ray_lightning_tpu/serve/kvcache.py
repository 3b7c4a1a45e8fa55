"""Slot-indexed, device-resident KV cache for continuous batching.

The cache is two arrays ``[n_layer, S, R, C]`` (keys / values): ``S``
batch slots x ``R`` rows a slot x ``C = n_head * head_dim``, living on
device for the whole life of the serve fleet and sharded through the
training strategies (``ShardingStrategy.kv_cache_spec`` — slots ride
the data axes like a batch dim, ``C`` rides ``tensor`` under SPMD: whole
heads, as long as ``n_head`` divides).

What a row is depends on the model, and the model says (``KVCacheSpec.
from_capture`` reads it off the model's prefill capture):

- **a row per position** (models/gpt.py): ``R = max_seq_len``, row ``t``
  is position ``t``'s key (value), and a slot at position ``p`` sees the
  rows ``<= p``.  Everything below about prefixes, pages and masks by
  position is this kind's.
- **one window and one summary row per chunk** (models/evabyte.py,
  ops/eva_attention.py): ``R = window + max_seq_len // chunk``; position
  ``m`` of the slot's current window lives in row ``m % window``, the
  pooled key (value) of chunk ``j`` in row ``window + j``, and a slot sees
  two ranges of rows (``visible_rows``).  Both kinds of row have the
  width ``C``, so the arrays, their donation and the programs' plumbing
  are the same; the model's own ``prefill`` and ``decode`` methods write
  and read them.  A prefix of a prompt is NOT a prefix of this state:
  prefix reuse, paging and KV shipping refuse such a model.
- **two kinds of layer, a cache of its own each** (models/command.py,
  ops/window_attention.py): a sliding layer keeps a ring of ``window``
  rows a slot (position ``t`` in row ``t % window``), a full layer a row
  per position.  Layers of one kind share a pair of arrays, so the state
  is a SHORT TUPLE of pairs, ``[n_sliding, S, window, C]`` and ``[n_full,
  S, positions, C]``: ``KVCacheSpec.kinds``.  The keys' and the values'
  arrays travel as two small pytrees (a tuple an array a kind) through
  the same programs, donated like the one pair; a model with one kind is
  the one-element case and its programs see the two bare arrays they
  always saw.  A model may ask for a small int32 accumulator beside them
  (``counters``: what its steps count on the device, read with the
  server's stats and never inside a step); it rides behind the keys'
  arrays.
- **one latent row a position** (models/xing.py, ops/flash_decode.py
  ``mla_decode``): a position keeps ONE row, the normed latent ``c_kv``
  beside the rotated shared key (512 + 64 values), which every query
  head reads as its key and whose first 512 lanes are its value.  There
  is no values' array: the state is ONE array a kind, ``[n_layer, S,
  positions, 640]`` (the 576 values and 64 lanes of zeros: whole lane
  tiles; ``KVCacheSpec.paired`` False, read off a capture
  that sows one block a layer and not two).  It travels where the keys'
  arrays travel, the accumulator behind it, and the values' side of
  every program is the empty tuple: the programs, their donation and
  their warm-up are the pairs'.
- **a row per position and a TAIL a slot** (models/zaya.py): the keys
  and values of compressed convolutional attention are a row per
  position (256 lanes each, 1,024 B in bfloat16 a layer), but a
  position's key is made from the position BEFORE it too: two
  convolutions of kernel 2 and a value shifted by one.  What a decode
  step at position ``t`` needs of ``t - 1`` is neither a row nor a
  position: a small block a slot a layer, ``[n_layer, S, r, c]`` of the
  model's own ``r`` and ``c`` (``KVCacheSpec.tail``, read off a capture
  that sows a THIRD block a layer; 2 x 2,688 values there), float32
  (``TAIL_DTYPE``: what the convolutions read).  It is one more array
  on the keys' side, behind the kinds' arrays and before the
  accumulator, donated with them.  A step may run
  twice at one position (serve/worker.py: a decode queued ahead and
  dropped is queued again from the plan), so a tail a step overwrote in
  place would be read as its own predecessor: the model keeps TWO
  generations, position ``t``'s in row ``t % 2``, and a step at ``t``
  reads row ``(t - 1) % 2`` and writes row ``t % 2``: run again, it
  reads and writes the same values.
- **a matrix a head and NO rows** (models/kimi_linear.py, ops/kda.py):
  a Kimi Delta Attention layer keeps no row at all.  A slot's state in
  such a layer is what the layer's capture sows as a :class:`SlotState`:
  blocks of the model's own shapes and types, here a float32 matrix a
  head (``[H V, K]``: 2 MB published) that every step MULTIPLIES, a ring
  of the last four positions' convolution inputs (position ``p`` in row
  ``p % 4``: written by position like a cache row, so idempotent), and an
  int32 STAMP: the position the matrix stands at.  Layers that sow alike
  share one array a block, ``[layers, S, *block]``
  (``KVCacheSpec.states``), on the keys' side behind the kinds' arrays
  and before a tail; the layers between them that keep rows (one latent
  row a position there) are the kinds, as ever, fewer than ``n_layer``.
  A matrix that a step multiplies cannot be rewritten by position, and a
  step may run twice (above).  The model keeps ONE generation and reads
  the stamp: a step at ``t`` updates a state that stands at ``t - 1``
  (and stamps it ``t``), reads out of one that already stands at ``t``
  unchanged, and leaves any other alone (a dead slot's dummy step), so a
  second run computes from the state the first run left and returns the
  first run's values.  A prefill writes state, ring and stamp (``length
  - 1``) whole at its slot, so a freed slot needs no clearing.  Two
  generations, as the tail has them, would have doubled the largest
  array of such a cell (3.0 GB at 192 slots) for the same traffic; the
  stamps are 4 B a slot a layer.

There is ONE layout, the one the decode kernels read: a row is a
token's heads side by side on the lane axis, which is how the qkv
projection makes it (ops/attention.py) and how ops/flash_decode.py
computes on it.  Every program takes the two arrays whole, donated, and
touches of them only the rows it writes and the rows attention reads;
none slices a layer out, unpacks the heads or stacks layers back (on
the TPU's tiled layouts ``[.., H, D] -> [.., H*D]`` is a copy of the
whole cache, not a view).  In-flight request insertion and eviction are
SLOT INDEX operations:

- insert  = the bucket prefill program ``dynamic_update_slice``-writes a
  prompt's K/V block at its slot (core/steps.py build_prefill_step; the
  window-and-summary kind: its last window and its summaries; a tail:
  the generation of the prompt's LAST position, ``length - 1``, whatever
  the bucket pads to);
- advance = the decode program scatter-writes one row per slot and
  layer at ``[layer, slot, position]`` (ops/attention.py
  MultiHeadAttention; the window-and-summary kind: at ``position %
  window``, and the current chunk's summary row again; a tail: the
  other generation read, this position's written);
- evict   = the driver frees the slot index — NO device work.  Stale
  rows beyond what a slot's position lets it see are unreachable by
  construction (the per-slot mask), so a freed slot is reusable the
  moment the next prefill overwrites its rows (and the one generation
  of its tail that the first decode reads).

Shapes are static whatever the live-request mix, so the decode loop
never re-traces — the property the serve acceptance pins with trace
counters (serve/engine.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

#: a slot's tail is kept as what the model's convolutions read
TAIL_DTYPE = np.dtype(np.float32)


class SlotState(NamedTuple):
    """What a layer that keeps NO rows sows in their place: ``blocks``, a
    tuple of ``[B, 1, *block]`` arrays of the model's own shapes and
    types, each one block a slot (module docstring)."""

    blocks: tuple


@dataclass(frozen=True)
class KVCacheSpec:
    """Host-side description of the device cache (picklable; shipped to
    workers inside the serve payload).  ``width`` is a row's length
    ``C = n_head * head_dim``: the cache never sees heads apart.
    ``max_seq_len`` is the positions a slot's sequence may reach;
    ``rows`` the rows a slot holds per layer: None for a model that
    keeps a row per position (``max_seq_len`` rows), the model's own
    count otherwise (module docstring).  ``kinds``: ``((n_layer, rows),
    ...)`` where layers of more than one kind keep a cache of their own
    each (empty: the one kind ``n_layer`` and ``rows`` describe);
    ``counters``: the length of the int32 accumulator a model asked
    for beside the cache (0: none); ``paired``: a keys' and a values'
    array a kind, or (False) the one array of a model whose row holds
    both (module docstring); ``tail``: ``(r, c)`` of the ``TAIL_DTYPE``
    block a slot keeps a layer beside its rows (empty: none);
    ``states``: ``((layers, block, dtype name), ...)``, one array
    ``[layers, S, *block]`` each, of the layers that keep no rows and a
    :class:`SlotState` instead (empty: none; ``kinds`` then counts only
    the layers that keep rows)."""

    n_layer: int
    slots: int
    max_seq_len: int
    width: int
    rows: "int | None" = None
    kinds: "tuple[tuple[int, int], ...]" = ()
    counters: int = 0
    paired: bool = True
    tail: "tuple[int, ...]" = ()
    states: "tuple[tuple[int, tuple[int, ...], str], ...]" = ()

    @property
    def own_state(self) -> bool:
        """Whether a slot's rows are the model's own kind and not a row
        per position (module docstring)."""
        return self.rows is not None or bool(self.kinds) \
            or bool(self.tail) or bool(self.states)

    @property
    def shapes(self) -> "tuple[tuple[int, int, int, int], ...]":
        """``[n_layer, S, R, C]`` of each kind's pair of arrays (or its
        one array)."""
        kinds = self.kinds or ((
            self.n_layer,
            self.max_seq_len if self.rows is None else self.rows),)
        return tuple((n, self.slots, rows, self.width)
                     for n, rows in kinds)

    @property
    def shape(self) -> tuple[int, int, int, int]:
        """``[n_layer, S, R, C]`` — THE shape every serve program and
        the decode kernels share, where the layers are of one kind."""
        if len(self.kinds) > 1:
            raise ValueError(
                f"the cache holds {len(self.kinds)} kinds of layer, "
                f"{self.shapes}: it has no one shape")
        return self.shapes[0]

    @property
    def tail_shape(self) -> "tuple[int, int, int, int] | None":
        """``[n_layer, S, r, c]`` of the slots' tails, None without."""
        if not self.tail:
            return None
        return (self.n_layer, self.slots) + tuple(self.tail)

    @property
    def state_bytes_per_slot(self) -> int:
        """Bytes of ``states`` one slot holds, all their layers."""
        return sum(n * int(np.prod(block, dtype=np.int64))
                   * np.dtype(dtype).itemsize
                   for n, block, dtype in self.states)

    def nbytes(self, itemsize: int = 2) -> int:
        """Device residency of BOTH cache arrays (k and v) of every kind,
        or of its one array, at the given element size (bf16 default),
        and of the tails and the states in theirs."""
        tail = int(np.prod(self.tail_shape, dtype=np.int64)) \
            * TAIL_DTYPE.itemsize if self.tail else 0
        return tail + self.slots * self.state_bytes_per_slot + sum(
            (1 + self.paired) * int(np.prod(shape, dtype=np.int64))
            * itemsize for shape in self.shapes)

    def state(self, make, dtype):
        """``(k, v)`` as every serve program takes and returns them,
        each leaf ``make(shape, dtype)`` (zeros, or an aval).  One kind
        and no accumulator: the two bare arrays.  Otherwise a tuple an
        array a kind, the states, the tails and then the int32
        accumulator behind the keys'; the values' side empty where a
        kind is one array."""
        kinds = tuple(make(shape, dtype) for shape in self.shapes)
        if len(kinds) == 1 and not self.counters and self.paired \
                and not self.tail and not self.states:
            return kinds[0], kinds[0]
        extra = (make((self.counters,), np.int32),) if self.counters else ()
        if self.tail:
            extra = (make(self.tail_shape, TAIL_DTYPE),) + extra
        states = tuple(make((n, self.slots) + tuple(block), np.dtype(t))
                       for n, block, t in self.states)
        return kinds + states + extra, kinds if self.paired else ()

    @classmethod
    def from_capture(cls, kv_shapes, slots: int, max_seq_len: int,
                     counters: int = 0) -> "KVCacheSpec":
        """Derive the cache geometry from a prefill ``eval_shape``
        capture: ``kv_shapes`` is any per-layer K aval list (core/steps.py
        _stacked_kv order), or the layers' captured tuples themselves
        (``kv_layer_pairs``), where a tuple of ONE block says that the
        layer's row holds key and value at once and a THIRD block
        ``[B, 1, r, c]`` is the tail a slot keeps in that layer beside
        its rows (every layer's alike).  A :class:`SlotState` is a layer
        that keeps no rows: its blocks are ``states``, one array for all
        the layers that sow a block alike, and the layers that do keep
        rows are ``kinds`` even where they are of one kind.  An entry
        shaped ``[B, T, C]`` is a row per captured position: the cache
        holds ``max_seq_len`` rows a slot.
        An entry shaped ``[B, 1, R, C]`` is a model's own state block,
        as its ``prefill`` method writes it at a slot: ``R`` rows a
        slot, whatever ``max_seq_len`` (the model sized it from its own
        configuration's positions, which the engine checks).  Blocks of
        differing ``R`` are layers of differing kinds: one kind a
        distinct ``R``, in the order of each kind's first layer, which
        is the order the model finds its arrays in."""
        n_layer = len(kv_shapes)
        stateful = [k for k in kv_shapes if isinstance(k, SlotState)]
        kv_shapes = [k for k in kv_shapes if not isinstance(k, SlotState)]
        blocks = {tuple((tuple(int(n) for n in b.shape[2:]),
                         np.dtype(b.dtype).name) for b in k.blocks)
                  for k in stateful}
        if len(blocks) > 1 or (stateful and not kv_shapes):
            raise ValueError(
                f"layers without rows keep states of differing blocks, or "
                f"no layer keeps rows: {sorted(blocks)}")
        states = tuple((len(stateful),) + b
                       for b in next(iter(blocks), ()))
        paired = not any(isinstance(k, tuple) and len(k) == 1
                         for k in kv_shapes)
        tails = {(tuple(int(n) for n in k[2].shape[2:]),
                  np.dtype(k[2].dtype).name)
                 for k in kv_shapes if isinstance(k, tuple) and len(k) == 3}
        if len(tails) > 1 or any(t != TAIL_DTYPE.name for _, t in tails):
            raise ValueError(f"layers keep tails of differing shapes, or "
                             f"not {TAIL_DTYPE.name}: {sorted(tails)}")
        tail = next(iter(tails), ((),))[0]
        kv_shapes = [k[0] if isinstance(k, tuple) else k for k in kv_shapes]
        if n_layer == 0:
            raise ValueError("model captured no kv_cache entries; does "
                             "its attention sow the 'kv_cache' "
                             "collection? (ops/attention.py)")
        per_layer = [int(k.shape[2]) if len(k.shape) == 4 else None
                     for k in kv_shapes]
        distinct = tuple(dict.fromkeys(per_layer))
        one_kind = len(distinct) == 1 and not stateful
        return cls(n_layer=n_layer, slots=slots, max_seq_len=max_seq_len,
                   width=int(kv_shapes[0].shape[-1]),
                   rows=distinct[0] if one_kind else None,
                   kinds=() if one_kind else tuple(
                       (per_layer.count(r), r or max_seq_len)
                       for r in distinct),
                   counters=counters, paired=paired, tail=tail,
                   states=states)


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


__all__ = ["KVCacheSpec", "SlotAllocator", "SlotState"]
