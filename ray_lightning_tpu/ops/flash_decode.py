"""Flash-decode: the per-token serve hot path as a TPU Pallas kernel.

``cached_attention`` (ops/attention.py) is a masked dense einsum: every
decoded token reads ALL ``S x L`` cache rows of its layer and
materializes ``[S, H, 1, L]`` fp32 scores, however short each slot's
live context is.
Decode is bandwidth-bound — one query token against L cache rows — so
the win is not FLOPs, it is *bytes not read*.  This kernel:

- splits the KV cache into ``block_k``-row blocks on a ``(slot, kv
  block)`` grid with an **online softmax** (running max ``m``, running
  sum ``l``, rescaled accumulator ``acc`` in VMEM scratch, exactly the
  flash forward decomposition of ops/flash_attention.py) and a final
  combine at the last block;
- is **length-aware**: ``positions`` rides the grid as a scalar-prefetch
  operand (SMEM), so both the compute guard (``@pl.when``) AND the
  BlockSpec index_map see each slot's bound.  The index_map *clamps*
  dead blocks to the last live block — Pallas skips the DMA for a block
  whose mapped index is unchanged from the previous grid step, so a slot
  at position p reads ``ceil((p+1)/block_k)`` KV blocks, not ``L/block_k``;
- reads blocks of the CALL's length, not the cache's: the flat, grouped
  and latent calls take ``ceil(L / block_k)`` blocks a slot whatever
  ``L`` is, and where ``block_k`` does not tile ``L`` the last block ends
  past the array.  Its rows there hold whatever VMEM held; the body
  zeroes those VALUE rows (their scores are out by the bound already,
  but ``0 x NaN`` is NaN), and emits nothing where the blocks tile
  (``_decode_body``'s ``cache_rows``; PR 42: a block halved until it
  tiled cost ZAYA1's 3,328-row slots a third of the kernel's time);
- has a **paged** variant whose KV index_map walks a page table
  (``serve/fleet/pages.py identity_page_table``): a layer of the cache
  is viewed as ``[S*pages_per_slot, page_size, C]`` physical pages and
  block ``p`` of slot ``s`` fetches physical page ``table[s, p]``.
  Today's table is the identity (the device cache is slot-contiguous);
  the kernel contract is already the indirect one, so physical page
  sharing only changes the table;
- reads the **resident cache as it lies**: the serve plane keeps K and V
  as ``[n_layer, S, L, C]`` (serve/kvcache.py) and every layer's call
  gets the whole buffer plus its static layer number.  The kernel sees
  ``[n_layer*S, L, C]`` — a merge of leading dimensions, which is a
  bitcast on the TPU's tiled layouts — and its K/V index_map adds
  ``layer*S`` to the slot (paged: ``layer*S*pages_per_slot`` to the
  physical page).  No slice of a layer and no relayout is ever made.

Heads are packed on the lane axis (``C = H*D``), which is the cache's
own minor dimension, and a block's heads are computed TOGETHER: the
query is kept in VMEM as a block-diagonal ``[Hp, C]`` matrix (row ``h``
holds head ``h``'s ``D`` columns, zeros elsewhere; ``Hp`` is ``H``
rounded up to the sublane tile), so one product with the packed K block
gives ``[Hp, block_k]`` scores, one masked online-softmax update runs
on them with ``m`` and ``l`` as ``[Hp, 1]`` columns (heads on sublanes,
keys on lanes: the flash forward kernel's own layout), and one product
``p @ V`` adds ``[Hp, C]`` to the accumulator, whose diagonal blocks
the slot's last grid step picks out as the ``[1, C]`` output row.  No
head is sliced out of the lanes and no product has one row; the
off-diagonal products are MXU work nobody waits for (a K or V tile is
pushed into the MXU once either way, and ``Hp`` rows pass it instead of
one).  On the v5e, at ``gpt2-large``'s 20 heads of 64 this runs a call
in 0.145 ms where the loop over heads (two M=1 products a head a block)
took 0.384, at EvaByte's 32 of 128 1.18 ms against 1.84 (PERF.md,
PR 29).  On non-TPU backends everything runs under the Pallas
interpreter so the tier-1 suite executes the real kernel body on CPU —
which says nothing about what Mosaic accepts:
tests/test_chip_compile.py compiles flat, paged and ``eva_decode``,
bf16 and f32, for a described v5e at the served geometries.

Numerics: operands in the cache's dtype, fp32 accumulation, fp32
softmax statistics, ``NEG_INF = -1e30`` masking (NaN-free under exp,
ops/flash_attention.py idiom), ``p`` cast to the cache's dtype before
the second product, output in the caller's compute dtype.  The zeros of
the block-diagonal query add exact zeros to the fp32 sums — parity with
the dense einsum within the documented bars, f32 2e-5 and bf16 2e-2
(tests/test_ops.py ``test_decode_parity``).
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_lightning_tpu.ops.flash_attention import NEG_INF, _use_interpret

VALID_DECODE_IMPLS = ("auto", "dense", "flash_decode", "paged")

#: stable op-name tag: pallas custom-calls carry the kernel function
#: name, and telemetry/anatomy.py buckets "flash"/"pallas"/"custom-call"
#: names into compute (never collectives — comm/audit.py guard)
_KERNEL_NAME = "flash_decode_kernel"


def resolve_decode_impl(value=None) -> str:
    """The REQUESTED decode attention impl: explicit value >
    ``RLT_DECODE_IMPL`` env > ``auto``.  ``auto`` is returned as such —
    :func:`select_decode_kernel` turns it into a kernel from the
    platform and the cache geometry."""
    v = (value or os.environ.get("RLT_DECODE_IMPL") or "auto").lower()
    if v not in VALID_DECODE_IMPLS:
        raise ValueError(
            f"RLT_DECODE_IMPL must be one of {VALID_DECODE_IMPLS}, "
            f"got {v!r}")
    return v


def select_decode_kernel(L: int, H: int, D: int, *, dtype, impl=None,
                         n_pages=None, by_slot: bool = False) -> str:
    """The kernel :func:`~ray_lightning_tpu.ops.attention.cached_attention`
    lowers for this cache geometry: ``dense``, ``flash_decode`` or
    ``paged``.  (The calls of the models that bring their own serve state
    choose beside theirs: ops/eva_attention.py ``eva_decode``,
    ops/window_attention.py ``gqa_decode``, models/xing.py's
    ``mla_decode`` through :func:`latent_kernel_supported`.)

    ``auto`` follows what the code can observe: the Pallas kernel on TPU
    when the geometry lowers (:func:`decode_kernel_supported`), the
    dense einsum otherwise (CPU serving stays untouched unless a caller
    opts in).  An EXPLICIT ``flash_decode``/``paged`` request that the
    geometry cannot lower raises — it never silently becomes the dense
    einsum.  ``paged`` without a page table (``n_pages=None``) is the
    slot-contiguous kernel: same Pallas body, identity fetch.
    ``by_slot``: the rows name their slots (``cached_attention``'s
    ``slots``), so without a page table they walk their slots' own
    pages, which have to tile the cache (:func:`_pick_block_k`)."""
    req = resolve_decode_impl(impl)
    if req == "dense":
        return "dense"
    if req == "auto":
        if jax.devices()[0].platform != "tpu":
            return "dense"
        want = "flash_decode"
    else:
        want = req
    if want == "paged" and n_pages is None:
        want = "flash_decode"
    if want == "paged":
        bk = L // n_pages
    else:
        bk = _pick_block_k(L) if by_slot else flat_block_k(L)
    if decode_kernel_supported(L, H, D, block_k=bk, dtype=dtype,
                               ragged=want != "paged" and not by_slot):
        return want
    if req == "auto":
        return "dense"
    raise ValueError(
        f"decode impl {req!r} was requested explicitly but the cache "
        f"geometry L={L}, H={H}, D={D}, block_k={bk}, "
        f"dtype={jnp.dtype(dtype).name} cannot lower on this platform "
        f"(needs H*D % 128 == 0 and block_k a sublane multiple, and "
        f"pages that tile L); use impl='auto' or 'dense'")


# -- which kernel a program actually lowered --------------------------------
#
# The serve engine reports the kernel its decode program LOWERED, not the
# one requested: cached_attention notes its choice here while a program
# traces, each kernel call notes the blocks it reads a slot's rows in,
# and the engine collects the notes around each trace (serve/engine.py
# ``_counted``).  Thread-local because the AOT precompiler traces on its
# own thread.

_trace_notes = threading.local()


@contextlib.contextmanager
def record_decode_kernels():
    """Collect what the decode attentions lower while tracing on this
    thread inside the block.  Yields a dict: each kernel's name (``dense``
    among them) to the distinct ``[block rows, blocks a slot, rows in the
    last block]`` its calls read a slot in, in the order they traced
    (none for ``dense``)."""
    prev = getattr(_trace_notes, "seen", None)
    seen: dict = {}
    _trace_notes.seen = seen
    try:
        yield seen
    finally:
        _trace_notes.seen = prev


def note_decode_kernel(kernel: str, block_k: "int | None" = None,
                       cache_rows: "int | None" = None) -> None:
    """``kernel`` lowered on this thread; with ``block_k``, a call of it
    that reads a slot's ``cache_rows`` rows in blocks of ``block_k``."""
    seen = getattr(_trace_notes, "seen", None)
    if seen is None:
        return
    blocks = seen.setdefault(kernel, [])
    if block_k is not None:
        nk = pl.cdiv(cache_rows, block_k)
        triple = [block_k, nk, cache_rows - (nk - 1) * block_k]
        if triple not in blocks:
            blocks.append(triple)


def kv_block_bound(kb: int, pos, block_k: int):
    """The length-aware index_map clamp: the KV block index block ``kb``
    actually fetches for a slot at position ``pos``.  Blocks past the
    slot's bound re-map to the last live block (``pos // block_k``) —
    an unchanged mapped index between sequential grid steps means Pallas
    skips the block's DMA, which is the measured traffic saving.
    Consistent with the compute guard: ``kb * block_k <= pos`` iff
    ``kb <= pos // block_k`` (integer division)."""
    return jnp.minimum(kb, pos // block_k)


#: A cache's rows a slot are whole tiles of 8 on the chip: the compiler
#: copies an array of other lengths whole before a kernel reads it (24
#: slots x 1,001 rows x 1,280 lanes: 248 MB of temporaries a call where
#: 1,000 rows leave none; described v5e, PR 42).  Blocks that tile the
#: cache imply it; a ragged last block does not.
_ROW_TILE = 8


def decode_kernel_supported(L: int, H: int, D: int, *,
                            block_k: int, dtype,
                            ragged: bool = False) -> bool:
    """Whether the kernel path can lower for this cache geometry.  The
    packed lane axis ``C = H*D`` must be a 128-lane multiple and a block
    whole sublane tiles on the TPU (the interpreter takes anything), and
    the blocks must tile ``L`` unless the call takes a ``ragged`` last
    block, one that ends past the cache, whose rows :func:`_decode_body`
    masks: the flat call and ``gqa_decode`` do (and ``mla_decode``, whose
    row is one head of 576 lanes with the value inside it and which asks
    :func:`latent_kernel_supported`); the paged call's pages and
    ``eva_decode``'s two ranges of rows do not.  Either way the cache
    holds whole tiles of rows (``_ROW_TILE``)."""
    C = H * D
    if L % block_k and not ragged:
        return False
    if _use_interpret():
        return True
    sub = 16 if dtype == jnp.bfloat16 else 8
    return C % 128 == 0 and block_k % sub == 0 and L % _ROW_TILE == 0


#: Cache rows a grid step reads.  The kernel alone, ms a call at 128 /
#: 256 / 512 rows (builder's chip run, PR 29; PERF.md section 6): 0.1453
#: / 0.1437 / 0.1536 at gpt2-large's geometry; at EvaByte's 256 is +5 %
#: and 512 does not fit Mosaic's 16 MB of scoped VMEM.
_BLOCK_K = 128


def flat_block_k(L: int) -> int:
    return min(_BLOCK_K, L)


def _pick_block_k(L: int, most: int = _BLOCK_K) -> int:
    """The largest block of at most ``most`` rows, by halving, that TILES
    ``L``: for the two calls that cannot take a ragged last block.  The
    paged call views a layer as ``[pages, page_size, C]``, a reshape, and
    ``eva_decode``'s block tiles two ranges of rows
    (ops/eva_attention.py ``decode_block_k``): a block that does not tile
    is there not a tail to mask but a wrong address.  The flat, grouped
    and latent calls read blocks of their constant whatever ``L`` is."""
    b = min(most, L)
    while L % b:
        b //= 2
    return max(b, 1)


def _decode_body(pos, kb, nk, logical_base,
                 q_ref, k_ref, v_ref, o_ref, qd_ref, m_ref, l_ref, acc_ref,
                 *, sm_scale, block_k, head_dim, rows=None, group=None,
                 value_dim=None, cache_rows=None):
    """Online-softmax update for one ``block_k``-row KV block of one
    slot, every packed head at once.  ``logical_base`` is the block's
    first LOGICAL cache row (page-table indirection moves only the
    physical fetch; masking is always in logical positions).

    A slot sees the rows ``<= pos``.  A cache whose rows are not
    positions (ops/eva_attention.py: two ranges of rows a slot) gives
    its own bound as ``rows = (live, seen)``: whether this block holds a
    row the slot sees, and which of a block's logical rows it sees
    (``seen(cols)``).  Row 0 is seen under either bound.

    Heads lie on the SUBLANE axis of everything the body keeps (the
    module's docstring says why): ``qd_ref`` [Hp, C] the block-diagonal
    query, ``m_ref`` / ``l_ref`` [Hp, 128] the running max and sum,
    ``acc_ref`` [Hp, C] the rescaled ``p @ V``, of whose row ``h`` only
    head ``h``'s own columns are kept (:func:`decode_scratch`).

    ``group`` (:func:`grouped_decode_attention`): ``group`` query heads
    read one K/V head, so ``Hp`` query heads stand against ``Hp / group``
    heads of ``head_dim`` lanes in a row.  Row ``h`` of the query then
    owns the columns of K/V head ``h // group``; ``q_ref`` and ``o_ref``
    are ``[1, Hp, head_dim]``, and the block-diagonal query is filled,
    and the output taken back, by ``Hp / group`` static tile copies.

    ``value_dim`` (:func:`latent_decode_attention`, with ``group``): a
    row's value is its key's first ``value_dim`` lanes, so ``v_ref`` is
    the block that ``k_ref`` is (one fetch serves both), ``p @`` reads
    those lanes of it, and ``acc_ref`` and ``o_ref`` are ``value_dim``
    wide a K/V head.

    ``cache_rows``: the rows the cache array holds a slot.  Where
    ``block_k`` does not tile them the last block ends past the array
    and its tail is whatever VMEM held (the interpreter: NaN).  Those
    rows are out of the scores by the bound (``cols <= pos``, here with
    ``cols < cache_rows`` for a bound past the cache; ``where`` selects,
    so a NaN score does not spread), but ``p = 0`` times a NaN value is
    NaN: the VALUE rows past the array are zeroed.  Where the blocks tile
    the cache nothing is emitted."""
    live = kb * block_k <= pos if rows is None else rows[0]
    hp, width = qd_ref.shape

    def own_columns():
        # [Hp, C] bool: the columns of row h's own head (none for a row
        # of padding, h >= H)
        first = jax.lax.broadcasted_iota(jnp.int32, (hp, width), 0) \
            * head_dim
        col = jax.lax.broadcasted_iota(jnp.int32, (hp, width), 1)
        return (col >= first) & (col < first + head_dim)

    def tiles(dim=head_dim):
        # (rows of a group's query heads, columns of its K/V head)
        return [(slice(g * group, (g + 1) * group),
                 slice(g * dim, (g + 1) * dim))
                for g in range(width // head_dim)]

    @pl.when(kb == 0)
    def _init():
        if group is None:
            # the query broadcast over sublanes under the mask: no
            # transpose, no lane slice (through float32, which holds
            # every bfloat16 exactly)
            q = q_ref[0].astype(jnp.float32)                # [1, C]
            qd_ref[:] = jnp.where(own_columns(), q, 0.0).astype(
                qd_ref.dtype)
        else:
            qd_ref[:] = jnp.zeros_like(qd_ref)
            for heads, cols in tiles():
                qd_ref[heads, cols] = q_ref[0, heads, :].astype(
                    qd_ref.dtype)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def update(tail: bool):
        k = k_ref[0]                                        # [block_k, C]
        v = v_ref[0] if value_dim is None else v_ref[0][:, :value_dim]
        cols = (jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
                + logical_base)
        valid = cols <= pos if rows is None else rows[1](cols)
        if tail:
            # (a bound past the cache, a slot speculating past its end,
            # sees every row there is and no more)
            valid &= cols < cache_rows
            row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(row < cache_rows % block_k, v, jnp.zeros_like(v))
        # q . k^T, the flash forward kernel's own form
        s = jax.lax.dot_general(
            qd_ref[:], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [Hp, bk]
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[:, :1]                               # [Hp, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)                     # [Hp, 1]
        p = jnp.exp(s - m_new)                              # [Hp, bk]
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [Hp, C]
        acc_ref[:] = alpha * acc_ref[:] + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    if cache_rows is None or cache_rows % block_k == 0:
        pl.when(live)(lambda: update(False))
    else:
        # the mask in the last block's step alone (the readings:
        # _GROUPED_BLOCK_K): every other step runs the tiling shape's code
        pl.when(live & (kb < nk - 1))(lambda: update(False))
        pl.when(live & (kb == nk - 1))(lambda: update(True))

    if group is not None:
        @pl.when(kb == nk - 1)
        def _final_grouped():
            for heads, cols in tiles(value_dim or head_dim):
                o_ref[0, heads, :] = (acc_ref[heads, cols]
                                      / l_ref[heads, :1]).astype(o_ref.dtype)
        return

    @pl.when(kb == nk - 1)
    def _final():
        # each column's own head: its accumulator and its sum, one
        # nonzero a column, so the sums over sublanes are exact.
        # l > 0 always: logical row 0 satisfies ``0 <= pos`` for any
        # non-negative position, so at least one key is live
        own = own_columns()
        acc = jnp.sum(jnp.where(own, acc_ref[:], 0.0), axis=0,
                      keepdims=True)                        # [1, C]
        l = jnp.sum(jnp.where(own, l_ref[:, :1], 0.0), axis=0,
                    keepdims=True)
        o_ref[0] = (acc / l).astype(o_ref.dtype)


def decode_scratch(n_head: int, width: int, dtype,
                   value_width: "int | None" = None) -> list:
    """The VMEM a call around :func:`_decode_body` keeps across a slot's
    blocks: the block-diagonal query in the cache's dtype and the
    float32 running max, running sum and rescaled accumulator
    (``value_width`` wide where a row's value is narrower than its key),
    heads on sublanes (``Hp``: ``n_head`` rounded up to ``dtype``'s
    tile)."""
    sub = 8 * 4 // jnp.dtype(dtype).itemsize
    hp = -(-n_head // sub) * sub
    return [pltpu.VMEM((hp, width), dtype),
            pltpu.VMEM((hp, 128), jnp.float32),
            pltpu.VMEM((hp, 128), jnp.float32),
            pltpu.VMEM((hp, value_width or width), jnp.float32)]


def flash_decode_kernel(positions_ref, *refs, **kw):
    s, kb = pl.program_id(0), pl.program_id(1)
    _decode_body(positions_ref[s], kb, pl.num_programs(1),
                 kb * kw["block_k"], *refs, **kw)


def flash_decode_paged_kernel(positions_ref, table_ref, *refs, **kw):
    s, p = pl.program_id(0), pl.program_id(1)
    _decode_body(positions_ref[s], p, pl.num_programs(1),
                 p * kw["block_k"], *refs, **kw)


def flash_decode_attention(q, k_cache, v_cache, positions, *, layer,
                           slots=None, dtype=jnp.bfloat16, block_k=None,
                           page_table=None, interpret=None):
    """Length-aware flash decode over one layer of the slot cache.

    ``q`` [S, 1, H, D]; ``k_cache``/``v_cache`` [n_layer, S, L, H*D] —
    the whole resident buffers; ``layer`` — which layer of them this
    call reads (a static int: it only offsets the K/V index_map);
    ``positions`` [S] int32; returns [S, 1, H, D] in ``dtype``.  With
    ``page_table`` ([S, pages_per_slot] int32, physical page ids into
    the layer's ``[S*pages_per_slot, page_size, C]`` page view) the KV
    index_map walks the table instead of the slot-contiguous layout;
    ``page_size`` is implied by ``L // page_table.shape[1]``.

    ``slots`` ([B] int32, traced): ``q`` is then [B, 1, H, D] and row b
    reads cache slot ``slots[b]`` — an index_map can follow a traced
    slot only through a scalar-prefetch table, so without a
    ``page_table`` (whose rows are the batch rows' then) the rows walk
    their slots' own ``block_k``-row pages through the paged kernel.

    Unpaged, a slot's rows are read in blocks of ``_BLOCK_K`` whatever
    ``L`` is: the last block may end past the array
    (``_decode_body``'s ``cache_rows``).  Pages tile ``L``."""
    B, _, H, D = q.shape
    n_layer, S, L, C = k_cache.shape
    if C != H * D or not 0 <= layer < n_layer \
            or (slots is None and B != S):
        raise ValueError(
            f"cache {k_cache.shape} does not hold layer {layer} of "
            f"{B} rows x {H} heads x {D}")
    noted = "flash_decode" if page_table is None else "paged"
    if slots is not None and page_table is None:
        nk = L // (block_k or _pick_block_k(L))
        page_table = slots[:, None] * nk + jnp.arange(nk)[None, :]
    paged = page_table is not None
    if paged:
        n_pages = page_table.shape[1]
        if L % n_pages:
            raise ValueError(
                f"page table with {n_pages} pages cannot tile L={L}")
        bk = L // n_pages
    else:
        bk = block_k or flat_block_k(L)
    nk = pl.cdiv(L, bk)
    note_decode_kernel(noted, bk, L)
    if interpret is None:
        interpret = _use_interpret()

    q2 = q.reshape(B, 1, C)
    # merges of leading dimensions only: the minor (L, C) tiles stay
    # where they are, so these are bitcasts, not copies
    k2 = k_cache.reshape(n_layer * S, L, C)
    v2 = v_cache.reshape(n_layer * S, L, C)
    base = layer * S

    if paged:
        # physical page view; the table maps (slot, logical page) ->
        # physical page row of the layer, whose pages start at base*nk
        k2 = k2.reshape(n_layer * S * nk, bk, C)
        v2 = v2.reshape(n_layer * S * nk, bk, C)

        def kv_map(s, p, pos_ref, tab_ref):
            return (base * nk
                    + tab_ref[s, kv_block_bound(p, pos_ref[s], bk)], 0, 0)

        def sq_map(s, p, pos_ref, tab_ref):
            return (s, 0, 0)

        kernel = flash_decode_paged_kernel
        scalars = (jnp.asarray(positions, jnp.int32),
                   jnp.asarray(page_table, jnp.int32))
        kv_block = (1, bk, C)
    else:
        def kv_map(s, kb, pos_ref):
            return (base + s, kv_block_bound(kb, pos_ref[s], bk), 0)

        def sq_map(s, kb, pos_ref):
            return (s, 0, 0)

        kernel = flash_decode_kernel
        scalars = (jnp.asarray(positions, jnp.int32),)
        kv_block = (1, bk, C)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((1, 1, C), sq_map),
            pl.BlockSpec(kv_block, kv_map),
            pl.BlockSpec(kv_block, kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, C), sq_map),
        scratch_shapes=decode_scratch(H, C, k_cache.dtype),
    )
    body = functools.partial(
        kernel, sm_scale=1.0 / float(np.sqrt(D)), block_k=bk, head_dim=D,
        cache_rows=L)
    # both names keep the "flash" stem: the anatomy category table and
    # the collective classifier key on it (telemetry/anatomy.py
    # bucket_of, comm/audit.py collective_kind)
    body.__name__ = _KERNEL_NAME if not paged \
        else "flash_decode_paged_kernel"
    out = pl.pallas_call(
        body,
        # the custom call's name in the compiled program and the trace
        name="flash_decode" if not paged else "flash_decode_paged",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, C), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*scalars, q2, k2, v2)
    return out.reshape(B, 1, H, D)


#: Cache rows a grid step of the GROUPED call reads, whatever the cache's
#: length (the last block may end past it).  Its row is 8 K/V heads of 128 lanes (2 KB in bf16)
#: and its scores are [128, block_k]: larger blocks pay.  The kernel
#: alone at 32 slots, ms a call at 128 / 256 / 512 rows (builder's chip
#: run, PR 33; PERF.md section 6): a ring of 4096 rows read whole 1.147 /
#: 0.883 / 0.780 (its bytes' time 0.656); 6,901 of 8,960 rows (which 512
#: does not tile) 1.997 / 1.498 (1.104); 101 rows 0.511 / 0.337.
#: models/zaya.py's row is 2 K/V heads (512 B) under 8 query heads, 128
#: slots (builder's chip run, PR 41; ~0.2 ms of each is the host's floor a
#: call), ms a call at 1,500 / 3,000 live rows a slot, bytes' time 0.240 /
#: 0.480: blocks of 128 rows 1.113 / 1.620, of 256 (which tile 3,328)
#: 0.696 / 1.035, of 512 (3,584) 0.513 / 0.753, and over 4,096 rows 512:
#: 0.515 / 0.766, 1024: 0.519 / 0.684, 2048: 0.539 / 0.725.  The narrow row
#: wants 512 as the wide one does.  Its benchmark cell holds 3,328 rows a
#: slot, which 512 does not tile: six blocks of 512 and a seventh whose
#: last 256 rows lie past the array, their values masked in the body.
#: The kernel alone (builder's chip run, PR 42, one call; three timings of
#: 50 calls each agree to 0.3 %, their medians here), ms a call at 1,500 / 3,000 / 3,328 live
#: rows over 3,328: blocks of 256 0.695 / 1.034 / 1.044; ragged blocks of
#: 512 with the values masked in the LAST block's step alone 0.513 / 0.763
#: / 0.764, by a ``where`` in every live step 0.512 / 0.764 / 0.763, the
#: block's tail zeroed in VMEM in place 0.513 / 0.763 / 0.764, no mask at
#: all (wrong: the floor) 0.513 / 0.763 / 0.763; 3,584 rows, which tile,
#: 0.515 / 0.752 / 0.766.  The wide row's full layer, 32 slots x 8,960
#: rows, at 6,901 / 8,960 live (bytes' time 1.104 / 1.434): 256 1.439 /
#: 1.755; ragged 512 last-only 1.349 / 1.602, every step 1.346 / 1.603, in
#: place 1.347 / 1.602, none 1.346 / 1.602.  The mask costs nothing that
#: shows wherever it stands; it stands in the last block's step, where no
#: other step can pay for it (the latent call's every-step mask read
#: +1.2 % at a full cache).
_GROUPED_BLOCK_K = 512
#: the grouped call's name in the compiled program and the trace
GROUPED_KERNEL_NAME = "gqa_decode"


def grouped_block_k(L: int) -> int:
    return min(_GROUPED_BLOCK_K, L)


def grouped_decode_attention(q, k_cache, v_cache, bound, *, layer,
                             dtype=jnp.bfloat16):
    """Flash decode where ``H`` query heads read ``G`` K/V heads (query
    head ``i`` the K/V head ``i // (H / G)``), over one layer of a
    resident cache whose row is the ``G`` K/V heads side by side.

    ``q`` [S, 1, H, D]; ``k_cache`` / ``v_cache`` [n_layer, S, L, G*D],
    whole; ``bound`` [S] int32: slot ``s`` sees the rows ``<= bound[s]``
    (a row-per-position cache: its position; a ring of ``L`` positions:
    ``min(position, L - 1)``, ops/window_attention.py).  Returns [S, 1,
    H, D] in ``dtype``.  The same body as :func:`flash_decode_attention`
    (``_decode_body`` with ``group``), the same length-aware index_map."""
    S, _, H, D = q.shape
    n_layer, slots, L, C = k_cache.shape
    if C % D or H % (C // D) or slots != S or not 0 <= layer < n_layer:
        raise ValueError(
            f"cache {k_cache.shape} does not hold layer {layer} of {S} "
            f"slots x K/V heads of {D} that divide {H} query heads")
    bk = grouped_block_k(L)
    nk = pl.cdiv(L, bk)
    note_decode_kernel(GROUPED_KERNEL_NAME, bk, L)
    base = layer * S

    def kv_map(s, kb, pos_ref):
        return (base + s, kv_block_bound(kb, pos_ref[s], bk), 0)

    def sq_map(s, kb, pos_ref):
        return (s, 0, 0)

    def kernel(bound_ref, *refs, **kw):
        s, kb = pl.program_id(0), pl.program_id(1)
        _decode_body(bound_ref[s], kb, pl.num_programs(1), kb * bk,
                     *refs, **kw)

    body = functools.partial(
        kernel, sm_scale=1.0 / float(np.sqrt(D)), block_k=bk, head_dim=D,
        group=H // (C // D), cache_rows=L)
    body.__name__ = GROUPED_KERNEL_NAME + "_kernel"
    out = pl.pallas_call(
        body,
        name=GROUPED_KERNEL_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S, nk),
            in_specs=[pl.BlockSpec((1, H, D), sq_map),
                      pl.BlockSpec((1, bk, C), kv_map),
                      pl.BlockSpec((1, bk, C), kv_map)],
            out_specs=pl.BlockSpec((1, H, D), sq_map),
            scratch_shapes=decode_scratch(H, C, k_cache.dtype)),
        out_shape=jax.ShapeDtypeStruct((S, H, D), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_use_interpret(),
    )(jnp.asarray(bound, jnp.int32), q.reshape(S, H, D),
      # merges of leading dimensions: bitcasts, not copies
      k_cache.reshape(n_layer * S, L, C),
      v_cache.reshape(n_layer * S, L, C))
    return out.reshape(S, 1, H, D)


#: Cache rows a grid step of the LATENT call reads, whatever the cache's
#: length.  Its row is one K/V head of 640 lanes (576 values, 1,152 B
#: in bf16, and 64 lanes of zeros), its scores [32, block_k].  The kernel
#: alone at 64 slots, ms a call at 128 / 256 / 512 / 1024 rows (builder's
#: chip run, PR 36; PERF.md section 6): 6,400-9,900 of 10,240 rows a slot
#: 3.305 / 2.363 / 1.879 / 1.634 (its 1,152 B rows' time 0.734, the 1,280 B
#: it reads 0.815); of 9,984 rows, which 512 does not tile, 3.258 / 2.319
#: (builder's chip run, PR 42, at 8,000 / 9,984 live rows: the 256 that
#: tile 9,984 1.613 / 1.856, ragged blocks of 1,024 1.013 / 1.114, with the
#: mask in every live step 1.013 / 1.127, none 1.011 / 1.112; 10,240 rows
#: 1.012 / 1.130);
#: 101 rows 1.275 / 0.996 / 0.937 / 0.933.  32 query rows are a quarter of
#: the MXU's height: a block's two products take about as long as its
#: bytes, and the two do not overlap.
_LATENT_BLOCK_K = 1024
#: the latent call's name in the compiled program and the trace
LATENT_KERNEL_NAME = "mla_decode"


def latent_block_k(L: int) -> int:
    return min(_LATENT_BLOCK_K, L)


def latent_kernel_supported(L: int, C: int, value_dim: int, *,
                            dtype) -> bool:
    """Whether :func:`latent_decode_attention` can lower: the interpreter
    takes anything; Mosaic wants a block to be whole sublane tiles (the
    last may end past the cache) and the value's lanes to end on a lane
    tile (the key's width is the array's whole minor dimension, whatever
    it is)."""
    bk = latent_block_k(L)
    if _use_interpret():
        return True
    sub = 16 if dtype == jnp.bfloat16 else 8
    return bk % sub == 0 and L % _ROW_TILE == 0 \
        and value_dim % 128 == 0 and value_dim <= C


def latent_decode_attention(q, cache, positions, *, layer: int,
                            value_dim: int, sm_scale: float,
                            dtype=jnp.bfloat16):
    """Flash decode over one layer of a LATENT cache (models/xing.py):
    every query head reads the ONE row a position keeps, whose value is
    the row's first ``value_dim`` lanes.

    ``q`` [S, H, C] (the absorbed query beside the rotated one, as a row
    lies); ``cache`` [n_layer, S, L, C], whole: ONE array, keys and
    values at once; ``positions`` [S] int32: slot ``s`` sees the rows
    ``<= positions[s]``.  Returns [S, H, value_dim] in ``dtype``.  The
    body is :func:`flash_decode_attention`'s (``_decode_body`` with
    ``group = H`` and ``value_dim``): a block of rows is fetched once,
    scored against all ``C`` lanes, and ``p @`` its first ``value_dim``;
    the same length-aware index_map."""
    S, H, C = q.shape
    n_layer, slots, L, width = cache.shape
    if width != C or slots != S or not 0 <= layer < n_layer \
            or value_dim > C:
        raise ValueError(
            f"cache {cache.shape} does not hold layer {layer} of {S} "
            f"slots x rows of {C} whose first {value_dim} are the value")
    bk = latent_block_k(L)
    nk = pl.cdiv(L, bk)
    note_decode_kernel(LATENT_KERNEL_NAME, bk, L)
    base = layer * S

    def kv_map(s, kb, pos_ref):
        return (base + s, kv_block_bound(kb, pos_ref[s], bk), 0)

    def sq_map(s, kb, pos_ref):
        return (s, 0, 0)

    def kernel(pos_ref, q_ref, k_ref, o_ref, *scratch, **kw):
        s, kb = pl.program_id(0), pl.program_id(1)
        _decode_body(pos_ref[s], kb, pl.num_programs(1), kb * bk,
                     q_ref, k_ref, k_ref, o_ref, *scratch, **kw)

    body = functools.partial(
        kernel, sm_scale=float(sm_scale), block_k=bk, head_dim=C, group=H,
        value_dim=value_dim, cache_rows=L)
    body.__name__ = LATENT_KERNEL_NAME + "_kernel"
    return pl.pallas_call(
        body,
        name=LATENT_KERNEL_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S, nk),
            in_specs=[pl.BlockSpec((1, H, C), sq_map),
                      pl.BlockSpec((1, bk, C), kv_map)],
            out_specs=pl.BlockSpec((1, H, value_dim), sq_map),
            scratch_shapes=decode_scratch(H, C, cache.dtype, value_dim)),
        out_shape=jax.ShapeDtypeStruct((S, H, value_dim), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_use_interpret(),
    )(jnp.asarray(positions, jnp.int32), q,
      # a merge of leading dimensions: a bitcast, not a copy
      cache.reshape(n_layer * S, L, C))


__all__ = [
    "NEG_INF",
    "VALID_DECODE_IMPLS",
    "decode_kernel_supported",
    "flash_decode_attention",
    "grouped_decode_attention",
    "kv_block_bound",
    "latent_decode_attention",
    "latent_kernel_supported",
    "note_decode_kernel",
    "record_decode_kernels",
    "resolve_decode_impl",
    "select_decode_kernel",
]
