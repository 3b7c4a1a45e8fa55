"""EVA attention: one exact window plus one summary row per chunk.

EVA (Zheng et al., "Efficient Attention via Control Variates", ICLR
2023) as EvaByte computes it (models/evabyte.py).  Positions fall into
windows ``w(t) = t // window`` and chunks ``j(t) = t // chunk``.  A chunk's
SUMMARY pools its members' keys and values under ``softmax_m(s * phi .
k_m)`` (``phi``, ``mu``: two learned vectors a head; ``mu`` is added to
the pooled key); it does not depend on the query.  A query attends, in
one softmax, the exact rows of its own window up to itself and the
summaries of every chunk of every earlier window.

So a slot of the serve cache (serve/kvcache.py) holds, per layer,
``window`` exact rows and ``max_positions // chunk`` summary rows, all of
the packed width ``H*D``:

    rows [0, window)                 position m lives in row m % window
    rows [window, window + n_chunks) chunk j's summary lives in row window + j

and a query at position ``t`` may see TWO ranges of them
(:func:`visible_rows`): rows ``<= t % window`` of the first part (what is
beyond is the previous window's, or a former tenant's) and the first
``(t // window) * (window // chunk)`` rows of the second (the current
window's summaries are being built and are not seen until it is over).

Here: the rotary embedding, the pooling (scope ``eva_summary``), prefill
attention over a whole prompt (:func:`eva_attention`, scope ``eva_attn``)
and decode attention of one query a slot against the resident cache
(:func:`eva_cached_attention`).

The layouts' contract.  Everything of a PROMPT's size is packed rows
``[B, T, H*D]`` from the q/k/v products to the output projection, and a
head is a block of ``D`` columns (128 lanes at the published size),
never an axis of its own: :func:`rotary_rows`, :func:`chunk_summaries_rows`
and :func:`eva_attention` take packed rows and return packed rows, and a
head's softmax statistics are one column, ``[.., H, T, 1]``.  On one TPU
chip with heads of 128 each is a Pallas kernel that addresses a head as a
column block (``eva_rotary``, ``eva_pool``, the flash kernel ``flash_fwd``
of ops/flash_attention.py, ``eva_far``); elsewhere the same mathematics
in ``jax.numpy`` on a ``[.., H, D]`` view.  (A ``[T, H, D]`` view of a
prompt puts the heads on sublanes, and the TPU compiler answers it with
relayout copies of the whole tensor, fused into whatever reads it: PR 39
found 35 ms of a 117 ms prefill in four output projections whose
operand arrived as such a view.)  DECODE keeps one row a slot, ``[S, 1,
H, D]`` (:func:`rotary`, :func:`chunk_summaries`), and on the TPU the
Pallas kernel ``eva_decode``, which is ops/flash_decode.py's
online-softmax body (every head of a block in one product against the
block-diagonal query, heads on sublanes; nothing of it is this file's)
under the two-range bound: its index_map clamps dead blocks of either
part to the last live one, so a slot reads only the blocks that hold
rows it may see.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_lightning_tpu.ops import flash_decode as _fd
from ray_lightning_tpu.ops.flash_attention import (
    NEG_INF, _pick_block, flash_attention_lse)

#: the decode kernel's name in the compiled program and the trace
KERNEL_NAME = "eva_decode"
#: the prompt's kernels: rotary on packed rows, and the summaries' part
#: of a prompt's attention with the merge
ROTARY_KERNEL_NAME = "eva_rotary"
FAR_KERNEL_NAME = "eva_far"
POOL_KERNEL_NAME = "eva_pool"


def cache_rows(window: int, chunk: int, max_positions: int) -> int:
    """Rows a slot holds per layer: the exact window and one summary row
    per chunk of ``max_positions``."""
    return window + max_positions // chunk


def visible_rows(position, window: int, chunk: int):
    """``(exact, summaries)``: how many rows of each part a query at
    ``position`` may see (ints or traced int arrays alike)."""
    return position % window + 1, (position // window) * (window // chunk)


def _rotary_angle(positions, theta: float, D: int):
    """``positions`` [..., T] -> the angles ``[..., T, D]`` of a head's
    ``D`` values, float32: the two halves turn alike."""
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.concatenate([angle, angle], axis=-1)


def rotary(x, positions, theta: float):
    """Rotate-half rotary embedding over every dimension of the head.
    ``x`` [..., T, H, D]; ``positions`` [..., T] (or [T]).  Computed in
    float32, returned in ``x``'s type.

    ``rotate_half(x) = concat(-x[D/2:], x[:D/2])`` is written as a product
    with a signed permutation matrix: exact (one term a sum, entries 0
    and +-1), and on the TPU a pass of the MXU instead of half-vreg
    slices and a concatenate on the lane axis, which the compiler
    lowered to relayout copies of the whole ``[T, H, D]`` tensor."""
    D = x.shape[-1]
    angle = _rotary_angle(positions, theta, D)[..., None, :]
    i = jnp.arange(D)
    turn = (jnp.where(i[:, None] == i[None, :] + D // 2, -1, 0)
            + jnp.where(i[:, None] + D // 2 == i[None, :], 1, 0)
            ).astype(x.dtype)
    turned = jnp.einsum("...d,de->...e", x, turn, precision="highest",
                        preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * jnp.cos(angle)
            + turned * jnp.sin(angle)).astype(x.dtype)


def _one_tpu_chip() -> bool:
    """Where a prompt's kernels run: one process driving one TPU chip (a
    serve worker).  Elsewhere (the CPU; a mesh, whose programs would need
    the kernels under ``shard_map``) the same mathematics in ``jax.numpy``
    (a test that wants the kernels under the interpreter says so here)."""
    return not _fd._use_interpret() and jax.device_count() == 1


def rotary_rows(xs, positions, theta: float, n_head: int):
    """:func:`rotary` on packed rows, to the bit.  ``xs``: arrays [B, T,
    H*D] of one shape and type, as the projections leave them (q and k
    turn by the same angles: one call, one reading of the tables);
    ``positions`` [T], shared by the batch.  Returns them rotated, packed.

    A head of 128 values is one block of lanes, so ``rotate_half`` is a
    roll of its lanes by 64 under a sign, and the sign lives in the
    sine's table: ``x * cos + roll(x, 64) * (-sin | +sin)``.  The Pallas
    kernel ``eva_rotary`` reads and writes each row once and nothing
    ever views the rows as ``[T, H, D]`` (the compiler answers that view
    with layouts that put positions on the lanes, and copies back).
    Heads of another size, rows that are not whole tiles, or no single
    TPU chip, take :func:`rotary` on the view."""
    B, T, C = xs[0].shape
    D = C // n_head
    if D != 128 or T % 16 or not _one_tpu_chip():
        return tuple(rotary(x.reshape(B, T, n_head, D), positions,
                            theta).reshape(B, T, C) for x in xs)
    angle = _rotary_angle(positions, theta, D)                  # [T, D]
    sin = jnp.sin(angle) * jnp.where(jnp.arange(D) < D // 2, -1.0, 1.0)
    # a step's rows and heads: the body is traced a head at a time, and
    # a program holds a call a layer (what a start pays to lower)
    rows, G = _pick_block(T, 256), _pick_block(n_head, 8)

    def kernel(cos_ref, sin_ref, *refs):
        cos, sin = cos_ref[...], sin_ref[...]
        for x_ref, o_ref in zip(refs[:len(xs)], refs[len(xs):]):
            for h in range(G):
                head = slice(h * D, (h + 1) * D)
                xh = x_ref[0, :, head].astype(jnp.float32)
                o_ref[0, :, head] = (
                    xh * cos + pltpu.roll(xh, D // 2, 1) * sin
                ).astype(o_ref.dtype)

    kernel.__name__ = ROTARY_KERNEL_NAME + "_kernel"
    heads = pl.BlockSpec((1, rows, G * D), lambda b, i, g: (b, i, g))
    table = pl.BlockSpec((rows, D), lambda b, i, g: (i, 0))
    return tuple(pl.pallas_call(
        kernel, name=ROTARY_KERNEL_NAME, grid=(B, T // rows, n_head // G),
        in_specs=[table, table] + [heads] * len(xs),
        out_specs=[heads] * len(xs),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in xs],
        interpret=_fd._use_interpret(),
    )(jnp.cos(angle), sin, *xs))


def chunk_summaries(k, v, phi, mu, member):
    """Pool chunks of packed rows.  ``k``, ``v`` [B, N, chunk, C] (rotary
    applied to ``k``); ``phi``, ``mu`` [H, D] with ``C = H*D``; ``member``
    [B, N, chunk] bool: which rows exist (a pad row, or one beyond the
    sequence's length, never enters a summary).  Returns ``(K~, V~)``
    [B, N, C] in ``k``'s type; float32 inside.  A chunk without a member
    gives finite rows (zeros, and ``mu``): nothing may see them, but
    what attention masks it still multiplies by zero."""
    B, N, M, C = k.shape
    H, D = phi.shape
    with jax.named_scope("eva_summary"):
        kf = k.astype(jnp.float32).reshape(B, N, M, H, D)
        vf = v.astype(jnp.float32).reshape(B, N, M, H, D)
        logits = jnp.sum(kf * phi.astype(jnp.float32), axis=-1) \
            / math.sqrt(D)
        keep = member[..., None]
        logits = jnp.where(keep, logits, NEG_INF)
        e = jnp.where(keep, jnp.exp(
            logits - jnp.max(logits, axis=2, keepdims=True)), 0.0)
        a = (e / jnp.maximum(jnp.sum(e, axis=2, keepdims=True),
                             1e-30))[..., None]
        k_sum = jnp.sum(a * kf, axis=2) + mu.astype(jnp.float32)
        v_sum = jnp.sum(a * vf, axis=2)
        return (k_sum.reshape(B, N, C).astype(k.dtype),
                v_sum.reshape(B, N, C).astype(v.dtype))


def _summary_rows_kernel(k_ref, v_ref, member_ref, phi_ref, mu_ref,
                         ks_ref, vs_ref, *, heads, chunk, sm_scale):
    """``R`` chunks of ``heads`` heads' columns of packed rows, a head at
    a time: its 128 lanes of the ``R * chunk`` rows as ``[R, chunk, 128]``
    (whole sublane tiles), the pooling weights one column a row."""
    R = k_ref.shape[1] // chunk
    keep = member_ref[0].reshape(R, chunk, 1) > 0
    for h in range(heads):
        head = slice(h * 128, (h + 1) * 128)
        kh = k_ref[0, :, head].astype(jnp.float32).reshape(R, chunk, 128)
        vh = v_ref[0, :, head].astype(jnp.float32).reshape(R, chunk, 128)
        logits = jnp.sum(kh * phi_ref[:, head], axis=-1,
                         keepdims=True) * sm_scale
        logits = jnp.where(keep, logits, NEG_INF)
        e = jnp.where(keep, jnp.exp(
            logits - jnp.max(logits, axis=1, keepdims=True)), 0.0)
        a = e / jnp.maximum(jnp.sum(e, axis=1, keepdims=True), 1e-30)
        ks_ref[0, :, head] = (jnp.sum(a * kh, axis=1)
                              + mu_ref[:, head]).astype(ks_ref.dtype)
        vs_ref[0, :, head] = jnp.sum(a * vh, axis=1).astype(vs_ref.dtype)


def chunk_summaries_rows(k, v, phi, mu, member, chunk: int):
    """:func:`chunk_summaries` of a whole sequence's packed rows: ``k``,
    ``v`` [B, T, C] with ``T`` whole chunks, ``member`` [B, T] bool.
    Returns ``(K~, V~)`` [B, T // chunk, C].

    With heads of 128 on one TPU chip the Pallas kernel ``eva_pool``
    reads each row once, a head a block of lanes (the ``[.., H, D]``
    view of :func:`chunk_summaries` costs a float32 copy of all of ``k``
    and of ``v`` that moves heads from lanes to sublanes); elsewhere,
    and for chunks that are not whole sublane tiles, that function."""
    B, T, C = k.shape
    H, D = phi.shape
    N = T // chunk
    if D != 128 or chunk % 8 or N % 16 or not _one_tpu_chip():
        return chunk_summaries(
            k.reshape(B, N, chunk, C), v.reshape(B, N, chunk, C), phi, mu,
            member.reshape(B, N, chunk))
    R, G = 16, _pick_block(H, 4)       # chunks and heads a grid step
    body = functools.partial(_summary_rows_kernel, heads=G, chunk=chunk,
                             sm_scale=1.0 / math.sqrt(D))
    body.__name__ = POOL_KERNEL_NAME + "_kernel"
    rows = pl.BlockSpec((1, R * chunk, G * D), lambda b, i, g: (b, i, g))
    one = pl.BlockSpec((1, G * D), lambda b, i, g: (0, g))
    pooled = pl.BlockSpec((1, R, G * D), lambda b, i, g: (b, i, g))
    with jax.named_scope("eva_summary"):
        return tuple(pl.pallas_call(
            body, name=POOL_KERNEL_NAME, grid=(B, N // R, H // G),
            in_specs=[rows, rows,
                      pl.BlockSpec((1, R * chunk, 1),
                                   lambda b, i, g: (b, i, 0)),
                      one, one],
            out_specs=[pooled, pooled],
            out_shape=[jax.ShapeDtypeStruct((B, N, C), k.dtype),
                       jax.ShapeDtypeStruct((B, N, C), v.dtype)],
            interpret=_fd._use_interpret(),
        )(k, v, member.astype(jnp.float32)[..., None],
          phi.astype(jnp.float32).reshape(1, C),
          mu.astype(jnp.float32).reshape(1, C)))


# -- a whole sequence (training forward, prefill) --------------------------------

def _causal_with_lse(q, k, v, n_head: int, dtype):
    """Plain causal attention of packed ``[N, T, C]`` rows with its
    log-sum-exp ``[N, H, T, 1]``: the flash kernel on one TPU chip,
    dense elsewhere."""
    if _one_tpu_chip():
        return flash_attention_lse(q, k, v, n_head=n_head, causal=True,
                                   interpret=_fd._use_interpret())
    N, T, C = q.shape
    D = C // n_head
    q, k, v = (a.reshape(N, T, n_head, D) for a in (q, k, v))
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1, keepdims=True)
    p = jnp.exp(s - lse).astype(dtype)
    return jnp.einsum("nhqk,nkhd->nqhd", p, v).astype(dtype).reshape(
        N, T, C), lse


def _far_merge_kernel(q_ref, near_ref, lse_ref, k_ref, v_ref, o_ref, *,
                      sm_scale, block_q, block_k, window, per):
    """One head's column block of ``block_q`` queries of one window:
    an online softmax over the summary rows of earlier windows, block of
    rows by block (as many as hold a row the window may see, and no
    more), and the merge with the near part by the two log-sum-exps.
    The first window sees no summary: its rows are the near part's."""
    w = pl.program_id(2) * block_q // window
    seen = w * per

    @pl.when(w == 0)
    def _near_only():
        o_ref[0] = near_ref[0].astype(o_ref.dtype)

    @pl.when(w > 0)
    def _merge():
        q = q_ref[0]
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

        def rows(j, carry):
            m, l, acc = carry
            at = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
            s = jax.lax.dot_general(
                q, k_ref[0, at, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(cols + j * block_k < seen, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, at, :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        m, l, acc = jax.lax.fori_loop(
            0, (seen + block_k - 1) // block_k, rows,
            (jnp.full((block_q, 1), NEG_INF, jnp.float32),
             jnp.zeros((block_q, 1), jnp.float32),
             jnp.zeros((block_q, q.shape[1]), jnp.float32)))
        near_lse, far_lse = lse_ref[0, 0], m + jnp.log(l)
        top = jnp.maximum(near_lse, far_lse)
        lse = top + jnp.log(jnp.exp(near_lse - top)
                            + jnp.exp(far_lse - top))
        # acc / l is the far part and l * exp(m - lse) its weight
        o_ref[0] = (near_ref[0].astype(jnp.float32)
                    * jnp.exp(near_lse - lse)
                    + acc * jnp.exp(m - lse)).astype(o_ref.dtype)


def _far_merge(q, near, near_lse, k_sum, v_sum, *, n_head, window, chunk,
               dtype):
    """The far part of :func:`eva_attention` and the merge.  ``q``,
    ``near`` [B, T, C] packed; ``near_lse`` [B * T/window, H, window, 1];
    ``k_sum``, ``v_sum`` [B, T/chunk, C].  Returns packed [B, T, C].

    With heads of 128 on one TPU chip the Pallas kernel ``eva_far`` over
    ``(batch, head, query block)``: a head is addressed as a column block
    of the packed rows on the way in and on the way out, so no relayout
    exists to be placed anywhere.  Elsewhere ``jax.numpy``, head-major."""
    B, T, C = q.shape
    H, D = n_head, C // n_head
    nw, per = T // window, window // chunk
    sm_scale = 1.0 / math.sqrt(D)
    if D != 128 or window % 16 or not _one_tpu_chip():
        f32 = {"preferred_element_type": jnp.float32}
        qh = q.reshape(B, nw, window, H, D)
        ks, vs = (a.reshape(B, nw * per, H, D) for a in (k_sum, v_sum))
        s = jnp.einsum("bwqhd,bjhd->bwhqj", qh, ks, **f32) * sm_scale
        seen = jnp.arange(nw * per) < (jnp.arange(nw) * per)[:, None]
        s = jnp.where(seen[:, None, None, :], s, NEG_INF)
        far_lse = jax.nn.logsumexp(s, axis=-1, keepdims=True)
        far = jnp.einsum("bwhqj,bjhd->bwhqd",
                         jnp.exp(s - far_lse).astype(dtype), vs, **f32)
        near_lse = near_lse.reshape(B, nw, H, window, 1)
        lse = jnp.logaddexp(near_lse, far_lse)
        near = jnp.moveaxis(near.reshape(B, nw, window, H, D), 3, 2)
        out = near.astype(jnp.float32) * jnp.exp(near_lse - lse) \
            + far * jnp.exp(far_lse - lse)
        return jnp.moveaxis(out, 2, 3).astype(dtype).reshape(B, T, C)
    bq, bk = _pick_block(window, 512), 128
    per_w = window // bq
    # whole blocks of summary rows; the rows added are never seen
    J = -(-nw * per // bk) * bk
    grow = ((0, 0), (0, J - nw * per), (0, 0))
    k_sum, v_sum = jnp.pad(k_sum, grow), jnp.pad(v_sum, grow)

    def rows(b, h, i):
        return (b, i, h)

    def stats(b, h, i):
        return (b * nw + i // per_w, h, i % per_w, 0)

    def summaries(b, h, i):
        return (b, 0, h)

    body = functools.partial(_far_merge_kernel, sm_scale=sm_scale,
                             block_q=bq, block_k=bk, window=window, per=per)
    body.__name__ = FAR_KERNEL_NAME + "_kernel"
    return pl.pallas_call(
        body, name=FAR_KERNEL_NAME, grid=(B, H, T // bq),
        in_specs=[pl.BlockSpec((1, bq, D), rows),
                  pl.BlockSpec((1, bq, D), rows),
                  pl.BlockSpec((1, 1, bq, 1), stats),
                  pl.BlockSpec((1, J, D), summaries),
                  pl.BlockSpec((1, J, D), summaries)],
        out_specs=pl.BlockSpec((1, bq, D), rows),
        out_shape=jax.ShapeDtypeStruct((B, T, C), dtype),
        interpret=_fd._use_interpret(),
    )(q, near, near_lse, k_sum, v_sum)


def eva_attention(q, k, v, k_sum, v_sum, *, n_head: int, window: int,
                  chunk: int, dtype=jnp.bfloat16):
    """EVA over a whole sequence, packed rows in and packed rows out.
    ``q``, ``k``, ``v`` [B, T, H*D] (rotary applied), ``T`` at most one
    window or a multiple of it; ``k_sum``, ``v_sum`` [B, T // chunk, H*D]
    (:func:`chunk_summaries`).  Returns [B, T, H*D] in ``dtype``: what
    the output projection multiplies, as it is.

    A sequence within one window is plain causal attention and takes the
    repo's attention dispatch (the flash kernel on the TPU).  A longer
    one is two attentions under one softmax: every window's causal
    attention over its own exact rows (all windows as one batch of the
    flash kernel, with its log-sum-exp), and a dense attention over the
    summaries of earlier windows (at most ``T / chunk`` keys), which the
    two log-sum-exps weigh against the first (:func:`_far_merge`)."""
    from ray_lightning_tpu.ops.attention import auto_attention
    B, T, C = q.shape
    with jax.named_scope("eva_attn"):
        if T <= window:
            # the dispatch's [B, T, H, D] views are bitcasts of packed
            # rows on both sides of the flash kernel
            return auto_attention(
                *(a.reshape(B, T, n_head, C // n_head) for a in (q, k, v)),
                causal=True, dtype=dtype).reshape(B, T, C)
        if T % window:
            raise ValueError(f"{T} positions are not whole windows of "
                             f"{window}")
        nw = T // window
        near, near_lse = _causal_with_lse(
            *(a.reshape(B * nw, window, C) for a in (q, k, v)), n_head,
            dtype)
        return _far_merge(q, near.reshape(B, T, C), near_lse, k_sum, v_sum,
                          n_head=n_head, window=window, chunk=chunk,
                          dtype=dtype)


# -- one query a slot against the resident cache ---------------------------------

def decode_block_k(window: int, far_rows: int) -> int:
    """Rows a grid step of the decode kernel reads: the largest block of
    at most ``flash_decode._BLOCK_K`` (128) rows that tiles both parts
    of a slot, so that no block holds rows of the two."""
    return _fd._pick_block_k(math.gcd(window, far_rows))


def select_eva_kernel(window: int, far_rows: int, H: int, D: int, *,
                      dtype, impl=None, page_table=None) -> str:
    """``dense`` or ``eva_decode``, as ops/flash_decode.py chooses between
    the dense einsum and its kernel: ``RLT_DECODE_IMPL`` (or ``impl``)
    ``dense`` / ``flash_decode`` (here: the Pallas kernel of this file) /
    ``auto`` (the kernel on the TPU when the geometry lowers).  ``paged``
    is refused: a page table addresses rows by position, and this
    cache's rows are not positions."""
    req = _fd.resolve_decode_impl(impl)
    if req == "paged" or page_table is not None:
        raise ValueError(
            "the paged decode kernel cannot read a window-and-summary "
            "cache: a page table maps positions to rows, and a slot's "
            "rows here are one window and one summary row per chunk "
            "(ops/eva_attention.py); use RLT_DECODE_IMPL=auto")
    if req == "dense":
        return "dense"
    if req == "auto" and jax.devices()[0].platform != "tpu":
        return "dense"
    bk = decode_block_k(window, far_rows)
    if _fd.decode_kernel_supported(window + far_rows, H, D, block_k=bk,
                                   dtype=dtype):
        return KERNEL_NAME
    if req == "auto":
        return "dense"
    raise ValueError(
        f"decode impl {req!r} was requested explicitly but a cache of "
        f"{window} + {far_rows} rows, H={H}, D={D}, block_k={bk}, "
        f"dtype={jnp.dtype(dtype).name} cannot lower on this platform")


def eva_cached_attention(q, k_cache, v_cache, positions, *, layer: int,
                         window: int, chunk: int, dtype=jnp.bfloat16,
                         impl=None, page_table=None):
    """One query a slot against layer ``layer`` of the resident cache.
    ``q`` [S, 1, H, D] (rotary applied); ``k_cache`` / ``v_cache``
    [n_layer, S, rows, H*D], whole, as they lie; ``positions`` [S].
    Slot ``s`` sees the two ranges :func:`visible_rows` gives for
    ``positions[s]``.  Returns [S, 1, H, D] in ``dtype``."""
    S, _, H, D = q.shape
    rows = k_cache.shape[2]
    kernel = select_eva_kernel(window, rows - window, H, D, dtype=q.dtype,
                               impl=impl, page_table=page_table)
    _fd.note_decode_kernel(kernel)
    with jax.named_scope("eva_attn"):
        if kernel != "dense":
            return _eva_decode_kernel_call(
                q, k_cache, v_cache, positions, layer=layer, window=window,
                chunk=chunk, dtype=dtype)
        k = k_cache[layer].reshape(S, rows, H, D)
        v = v_cache[layer].reshape(S, rows, H, D)
        scores = jnp.einsum("sqhd,slhd->shql", q, k,
                            preferred_element_type=jnp.float32) \
            / math.sqrt(D)
        exact, far = visible_rows(positions[:, None], window, chunk)
        row = jnp.arange(rows)[None, :]
        seen = (row < exact) | ((row >= window) & (row < window + far))
        scores = jnp.where(seen[:, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        return jnp.einsum("shql,slhd->sqhd", probs, v)


def _eva_decode_kernel(positions_ref, *refs, window, per_window, **kw):
    s, kb = pl.program_id(0), pl.program_id(1)
    pos, bk = positions_ref[s], kw["block_k"]
    exact, far = pos % window + 1, (pos // window) * per_window
    _fd._decode_body(
        pos, kb, pl.num_programs(1), kb * bk, *refs, **kw,
        rows=(jnp.where(kb * bk < window, kb * bk < exact,
                        kb * bk - window < far),
              lambda cols: (cols < exact) | (
                  (cols >= window) & (cols < window + far))))


def _eva_decode_kernel_call(q, k_cache, v_cache, positions, *, layer,
                            window, chunk, dtype):
    S, _, H, D = q.shape
    n_layer, slots, rows, C = k_cache.shape
    if C != H * D or slots != S or not 0 <= layer < n_layer:
        raise ValueError(f"cache {k_cache.shape} does not hold layer "
                         f"{layer} of {S} slots x {H} heads x {D}")
    bk = decode_block_k(window, rows - window)
    nk, wb, per = rows // bk, window // bk, window // chunk
    _fd.note_decode_kernel(KERNEL_NAME, bk, rows)
    base = layer * S

    def kv_map(s, kb, pos_ref):
        # dead blocks of either part re-map to the last live block
        # before them: an unchanged index between grid steps skips the
        # block's DMA (ops/flash_decode.py kv_block_bound)
        pos = pos_ref[s]
        last_exact = (pos % window) // bk
        far_blocks = ((pos // window) * per + bk - 1) // bk
        far_at = jnp.where(far_blocks > 0,
                           wb + jnp.minimum(kb - wb, far_blocks - 1),
                           last_exact)
        return (base + s,
                jnp.where(kb < wb, jnp.minimum(kb, last_exact), far_at), 0)

    def sq_map(s, kb, pos_ref):
        return (s, 0, 0)

    body = functools.partial(
        _eva_decode_kernel, window=window, per_window=per,
        sm_scale=1.0 / math.sqrt(D), block_k=bk, head_dim=D)
    body.__name__ = KERNEL_NAME + "_kernel"
    out = pl.pallas_call(
        body,
        name=KERNEL_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S, nk),
            in_specs=[pl.BlockSpec((1, 1, C), sq_map),
                      pl.BlockSpec((1, bk, C), kv_map),
                      pl.BlockSpec((1, bk, C), kv_map)],
            out_specs=pl.BlockSpec((1, 1, C), sq_map),
            scratch_shapes=_fd.decode_scratch(H, C, k_cache.dtype)),
        out_shape=jax.ShapeDtypeStruct((S, 1, C), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_fd._use_interpret(),
    )(jnp.asarray(positions, jnp.int32), q.reshape(S, 1, C),
      # merges of leading dimensions: bitcasts, not copies
      k_cache.reshape(n_layer * S, rows, C),
      v_cache.reshape(n_layer * S, rows, C))
    return out.reshape(S, 1, H, D)


__all__ = ["KERNEL_NAME", "cache_rows", "visible_rows", "rotary",
           "rotary_rows", "chunk_summaries", "chunk_summaries_rows",
           "eva_attention", "eva_cached_attention", "select_eva_kernel"]
