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

Here: the rotary embedding, the pooling (:func:`chunk_summaries`, scope
``eva_summary``), prefill attention over a whole prompt
(:func:`eva_attention`, scope ``eva_attn``) and decode attention of one
query a slot against the resident cache (:func:`eva_cached_attention`):
the dense ``jax.numpy`` path, and on the TPU the Pallas kernel
``eva_decode``, which is ops/flash_decode.py's online-softmax body
(every head of a block in one product against the block-diagonal query,
heads on sublanes; nothing of it is this file's) under the two-range
bound: its index_map clamps dead blocks of either part to the last live
one, so a slot reads only the blocks that hold rows it may see.
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
    NEG_INF, flash_attention_lse)

#: the decode kernel's name in the compiled program and the trace
KERNEL_NAME = "eva_decode"


def cache_rows(window: int, chunk: int, max_positions: int) -> int:
    """Rows a slot holds per layer: the exact window and one summary row
    per chunk of ``max_positions``."""
    return window + max_positions // chunk


def visible_rows(position, window: int, chunk: int):
    """``(exact, summaries)``: how many rows of each part a query at
    ``position`` may see (ints or traced int arrays alike)."""
    return position % window + 1, (position // window) * (window // chunk)


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
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    angle = jnp.concatenate([angle, angle], axis=-1)[..., None, :]
    i = jnp.arange(D)
    turn = (jnp.where(i[:, None] == i[None, :] + D // 2, -1, 0)
            + jnp.where(i[:, None] + D // 2 == i[None, :], 1, 0)
            ).astype(x.dtype)
    turned = jnp.einsum("...d,de->...e", x, turn, precision="highest",
                        preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * jnp.cos(angle)
            + turned * jnp.sin(angle)).astype(x.dtype)


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


# -- a whole sequence (training forward, prefill) --------------------------------

def _causal_with_lse(q, k, v, dtype):
    """Plain causal attention of ``[N, T, H, D]`` with its log-sum-exp
    ``[N, T, H]``: the flash kernel on one TPU chip, dense elsewhere."""
    if not _fd._use_interpret() and jax.device_count() == 1:
        return flash_attention_lse(q, k, v, causal=True, interpret=False)
    T, D = q.shape[1], q.shape[-1]
    s = jnp.einsum("nqhd,nkhd->nqhk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, :, None, :], s,
                  NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None]).astype(dtype)
    return jnp.einsum("nqhk,nkhd->nqhd", p, v).astype(dtype), lse


def eva_attention(q, k, v, k_sum, v_sum, *, window: int, chunk: int,
                  dtype=jnp.bfloat16):
    """EVA over a whole sequence.  ``q``, ``k``, ``v`` [B, T, H, D]
    (rotary applied), ``T`` at most one window or a multiple of it;
    ``k_sum``, ``v_sum`` [B, T // chunk, H, D] (:func:`chunk_summaries`).
    Returns [B, T, H, D] in ``dtype``.

    A sequence within one window is plain causal attention and takes the
    repo's attention dispatch (the flash kernel on the TPU).  A longer
    one is two attentions under one softmax: every window's causal
    attention over its own exact rows (all windows as one batch of the
    flash kernel, with its log-sum-exp), and, window by window under one
    ``lax.map``, a dense attention over the summaries of earlier windows
    (at most ``T / chunk`` keys); ``logaddexp`` of the two log-sum-exps
    weighs the two outputs."""
    from ray_lightning_tpu.ops.attention import auto_attention
    B, T, H, D = q.shape
    with jax.named_scope("eva_attn"):
        if T <= window:
            return auto_attention(q, k, v, causal=True, dtype=dtype)
        if T % window:
            raise ValueError(f"{T} positions are not whole windows of "
                             f"{window}")
        nw, per = T // window, window // chunk
        far_rows = (nw - 1) * per      # the last window's are never seen
        k_far, v_far = k_sum[:, :far_rows], v_sum[:, :far_rows]
        chunk_no = jnp.arange(far_rows)
        f32 = {"preferred_element_type": jnp.float32}
        near, near_lse = _causal_with_lse(
            *(a.reshape(B * nw, window, H, D) for a in (q, k, v)), dtype)

        def one_window(args):
            w, qw, near, near_lse = args
            s = jnp.einsum("bqhd,bjhd->bqhj", qw, k_far, **f32) \
                / math.sqrt(D)
            s = jnp.where(chunk_no < w * per, s, NEG_INF)
            far_lse = jax.nn.logsumexp(s, axis=-1)
            far = jnp.einsum(
                "bqhj,bjhd->bqhd",
                jnp.exp(s - far_lse[..., None]).astype(dtype), v_far, **f32)
            lse = jnp.logaddexp(near_lse, far_lse)
            return (near.astype(jnp.float32)
                    * jnp.exp(near_lse - lse)[..., None]
                    + far * jnp.exp(far_lse - lse)[..., None]).astype(dtype)

        def windows(a):
            return jnp.moveaxis(a.reshape((B, nw) + a.shape[1:]), 1, 0)

        out = jax.lax.map(one_window, (jnp.arange(nw), windows(
            q.reshape(B * nw, window, H, D)), windows(near),
            windows(near_lse)))
        return jnp.moveaxis(out, 0, 1).reshape(B, T, H, D)


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
           "chunk_summaries", "eva_attention", "eva_cached_attention",
           "select_eva_kernel"]
