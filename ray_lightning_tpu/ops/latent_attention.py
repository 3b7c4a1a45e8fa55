"""Latent (MLA) attention's two paths over one set of weights.

What ``models/xing.py`` needs beside ops/attention.py and
ops/window_attention.py (``deepseek_v3``-family latent attention): a
position keeps ONE cache row, the normed latent ``c_kv`` (``r`` values)
beside the rotated key ``k_rope`` that all heads share (``dr`` values).

- **a whole prompt** (:func:`causal_attention`): the latent is expanded
  into every head's keys (``dn`` of its own beside the shared ``dr``) and
  values (``dv``), and causal attention runs with a query / key width of
  ``dn + dr`` and a value width of ``dv``.  On one TPU chip jax's splash
  attention, which takes a value width of its own; plain masked
  ``jax.numpy`` elsewhere.
- **one query a slot** (:func:`cached_attention`): the up-projection of
  the keys is absorbed into the query (``q' = q_nope W_uk^T``, ``r``
  wide), so the scores are ``[q' | q_rope] . [c_kv | k_rope]`` against
  the row as it lies, and the weighted sum of the rows' first ``r``
  lanes goes through ``W_uv`` after the softmax.  The cache is never
  expanded.  On the TPU the ``mla_decode`` call around
  ops/flash_decode.py's shared body; dense elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_lightning_tpu.ops import flash_decode as _fd
from ray_lightning_tpu.ops.flash_attention import NEG_INF, _pick_block

KERNEL_NAME = _fd.LATENT_KERNEL_NAME

#: rows of queries / keys to a block of the splash kernel, and keys to a
#: product inside one.  By hand on the v5e at T = 8192, 32 heads, query /
#: key 192, value 128, causal (builder's chip run, PR 36; PERF.md section
#: 6), ms a layer (and the share of the MXU peak over the visible scores):
#: (1024, 1024, 512) 8.63 (40.4 %), (1024, 2048, 512) 8.83, (1024, 1024,
#: 1024) 8.85, (512, 1024, 512) 8.90, (2048, 1024, 512) 9.01, (512, 512,
#: 512) 9.38.  With the queries' and keys' heads padded to 256 lanes: 9.31
#: at (1024, 1024, 512), 9.50-10.33 at the others.  The repo's own flash
#: forward, which takes one width (q, k and v at 256): 13.23.
_SPLASH_BLOCK_Q = 1024
_SPLASH_BLOCK_KV = 1024
_SPLASH_BLOCK_KV_COMPUTE = 512


# -- a whole prompt --------------------------------------------------------------

def select_prefill_kernel(T: int, dv: int) -> str:
    """``splash`` on one TPU chip where the geometry lowers (a value of
    whole lane tiles, whole blocks), ``dense`` elsewhere."""
    if _fd._use_interpret() or jax.device_count() != 1:
        return "dense"
    if dv % 128 or T % 128:
        return "dense"
    return "splash"


@functools.lru_cache(maxsize=None)
def _splash_kernel(T: int, heads: int):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    # the preferred rows, halved until the block divides T: the kernel
    # refuses one that does not ("q_block_size=1024 should divide
    # q_seq_len=2560": a described-v5e compile, PR 43).  1024 wherever it
    # divides (every bucket the numbers above were read at); 512 at 2,560,
    # 256 at 3,328
    bq, bkv = (_pick_block(T, _SPLASH_BLOCK_Q),
               _pick_block(T, _SPLASH_BLOCK_KV))
    # made under no trace: the mask's block tables are constants of
    # whatever program calls the kernel
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha_single_device(
            sm.MultiHeadMask([sm.CausalMask((T, T))] * heads),
            block_sizes=sk.BlockSizes(
                block_q=bq, block_kv=bkv,
                block_kv_compute=min(bkv, _SPLASH_BLOCK_KV_COMPUTE)))


def causal_attention(q, k, v, *, sm_scale: float, dtype=jnp.bfloat16):
    """Causal attention of a whole sequence with two head widths.  ``q``,
    ``k`` [B, T, H, dq] (positions applied), ``v`` [B, T, H, dv]; scores
    ``q . k * sm_scale``.  Returns [B, T, H, dv] in ``dtype``."""
    T, H = q.shape[1:3]
    if select_prefill_kernel(T, v.shape[-1]) == "splash":
        kernel = _splash_kernel(T, H)
        qs = (q * sm_scale).astype(q.dtype)
        qs, ks, vs = (a.transpose(0, 2, 1, 3) for a in (qs, k, v))
        return jax.vmap(kernel)(qs, ks, vs).transpose(0, 2, 1, 3) \
            .astype(dtype)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    at = jnp.arange(T)
    seen = at[None, :] <= at[:, None]
    p = jax.nn.softmax(jnp.where(seen, s, NEG_INF), axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(dtype)


# -- one query a slot against the resident cache ----------------------------------

def select_decode_kernel(rows: int, C: int, value_dim: int, *, dtype,
                         impl=None) -> str:
    """``dense`` or ``mla_decode``, as ops/flash_decode.py chooses:
    ``RLT_DECODE_IMPL`` (or ``impl``) ``dense`` / ``flash_decode`` (here:
    the latent call) / ``auto`` (the kernel on the TPU when the geometry
    lowers).  ``paged`` is refused: the paged kernel reads a keys' and a
    values' array through a page table, and a latent row is one array."""
    req = _fd.resolve_decode_impl(impl)
    if req == "paged":
        raise ValueError(
            "the paged decode kernel reads keys and values through a page "
            "table; a latent cache keeps one array of rows "
            "(ops/latent_attention.py); use RLT_DECODE_IMPL=auto")
    if req == "dense":
        return "dense"
    if req == "auto" and jax.devices()[0].platform != "tpu":
        return "dense"
    if _fd.latent_kernel_supported(rows, C, value_dim, dtype=dtype):
        return KERNEL_NAME
    if req == "auto":
        return "dense"
    raise ValueError(
        f"decode impl {req!r} was requested explicitly but a latent cache "
        f"of {rows} rows of {C} (value: the first {value_dim}), "
        f"dtype={jnp.dtype(dtype).name} cannot lower on this platform")


def cached_attention(q, cache, positions, *, layer: int, value_dim: int,
                     sm_scale: float, dtype=jnp.bfloat16, impl=None):
    """One query a slot against layer ``layer`` of the resident latent
    cache.  ``q`` [S, H, C]: the absorbed query beside the rotated one, as
    a row lies; ``cache`` [n_layer, S, rows, C], whole, as it lies;
    ``positions`` [S]: slot ``s`` sees the rows ``<= positions[s]``.
    Returns ``sum_j p_j row_j[:value_dim]``, [S, H, value_dim] in
    ``dtype``."""
    rows, C = cache.shape[2:]
    kernel = select_decode_kernel(rows, C, value_dim, dtype=cache.dtype,
                                  impl=impl)
    _fd.note_decode_kernel(kernel)
    if kernel != "dense":
        return _fd.latent_decode_attention(
            q.astype(cache.dtype), cache, positions, layer=layer,
            value_dim=value_dim, sm_scale=sm_scale, dtype=dtype)
    own = cache[layer]
    s = jnp.einsum("shc,slc->shl", q.astype(own.dtype), own,
                   preferred_element_type=jnp.float32) * sm_scale
    seen = jnp.arange(rows)[None, :] <= positions[:, None]
    p = jax.nn.softmax(jnp.where(seen[:, None, :], s, NEG_INF),
                       axis=-1).astype(dtype)
    return jnp.einsum("shl,slc->shc", p, own[..., :value_dim]).astype(dtype)


__all__ = ["KERNEL_NAME", "cached_attention", "causal_attention",
           "select_decode_kernel", "select_prefill_kernel"]
