"""Grouped-head attention under a causal band, and its serve state.

What ``models/command.py``'s two kinds of layer need beside
ops/attention.py (one K/V head a query head, a row per position):

- **grouped heads**: ``H`` query heads read ``G`` K/V heads, query head
  ``i`` the K/V head ``i // (H / G)``; a cache row is the ``G`` K/V heads
  side by side, ``C = G * D`` lanes;
- **a band**: a ``sliding_attention`` layer's query ``i`` sees the keys
  ``i - window < j <= i``; a ``full_attention`` layer's, every ``j <= i``
  (``window=None``);
- **a ring**: a sliding layer keeps ``window`` rows a slot, position ``t``
  in row ``t % window``.  Rotary is applied before a row is written, so
  the order of the rows does not matter, and once a row is written a
  slot at position ``t`` sees the rows ``<= min(t, window - 1)``: the
  ring is read by the same decode call as a row-per-position cache, at
  that bound.

:func:`banded_attention` is the prefill of a whole prompt: on one TPU
chip jax's splash attention (``pallas.ops.tpu.splash_attention``: a
block-sparse flash forward that skips the blocks outside the mask, K/V
heads shared by a group of query heads without a copy of them), plain
masked ``jax.numpy`` elsewhere.  :func:`cached_attention` is one query a
slot against the resident cache: the ``gqa_decode`` call around
ops/flash_decode.py's shared body on the TPU, dense elsewhere.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ray_lightning_tpu.ops import flash_decode as _fd
from ray_lightning_tpu.ops.flash_attention import NEG_INF

#: rows of queries / keys to a block of the splash kernel, and keys to a
#: product inside one.  By hand on the v5e at T = 8192, 128 / 8 heads of
#: 128 (builder's chip run, PR 33; PERF.md section 6), ms a layer under
#: the band of 4096 / causal: (512, 512, 512) 16.9 / 24.2; (1024, 1024,
#: 512) 14.2 / 17.8; (1024, 1024, 1024) 16.1 / 19.7; (2048, 1024, 512)
#: 16.1 / 18.3; (1024, 2048, 512) 15.7 / 17.8.  The repo's own flash
#: forward, which knows neither band nor groups, takes 23.2 causal with
#: K and V already repeated to 128 heads (24.1 with the repeat).
_SPLASH_BLOCK_Q = 1024
_SPLASH_BLOCK_KV = 1024
_SPLASH_BLOCK_KV_COMPUTE = 512


def rotary_interleaved(x, positions, theta: float, inv_freq=None):
    """Rotary embedding over every dimension of the head with the pairs
    ``(2j, 2j + 1)`` interleaved (``rope_gptj``).  ``x`` [..., T, H, D];
    ``positions`` [..., T] (or [T]).  Pair ``j`` turns by ``theta ** (-2j
    / D)`` a position, or by ``inv_freq[j]`` ([D / 2] float32) where a
    family scales its frequencies (:func:`yarn_inv_freq`).  Computed in
    float32, returned in ``x``'s type.  The partner of each dimension
    comes from a product with a signed permutation matrix, as in
    ops/eva_attention.py ``rotary``: exact, and a pass of the MXU instead
    of lane shuffles."""
    D = x.shape[-1]
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    angle = jnp.repeat(angle, 2, axis=-1)[..., None, :]
    i = jnp.arange(D)
    # turned[2j] = -x[2j + 1], turned[2j + 1] = x[2j]
    turn = (jnp.where((i[:, None] == i[None, :] + 1) & (i[None, :] % 2 == 0),
                      -1, 0)
            + jnp.where((i[:, None] + 1 == i[None, :])
                        & (i[:, None] % 2 == 0), 1, 0)).astype(x.dtype)
    turned = jnp.einsum("...d,de->...e", x, turn, precision="highest",
                        preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * jnp.cos(angle)
            + turned * jnp.sin(angle)).astype(x.dtype)


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """[dim / 2] float32 (a constant): YaRN's frequencies (arXiv:
    2309.00071, as the ``deepseek_v3`` family computes them).  Pair ``j``
    of a head of ``dim`` turns ``original * theta ** (-2j / dim) / 2 pi``
    times over the ``original`` positions it was trained on; the pairs
    that turn more than ``beta_fast`` times keep their frequency, those
    that turn fewer than ``beta_slow`` are slowed by ``factor``, and a
    linear ramp between the two (over whole pair numbers, the lower
    rounded down and the upper up) blends the rest."""
    j = np.arange(0, dim, 2, dtype=np.float64) / dim
    plain = theta ** -j

    def pair_that_turns(n):
        return dim * math.log(original / (n * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_that_turns(beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return jnp.asarray(plain / factor * ramp + plain * (1.0 - ramp),
                       jnp.float32)


def yarn_softmax_scale(width: int, factor: float,
                       mscale_all_dim: float) -> float:
    """The scale of the scores under YaRN: ``width ** -0.5`` times the
    square of ``0.1 mscale_all_dim ln(factor) + 1``."""
    m = 0.1 * mscale_all_dim * math.log(factor) + 1.0 if factor > 1 else 1.0
    return width ** -0.5 * m * m


def visible_scores(length: int, window: "int | None") -> int:
    """Query-key pairs a causal prompt of ``length`` positions computes
    under the band: the triangle, less what lies below the band."""
    tri = length * (length + 1) // 2
    if window is None or length <= window:
        return tri
    below = length - window
    return tri - below * (below + 1) // 2


# -- a whole prompt --------------------------------------------------------------

def select_prefill_kernel(T: int, D: int) -> str:
    """``splash`` on one TPU chip where the geometry lowers (128-lane
    heads, whole blocks), ``dense`` elsewhere."""
    if _fd._use_interpret() or jax.device_count() != 1:
        return "dense"
    if D % 128 or T % 128:
        return "dense"
    return "splash"


@functools.lru_cache(maxsize=None)
def _splash_kernel(T: int, per: int, window: "int | None"):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    one = sm.CausalMask((T, T)) if window is None \
        else sm.LocalMask((T, T), (window - 1, 0), 0)
    bq, bkv = min(_SPLASH_BLOCK_Q, T), min(_SPLASH_BLOCK_KV, T)
    # keys to a product: a whole divisor of the block (a prompt of 768
    # positions is one block of 768 keys, three products of 256)
    compute = math.gcd(bkv, _SPLASH_BLOCK_KV_COMPUTE)
    # made under no trace: the mask's block tables are constants of
    # whatever program calls the kernel
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mqa_single_device(
            sm.MultiHeadMask([one] * per),
            block_sizes=sk.BlockSizes(
                block_q=bq, block_kv=bkv, block_kv_compute=compute))


def banded_attention(q, k, v, *, window: "int | None",
                     dtype=jnp.bfloat16):
    """Causal attention of a whole sequence.  ``q`` [B, T, H, D], ``k``,
    ``v`` [B, T, G, D] (positions applied); ``window``: keys a query
    sees counting its own position, None for all.  Returns [B, T, H, D]
    in ``dtype``."""
    B, T, H, D = q.shape
    G = k.shape[2]
    per = H // G
    scale = 1.0 / math.sqrt(D)
    if select_prefill_kernel(T, D) == "splash":
        kernel = _splash_kernel(T, per, window)
        # [B, G, per, T, D] queries against [B, G, T, D] keys: one K/V
        # head serves its group of query heads
        qs = (q * scale).astype(q.dtype).reshape(B, T, G, per, D) \
            .transpose(0, 2, 3, 1, 4)
        ks, vs = (a.transpose(0, 2, 1, 3) for a in (k, v))
        out = jax.vmap(jax.vmap(kernel))(qs, ks, vs)
        return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, D) \
            .astype(dtype)
    s = jnp.einsum("bqgpd,bkgd->bgpqk", q.reshape(B, T, G, per, D), k,
                   preferred_element_type=jnp.float32) * scale
    at = jnp.arange(T)
    seen = at[None, :] <= at[:, None]
    if window is not None:
        seen = seen & (at[None, :] > at[:, None] - window)
    p = jax.nn.softmax(jnp.where(seen, s, NEG_INF), axis=-1).astype(dtype)
    return jnp.einsum("bgpqk,bkgd->bqgpd", p, v).reshape(B, T, H, D) \
        .astype(dtype)


# -- one query a slot against the resident cache ----------------------------------

KERNEL_NAME = _fd.GROUPED_KERNEL_NAME


def select_decode_kernel(rows: int, G: int, D: int, *, dtype,
                         impl=None) -> str:
    """``dense`` or ``gqa_decode``, as ops/flash_decode.py chooses:
    ``RLT_DECODE_IMPL`` (or ``impl``) ``dense`` / ``flash_decode`` (here:
    the grouped call) / ``auto`` (the kernel on the TPU when the
    geometry lowers).  ``paged`` is refused: a page table addresses rows
    by position, and a ring's rows are not positions."""
    req = _fd.resolve_decode_impl(impl)
    if req == "paged":
        raise ValueError(
            "the paged decode kernel cannot read a ring of window rows: "
            "a page table maps positions to rows "
            "(ops/window_attention.py); use RLT_DECODE_IMPL=auto")
    if req == "dense":
        return "dense"
    if req == "auto" and jax.devices()[0].platform != "tpu":
        return "dense"
    bk = _fd.grouped_block_k(rows)
    if _fd.decode_kernel_supported(rows, G, D, block_k=bk, dtype=dtype,
                                   ragged=True) \
            and (_fd._use_interpret() or D % 128 == 0):
        return KERNEL_NAME
    if req == "auto":
        return "dense"
    raise ValueError(
        f"decode impl {req!r} was requested explicitly but a cache of "
        f"{rows} rows, {G} K/V heads of {D}, block_k={bk}, "
        f"dtype={jnp.dtype(dtype).name} cannot lower on this platform "
        f"(the grouped call needs head_dim a lane multiple)")


def cached_attention(q, k_cache, v_cache, positions, *, layer: int,
                     ring: bool, dtype=jnp.bfloat16, impl=None):
    """One query a slot against layer ``layer`` of one kind's resident
    cache.  ``q`` [S, 1, H, D] (positions applied); ``k_cache`` /
    ``v_cache`` [n_layer, S, rows, G * D], whole, as they lie;
    ``positions`` [S].  ``ring``: the rows are a ring of ``rows``
    positions (module docstring), so a slot sees the rows ``<=
    min(position, rows - 1)``; otherwise a row per position.  Returns
    [S, 1, H, D] in ``dtype``."""
    S, _, H, D = q.shape
    rows, C = k_cache.shape[2:]
    G = C // D
    bound = jnp.minimum(positions, rows - 1) if ring else positions
    kernel = select_decode_kernel(rows, G, D, dtype=k_cache.dtype,
                                  impl=impl)
    _fd.note_decode_kernel(kernel)
    if kernel != "dense":
        return _fd.grouped_decode_attention(
            q, k_cache, v_cache, bound, layer=layer, dtype=dtype)
    per = H // G
    k = k_cache[layer].reshape(S, rows, G, D)
    v = v_cache[layer].reshape(S, rows, G, D)
    s = jnp.einsum("sgpd,slgd->sgpl", q.reshape(S, G, per, D), k,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    seen = jnp.arange(rows)[None, :] <= bound[:, None]
    p = jax.nn.softmax(jnp.where(seen[:, None, None, :], s, NEG_INF),
                       axis=-1).astype(dtype)
    return jnp.einsum("sgpl,slgd->sgpd", p, v).reshape(S, 1, H, D) \
        .astype(dtype)


def ring_rows(length, window: int, bucket: int):
    """[window] int32: for each row of a slot's ring the prompt position
    it holds once a prompt of ``length`` (traced) positions is written:
    the last position ``p < length`` with ``p % window == row`` (the row's
    own number where there is none yet: what lies there is unseen until
    the decode writes it)."""
    row = jnp.arange(window, dtype=jnp.int32)
    laps = jnp.maximum(length - 1 - row, 0) // window
    return jnp.minimum(row + window * laps, bucket - 1)


__all__ = ["KERNEL_NAME", "banded_attention", "cached_attention",
           "ring_rows", "rotary_interleaved", "select_decode_kernel",
           "select_prefill_kernel", "visible_scores", "yarn_inv_freq",
           "yarn_softmax_scale"]
