"""ZAYA1: compressed convolutional attention (CCA) whose serve state is a
narrow row a position AND a convolution's tail a slot, a top-1 MLP router
with a "no expert" output that reads the router of the layer before it,
and learned scales on both arms of every residual add (``model_type``
``zaya``; arXiv:2510.04476 for the attention, arXiv:2511.17127 for the
router).

Each of the ``num_hidden_layers`` ``hybrid`` layers is an attention
sublayer and an expert sublayer on one float32 residual stream.  ``d`` =
2048, ``H`` = 8 query heads over ``G`` = 2 K/V heads of ``D`` = 128 (the
queries live in ``H D`` = d / 2 values, keys and values in ``G D`` = d / 8
each), no bias on a projection.  ``n(x) = x / sqrt(mean(x^2) + eps) * g``.
Everything "at t - 1" is zero at t = 0.  **(A)** marks a reading that the
published ``config.json`` does not settle (``chipbench/configs/
zaya1-8b.json`` lists each under ``assumed`` with its source)::

    each sublayer F with learned float32 vectors a_r, b_r, a_f, b_f [d]
    (A, ``scale_residual_merge``):
      x' = (x + b_r) * a_r + (F(n(x)) + b_f) * a_f

    Attn (CCA), u = n(x):
      q~ = u W_q [H D], k~ = u W_k [G D]
      v_t = [u_t W_v1 ; u_{t-1} W_v2]                  (A: the value shift;
            K/V head 0 the position's own value, head 1 the one before's)
      z = [q~ ; k~] (1280 channels), two causal convolutions of kernel 2
      (A: their grouping and biases):
        a_t = w0[:, 0] * z_{t-1} + w0[:, 1] * z_t + b0        (depthwise)
        c_t^(h) = W1^(h)[0] a_{t-1}^(h) + W1^(h)[1] a_t^(h) + b1^(h)
                  for each of the H + G heads, W1^(h)[j] [D, D]
      q-k mean (A), g(h) = h // (H / G), taken BEFORE the convolutions:
        m_q^(h) = (q~^(h) + k~^(g(h))) / 2
        m_k^(g) = (mean_{h in g} q~^(h) + k~^(g)) / 2
      q = c_q + m_q, k = c_k + m_k; per head (A: norm and temperature)
        q <- sqrt(D) q / |q|,  k <- tau_g sqrt(D) k / |k|
      rotary on the first D / 2 lanes of each head of q and k, rotate-
      half, after the norm (A: order); causal softmax of q . k / sqrt(D),
      query head h against K/V head g(h); W_o [H D -> d].
    MoE, u = n(x), l the layer, s_{-1} = 0 (A: arXiv:2511.17127,
    ``zaya_use_eda``, ``zaya_use_mod``):
      s_l = u W_d + gamma_l s_{l-1}                   (R = 256, float32)
      p = softmax(W_3 gelu(W_2 gelu(W_1 n(s_l))))     (E + 1 = 17 outputs)
      e = argmax(p + b); the LAST output is "no expert"
      MoE(u) = p_e E_e(u) for e < E, 0 for e = E;  E(u) = D_e (silu(G_e u)
      * U_e u) (ops/moe.py ``mlp_softmax_top1`` and ``dropless_experts``,
      called by this file's own ``Router`` and ``Experts``).
    out: logits = E n_f(x), the embedding table tied, float32.

What is new to this repo beside the four decoder files (ROADMAP R1, R2,
R6): partial rotary; a router that is not the expert layer's own; and a
serve state that is neither a row nor a position.  A position's cache row
is ``k_t`` and ``v_t`` as attention reads them (256 lanes each: 1,024 B a
layer in bfloat16, an eighth of 8 full heads' keys and values), but
``k_t`` is made of ``z_{t-1}`` and ``a_{t-1}`` and ``v_t`` of ``u_{t-1}
W_v2``: a decode step needs 2 x 1280 + 128 float32 values of the position
before it.  They are the slot's TAIL (serve/kvcache.py ``KVCacheSpec.
tail``): ``[n_layer, S, 2, 2688]`` float32, two generations, position
``t``'s in row ``t % 2``, so that a step run twice at one position (a
decode queued ahead, dropped, and queued again: serve/worker.py) reads
and writes the same values both times.

- :meth:`Zaya.prefill` computes both convolutions and the shift as
  shifted adds over the prompt's packed rows, writes the bucket's rows at
  the slot and the tail of position ``length - 1`` (not of the bucket's
  end) into generation ``(length - 1) % 2``;
- :meth:`Zaya.decode` reads generation ``(t - 1) % 2`` (zeros at t = 0),
  computes position ``t`` through the SAME functions, writes row ``t``
  and generation ``t % 2``.

A tail would have to travel with a prefix's rows, so prefix reuse, KV
shipping and the layer-truncated draft are refused by name
(:meth:`ZayaLightningModule.refuse_serve_options`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_lightning_tpu.core.module import LightningModule
from ray_lightning_tpu.models.xing import RMSNorm
from ray_lightning_tpu.ops import moe
from ray_lightning_tpu.ops import window_attention as wa

#: the accumulator's entries (serve/engine.py ``stats()['counters']``)
SERVE_COUNTERS = moe.SERVE_COUNTERS
#: subtrees and leaves served in float32 (everything else is bfloat16):
#: the router whole, both residual merges, the keys' temperature
FLOAT32_PARAMS = ("router", "res_attn", "res_mlp", "tau")

_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    """The published ``config.json``'s keys under their own names."""

    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5000000.0
    rms_norm_eps: float = 1e-5
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    max_position_embeddings: int = 131072
    #: rows a slot holds: a server's longest sequence; None: every position
    served_positions: "int | None" = None
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16        # compute dtype; the residual is fp32

    def __post_init__(self):
        if self.cca_time0 != 2 or self.cca_time1 != 2 \
                or self.num_experts_per_tok != 1 \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"both convolutions are kernels of 2 (a tail holds ONE "
                f"position), one expert a token, whole groups of heads: "
                f"{self}")

    @property
    def block_size(self) -> int:
        """Positions a sequence may have (what ``Server`` asks for)."""
        return self.served_positions or self.max_position_embeddings

    @property
    def channels(self) -> int:
        """``z``'s width: the queries' and the keys' heads side by side."""
        return (self.num_attention_heads + self.num_key_value_heads) \
            * self.head_dim

    @property
    def tail_width(self) -> int:
        """A generation of a slot's tail: ``z`` and ``a`` of a position
        and its ``u W_v2``."""
        return 2 * self.channels + self.head_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)


CONFIGS = {
    "tiny": ZayaConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=4, moe_intermediate_size=32, router_hidden_size=16,
        rope_theta=10000.0, max_position_embeddings=64),
    "zaya1-8b": ZayaConfig(),
}


class ResidualMerge(nn.Module):
    """``(x + b_r) * a_r + (y + b_f) * a_f``, float32."""

    @nn.compact
    def __call__(self, x, y):
        d = x.shape[-1]
        a_r, a_f = (self.param(n, nn.initializers.ones, (d,), _F32)
                    for n in ("a_r", "a_f"))
        b_r, b_f = (self.param(n, nn.initializers.zeros, (d,), _F32)
                    for n in ("b_r", "b_f"))
        return (x + b_r) * a_r + (y.astype(_F32) + b_f) * a_f


# -- the attention sublayer's own arithmetic, on packed rows [..., channels] ----

def shifted(x):
    """``x`` [B, T, n] a position later, zeros at position 0."""
    return jnp.pad(x[:, :-1], ((0, 0), (1, 0), (0, 0)))


def qk_mean(z, H: int, G: int, D: int):
    """``[m_q ; m_k]`` of ``z = [q~ ; k~]`` [..., (H + G) D] float32: each
    query head averaged with its K/V head, each K/V head with the mean of
    its group's query heads.  Heads are blocks of ``D`` lanes: slices and
    adds, no view by head."""
    per = H // G
    q = [z[..., h * D:(h + 1) * D] for h in range(H)]
    k = [z[..., (H + g) * D:(H + g + 1) * D] for g in range(G)]
    m_q = [(q[h] + k[h // per]) * 0.5 for h in range(H)]
    m_k = [(sum(q[g * per:(g + 1) * per]) * (1.0 / per) + k[g]) * 0.5
           for g in range(G)]
    return jnp.concatenate(m_q + m_k, axis=-1)


def conv_heads(a_prev, a, w1, b1, dtype):
    """The second convolution: head ``h`` of the result is ``[a_prev^(h)
    | a^(h)] W1^(h)`` with ``W1^(h)`` [2 D, D] (tap 0's rows, then tap
    1's), plus ``b1``.  ``a_prev``, ``a`` [..., n D] float32; ``w1`` [n,
    2, D, D]; operands in ``dtype``, float32 accumulation.  One product a
    head over its own block of lanes."""
    n, _, D, _ = w1.shape
    out = []
    for h in range(n):
        at = slice(h * D, (h + 1) * D)
        pair = jnp.concatenate([a_prev[..., at], a[..., at]], axis=-1)
        out.append(jnp.einsum(
            "...c,cd->...d", pair.astype(dtype),
            w1[h].reshape(2 * D, D).astype(dtype),
            preferred_element_type=_F32))
    return jnp.concatenate(out, axis=-1) + b1.astype(_F32)


def partial_rotary(x, positions, theta: float, rot: int):
    """Rotate-half rotary on the first ``rot`` of each head's ``D``
    lanes, the rest passed through.  ``x`` [..., T, n, D] float32;
    ``positions`` [..., T] (or [T]).  Pair ``(j, j + rot / 2)`` turns by
    ``theta ** (-2 j / rot)`` a position.  The partner of each lane comes
    from a product with a signed permutation matrix whose rows past
    ``rot`` are zero (as ops/eva_attention.py ``rotary``: exact, whole
    lane tiles, no slice at lane ``rot``), and the tables are 1 and 0
    there."""
    D = x.shape[-1]
    half = rot // 2
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=_F32) / rot)
    angle = positions.astype(_F32)[..., None] * inv          # [..., T, half]
    pad = jnp.zeros(angle.shape[:-1] + (D - rot,), _F32)
    cos = jnp.concatenate([jnp.cos(angle)] * 2 + [pad + 1.0], axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2 + [pad], axis=-1)
    i = jnp.arange(D)
    # turned[j] = -x[j + half] (j < half), x[j - half] (half <= j < rot)
    turn = (jnp.where((i[:, None] == i[None, :] + half)
                      & (i[None, :] < half), -1, 0)
            + jnp.where((i[:, None] + half == i[None, :])
                        & (i[None, :] < rot), 1, 0)).astype(_F32)
    turned = jnp.einsum("...d,de->...e", x, turn, precision="highest")
    return x * cos[..., None, :] + turned * sin[..., None, :]


class CompressedAttention(nn.Module):
    """The CCA sublayer after its norm.  Three ways in, as
    ``models/command.py GroupedAttention``: a whole sequence (no
    ``cache``); a prompt at a slot (``cache`` with ``slot`` and
    ``length``); one token a slot (``cache`` with ``positions`` [S]).
    ``cache`` is ``(k_cache, v_cache, tail)``: ``[n_layer, S, rows, G D]``
    twice and ``[n_layer, S, 2, tail_width]``; with it it returns ``(y,
    cache)``."""

    config: ZayaConfig
    layer: int

    @nn.compact
    def __call__(self, u, *, cache=None, positions=None, slot=None,
                 length=None):
        cfg = self.config
        B, T, d = u.shape
        H, G, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
        n, C = H + G, cfg.channels
        init = nn.initializers.normal(cfg.init_std)

        def proj(x, name, width):
            w = self.param(name, init, (x.shape[-1], width))
            return jnp.einsum("...d,de->...e", x, w.astype(cfg.dtype),
                              preferred_element_type=_F32)

        w0 = self.param("conv0_w", init, (C, 2)).astype(_F32)
        b0 = self.param("conv0_b", nn.initializers.zeros, (C,)).astype(_F32)
        w1 = self.param("conv1_w", init, (n, 2, D, D))
        b1 = self.param("conv1_b", nn.initializers.zeros, (C,))
        tau = self.param("tau", nn.initializers.ones, (G,), _F32)
        with jax.named_scope("cca_proj"):
            z = jnp.concatenate([proj(u, "q", H * D), proj(u, "k", G * D)],
                                axis=-1)                     # [B, T, C] f32
            v_own, v_next = proj(u, "v1", D), proj(u, "v2", D)
        decoding = positions is not None
        with jax.named_scope("cca_mix"):
            if decoding:
                # the generation of position t - 1; zeros at t = 0
                slots = jnp.arange(B)
                tail = cache[2]
                before = tail[self.layer, slots, (positions + 1) % 2]
                before = jnp.where((positions > 0)[:, None], before, 0.0)
                z_prev, a_prev, v_prev = (
                    before[:, None, :C], before[:, None, C:2 * C],
                    before[:, None, 2 * C:])
            else:
                z_prev, a_prev, v_prev = shifted(z), None, shifted(v_next)
            a = w0[:, 0] * z_prev + w0[:, 1] * z + b0
            if a_prev is None:
                a_prev = shifted(a)
            qk = conv_heads(a_prev, a, w1, b1, cfg.dtype) + qk_mean(z, H, G, D)
            # per head: unit length times sqrt(D), the keys' times tau
            qk = qk.reshape(B, T, n, D)
            scale = math.sqrt(D) * jnp.concatenate(
                [jnp.ones((H,), _F32), tau])
            qk = qk * (jax.lax.rsqrt(
                jnp.sum(jnp.square(qk), axis=-1, keepdims=True) + 1e-12)
                * scale[:, None])
            v = jnp.concatenate([v_own, v_prev], axis=-1).astype(cfg.dtype)
            # what position t + 1 will need of this one
            gen = jnp.concatenate([z, a, v_next], axis=-1)   # [B, T, tail]
        with jax.named_scope("cca_proj"):
            at = positions[:, None] if decoding else jnp.arange(T)
            qk = partial_rotary(qk, at, cfg.rope_theta, cfg.rotary_dim) \
                .astype(cfg.dtype)
            q, k = qk[:, :, :H], qk[:, :, H:].reshape(B, T, G * D)

        def out(y):
            with jax.named_scope("cca_proj"):
                return proj(y.reshape(B, T, H * D).astype(cfg.dtype), "o", d)

        if decoding:
            k_cache, v_cache, tail = cache
            with jax.named_scope("kv_cache"):
                row = (self.layer, slots, positions)
                k_cache = k_cache.at[row].set(k[:, 0].astype(k_cache.dtype))
                v_cache = v_cache.at[row].set(v[:, 0].astype(v_cache.dtype))
            with jax.named_scope("cca_mix"):
                tail = tail.at[self.layer, slots, positions % 2].set(
                    gen[:, 0].astype(tail.dtype))
            y = wa.cached_attention(q, k_cache, v_cache, positions,
                                    layer=self.layer, ring=False,
                                    dtype=cfg.dtype)
            return out(y), (k_cache, v_cache, tail)
        y = out(wa.banded_attention(
            q, k.reshape(B, T, G, D), v.reshape(B, T, G, D), window=None,
            dtype=cfg.dtype))
        if cache is None:
            if not self.is_initializing():
                # what a slot keeps in this layer, for the engine to size
                # its state by (serve/kvcache.py from_capture): a row a
                # position of keys and of values, and the tail's block
                self.sow("kv_cache", "kv", (k, v, jnp.zeros(
                    (B, 1, 2, cfg.tail_width), _F32)))
            return y
        k_cache, v_cache, tail = cache
        with jax.named_scope("kv_cache"):
            # the bucket's rows at rows [0, bucket) of the slot
            k_cache, v_cache = (
                jax.lax.dynamic_update_slice(
                    c, rows[None].astype(c.dtype), (self.layer, slot, 0, 0))
                for c, rows in ((k_cache, k), (v_cache, v)))
        with jax.named_scope("cca_mix"):
            # the prompt's LAST position, wherever the bucket ends
            last = jax.lax.dynamic_slice_in_dim(gen, length - 1, 1, axis=1)
            tail = jax.lax.dynamic_update_slice(
                tail, last[None].astype(tail.dtype),
                (self.layer, slot, (length - 1) % 2, 0))
        return y, (k_cache, v_cache, tail)


class Router(nn.Module):
    """The expert sublayer's MLP router (ops/moe.py
    ``mlp_softmax_top1``): its parameters, all float32.  ``u`` [N, d]
    float32, ``prev`` [N, R] the previous layer's state or None.  Returns
    ``((idx, w), s)``."""

    config: ZayaConfig

    @nn.compact
    def __call__(self, u, prev):
        cfg = self.config
        d, R, E = cfg.hidden_size, cfg.router_hidden_size, cfg.num_experts
        init = nn.initializers.normal(cfg.init_std)
        proj = self.param("proj", init, (d, R), _F32)
        gamma = self.param("gamma", nn.initializers.ones, (), _F32)
        norm = self.param("norm", nn.initializers.ones, (R,), _F32)
        w1 = self.param("w1", init, (R, R), _F32)
        w2 = self.param("w2", init, (R, R), _F32)
        w3 = self.param("w3", init, (R, E + 1), _F32)
        bias = self.param("bias", nn.initializers.zeros, (E + 1,), _F32)
        idx, w, s = moe.mlp_softmax_top1(
            u, prev, proj, gamma, norm, w1, w2, w3, bias, cfg.rms_norm_eps)
        return (idx, w), s


class Experts(nn.Module):
    """The routed experts: every one of ``num_experts`` held, ONE a token,
    no shared expert.  ``u`` [N, d], ``idx`` / ``w`` [N, 1] from
    :class:`Router`, whose last output (index ``num_experts``) lands on no
    expert here (ops/moe.py ``dropless_experts``' ``held``).  Returns
    ``(y [N, d] float32, (pairs, experts_hit, rows))``."""

    config: ZayaConfig

    @nn.compact
    def __call__(self, u, idx, w, valid=None):
        cfg = self.config
        d, F, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
        init = nn.initializers.normal(cfg.init_std)
        gate, up, down = (
            self.param(name, init, shape).astype(cfg.dtype)
            for name, shape in (("gate", (E, d, F)), ("up", (E, d, F)),
                                ("down", (E, F, d))))
        y, *counts = moe.dropless_experts(
            u.astype(cfg.dtype), idx, w, gate, up, down, valid=valid,
            published=E)
        return y, tuple(counts)


class ZayaBlock(nn.Module):
    config: ZayaConfig
    layer: int

    @nn.compact
    def __call__(self, x, s, *, cache=None, valid=None, **where):
        """``x`` [B, T, d] float32, ``s`` the router state of the layer
        before ([B T, R], None before the first).  ``where``:
        ``positions`` (decode) or ``slot`` and ``length`` (prefill), with
        ``cache``.  Returns ``(x', s', cache, (pairs, experts_hit,
        rows))``."""
        cfg = self.config
        B, T, d = x.shape
        with jax.named_scope("ln"):
            u = RMSNorm(cfg.rms_norm_eps, name="ln_attn")(x)
        a = CompressedAttention(cfg, self.layer, name="attn")(
            u.astype(cfg.dtype), cache=cache, **where)
        if cache is not None:
            a, cache = a
        with jax.named_scope("attn"):
            x = ResidualMerge(name="res_attn")(x, a)
        with jax.named_scope("ln"):
            u = RMSNorm(cfg.rms_norm_eps, name="ln_mlp")(x).reshape(B * T, d)
        with jax.named_scope("mlp"):
            (idx, w), s = Router(cfg, name="router")(u, s)
            m, counts = Experts(cfg, name="moe")(
                u, idx, w, None if valid is None else valid.reshape(B * T))
            x = ResidualMerge(name="res_mlp")(x, m.reshape(B, T, d))
        return x, s, cache, counts


class Zaya(nn.Module):
    """``__call__(tokens) -> logits [B, T, vocab]`` float32."""

    config: ZayaConfig

    def setup(self):
        cfg = self.config
        self.wte = nn.Embed(
            cfg.vocab_size, cfg.hidden_size,
            embedding_init=nn.initializers.normal(cfg.init_std))
        self.blocks = [ZayaBlock(cfg, i, name=f"h{i}")
                       for i in range(cfg.num_hidden_layers)]
        self.ln_f = RMSNorm(cfg.rms_norm_eps)

    def _embed(self, tokens):
        with jax.named_scope("embed"):
            return self.wte(tokens).astype(_F32)

    def _head(self, x):
        """Float32 logits over the tied table."""
        cfg = self.config
        with jax.named_scope("ln"):
            x = self.ln_f(x).astype(cfg.dtype)
        with jax.named_scope("lm_head"):
            return jnp.einsum("...d,vd->...v", x,
                              self.wte.embedding.astype(cfg.dtype),
                              preferred_element_type=_F32)

    def _run(self, x, cache, **where):
        pairs = hit = jnp.zeros((), jnp.int32)
        rows = 0
        s = None
        for blk in self.blocks:
            x, s, cache, (p, e, r) = blk(x, s, cache=cache, **where)
            pairs, hit, rows = pairs + p, hit + e, rows + r
        return x, cache, (pairs, hit, rows)

    def __call__(self, idx, deterministic: bool = True):
        x, _, _ = self._run(self._embed(idx), None)
        return self._head(x)

    def prefill(self, tokens, length, slot, k_caches, v_caches):
        """A prompt at a slot: ``tokens`` [1, bucket] right-padded,
        ``length`` and ``slot`` traced scalars; ``k_caches`` the keys'
        array, the tails and the accumulator behind it, ``v_caches`` the
        values' array in a tuple (serve/kvcache.py ``state``).  Writes the
        slot's rows and tail and returns ``(next-token logits [vocab]
        float32 at position length - 1, k_caches, v_caches)``."""
        (k_cache, tail), counters = moe.split_counters(k_caches)
        valid = jnp.arange(tokens.shape[1])[None, :] < length
        x, cache, counts = self._run(
            self._embed(tokens), (k_cache, v_caches[0], tail),
            valid=valid, slot=slot, length=length)
        last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
        return (self._head(last)[0, 0],
                (cache[0], cache[2]) + moe.count_run(counters, 4, *counts),
                (cache[1],))

    def decode(self, tokens, positions, k_caches, v_caches,
               page_table=None, slots=None):
        """One continuous-batching decode step over ``S`` slots, with
        ``models/gpt.py GPT.decode``'s signature and contract: ``(next-
        token logits [S, vocab] float32, k_caches, v_caches)``."""
        if page_table is not None or slots is not None:
            raise ValueError(
                "Zaya's serve state has a tail a slot beside its rows: it "
                "has no paged fetch and no one-slot suffix program (a "
                "prefix's tail does not travel with its rows)")
        (k_cache, tail), counters = moe.split_counters(k_caches)
        x, cache, counts = self._run(
            self._embed(tokens[:, None]), (k_cache, v_caches[0], tail),
            positions=positions)
        return (self._head(x)[:, 0],
                (cache[0], cache[2]) + moe.count_run(counters, 0, *counts),
                (cache[1],))


class ZayaLightningModule(LightningModule):
    """ZAYA1 for ``Server(module).start()``.  Training it is not wired
    (no ``training_step``): the dropless layer has no backward here
    (PERF.md section 4)."""

    #: the parameters are made in their resident types (``init_params``)
    param_dtype = None
    #: the accumulator the serve engine makes beside the cache
    serve_counters = SERVE_COUNTERS

    def __init__(self, config: "ZayaConfig | str" = "tiny"):
        super().__init__()
        self.config = CONFIGS[config] if isinstance(config, str) else config

    def configure_model(self):
        return Zaya(self.config)

    def init_params(self, rng, batch):
        variables = super().init_params(rng, batch)
        return {**variables, "params": resident(variables["params"])}

    def configure_draft(self, layers: "int | None" = None):
        raise ValueError(
            "spec= is refused for Zaya: a layer-truncated draft replays "
            "rows by position, and a rejected draft token would leave its "
            "convolutions' tail behind in the slot")

    def refuse_serve_options(self, *, paged: bool, spec: bool,
                             kvship: bool) -> None:
        """What ``Server`` must not combine with this model, each with
        its reason (serve/server.py asks before it starts anything)."""
        if paged:
            raise ValueError(
                "paged= is refused for Zaya: prefix reuse copies a "
                "prefix's cache rows, and the position after a prefix "
                "needs the prefix's tail (its last position's z, a and "
                "u W_v2), which no page holds")
        if kvship:
            raise ValueError(
                "kvship= is refused for Zaya: the import programs install "
                "a keys' and a values' block, and a tail would have to "
                "travel with them")
        if spec:
            self.configure_draft()

    def live_cache_rows(self, position: int) -> float:
        """Cache rows a slot at ``position`` reads in one decode step, the
        mean over the layers (``Scheduler.stats()['live_rows']``): a row a
        position in every layer."""
        return float(int(position) + 1)


def resident(params: dict) -> dict:
    """A parameter tree in the types it is served in: bfloat16, and
    float32 whatever lies under a name of ``FLOAT32_PARAMS``."""
    def cast(path, a):
        names = {getattr(p, "key", None) for p in path}
        if names & set(FLOAT32_PARAMS) \
                or not jnp.issubdtype(a.dtype, jnp.floating):
            return a
        return a.astype(jnp.bfloat16)

    return jax.tree_util.tree_map_with_path(cast, params)


__all__ = ["CONFIGS", "FLOAT32_PARAMS", "SERVE_COUNTERS", "Zaya",
           "ZayaConfig", "ZayaLightningModule", "resident"]
