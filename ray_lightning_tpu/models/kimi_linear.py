"""Kimi Linear: Kimi Delta Attention (KDA) layers, whose serve state is a
float32 matrix a head that every step multiplies, three to one latent
(MLA) layer without positions, and sigmoid-routed experts beside a shared
one (``model_type`` ``kimi_linear``; arXiv:2510.26692).

``d`` = 2304, ``H`` = 32 heads of ``K = V`` = 128 in a KDA layer (``P = H
K`` = 4096), no bias on a projection.  ``n(x) = x / sqrt(mean(x^2) + eps)
* g``.  Pre-norm, plain residual on one float32 stream: ``x <- x +
Attn(n(x))``, ``x <- x + FFN(n(x))``.  **(A)** marks a reading that the
published ``config.json`` does not settle (``chipbench/configs/
kimi-linear-48b-a3b.json`` lists each under ``assumed`` with its
source)::

    KDA (layers ``linear_attn_config.kda_layers``), u = n(x):
      z~ = [u W_q ; u W_k ; u W_v]                      (3 P channels)
      c_t = sum_{j=0..3} w[:, j] * z~_{t-3+j}, zeros before position 0
            (A: a depthwise causal convolution of kernel 4 each for q, k
            and v, no bias, SiLU after it)
      a head: q = L2norm(silu(c^q)) K^-1/2 (A: the scale), k =
              L2norm(silu(c^k)), v = silu(c^v)
      g_t = -exp(A_log_h) softplus((u W_a1) W_a2 + dt_bias)   [K] a head
            (A: the gate's form; W_a1 d -> K, W_a2 K -> P)
      beta_t = sigmoid(u W_b)                                  a head
      S' = Diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t                         (ops/kda.py; S_{-1} = 0)
      W_o [ norm_head(o_t) * sigmoid((u W_g1) W_g2 + b_g) ]  (A: the bias)
    MLA without positions (``full_attn_layers``; ``mla_use_nope``), u = n(x):
      [q_nope | q_pe] = u W_q a head (dn | dr; ``q_lora_rank`` null)
      [c | k_pe] = u W_kva (r | dr); c <- n(c); NOTHING is rotated
      [k_nope | v] = c W_kvb a head (dn | dv)
      scores (q_nope . k_nope + q_pe . k_pe) / sqrt(dn + dr) (A), causal
      softmax, W_o [heads of p . v]
    FFN: the first ``first_k_dense_replace`` layers a gated MLP of
      ``intermediate_size``; after them ops/moe.py ``ExpertLayer``:
      sigmoid scores over all ``num_experts_published`` outputs, a float32
      bias that chooses and does not weigh, top-8 renormalised times
      ``routed_scaling_factor``, the pairs on the experts HELD here
      (``num_experts`` from ``expert_offset``) computed, one shared expert.
    out: logits = W_head n_f(x), the head untied, float32.

What is new to this repo beside the five decoder files (ROADMAP R6): a
layer that keeps NO cache rows.  A KDA layer's state a slot is the matrix
``[P, K]`` float32 (2 MB published: a head's ``S^T``), a ring of the last four positions'
``z~`` (position ``p`` in row ``p % 4``: written by position, like a
cache row) and an int32 STAMP, the position the matrix stands at
(serve/kvcache.py ``SlotState`` / ``KVCacheSpec.states``); the MLA
layers between them keep one latent row a position in ONE array, as
models/xing.py's, read by the same ``mla_decode`` call.

- :meth:`KimiLinear.prefill` runs the chunkwise form over the bucket with
  the padding's ``g`` and ``beta`` zeroed, and writes the state after
  position ``length - 1``, that position's ring rows and the stamp
  ``length - 1`` whole at the slot: a freed slot needs no clearing.
- :meth:`KimiLinear.decode` at position ``t`` updates a slot's matrix only
  where its stamp reads ``t - 1`` and stamps it ``t``; where it already
  reads ``t`` (the step runs a second time: serve/worker.py queues a
  decode ahead, and queues the plan's own again after a miss) ``g`` and
  ``beta`` are zeroed, the matrix stays as the first run left it and the
  read-out is the first run's; any other stamp (a dead slot's dummy
  step) leaves the matrix alone too.  ONE generation: two, as
  models/zaya.py's tail has them, would double the largest array of the
  cell for no less traffic (3.0 GB at 192 slots; the stamps are 5 KB).

A state would have to travel with a prefix's rows, so prefix reuse, KV
shipping and the layer-truncated draft are refused by name
(:meth:`KimiLinearLightningModule.refuse_serve_options`).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_lightning_tpu.core.module import LightningModule
from ray_lightning_tpu.models.xing import RMSNorm
from ray_lightning_tpu.ops import kda
from ray_lightning_tpu.ops import latent_attention as la
from ray_lightning_tpu.ops import moe
from ray_lightning_tpu.serve.kvcache import SlotState

#: the accumulator's entries (serve/engine.py ``stats()['counters']``)
SERVE_COUNTERS = moe.SERVE_COUNTERS
#: parameters served in float32 (everything else is bfloat16): the router
#: and its selection bias, and everything that makes a KDA layer's decay
#: and beta
FLOAT32_PARAMS = ("router", "bias", "a1", "a2", "A_log", "dt_bias", "b")
#: positions of convolution input a slot keeps: position p in row p % RING
RING = 4

_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """The published ``config.json``'s keys under their own names
    (``linear_attn_config``'s ``num_heads`` / ``head_dim`` as ``kda_<key>``;
    its layer lists 1-based, as published)."""

    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216          # a leading dense layer's MLP
    moe_intermediate_size: int = 1024      # the width of one expert
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    kda_layers: tuple = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                         19, 21, 22, 23, 25, 26)
    full_attn_layers: tuple = (4, 8, 12, 16, 20, 24, 27)
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    #: experts HELD here, from ``expert_offset``, of the router's
    #: ``num_experts_published`` outputs (None: every one is held)
    num_experts: int = 256
    num_experts_published: "int | None" = None
    expert_offset: int = 0
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    model_max_length: int = 1048576
    #: rows a slot holds: a server's longest sequence; None: every position
    served_positions: "int | None" = None
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16        # compute dtype; the residual is fp32

    def __post_init__(self):
        layers = sorted(tuple(self.kda_layers) + tuple(self.full_attn_layers))
        if layers != list(range(1, self.num_hidden_layers + 1)) \
                or self.short_conv_kernel_size != RING \
                or not self.full_attn_layers:
            raise ValueError(
                f"every layer 1..{self.num_hidden_layers} is a KDA layer or "
                f"a full-attention layer (at least one: the serve state is "
                f"sized around its rows), and the convolution's kernel is "
                f"the ring's {RING} rows: {self}")

    @property
    def block_size(self) -> int:
        """Positions a sequence may have (what ``Server`` asks for)."""
        return self.served_positions or self.model_max_length

    @property
    def published_experts(self) -> int:
        return self.num_experts_published or self.num_experts

    @property
    def kda_width(self) -> int:
        """``P``: a KDA layer's heads side by side."""
        return self.kda_num_heads * self.kda_head_dim

    @property
    def row_width(self) -> int:
        """Lanes of a full-attention layer's cache row: ``c`` beside
        ``k_pe``, padded with zeros to whole lane tiles (models/xing.py)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    def index_of(self, layer: int) -> "tuple[bool, int]":
        """``(is KDA, its index among the layers of its kind)`` of block
        ``layer`` (0-based): where its state lies in its kind's arrays."""
        mine = self.kda_layers if layer + 1 in self.kda_layers \
            else self.full_attn_layers
        return mine is self.kda_layers, sorted(mine).index(layer + 1)


CONFIGS = {
    "tiny": KimiLinearConfig(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=5,
        kda_layers=(1, 2, 3, 5), full_attn_layers=(4,), kda_num_heads=4,
        kda_head_dim=8, num_attention_heads=4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        num_experts=4, num_experts_published=8, num_experts_per_token=2,
        model_max_length=256),
    "kimi-linear-48b-a3b": KimiLinearConfig(),
}


def _dense(cfg: KimiLinearConfig, n: int, name: str) -> nn.Dense:
    return nn.Dense(n, use_bias=False, dtype=cfg.dtype, name=name,
                    kernel_init=nn.initializers.normal(cfg.init_std))


# -- the KDA sublayer ----------------------------------------------------------------

def causal_conv(z, w):
    """``c_t = sum_j w[:, j] z_{t - 3 + j}`` over a sequence, zeros before
    position 0.  ``z`` [B, T, C] float32, ``w`` [C, RING]."""
    T = z.shape[1]
    padded = jnp.pad(z, ((0, 0), (RING - 1, 0), (0, 0)))
    return sum(w[:, j] * padded[:, j:j + T] for j in range(RING))


def unit(x):
    return x * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


class DeltaAttention(nn.Module):
    """The KDA sublayer after its norm.  Three ways in, as
    ``models/command.py GroupedAttention``: a whole sequence (no
    ``cache``); a prompt at a slot (``cache`` with ``slot`` and
    ``length``); one token a slot (``cache`` with ``positions`` [S]).
    ``u`` [B, T, d] float32.  ``cache`` is ``(state [n, S, P, K] float32,
    ring [n, S, RING, 3 P] float32, stamp [n, S] int32)`` of the ``n``
    KDA layers, this one's at ``index`` (a head's matrix value-major, ``S^T``
    [V, K], its rows at ``h V``: ops/kda.py); with it it returns ``(y,
    cache)``."""

    config: KimiLinearConfig
    index: int

    @nn.compact
    def __call__(self, u, *, cache=None, positions=None, slot=None,
                 length=None):
        cfg = self.config
        B, T, d = u.shape
        H, K, P = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_width
        init = nn.initializers.normal(cfg.init_std)
        decoding = positions is not None

        def f32(name, shape, make=init):
            return self.param(name, make, shape, _F32)

        conv_w = jnp.concatenate(
            [self.param("conv_" + n, init, (P, RING)) for n in "qkv"],
            axis=0).astype(_F32)
        a1, a2 = f32("a1", (d, K)), f32("a2", (K, P))
        A_log = f32("A_log", (H,), nn.initializers.zeros)
        dt_bias = f32("dt_bias", (P,), nn.initializers.zeros)
        w_b = f32("b", (d, H))
        g_bias = self.param("g_bias", nn.initializers.zeros, (P,))
        o_norm = self.param("o_norm", nn.initializers.ones, (K,))
        with jax.named_scope("kda_proj"):
            uc = u.astype(cfg.dtype)
            z = jnp.concatenate(
                [_dense(cfg, P, n)(uc).astype(_F32) for n in "qkv"], axis=-1)
            gate = _dense(cfg, P, "g2")(_dense(cfg, K, "g1")(uc)) \
                .astype(_F32) + g_bias.astype(_F32)
            rate = jnp.einsum(
                "...k,kp->...p",
                jnp.einsum("...d,dk->...k", u, a1, precision="highest"), a2,
                precision="highest") + dt_bias
            g = -jnp.exp(A_log)[:, None] \
                * jax.nn.softplus(rate).reshape(B, T, H, K)
            beta = jax.nn.sigmoid(
                jnp.einsum("...d,dh->...h", u, w_b, precision="highest"))
            if decoding:
                # the three positions before this one from the ring (zeros
                # before position 0), this one's as it was just made
                state, ring, stamp = cache
                slots = jnp.arange(B)
                taps = positions[:, None] - (RING - 1) + jnp.arange(RING - 1)
                before = jnp.take_along_axis(
                    ring[self.index], (taps % RING)[:, :, None], axis=1)
                before = jnp.where((taps >= 0)[:, :, None], before, 0.0)
                c = jnp.sum(before * conv_w.T[:RING - 1], axis=1) \
                    + z[:, 0] * conv_w[:, RING - 1]
                c = c[:, None]
            else:
                c = causal_conv(z, conv_w)
            c = jax.nn.silu(c).reshape(B, T, 3, H, K)
            q = unit(c[:, :, 0]) * K ** -0.5
            k, v = unit(c[:, :, 1]), c[:, :, 2]

        def out(o):
            with jax.named_scope("kda_proj"):
                o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                               keepdims=True)
                                      + cfg.rms_norm_eps) * o_norm.astype(_F32)
                o = o.reshape(B, T, P) * jax.nn.sigmoid(gate)
                return _dense(cfg, d, "o")(o.astype(cfg.dtype))

        if decoding:
            with jax.named_scope("kda_state"):
                # only a state that stands at t - 1 moves (module docstring)
                fresh = stamp[self.index] == positions - 1
                g = jnp.where(fresh[:, None, None], g[:, 0], 0.0)
                beta = jnp.where(fresh[:, None], beta[:, 0], 0.0)
                if kda.decode_kernel():
                    o, state = kda.kda_decode(q[:, 0], k[:, 0], v[:, 0], g,
                                              beta, state, layer=self.index)
                else:
                    o, S = kda.kda_step(
                        q[:, 0], k[:, 0], v[:, 0], g, beta,
                        state[self.index].reshape(B, H, K, K))
                    state = state.at[self.index].set(S.reshape(B, P, K))
                stamp = stamp.at[self.index].set(
                    jnp.where(fresh, positions, stamp[self.index]))
            with jax.named_scope("kda_proj"):
                ring = ring.at[self.index, slots, positions % RING].set(z[:, 0])
            return out(o[:, None]), (state, ring, stamp)
        with jax.named_scope("kda_state"):
            if length is not None:
                # a bucket's padding leaves the state alone
                real = jnp.arange(T) < length
                g = jnp.where(real[None, :, None, None], g, 0.0)
                beta = jnp.where(real[None, :, None], beta, 0.0)
            o, S = kda.kda_chunked(q, k, v, g, beta,
                                   jnp.zeros((B, H, K, K), _F32))
        y = out(o)
        if cache is None:
            if not self.is_initializing():
                # what a slot keeps in this layer, for the engine to size
                # its state by (serve/kvcache.py from_capture): no rows
                self.sow("kv_cache", "kv", SlotState((
                    jnp.zeros((B, 1, P, K), _F32),
                    jnp.zeros((B, 1, RING, 3 * P), _F32),
                    jnp.zeros((B, 1), jnp.int32))))
            return y
        state, ring, stamp = cache
        with jax.named_scope("kda_state"):
            state = jax.lax.dynamic_update_slice(
                state, S.reshape(1, 1, P, K), (self.index, slot, 0, 0))
            stamp = jax.lax.dynamic_update_slice(
                stamp, jnp.reshape(length - 1, (1, 1)).astype(stamp.dtype),
                (self.index, slot))
        with jax.named_scope("kda_proj"):
            # row r: the last position p <= length - 1 with p % RING == r
            # (one before position 0 is never read: clamped)
            last = length - 1
            at = jnp.maximum(last - (last - jnp.arange(RING)) % RING, 0)
            ring = jax.lax.dynamic_update_slice(
                ring, jnp.take(z, at, axis=1)[None],
                (self.index, slot, 0, 0))
        return y, (state, ring, stamp)


# -- the latent sublayer, no positions ---------------------------------------------------

class NopeLatentAttention(nn.Module):
    """``models/xing.py LatentAttention`` with the query from one matrix
    and nothing rotated; the same two paths of ops/latent_attention.py
    over the same row ``[c | k_pe | zeros]``.  ``cache`` is the ONE array
    ``[n_full, S, rows, row_width]`` of the full-attention layers, this
    one's at ``index``."""

    config: KimiLinearConfig
    index: int

    @nn.compact
    def __call__(self, h, *, cache=None, positions=None, slot=None,
                 length=None):
        cfg = self.config
        B, T, _ = h.shape
        H, r = cfg.num_attention_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        init = nn.initializers.normal(cfg.init_std)
        uk = self.param("uk", init, (r, H, dn)).astype(cfg.dtype)
        uv = self.param("uv", init, (r, H, dv)).astype(cfg.dtype)
        with jax.named_scope("mla_proj"):
            q = _dense(cfg, H * (dn + dr), "q")(h).reshape(B, T, H, dn + dr)
            kv = _dense(cfg, r + dr, "dkv")(h)
            c_kv = RMSNorm(cfg.rms_norm_eps, name="kv_norm")(
                kv[..., :r]).astype(cfg.dtype)
            # a position's cache row, [B, T, row_width]: zeros behind
            pad = cfg.row_width - r - dr
            row = jnp.concatenate(
                [c_kv, kv[..., r:], jnp.zeros((B, T, pad), c_kv.dtype)],
                axis=-1)
        if positions is not None:
            with jax.named_scope("kv_cache"):
                cache = cache.at[self.index, jnp.arange(B), positions].set(
                    row[:, 0].astype(cache.dtype))
            with jax.named_scope("mla_proj"):
                # the query as a row lies: absorbed | q_pe | zeros
                qa = jnp.einsum("shd,chd->shc", q[:, 0, :, :dn], uk)
                qa = jnp.concatenate(
                    [qa, q[:, 0, :, dn:].astype(qa.dtype),
                     jnp.zeros((B, H, pad), qa.dtype)], axis=-1)
            seen = la.cached_attention(
                qa, cache, positions, layer=self.index, value_dim=r,
                sm_scale=cfg.softmax_scale, dtype=cfg.dtype)
            with jax.named_scope("mla_proj"):
                y = jnp.einsum("shc,chd->shd", seen, uv).reshape(B, 1, H * dv)
            return _dense(cfg, cfg.hidden_size, "o")(y), cache
        with jax.named_scope("mla_proj"):
            k = jnp.concatenate(
                [jnp.einsum("btc,chd->bthd", c_kv, uk),
                 jnp.broadcast_to(kv[..., None, r:], (B, T, H, dr))], axis=-1)
            v = jnp.einsum("btc,chd->bthd", c_kv, uv)
        y = la.causal_attention(q, k, v, sm_scale=cfg.softmax_scale,
                                dtype=cfg.dtype)
        y = _dense(cfg, cfg.hidden_size, "o")(y.reshape(B, T, H * dv))
        if cache is None:
            if not self.is_initializing():
                # ONE block: the row holds key and value (models/xing.py)
                self.sow("kv_cache", "kv", (jnp.zeros(
                    (B, 1, cfg.block_size, cfg.row_width), row.dtype),))
            return y
        with jax.named_scope("kv_cache"):
            # the bucket's rows at rows [0, bucket) of the slot
            cache = jax.lax.dynamic_update_slice(
                cache, row[None].astype(cache.dtype),
                (self.index, slot, 0, 0))
        return y, cache


# -- a block -------------------------------------------------------------------------

class DenseMLP(nn.Module):
    config: KimiLinearConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        a = nn.silu(_dense(cfg, cfg.intermediate_size, "gate")(h)) \
            * _dense(cfg, cfg.intermediate_size, "up")(h)
        return _dense(cfg, cfg.hidden_size, "down")(a)


def expert_layer(cfg: KimiLinearConfig, name: str) -> moe.ExpertLayer:
    """ops/moe.py's layer at this configuration's sizes: the held experts
    of the published ones, a selection bias, the scaling factor, the
    shared expert."""
    return moe.ExpertLayer(
        d=cfg.hidden_size, width=cfg.moe_intermediate_size,
        held=cfg.num_experts, published=cfg.published_experts,
        top_k=cfg.num_experts_per_token, n_shared=cfg.num_shared_experts,
        offset=cfg.expert_offset, select_bias=True,
        scale=float(cfg.routed_scaling_factor), init_std=cfg.init_std,
        dtype=cfg.dtype, name=name)


class KimiBlock(nn.Module):
    config: KimiLinearConfig
    layer: int

    @nn.compact
    def __call__(self, x, *, cache=None, valid=None, **where):
        """``x`` [B, T, d] float32.  ``cache``: ``(rows, state, ring,
        stamp)`` (serve/kvcache.py ``state``) or None; ``where``:
        ``positions`` (decode) or ``slot`` and ``length`` (prefill).
        Returns ``(x', cache, (pairs, experts_hit, rows))``."""
        cfg = self.config
        B, T, d = x.shape
        is_kda, index = cfg.index_of(self.layer)
        with jax.named_scope("ln"):
            u = RMSNorm(cfg.rms_norm_eps, name="ln_attn")(x)
        if is_kda:
            a = DeltaAttention(cfg, index, name="attn")(
                u, cache=None if cache is None else cache[1:], **where)
            if cache is not None:
                a, cache = a[0], cache[:1] + a[1]
        else:
            a = NopeLatentAttention(cfg, index, name="attn")(
                u.astype(cfg.dtype),
                cache=None if cache is None else cache[0], **where)
            if cache is not None:
                a, cache = a[0], (a[1],) + cache[1:]
        with jax.named_scope("attn"):
            x = x + a.astype(_F32)
        with jax.named_scope("ln"):
            u = RMSNorm(cfg.rms_norm_eps, name="ln_mlp")(x)
        with jax.named_scope("mlp"):
            if self.layer < cfg.first_k_dense_replace:
                m = DenseMLP(cfg, name="mlp")(u.astype(cfg.dtype))
                counts = (jnp.zeros((), jnp.int32),) * 2 + (0,)
            else:
                m, counts = expert_layer(cfg, "moe")(
                    u.reshape(B * T, d),
                    None if valid is None else valid.reshape(B * T))
                m = m.reshape(B, T, d)
            x = x + m.astype(_F32)
        return x, cache, counts


class KimiLinear(nn.Module):
    """``__call__(tokens) -> logits [B, T, vocab]`` float32."""

    config: KimiLinearConfig

    def setup(self):
        cfg = self.config
        init = nn.initializers.normal(cfg.init_std)
        self.wte = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                            embedding_init=init)
        self.blocks = [KimiBlock(cfg, i, name=f"h{i}")
                       for i in range(cfg.num_hidden_layers)]
        self.ln_f = RMSNorm(cfg.rms_norm_eps)
        self.lm_head = self.param("lm_head", init,
                                  (cfg.hidden_size, cfg.vocab_size))

    def _embed(self, tokens):
        with jax.named_scope("embed"):
            return self.wte(tokens).astype(_F32)

    def _head(self, x):
        cfg = self.config
        with jax.named_scope("ln"):
            x = self.ln_f(x).astype(cfg.dtype)
        with jax.named_scope("lm_head"):
            return jnp.einsum("...d,dv->...v", x,
                              self.lm_head.astype(cfg.dtype),
                              preferred_element_type=_F32)

    def _run(self, x, cache, **where):
        pairs = hit = jnp.zeros((), jnp.int32)
        rows = 0
        for blk in self.blocks:
            x, cache, (p, e, r) = blk(x, cache=cache, **where)
            pairs, hit, rows = pairs + p, hit + e, rows + r
        return x, cache, (pairs, hit, rows)

    def __call__(self, idx, deterministic: bool = True):
        x, _, _ = self._run(self._embed(idx), None)
        return self._head(x)

    def prefill(self, tokens, length, slot, k_caches, v_caches):
        """A prompt at a slot: ``tokens`` [1, bucket] right-padded,
        ``length`` and ``slot`` traced scalars; ``k_caches`` the latent
        rows' array, the KDA layers' matrices, rings and stamps and the
        accumulator behind them, ``v_caches`` the empty tuple
        (serve/kvcache.py ``state``).  Writes the slot's rows and state
        and returns ``(next-token logits [vocab] float32 at position
        length - 1, k_caches, v_caches)``."""
        cache, counters = moe.split_counters(k_caches)
        valid = jnp.arange(tokens.shape[1])[None, :] < length
        x, cache, counts = self._run(self._embed(tokens), cache,
                                     valid=valid, slot=slot, length=length)
        last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
        return (self._head(last)[0, 0],
                cache + moe.count_run(counters, 4, *counts), v_caches)

    def decode(self, tokens, positions, k_caches, v_caches,
               page_table=None, slots=None):
        """One continuous-batching decode step over ``S`` slots, with
        ``models/gpt.py GPT.decode``'s signature and contract: ``(next-
        token logits [S, vocab] float32, k_caches, v_caches)``."""
        if page_table is not None or slots is not None:
            raise ValueError(
                "KimiLinear's serve state is a matrix a head beside latent "
                "rows: it has no paged fetch and no one-slot suffix program "
                "(a prefix's state is not kept)")
        cache, counters = moe.split_counters(k_caches)
        x, cache, counts = self._run(self._embed(tokens[:, None]), cache,
                                     positions=positions)
        return (self._head(x)[:, 0],
                cache + moe.count_run(counters, 0, *counts), v_caches)


class KimiLinearLightningModule(LightningModule):
    """Kimi Linear for ``Server(module).start()``.  Training it is not
    wired (no ``training_step``): neither the chunkwise scan nor the
    dropless layer has a backward here (PERF.md section 7)."""

    #: the parameters are made in their resident types (``init_params``)
    param_dtype = None
    #: the accumulator the serve engine makes beside the cache
    serve_counters = SERVE_COUNTERS

    def __init__(self, config: "KimiLinearConfig | str" = "tiny"):
        super().__init__()
        self.config = CONFIGS[config] if isinstance(config, str) else config

    def configure_model(self):
        return KimiLinear(self.config)

    def init_params(self, rng, batch):
        variables = super().init_params(rng, batch)
        return {**variables, "params": resident(variables["params"])}

    def configure_draft(self, layers: "int | None" = None):
        raise ValueError(
            "spec= is refused for KimiLinear: a rejected draft token would "
            "have moved the slot's matrix state on, and the state before it "
            "is not kept")

    def refuse_serve_options(self, *, paged: bool, spec: bool,
                             kvship: bool) -> None:
        """What ``Server`` must not combine with this model, each with
        its reason (serve/server.py asks before it starts anything)."""
        if paged:
            raise ValueError(
                "paged= is refused for KimiLinear: prefix reuse copies a "
                "prefix's cache rows, and a prefix's rows are rows but its "
                "matrix state (as it stood after the prefix's last "
                "position) is not kept")
        if kvship:
            raise ValueError(
                "kvship= is refused for KimiLinear: the import programs "
                "install a keys' and a values' block, and the KDA layers' "
                "state would have to travel with them")
        if spec:
            self.configure_draft()

    def live_cache_rows(self, position: int) -> float:
        """Cache rows a slot at ``position`` reads in one decode step, the
        mean over ALL the layers (``Scheduler.stats()['live_rows']``): a
        row a position in the full-attention layers, none in a KDA
        layer."""
        cfg = self.config
        return (int(position) + 1) * len(cfg.full_attn_layers) \
            / cfg.num_hidden_layers


def resident(params: dict) -> dict:
    """A parameter tree in the types it is served in: bfloat16, and
    ``FLOAT32_PARAMS`` float32."""
    def cast(path, a):
        name = getattr(path[-1], "key", None)
        if name in FLOAT32_PARAMS \
                or not jnp.issubdtype(a.dtype, jnp.floating):
            return a
        return a.astype(jnp.bfloat16)

    return jax.tree_util.tree_map_with_path(cast, params)


__all__ = ["CONFIGS", "FLOAT32_PARAMS", "RING", "SERVE_COUNTERS",
           "KimiLinear", "KimiLinearConfig", "KimiLinearLightningModule",
           "resident"]
