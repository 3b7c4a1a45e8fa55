"""Xing4.0: latent (MLA) attention with a cache of one row a position,
four residual streams mixed by manifold-constrained hyper-connections,
and sigmoid-routed experts beside a shared one (``model_type``
``xing4_0``).

``X`` the ``n = hc_mult`` residual streams ``[n, d]`` of a token, float32,
no bias anywhere.  RMSNorm ``n(x) = x / sqrt(mean(x^2) + eps) * g``;
gated MLPs ``W_down (silu(W_gate h) * W_up h)``::

    X_0 = the token's embedding, n times
    each sublayer F (attention, then the dense MLP or the experts), with
    float32 parameters phi [n d, n + n + n n], b [n + n + n n], a [3]:
      x~     = vec(X) / sqrt(mean(vec(X)^2) + eps)
      z      = x~ phi                         (pre | post | res columns)
      H_pre  = sigmoid(a_0 z_pre + b_pre)                       [n]
      H_post = 2 sigmoid(a_1 z_post + b_post)                   [n]
      M_0    = exp(clip(a_2 mat(z_res) + b_res, min, max))      [n, n]
      M_i+1  = M_i with its rows divided by their sums (+ hc_eps), then
               its columns by theirs; H_res = M_iters (Sinkhorn-Knopp)
      y      = F(n(H_pre X))                  (a d-vector in and out)
      X'     = H_res X + H_post^T y
    out: n_f(sum of the streams), logits = W_head x, the head untied.

    Attn (arXiv:2405.04434's latent attention, ``deepseek_v3``'s form):
      c_q = n(W_dq h); [q_nope | q_rope] = W_uq c_q a head (dn | dr)
      [c_kv | k_r] = W_dkv h (r | dr); c_kv <- n(c_kv)
      q_rope, k_r rotated (interleaved pairs, YaRN's frequencies); k_r
      is one for all heads
      [k_nope | v] = W_uk c_kv | W_uv c_kv a head (dn | dv)
      scores (q_nope . k_nope + q_rope . k_r) * s, causal softmax,
      W_o [heads of p . v];  s = (dn + dr)^-0.5 * (0.1 ln factor + 1)^2
    MoE: s = sigmoid(W_r h) over the experts (float32); the k largest of
      s + bias are CHOSEN; weights s_e / sum of the chosen s, times
      routed_scaling_factor; sum of w_e E_e(h) + E_shared(h)
      (ops/moe.py ``ExpertLayer``, which models/command.py builds too).

What is new to this repo beside the three decoder files (ROADMAP R1, R4):
a residual path of more than one stream, and a serve state of ONE array
(serve/kvcache.py): a position's row is ``[c_kv | k_r]``, ``r + dr`` = 576
values where the heads' keys and values would be 10,240, so the cache is
``[n_layer, S, served_positions, 640]`` (a row padded with zeros to whole
lane tiles, ``XingConfig.row_width``) and has no values' array.  TWO
attention paths read the one set of weights (ops/latent_attention.py):

- :meth:`Xing.prefill` expands ``c_kv`` through ``W_uk`` / ``W_uv`` into
  every head's keys and values, runs causal attention with a query / key
  width of ``dn + dr`` and a value width of ``dv``, and writes the
  prompt's rows at a slot;
- :meth:`Xing.decode` writes one row a slot and never expands the cache:
  ``q' = q_nope W_uk^T`` (r wide) is scored against the rows as they lie,
  the weighted sum of their first ``r`` lanes goes through ``W_uv``.

The multi-token-prediction module (``num_nextn_predict_layers``) is a
training loss and a self-drafter beyond the last layer; it feeds no
next-token logit and is not part of the served forward.

A latent row per position COULD be paged, shipped and replayed; the paged
kernel, the suffix program and the layer-truncated draft read a keys' and
a values' array, so prefix reuse, KV shipping and ``spec=`` are refused by
name (:meth:`XingLightningModule.refuse_serve_options`).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_lightning_tpu.core.module import LightningModule
from ray_lightning_tpu.ops import latent_attention as la
from ray_lightning_tpu.ops import moe
from ray_lightning_tpu.ops import window_attention as wa

#: the accumulator's entries (serve/engine.py ``stats()['counters']``)
SERVE_COUNTERS = moe.SERVE_COUNTERS
#: parameters served in float32 (everything else is bfloat16): the router
#: and its selection bias, and every hyper-connection parameter
FLOAT32_PARAMS = ("router", "bias", "hc_phi", "hc_b", "hc_a")


@dataclasses.dataclass(frozen=True)
class XingConfig:
    """The published ``config.json``'s keys under their own names
    (``rope_scaling``'s as ``rope_<key>``)."""

    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216          # a leading dense layer's MLP
    moe_intermediate_size: int = 1024      # the width of one expert
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 64.0
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_position_embeddings: int = 262144
    #: rows a slot holds: a server's longest sequence; None: every position
    served_positions: "int | None" = None
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16        # compute dtype; the streams are fp32

    @property
    def block_size(self) -> int:
        """Positions a sequence may have (what ``Server`` asks for)."""
        return self.served_positions or self.max_position_embeddings

    @property
    def row_width(self) -> int:
        """Lanes of a position's cache row: ``c_kv`` beside ``k_r`` (576
        values at the published sizes), padded with zeros to whole lane
        tiles (640).  Compiled for a described v5e, a cache whose minor
        dimension is 576 is COPIED whole before the decode call (4.19 GB
        of temporaries at 64 slots x 10,240 rows) and one of 640 is read
        as it lies (PERF.md section 6, PR 36); in HBM's tiled layout the
        two take the same room."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return wa.yarn_softmax_scale(
            self.qk_nope_head_dim + self.qk_rope_head_dim,
            self.rope_factor, self.rope_mscale_all_dim)

    def inv_freq(self):
        return wa.yarn_inv_freq(
            self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
            self.rope_original_max_position_embeddings,
            self.rope_beta_fast, self.rope_beta_slow)


CONFIGS = {
    "tiny": XingConfig(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=3,
        first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
        rope_factor=4.0, rope_original_max_position_embeddings=16,
        max_position_embeddings=64),
    "xing4-29b-a4b": XingConfig(),
}


class RMSNorm(nn.Module):
    """In float32 and returned so."""

    eps: float

    @nn.compact
    def __call__(self, x):
        g = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps) \
            * g.astype(jnp.float32)


def _dense(cfg: XingConfig, n: int, name: str) -> nn.Dense:
    return nn.Dense(n, use_bias=False, dtype=cfg.dtype, name=name,
                    kernel_init=nn.initializers.normal(cfg.init_std))


# -- the residual path -------------------------------------------------------------

def sinkhorn(m, iters: int, eps: float):
    """``m`` [n, n, ...] positive: ``iters`` times its rows divided by
    their sums, then its columns by theirs (``eps`` added to each sum)."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


class HyperConnection(nn.Module):
    """One sublayer's coefficients from the streams themselves.  ``X``: a
    tuple of ``n`` streams [B, T, d] float32.  Returns ``(H_pre [n, B, T,
    1], H_post [n, B, T, 1], H_res [n, n, B, T, 1])`` float32.  Inside,
    the coefficients lie streams-major with the tokens on the minor axis
    (``[n, n, B * T]``), so that the Sinkhorn iterations are elementwise
    over whole lane tiles of tokens."""

    config: XingConfig

    @nn.compact
    def __call__(self, X):
        cfg = self.config
        n, d = cfg.hc_mult, cfg.hidden_size
        c = n * (n + 2)
        phi = self.param("hc_phi", nn.initializers.normal(cfg.init_std),
                         (n * d, c), jnp.float32)
        b = self.param("hc_b", nn.initializers.zeros, (c,), jnp.float32)
        a = self.param("hc_a", nn.initializers.ones, (3,), jnp.float32)
        with jax.named_scope("mhc_mix"):
            lead = X[0].shape[:-1]
            mean = sum(jnp.mean(jnp.square(x), axis=-1) for x in X) / n
            # x~ phi = (vec(X) phi) / rms: a product a stream, no copy of
            # the streams side by side
            z = sum(jnp.einsum("...d,dc->...c", x, phi[m * d:(m + 1) * d],
                               precision="highest")
                    for m, x in enumerate(X))
            z = z * jax.lax.rsqrt(mean + cfg.rms_norm_eps)[..., None]
            z = z.reshape(-1, c).T                          # [c, B * T]
            b = b[:, None]
            pre = jax.nn.sigmoid(a[0] * z[:n] + b[:n])
            post = 2.0 * jax.nn.sigmoid(a[1] * z[n:2 * n] + b[n:2 * n])
            res = jnp.clip(a[2] * z[2 * n:] + b[2 * n:],
                           cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max)
            res = sinkhorn(jnp.exp(res).reshape(n, n, -1),
                           cfg.hc_sinkhorn_iters, cfg.hc_eps)
            return (pre.reshape((n,) + lead + (1,)),
                    post.reshape((n,) + lead + (1,)),
                    res.reshape((n, n) + lead + (1,)))


def streams_in(X, pre):
    """``H_pre X``: the sublayer's input [B, T, d] float32."""
    with jax.named_scope("mhc_apply"):
        return sum(pre[m] * x for m, x in enumerate(X))


def streams_out(X, y, post, res):
    """``H_res X + H_post^T y``: the ``n`` streams after the sublayer."""
    with jax.named_scope("mhc_apply"):
        return tuple(
            sum(res[i, m] * x for m, x in enumerate(X)) + post[i] * y
            for i in range(len(X)))


# -- attention -----------------------------------------------------------------------

class LatentAttention(nn.Module):
    """The projections and the two attention paths.  Three ways in, as
    ``models/command.py GroupedAttention``: a whole sequence (no
    ``cache``); a prompt at a slot (``cache`` with ``slot`` and
    ``length``); one token a slot (``cache`` with ``positions`` [S]).
    ``cache`` is the ONE array ``[n_layer, S, rows, row_width]``; with it
    it returns ``(y, cache)``."""

    config: XingConfig
    layer: int

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
            c_q = RMSNorm(cfg.rms_norm_eps, name="q_norm")(
                _dense(cfg, cfg.q_lora_rank, "dq")(h)).astype(cfg.dtype)
            q = _dense(cfg, H * (dn + dr), "uq")(c_q) \
                .reshape(B, T, H, dn + dr)
            kv = _dense(cfg, r + dr, "dkv")(h)
            c_kv = RMSNorm(cfg.rms_norm_eps, name="kv_norm")(
                kv[..., :r]).astype(cfg.dtype)
            at = jnp.arange(T) if positions is None else positions[:, None]
            inv_freq = cfg.inv_freq()
            q_rope = wa.rotary_interleaved(q[..., dn:], at, cfg.rope_theta,
                                           inv_freq)
            k_rope = wa.rotary_interleaved(kv[..., None, r:], at,
                                           cfg.rope_theta, inv_freq)
            # a position's cache row, [B, T, row_width]: zeros behind
            pad = cfg.row_width - r - dr
            row = jnp.concatenate(
                [c_kv, k_rope[..., 0, :], jnp.zeros((B, T, pad), c_kv.dtype)],
                axis=-1)
        if positions is not None:
            y, cache = self._decode(q[:, 0, :, :dn], q_rope[:, 0], row[:, 0],
                                    positions, cache, uk, uv)
            return _dense(cfg, cfg.hidden_size, "o")(y[:, None]), cache
        with jax.named_scope("mla_proj"):
            k_nope = jnp.einsum("btc,chd->bthd", c_kv, uk)
            v = jnp.einsum("btc,chd->bthd", c_kv, uv)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (B, T, H, dr))], axis=-1)
            q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
        y = la.causal_attention(q, k, v, sm_scale=cfg.softmax_scale,
                                dtype=cfg.dtype)
        y = _dense(cfg, cfg.hidden_size, "o")(y.reshape(B, T, H * dv))
        if cache is None:
            if not self.is_initializing():
                # the shape of a slot's state in this layer, for the
                # engine to size the cache by (serve/kvcache.py
                # from_capture): ONE block, the row holds key and value
                self.sow("kv_cache", "kv", (jnp.zeros(
                    (B, 1, cfg.block_size, cfg.row_width), row.dtype),))
            return y
        with jax.named_scope("kv_cache"):
            # the bucket's rows at rows [0, bucket) of the slot
            cache = jax.lax.dynamic_update_slice(
                cache, row[None].astype(cache.dtype),
                (self.layer, slot, 0, 0))
        return y, cache

    def _decode(self, q_nope, q_rope, row, positions, cache, uk, uv):
        """The absorbed path.  ``q_nope`` [S, H, dn], ``q_rope`` [S, H,
        dr], ``row`` [S, row_width]."""
        cfg = self.config
        H = cfg.num_attention_heads
        with jax.named_scope("kv_cache"):
            cache = cache.at[self.layer, jnp.arange(row.shape[0]),
                             positions].set(row.astype(cache.dtype))
        with jax.named_scope("mla_proj"):
            # the query as a row lies: absorbed | rotated | zeros
            q = jnp.einsum("shd,chd->shc", q_nope, uk)
            q = jnp.concatenate(
                [q, q_rope.astype(q.dtype), jnp.zeros(
                    q.shape[:2] + (row.shape[-1] - q.shape[-1]
                                   - q_rope.shape[-1],), q.dtype)], axis=-1)
        u = la.cached_attention(
            q, cache, positions, layer=self.layer,
            value_dim=cfg.kv_lora_rank, sm_scale=cfg.softmax_scale,
            dtype=cfg.dtype)
        with jax.named_scope("mla_proj"):
            y = jnp.einsum("shc,chd->shd", u, uv)
        return y.reshape(y.shape[0], H * cfg.v_head_dim), cache


# -- a block -------------------------------------------------------------------------

class DenseMLP(nn.Module):
    config: XingConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        a = nn.silu(_dense(cfg, cfg.intermediate_size, "gate")(h)) \
            * _dense(cfg, cfg.intermediate_size, "up")(h)
        return _dense(cfg, cfg.hidden_size, "down")(a)


def expert_layer(cfg: XingConfig, name: str) -> moe.ExpertLayer:
    """ops/moe.py's layer at this configuration's sizes: every routed
    expert held, a selection bias, the scaling factor, one shared
    expert."""
    return moe.ExpertLayer(
        d=cfg.hidden_size, width=cfg.moe_intermediate_size,
        held=cfg.n_routed_experts, published=cfg.n_routed_experts,
        top_k=cfg.num_experts_per_tok, n_shared=cfg.n_shared_experts,
        select_bias=True, scale=float(cfg.routed_scaling_factor),
        init_std=cfg.init_std, dtype=cfg.dtype, name=name)


class XingBlock(nn.Module):
    config: XingConfig
    layer: int

    @nn.compact
    def __call__(self, X, *, cache=None, valid=None, **where):
        """``X``: the streams, a tuple of ``hc_mult`` [B, T, d] float32.
        ``where``: ``positions`` (decode) or ``slot`` and ``length``
        (prefill), with ``cache``.  Returns ``(X', cache, (pairs,
        experts_hit, rows))``."""
        cfg = self.config
        B, T, d = X[0].shape
        with jax.named_scope("attn"):
            pre, post, res = HyperConnection(cfg, name="hc_attn")(X)
            h = streams_in(X, pre)
        with jax.named_scope("ln"):
            h = RMSNorm(cfg.rms_norm_eps, name="ln_attn")(h)
        a = LatentAttention(cfg, self.layer, name="attn")(
            h.astype(cfg.dtype), cache=cache, **where)
        if cache is not None:
            a, cache = a
        with jax.named_scope("attn"):
            X = streams_out(X, a.astype(jnp.float32), post, res)
        with jax.named_scope("mlp"):
            pre, post, res = HyperConnection(cfg, name="hc_mlp")(X)
            h = streams_in(X, pre)
        with jax.named_scope("ln"):
            h = RMSNorm(cfg.rms_norm_eps, name="ln_mlp")(h)
        with jax.named_scope("mlp"):
            if self.layer < cfg.first_k_dense_replace:
                m = DenseMLP(cfg, name="mlp")(h.astype(cfg.dtype))
                counts = (jnp.zeros((), jnp.int32),) * 2 + (0,)
            else:
                m, counts = expert_layer(cfg, "moe")(
                    h.reshape(B * T, d),
                    None if valid is None else valid.reshape(B * T))
                m = m.reshape(B, T, d)
            X = streams_out(X, m.astype(jnp.float32), post, res)
        return X, cache, counts


class Xing(nn.Module):
    """``__call__(tokens) -> logits [B, T, vocab]`` float32."""

    config: XingConfig

    def setup(self):
        cfg = self.config
        init = nn.initializers.normal(cfg.init_std)
        self.wte = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                            embedding_init=init)
        self.blocks = [XingBlock(cfg, i, name=f"h{i}")
                       for i in range(cfg.num_hidden_layers)]
        self.ln_f = RMSNorm(cfg.rms_norm_eps)
        self.lm_head = self.param("lm_head", init,
                                  (cfg.hidden_size, cfg.vocab_size))

    def _embed(self, tokens):
        with jax.named_scope("embed"):
            x = self.wte(tokens).astype(jnp.float32)
            return (x,) * self.config.hc_mult

    def _head(self, X):
        """Float32 logits from the sum of the streams."""
        cfg = self.config
        with jax.named_scope("ln"):
            x = self.ln_f(sum(X)).astype(cfg.dtype)
        with jax.named_scope("lm_head"):
            return jnp.einsum("...d,dv->...v", x,
                              self.lm_head.astype(cfg.dtype),
                              preferred_element_type=jnp.float32)

    def __call__(self, idx, deterministic: bool = True):
        X = self._embed(idx)
        for blk in self.blocks:
            X, _, _ = blk(X)
        return self._head(X)

    def _run(self, X, cache, **where):
        pairs = hit = jnp.zeros((), jnp.int32)
        rows = 0
        for blk in self.blocks:
            X, cache, (p, e, r) = blk(X, cache=cache, **where)
            pairs, hit, rows = pairs + p, hit + e, rows + r
        return X, cache, (pairs, hit, rows)

    def prefill(self, tokens, length, slot, k_caches, v_caches):
        """A prompt at a slot: ``tokens`` [1, bucket] right-padded,
        ``length`` and ``slot`` traced scalars; ``k_caches`` the latent
        cache (a tuple of the one array, the accumulator behind it where
        there is one), ``v_caches`` the empty tuple (serve/kvcache.py).
        Writes the slot's rows and returns ``(next-token logits [vocab]
        float32 at position length - 1, k_caches, v_caches)``."""
        (cache,), counters = moe.split_counters(k_caches)
        valid = jnp.arange(tokens.shape[1])[None, :] < length
        X, cache, counts = self._run(self._embed(tokens), cache,
                                     valid=valid, slot=slot, length=length)
        last = tuple(jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
                     for x in X)
        return (self._head(last)[0, 0],
                (cache,) + moe.count_run(counters, 4, *counts), v_caches)

    def decode(self, tokens, positions, k_caches, v_caches,
               page_table=None, slots=None):
        """One continuous-batching decode step over ``S`` slots, with
        ``models/gpt.py GPT.decode``'s signature and contract: ``(next-
        token logits [S, vocab] float32, k_caches, v_caches)``."""
        if page_table is not None or slots is not None:
            raise ValueError(
                "Xing's serve state is one array of latent rows: the "
                "paged fetch and the one-slot suffix program (prefix "
                "reuse) read a keys' and a values' array")
        (cache,), counters = moe.split_counters(k_caches)
        X, cache, counts = self._run(self._embed(tokens[:, None]), cache,
                                     positions=positions)
        return (self._head(X)[:, 0],
                (cache,) + moe.count_run(counters, 0, *counts), v_caches)


class XingLightningModule(LightningModule):
    """Xing4.0 for ``Server(module).start()``.  Training it is not wired
    (no ``training_step``): the dropless layer has no backward here, nor
    has attention with two head widths (PERF.md section 4)."""

    #: the parameters are made in their resident types (``init_params``)
    param_dtype = None
    #: the accumulator the serve engine makes beside the cache
    serve_counters = SERVE_COUNTERS

    def __init__(self, config: "XingConfig | str" = "tiny"):
        super().__init__()
        self.config = CONFIGS[config] if isinstance(config, str) else config

    def configure_model(self):
        return Xing(self.config)

    def init_params(self, rng, batch):
        variables = super().init_params(rng, batch)
        return {**variables, "params": resident(variables["params"])}

    def configure_draft(self, layers: "int | None" = None):
        raise ValueError(
            "spec= is refused for Xing: the draft plane keeps a keys' and "
            "a values' array of its own and replays rows of both "
            "(serve/spec.py); a latent cache is one array, and the model's "
            "own next-token-prediction module is not wired as a drafter")

    def refuse_serve_options(self, *, paged: bool, spec: bool,
                             kvship: bool) -> None:
        """What ``Server`` must not combine with this model, each with
        its reason (serve/server.py asks before it starts anything)."""
        if paged:
            raise ValueError(
                "paged= is refused for Xing: a latent row a position could "
                "be paged, but the paged kernel and the suffix program of "
                "prefix reuse read a keys' and a values' array")
        if kvship:
            raise ValueError(
                "kvship= is refused for Xing: a latent row a position could "
                "be shipped, but the import programs install a keys' and a "
                "values' block")
        if spec:
            self.configure_draft()

    def live_cache_rows(self, position: int) -> float:
        """Cache rows a slot at ``position`` reads in one decode step, the
        mean over the layers (``Scheduler.stats()['live_rows']``): a row a
        position in every layer."""
        return float(int(position) + 1)


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


__all__ = ["CONFIGS", "FLOAT32_PARAMS", "SERVE_COUNTERS", "Xing",
           "XingConfig", "XingLightningModule", "resident"]
