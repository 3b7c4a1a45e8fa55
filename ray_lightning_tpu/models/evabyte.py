"""EvaByte: a byte-level decoder whose attention keeps one exact window
and one summary row per chunk (``model_type`` ``evabyte``).

Pre-norm blocks on a float32 residual stream (``fp32_skip_add``), no
bias anywhere::

    n(x) = x / sqrt(mean(x^2) + eps) * (1 + g)       (norm_add_unit_offset)
    h = x + W_o EVA(n1(x))                            (ops/eva_attention.py)
    y = h + W_down (silu(W_gate n2(h)) * W_up n2(h))

rotary positions on q and k, and after the last block ``n_f`` and an
UNTIED head ``hidden -> num_pred_heads * vocab`` with float32 logits:
head ``i`` predicts byte ``t + 1 + i``; next-byte decoding reads head 0.
What is new to this repo beside ``models/gpt.py`` (ROADMAP R1): RMSNorm,
rotary positions, the gated MLP, the untied multi-head output, and a
serve state that is not a row per position (ROADMAP R6).

Serving (serve/engine.py, core/steps.py): the model keeps its own kind
of state in the cache's two arrays ``[n_layer, S, rows, H*D]``, ``rows =
window + max_position_embeddings // chunk`` (serve/kvcache.py), so it
brings the two methods that touch it:

- :meth:`EvaByte.prefill` writes a prompt's LAST window of exact rows and
  all its chunk summaries at a slot (not a K/V block of ``bucket`` rows);
- :meth:`EvaByte.decode` writes each slot's row at ``t % window``,
  rewrites the summary of the chunk that holds ``t`` from its up to
  ``chunk`` rows (it is unseen until its window is over, so no branch),
  and attends under the two-range bound.

The training forward's ``kv_cache`` capture declares that block's shape
(``[B, 1, rows, H*D]``) and nothing else: the engine sizes the cache
from it.  A prefix of the prompt is not a prefix of this state, so
prefix reuse, the paged kernel, KV shipping and the layer-truncated
draft are refused by name (:meth:`EvaByteLightningModule.
refuse_serve_options`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_lightning_tpu.core.module import LightningModule
from ray_lightning_tpu.ops import eva_attention as eva


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    """The published ``config.json``'s keys, under their own names."""

    vocab_size: int = 320
    hidden_size: int = 4096
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    intermediate_size: int = 11008
    num_pred_heads: int = 8
    window_size: int = 2048
    chunk_size: int = 16
    max_position_embeddings: int = 32768
    rope_theta: float = 100000.0
    rms_norm_eps: float = 1e-5
    init_std: float = 0.01275
    dtype: Any = jnp.bfloat16        # compute dtype; the residual is fp32

    def __post_init__(self):
        if self.hidden_size % self.num_attention_heads \
                or self.window_size % self.chunk_size \
                or self.max_position_embeddings % self.chunk_size:
            raise ValueError(f"sizes do not divide: {self}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def block_size(self) -> int:
        """Positions a sequence may have (what ``Server`` asks for)."""
        return self.max_position_embeddings

    @property
    def cache_rows(self) -> int:
        return eva.cache_rows(self.window_size, self.chunk_size,
                              self.max_position_embeddings)


CONFIGS = {
    "tiny": EvaByteConfig(hidden_size=64, num_hidden_layers=2,
                          num_attention_heads=2, intermediate_size=176,
                          num_pred_heads=2, window_size=32, chunk_size=4,
                          max_position_embeddings=256),
    "evabyte-6p5b": EvaByteConfig(),
}

_dot_f32 = functools.partial(jax.lax.dot_general,
                             preferred_element_type=jnp.float32)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * (1 + g)`` in float32, returned in
    the compute dtype."""

    eps: float
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        g = self.param("scale", nn.initializers.zeros, (x.shape[-1],))
        x = x.astype(jnp.float32)
        y = x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps)
        return (y * (1.0 + g.astype(jnp.float32))).astype(self.dtype)


class GatedMLP(nn.Module):
    config: EvaByteConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config

        def dense(n, name):
            return nn.Dense(n, use_bias=False, dtype=cfg.dtype, name=name,
                            kernel_init=nn.initializers.normal(cfg.init_std))

        h = nn.silu(dense(cfg.intermediate_size, "gate")(x)) \
            * dense(cfg.intermediate_size, "up")(x)
        return dense(cfg.hidden_size, "down")(h)


def _phi_mu_init(scale: float):
    def init(key, shape, dtype=jnp.float32):
        return scale * jnp.clip(jax.random.normal(key, shape, dtype), -1, 1)
    return init


class EvaAttention(nn.Module):
    """q/k/v/o projections, rotary positions and EVA.  Three ways in: a
    whole sequence (the training forward: no ``cache``); a prompt at a
    slot (``cache=(k_cache, v_cache, layer)`` with ``slot`` and
    ``length``); one token a slot (``cache`` with ``positions`` [S]).
    With a cache it returns ``(y, (k_cache, v_cache))``.

    A whole sequence and a prompt are ONE path, and on it q, k, v and the
    attention's output stay the packed ``[B, T, H*D]`` rows the products
    write and the ``o`` product reads (ops/eva_attention.py: a head is a
    block of columns): no ``[T, H, D]`` view of anything of a prompt's
    size, so no product carries a relayout inside it.  One token a slot
    rotates its row on the ``[S, 1, H, D]`` view."""

    config: EvaByteConfig

    @nn.compact
    def __call__(self, x, *, cache=None, positions=None, slot=None,
                 length=None):
        cfg = self.config
        B, T, C = x.shape
        H, D = cfg.num_attention_heads, cfg.head_dim
        window, chunk = cfg.window_size, cfg.chunk_size

        def dense(name):
            return nn.Dense(C, use_bias=False, dtype=cfg.dtype, name=name,
                            kernel_init=nn.initializers.normal(cfg.init_std))

        s = 1.0 / D ** 0.5
        phi = self.param("phi", _phi_mu_init(s), (H, D))
        mu = self.param("mu", _phi_mu_init(s), (H, D))
        if positions is not None:
            # one token a slot: the [S, 1, H, D] view is a row's own
            at = positions[:, None]
            q = eva.rotary(dense("q")(x).reshape(B, T, H, D), at,
                           cfg.rope_theta)
            k = eva.rotary(dense("k")(x).reshape(B, T, H, D), at,
                           cfg.rope_theta).reshape(B, T, C)
            v = dense("v")(x)
            y, cache = self._decode(q, k, v, phi, mu, positions, cache)
            return dense("o")(y.reshape(B, T, C)), cache

        # a whole sequence: packed [B, T, C] rows from the three products
        # to the fourth, a head a block of D columns all the way
        at = jnp.arange(T)
        q, k = eva.rotary_rows((dense("q")(x), dense("k")(x)), at,
                               cfg.rope_theta, H)
        v = dense("v")(x)
        # whole chunks, and whole windows beyond the first: zero rows
        # that causality hides from every row before them
        pad = -T % (window if T > window else chunk)
        qp, kp, vp = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                      for a in (q, k, v))
        Tp = T + pad
        member = jnp.arange(Tp) < (T if length is None else length)
        k_sum, v_sum = eva.chunk_summaries_rows(
            kp, vp, phi, mu, jnp.broadcast_to(member, (B, Tp)), chunk)
        y = eva.eva_attention(qp, kp, vp, k_sum, v_sum, n_head=H,
                              window=window, chunk=chunk,
                              dtype=cfg.dtype)[:, :T]
        y = dense("o")(y)
        if cache is None:
            if not self.is_initializing():
                # the shape of a slot's state, for the engine to size the
                # cache by (serve/kvcache.py from_capture); the values
                # are the prefill method's to write
                block = jnp.zeros((B, 1, cfg.cache_rows, C), k.dtype)
                self.sow("kv_cache", "kv", (block, block))
            return y
        return y, self._write_prompt(kp, vp, k_sum, v_sum, cache, slot,
                                     length)

    def _write_prompt(self, k, v, k_sum, v_sum, cache, slot, length):
        """The prompt's last window of exact rows at rows [0, window) of
        its slot, and every chunk summary from row ``window`` on."""
        window = self.config.window_size
        k_cache, v_cache, layer = cache
        with jax.named_scope("kv_cache"):
            Tp = k.shape[1]
            if Tp > window:
                first = (length - 1) // window * window
                k, v = (jax.lax.dynamic_slice_in_dim(a, first, window, 1)
                        for a in (k, v))

            def put(cache, rows, at):
                return jax.lax.dynamic_update_slice(
                    cache, rows[None].astype(cache.dtype),
                    (layer, slot, at, 0))

            return (put(put(k_cache, k, 0), k_sum, window),
                    put(put(v_cache, v, 0), v_sum, window))

    def _decode(self, q, k, v, phi, mu, positions, cache):
        cfg = self.config
        window, chunk = cfg.window_size, cfg.chunk_size
        k_cache, v_cache, layer = cache
        S, _, C = k.shape
        slots = jnp.arange(S)
        with jax.named_scope("kv_cache"):
            at = (layer, slots, positions % window)
            k_cache = k_cache.at[at].set(k[:, 0].astype(k_cache.dtype))
            v_cache = v_cache.at[at].set(v[:, 0].astype(v_cache.dtype))
        # the chunk that holds t, pooled again from its rows <= t (they
        # lie side by side in the window part: chunk divides window)
        chunk_no = positions // chunk
        first = chunk_no * chunk % window
        rows = first[:, None] + jnp.arange(chunk)[None, :]
        member = (chunk_no * chunk)[:, None] + jnp.arange(chunk)[None, :] \
            <= positions[:, None]
        with jax.named_scope("eva_summary"):
            pick = (layer, slots[:, None], rows)
            k_sum, v_sum = eva.chunk_summaries(
                k_cache[pick][:, None], v_cache[pick][:, None], phi, mu,
                member[:, None])
            with jax.named_scope("kv_cache"):
                at = (layer, slots, window + chunk_no)
                k_cache = k_cache.at[at].set(k_sum[:, 0])
                v_cache = v_cache.at[at].set(v_sum[:, 0])
        y = eva.eva_cached_attention(
            q, k_cache, v_cache, positions, layer=layer, window=window,
            chunk=chunk, dtype=cfg.dtype)
        return y, (k_cache, v_cache)


class EvaBlock(nn.Module):
    config: EvaByteConfig

    @nn.compact
    def __call__(self, x, *, cache=None, **where):
        """``where``: ``positions`` (decode) or ``slot`` and ``length``
        (prefill), with ``cache=(k_cache, v_cache, layer)``."""
        cfg = self.config
        with jax.named_scope("ln"):
            a = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="ln1")(x)
        a = EvaAttention(cfg, name="attn")(a, cache=cache, **where)
        if cache is not None:
            a, cache = a
        with jax.named_scope("attn"):
            x = x + a.astype(jnp.float32)
        with jax.named_scope("ln"):
            a = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="ln2")(x)
        with jax.named_scope("mlp"):
            x = x + GatedMLP(cfg, name="mlp")(a).astype(jnp.float32)
        return x if cache is None else (x, cache)


class EvaByte(nn.Module):
    """``__call__(tokens) -> logits [B, T, num_pred_heads, vocab]``."""

    config: EvaByteConfig

    def setup(self):
        cfg = self.config
        self.wte = nn.Embed(
            cfg.vocab_size, cfg.hidden_size,
            embedding_init=nn.initializers.normal(cfg.init_std))
        self.blocks = [EvaBlock(cfg, name=f"h{i}")
                       for i in range(cfg.num_hidden_layers)]
        self.ln_f = RMSNorm(cfg.rms_norm_eps, cfg.dtype)
        self.lm_head = nn.Dense(
            cfg.num_pred_heads * cfg.vocab_size, use_bias=False,
            dtype=cfg.dtype, dot_general=_dot_f32,
            kernel_init=nn.initializers.normal(cfg.init_std))

    def _embed(self, tokens):
        with jax.named_scope("embed"):
            return self.wte(tokens).astype(jnp.float32)

    def _head(self, x):
        """Float32 logits ``[..., num_pred_heads, vocab]``.  The serve
        programs read head 0 of them; the other heads' columns are 19 MB
        of a step's 5 GB."""
        cfg = self.config
        with jax.named_scope("ln"):
            x = self.ln_f(x)
        with jax.named_scope("lm_head"):
            return self.lm_head(x).reshape(
                *x.shape[:-1], cfg.num_pred_heads, cfg.vocab_size)

    def __call__(self, idx, deterministic: bool = True):
        x = self._embed(idx)
        for blk in self.blocks:
            x = blk(x)
        return self._head(x)

    def prefill(self, tokens, length, slot, k_caches, v_caches):
        """A prompt at a slot: ``tokens`` [1, bucket] right-padded,
        ``length`` and ``slot`` traced scalars.  Writes the slot's state
        into the resident buffers and returns ``(next-byte logits [vocab]
        float32 at position length - 1, k_caches, v_caches)``."""
        x = self._embed(tokens)
        for i, blk in enumerate(self.blocks):
            x, (k_caches, v_caches) = blk(
                x, cache=(k_caches, v_caches, i), slot=slot, length=length)
        last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
        return self._head(last)[0, 0, 0], k_caches, v_caches

    def decode(self, tokens, positions, k_caches, v_caches,
               page_table=None, slots=None):
        """One continuous-batching decode step over ``S`` slots, with
        ``models/gpt.py GPT.decode``'s signature and contract: ``(next-
        byte logits [S, vocab] float32, k_caches, v_caches)``, the
        buffers updated in place."""
        if page_table is not None or slots is not None:
            raise ValueError(
                "EvaByte's serve state is one window and one summary row "
                "per chunk, not a row per position: it has no paged "
                "fetch and no one-slot suffix program (prefix reuse)")
        x = self._embed(tokens[:, None])
        for i, blk in enumerate(self.blocks):
            x, (k_caches, v_caches) = blk(
                x, cache=(k_caches, v_caches, i), positions=positions)
        return self._head(x)[:, 0, 0], k_caches, v_caches


class EvaByteLightningModule(LightningModule):
    """EvaByte for ``Server(module).start()``.  Training it is not wired
    (no ``training_step``): the published size does not fit one chip's
    optimizer state (PERF.md section 4)."""

    param_dtype = jnp.bfloat16

    def __init__(self, config: "EvaByteConfig | str" = "tiny"):
        super().__init__()
        self.config = CONFIGS[config] if isinstance(config, str) else config

    def configure_model(self):
        return EvaByte(self.config)

    def configure_draft(self, layers: "int | None" = None):
        raise ValueError(
            "spec= is refused for EvaByte: it has no draft model here "
            "(its own multi-byte heads would draft; a step that yields "
            "more than one token a slot is not wired)")

    def refuse_serve_options(self, *, paged: bool, spec: bool,
                             kvship: bool) -> None:
        """What ``Server`` must not combine with this model, each with
        its reason (serve/server.py asks before it starts anything)."""
        if paged or kvship:
            raise ValueError(
                "paged= / kvship= are refused for EvaByte: prefix reuse "
                "and KV shipping copy a prefix's cache rows, and a "
                "prefix's rows are not a prefix of this state (a slot "
                "holds its LAST window and pooled summaries)")
        if spec:
            self.configure_draft()

    def live_cache_rows(self, position: int) -> int:
        """Cache rows a slot at ``position`` reads in one decode step
        (``Scheduler.stats()['live_rows']``)."""
        return sum(eva.visible_rows(int(position), self.config.window_size,
                                    self.config.chunk_size))


__all__ = ["CONFIGS", "EvaByte", "EvaByteConfig", "EvaByteLightningModule"]
