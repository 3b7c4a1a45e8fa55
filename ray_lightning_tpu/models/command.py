"""Command A+: sigmoid-routed experts beside averaged shared experts,
grouped K/V heads, and window and full-attention layers that each keep a
cache of their own (``model_type`` ``cohere2_moe``).

One norm a block, which attention and experts both read
(``use_parallel_block``), on a float32 residual stream, no bias anywhere::

    h  = LN(x) = (x - mean(x)) / sqrt(var(x) + eps) * g
    x' = x + Attn_l(h) + MoE(h)

    Attn: H query heads read G K/V heads (query head i the K/V head
          i // (H / G)).  ``sliding_attention``: interleaved rotary on q
          and k, key j seen iff i - window < j <= i.  ``full_attention``:
          no positional transform, key j seen iff j <= i.
    MoE:  s = sigmoid(Wr h) over ALL published experts (float32), the k
          largest, weights normalised; sum of w_e E_e(h) over the chosen
          experts HELD here, plus the mean of the shared experts (one
          gated product of width n_shared * F whose output is divided by
          n_shared).  E(h) = Wdown (silu(Wgate h) * Wup h).
    out:  LN_f, logits = logit_scale * table^T x, the table tied.

What is new to this repo beside ``models/gpt.py`` and
``models/evabyte.py`` (ROADMAP R1, R2, R5): grouped heads, layers of two
kinds in one model (``layer_types``), a dropless expert layer that is
told which experts it holds (ops/moe.py ``dropless_experts``), and a
serve state of TWO kinds side by side (serve/kvcache.py): a sliding
layer keeps a ring of ``sliding_window`` rows a slot, a full layer a row
per position, so the cache is a pair of arrays a kind,
``[n_sliding, S, window, G*D]`` and ``[n_full, S, served_positions,
G*D]``.  The model brings the two methods that touch it:

- :meth:`Command.prefill` writes, at a slot, the prompt's LAST window of
  rows of each sliding layer at ``row = position % window`` and every row
  of each full layer;
- :meth:`Command.decode` writes row ``t % window`` / row ``t`` and
  attends (ops/window_attention.py).

Both also add to a small int32 accumulator that rides the donated state
(``SERVE_COUNTERS``): runs, token-expert pairs computed here, experts
hit and the rows pushed through the grouped products (over the pairs:
how many pieces ``dropless_experts`` ran), apart for decode runs and
prefills, read where the server's stats are asked and never inside a
step.

A prefix of a prompt is a prefix of a full layer's rows but not of a
wrapped ring, so prefix reuse, the paged kernel, KV shipping and the
layer-truncated draft are refused by name
(:meth:`CommandLightningModule.refuse_serve_options`).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_lightning_tpu.core.module import LightningModule
from ray_lightning_tpu.ops import moe
from ray_lightning_tpu.ops import window_attention as wa

SLIDING, FULL = "sliding_attention", "full_attention"

#: the accumulator's entries (serve/engine.py ``stats()['counters']``)
SERVE_COUNTERS = moe.SERVE_COUNTERS


@dataclasses.dataclass(frozen=True)
class CommandConfig:
    """The published ``config.json``'s keys, under their own names, and
    the chip's share of them (``num_experts`` HELD of
    ``num_experts_published``, from ``expert_offset``)."""

    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096          # the width of one expert
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_experts: int = 128
    num_experts_published: "int | None" = None
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    sliding_window: int = 4096
    layer_types: tuple = (SLIDING, SLIDING, SLIDING, FULL) * 8
    rope_theta: float = 50000.0
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    max_position_embeddings: int = 200000
    #: rows a slot of a full layer holds: a server's longest sequence
    #: (the model has no table that ends); None: every position
    served_positions: "int | None" = None
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16        # compute dtype; the residual is fp32

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.num_experts_published is None:
            object.__setattr__(self, "num_experts_published",
                               self.num_experts + self.expert_offset)
        if self.num_attention_heads % self.num_key_value_heads \
                or len(self.layer_types) < self.num_hidden_layers \
                or set(self.layer_types) - {SLIDING, FULL} \
                or self.expert_offset + self.num_experts \
                > self.num_experts_published:
            raise ValueError(f"sizes do not fit: {self}")
        if self.block_size <= self.sliding_window \
                and SLIDING in self.kinds and FULL in self.kinds:
            raise ValueError(
                f"a ring of {self.sliding_window} rows beside full layers "
                f"of {self.block_size}: the window has to be the shorter")

    @property
    def block_size(self) -> int:
        """Positions a sequence may have (what ``Server`` asks for)."""
        return self.served_positions or self.max_position_embeddings

    @property
    def kinds(self) -> tuple:
        """The kinds of layer present, in the order of their first
        layer: the order of the cache's arrays."""
        return tuple(dict.fromkeys(
            self.layer_types[:self.num_hidden_layers]))

    def place(self, layer: int) -> "tuple[int, int]":
        """``(kind, index)``: which of the cache's arrays holds block
        ``layer``'s rows, and which of its layers it is there."""
        types = self.layer_types[:self.num_hidden_layers]
        return (self.kinds.index(types[layer]),
                types[:layer].count(types[layer]))

    def rows(self, kind: str) -> int:
        return self.sliding_window if kind == SLIDING else self.block_size


_PERIOD = (SLIDING, SLIDING, SLIDING, FULL)

CONFIGS = {
    "tiny": CommandConfig(
        vocab_size=256, hidden_size=64, intermediate_size=48,
        num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=2,
        head_dim=16, num_experts=4, num_experts_published=16,
        num_experts_per_tok=4, num_shared_experts=2, sliding_window=8,
        layer_types=_PERIOD, max_position_embeddings=64),
    "command-a-plus": CommandConfig(),
}


class LayerNorm(nn.Module):
    """Cohere's bias-free LayerNorm, in float32 and returned so."""

    eps: float

    @nn.compact
    def __call__(self, x):
        g = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x = x.astype(jnp.float32)
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + self.eps) \
            * g.astype(jnp.float32)


class GroupedAttention(nn.Module):
    """q/k/v/o projections, the layer kind's positions and mask.  Three
    ways in, as ``models/evabyte.py EvaAttention``: a whole sequence (no
    ``cache``); a prompt at a slot (``cache=(k_caches, v_caches)`` with
    ``slot`` and ``length``); one token a slot (``cache`` with
    ``positions`` [S]).  ``k_caches`` / ``v_caches`` are the tuples of a
    kind's arrays; with them it returns ``(y, (k_caches, v_caches))``."""

    config: CommandConfig
    layer: int

    @nn.compact
    def __call__(self, x, *, cache=None, positions=None, slot=None,
                 length=None):
        cfg = self.config
        B, T, _ = x.shape
        H, G, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
        kind = cfg.layer_types[self.layer]
        sliding = kind == SLIDING

        def dense(n, name):
            return nn.Dense(n, use_bias=False, dtype=cfg.dtype, name=name,
                            kernel_init=nn.initializers.normal(cfg.init_std))

        q = dense(H * D, "q")(x).reshape(B, T, H, D)
        k = dense(G * D, "k")(x).reshape(B, T, G, D)
        v = dense(G * D, "v")(x)
        if sliding:
            at = jnp.arange(T) if positions is None else positions[:, None]
            q = wa.rotary_interleaved(q, at, cfg.rope_theta)
            k = wa.rotary_interleaved(k, at, cfg.rope_theta)
        k = k.reshape(B, T, G * D)
        if positions is not None:
            y, cache = self._decode(q, k, v, positions, cache)
            return dense(cfg.hidden_size, "o")(y.reshape(B, T, H * D)), cache
        y = wa.banded_attention(
            q, k.reshape(B, T, G, D), v.reshape(B, T, G, D),
            window=cfg.sliding_window if sliding else None, dtype=cfg.dtype)
        y = dense(cfg.hidden_size, "o")(y.reshape(B, T, H * D))
        if cache is None:
            if not self.is_initializing():
                # the shape of a slot's state in this layer, for the
                # engine to size the cache by (serve/kvcache.py
                # from_capture); the prefill method writes the values
                block = jnp.zeros((B, 1, cfg.rows(kind), G * D), k.dtype)
                self.sow("kv_cache", "kv", (block, block))
            return y
        return y, self._write_prompt(k, v, cache, slot, length)

    def _write_prompt(self, k, v, cache, slot, length):
        """A full layer: the bucket's rows at rows [0, bucket) of the
        slot.  A sliding layer: the prompt's last window, position ``p``
        at row ``p % window``."""
        cfg = self.config
        k_caches, v_caches = cache
        kind, index = cfg.place(self.layer)
        with jax.named_scope("kv_cache"):
            if cfg.layer_types[self.layer] == SLIDING:
                pick = wa.ring_rows(length, cfg.sliding_window, k.shape[1])
                k, v = (jnp.take(a, pick, axis=1) for a in (k, v))

            def put(caches, rows):
                return _with_kind(caches, kind, jax.lax.dynamic_update_slice(
                    caches[kind], rows[None].astype(caches[kind].dtype),
                    (index, slot, 0, 0)))

            return put(k_caches, k), put(v_caches, v)

    def _decode(self, q, k, v, positions, cache):
        cfg = self.config
        k_caches, v_caches = cache
        kind, index = cfg.place(self.layer)
        sliding = cfg.layer_types[self.layer] == SLIDING
        slots = jnp.arange(k.shape[0])
        row = positions % cfg.sliding_window if sliding else positions
        with jax.named_scope("kv_cache"):
            at = (index, slots, row)
            k_kind = k_caches[kind].at[at].set(
                k[:, 0].astype(k_caches[kind].dtype))
            v_kind = v_caches[kind].at[at].set(
                v[:, 0].astype(v_caches[kind].dtype))
        y = wa.cached_attention(q, k_kind, v_kind, positions, layer=index,
                                ring=sliding, dtype=cfg.dtype)
        return y, (_with_kind(k_caches, kind, k_kind),
                   _with_kind(v_caches, kind, v_kind))


def _with_kind(caches: tuple, kind: int, one) -> tuple:
    """``caches`` with the array of one kind replaced."""
    return caches[:kind] + (one,) + caches[kind + 1:]


def expert_layer(cfg: CommandConfig, name: str) -> moe.ExpertLayer:
    """ops/moe.py's layer at this configuration's sizes: the routed
    experts held here beside the averaged shared experts."""
    return moe.ExpertLayer(
        d=cfg.hidden_size, width=cfg.intermediate_size,
        held=cfg.num_experts, published=cfg.num_experts_published,
        top_k=cfg.num_experts_per_tok, n_shared=cfg.num_shared_experts,
        offset=cfg.expert_offset, init_std=cfg.init_std, dtype=cfg.dtype,
        name=name)


class CommandBlock(nn.Module):
    config: CommandConfig
    layer: int

    @nn.compact
    def __call__(self, x, *, cache=None, valid=None, **where):
        """``x`` [B, T, d] float32.  ``where``: ``positions`` (decode) or
        ``slot`` and ``length`` (prefill), with ``cache=(k_caches,
        v_caches)``.  Returns ``(x', cache, (pairs, experts_hit,
        rows))``."""
        cfg = self.config
        B, T, d = x.shape
        with jax.named_scope("ln"):
            h = LayerNorm(cfg.layer_norm_eps, name="ln")(x)
        a = GroupedAttention(cfg, self.layer, name="attn")(
            h.astype(cfg.dtype), cache=cache, **where)
        if cache is not None:
            a, cache = a
        with jax.named_scope("mlp"):
            m, counts = expert_layer(cfg, "moe")(
                h.reshape(B * T, d),
                None if valid is None else valid.reshape(B * T))
        with jax.named_scope("attn"):
            x = x + a.astype(jnp.float32)
        with jax.named_scope("mlp"):
            x = x + m.reshape(B, T, d)
        return x, cache, counts


class Command(nn.Module):
    """``__call__(tokens) -> logits [B, T, vocab]`` float32."""

    config: CommandConfig

    def setup(self):
        cfg = self.config
        self.wte = nn.Embed(
            cfg.vocab_size, cfg.hidden_size,
            embedding_init=nn.initializers.normal(cfg.init_std))
        self.blocks = [CommandBlock(cfg, i, name=f"h{i}")
                       for i in range(cfg.num_hidden_layers)]
        self.ln_f = LayerNorm(cfg.layer_norm_eps)

    def _embed(self, tokens):
        with jax.named_scope("embed"):
            return self.wte(tokens).astype(jnp.float32)

    def _head(self, x):
        """Float32 logits over the held rows of the tied table."""
        cfg = self.config
        with jax.named_scope("ln"):
            x = self.ln_f(x).astype(cfg.dtype)
        with jax.named_scope("lm_head"):
            return cfg.logit_scale * jnp.einsum(
                "...d,vd->...v", x,
                self.wte.embedding.astype(cfg.dtype),
                preferred_element_type=jnp.float32)

    def __call__(self, idx, deterministic: bool = True):
        x = self._embed(idx)
        for blk in self.blocks:
            x, _, _ = blk(x)
        return self._head(x)

    def prefill(self, tokens, length, slot, k_caches, v_caches):
        """A prompt at a slot: ``tokens`` [1, bucket] right-padded,
        ``length`` and ``slot`` traced scalars; ``k_caches`` /
        ``v_caches`` the tuples of a kind's arrays (``k_caches`` with the
        accumulator behind them, where there is one).  Writes the slot's
        state and returns ``(next-token logits [vocab] float32 at
        position length - 1, k_caches, v_caches)``."""
        kinds, counters = moe.split_counters(k_caches)
        state = (kinds, tuple(v_caches))
        valid = jnp.arange(tokens.shape[1])[None, :] < length
        x = self._embed(tokens)
        pairs = hit = jnp.zeros((), jnp.int32)
        rows = 0
        for blk in self.blocks:
            x, state, (p, e, r) = blk(x, cache=state, valid=valid,
                                      slot=slot, length=length)
            pairs, hit, rows = pairs + p, hit + e, rows + r
        last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
        return (self._head(last)[0, 0],
                state[0] + moe.count_run(counters, 4, pairs, hit, rows), state[1])

    def decode(self, tokens, positions, k_caches, v_caches,
               page_table=None, slots=None):
        """One continuous-batching decode step over ``S`` slots, with
        ``models/gpt.py GPT.decode``'s signature and contract: ``(next-
        token logits [S, vocab] float32, k_caches, v_caches)``."""
        if page_table is not None or slots is not None:
            raise ValueError(
                "Command's serve state is a ring of window rows beside a "
                "row per position: it has no paged fetch and no one-slot "
                "suffix program (prefix reuse)")
        kinds, counters = moe.split_counters(k_caches)
        state = (kinds, tuple(v_caches))
        x = self._embed(tokens[:, None])
        pairs = hit = jnp.zeros((), jnp.int32)
        rows = 0
        for blk in self.blocks:
            x, state, (p, e, r) = blk(x, cache=state, positions=positions)
            pairs, hit, rows = pairs + p, hit + e, rows + r
        return (self._head(x)[:, 0],
                state[0] + moe.count_run(counters, 0, pairs, hit, rows), state[1])


class CommandLightningModule(LightningModule):
    """Command A+ for ``Server(module).start()``.  Training it is not
    wired (no ``training_step``): the dropless layer has no backward
    here, and the cut that serves does not fit trained (PERF.md section
    4)."""

    #: the parameters are made in their resident types (``init_params``):
    #: bfloat16 but for the router, which is read in float32
    param_dtype = None
    #: the accumulator the serve engine makes beside the cache
    serve_counters = SERVE_COUNTERS

    def __init__(self, config: "CommandConfig | str" = "tiny"):
        super().__init__()
        self.config = CONFIGS[config] if isinstance(config, str) else config

    def configure_model(self):
        return Command(self.config)

    def init_params(self, rng, batch):
        variables = super().init_params(rng, batch)
        return {**variables, "params": resident(variables["params"])}

    def configure_draft(self, layers: "int | None" = None):
        raise ValueError(
            "spec= is refused for Command: it has no draft model here (a "
            "layer-truncated draft would replay rows by position, and a "
            "wrapped ring holds no such rows)")

    def refuse_serve_options(self, *, paged: bool, spec: bool,
                             kvship: bool) -> None:
        """What ``Server`` must not combine with this model, each with
        its reason (serve/server.py asks before it starts anything)."""
        if paged or kvship:
            raise ValueError(
                "paged= / kvship= are refused for Command: prefix reuse "
                "(its page copy and its suffix program) and KV shipping "
                "copy a prefix's cache rows; a prefix's rows are a prefix "
                "of a full layer's rows but not of a sliding layer's "
                "wrapped ring")
        if spec:
            self.configure_draft()

    def live_cache_rows(self, position: int) -> float:
        """Cache rows a slot at ``position`` reads in one decode step,
        the mean over the layers (``Scheduler.stats()['live_rows']``): 1
        a position for a row per position in every layer."""
        cfg = self.config
        types = cfg.layer_types[:cfg.num_hidden_layers]
        seen = int(position) + 1
        return sum(min(seen, cfg.sliding_window) if t == SLIDING else seen
                   for t in types) / len(types)


def resident(params: dict) -> dict:
    """A parameter tree in the types it is served in: bfloat16, and the
    routers float32."""
    def cast(path, a):
        name = getattr(path[-1], "key", None)
        if name == "router" or not jnp.issubdtype(a.dtype, jnp.floating):
            return a
        return a.astype(jnp.bfloat16)

    return jax.tree_util.tree_map_with_path(cast, params)


__all__ = ["CONFIGS", "Command", "CommandConfig", "CommandLightningModule",
           "SERVE_COUNTERS", "resident"]
