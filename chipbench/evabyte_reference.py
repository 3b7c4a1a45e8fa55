"""Plain reference for the EvaByte configurations (`evabyte-6p5b` names it
through `configs/evabyte-6p5b_reference.py`; the sizes come from the
configuration's file): the forward pass in straightforward ``jax.numpy``,
float32, matrix products at ``highest`` precision.  No kernel, no cache,
no batching trick, and nothing imported from the program under test.

The model (``model_type`` ``evabyte``; the configuration's file names the
published ``config.json``): a byte-level decoder of pre-norm blocks on a
float32 residual stream.  Per block, with ``n(x) = x / sqrt(mean(x^2) +
eps) * (1 + g)``::

    h = x + W_o EVA(n1(x))
    y = h + W_down (silu(W_gate n2(h)) * W_up n2(h))

no bias anywhere.  After the last block ``n_f`` and an untied head
``hidden -> num_pred_heads * vocab``: head ``i`` predicts byte ``t + 1 + i``.

EVA (Zheng, Yuan, Wang, Kong, "Efficient Attention via Control
Variates", ICLR 2023), in the form the issue writes down: rotary
positions (rotate-half, every dimension of the head) on q and k; windows
``w(t) = t // window``, chunks ``j(t) = t // chunk``; per head two learned
vectors ``phi`` and ``mu``.  A chunk's summary pools its members' keys
and values under ``softmax_m(s * phi . k_m)``, with ``mu`` added to the
pooled key; it does not depend on the query.  A query attends, in ONE
softmax, the exact rows of its own window up to itself and the
summaries of every chunk of every earlier window.  A context of at most
one window is plain causal softmax attention.

What the published config cannot confirm is listed under ``assumed`` in
the configuration's file (the scale on the pooling logits, ``mu`` on the
key only, rotary before pooling, windows as blocks, the head layout).

Parameters are one dict of arrays with the blocks stacked on a leading
axis (``chipbench/adapters/evabyte.py`` makes them from the seed), so the
blocks run under one ``lax.scan``; attention runs window by window, so
that a row of 12k positions never holds more than one window's scores.

``precision`` re-computes the same mathematics with every matrix product
fed lower-precision operands, for the control that ``chipbench/check.py``
has to fail: ``bfloat16`` is what the configuration states, ``fp8`` (e4m3
with one scale per tensor) the step below it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = ("float32", "bfloat16", "fp8")
_FP8_MAX = 448.0
_STACKED = ("ln1_g", "q_w", "k_w", "v_w", "o_w", "phi", "mu",
            "ln2_g", "gate_w", "up_w", "down_w")


def _fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _FP8_MAX
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _operand(a, precision: str):
    if precision == "float32":
        return a
    if precision == "fp8":
        a = _fp8(a)
    return a.astype(jnp.bfloat16)


def _einsum(eq: str, a, b, precision: str):
    return jnp.einsum(eq, _operand(a, precision), _operand(b, precision),
                      precision="highest",
                      preferred_element_type=jnp.float32)


def _rms_norm(x, gain, eps: float):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * (1.0 + gain)


def _rotary(x, theta: float):
    """Rotate-half rotary embedding of ``x`` [B, T, H, D] at positions
    0..T-1, over all D dimensions."""
    T, D = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * jnp.cos(angle) + jnp.concatenate([-x2, x1], -1) \
        * jnp.sin(angle)


def chunk_summaries(k, v, phi, mu, chunk: int, precision: str):
    """``(K~, V~)`` [B, ceil(T / chunk), H, D]: each chunk's keys and
    values pooled under ``softmax_m(s * phi . k_m)`` over the members
    that exist, ``mu`` added to the pooled key."""
    B, T, H, D = k.shape
    n = -(-T // chunk)
    pad = n * chunk - T
    member = jnp.arange(n * chunk) < T
    k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        B, n, chunk, H, D) for a in (k, v))
    logits = _einsum("bnchd,hd->bnch", k, phi, precision) / math.sqrt(D)
    logits = jnp.where(member.reshape(1, n, chunk, 1), logits, -jnp.inf)
    a = jax.nn.softmax(logits, axis=2)
    k_sum = _einsum("bnch,bnchd->bnhd", a, k, precision) + mu
    v_sum = _einsum("bnch,bnchd->bnhd", a, v, precision)
    return k_sum, v_sum


def eva_attention(q, k, v, phi, mu, window: int, chunk: int,
                  precision: str):
    """EVA over ``q``, ``k``, ``v`` [B, T, H, D] (rotary applied): window
    by window, one softmax over the window's causal exact rows and the
    summaries of every chunk of every earlier window."""
    B, T, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    k_sum, v_sum = chunk_summaries(k, v, phi, mu, chunk, precision)
    per_window = window // chunk
    out = []
    for w in range(-(-T // window)):
        lo, hi = w * window, min((w + 1) * window, T)
        n = hi - lo
        causal = jnp.tril(jnp.ones((n, n), bool))
        exact = _einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, lo:hi],
                        precision) * scale
        exact = jnp.where(causal, exact, -jnp.inf)
        seen = w * per_window           # every chunk of earlier windows
        if not seen:                    # the first window: nothing earlier
            out.append(_einsum("bhqk,bkhd->bqhd",
                               jax.nn.softmax(exact, axis=-1), v[:, lo:hi],
                               precision))
            continue
        far = _einsum("bqhd,bjhd->bhqj", q[:, lo:hi], k_sum[:, :seen],
                      precision) * scale
        p = jax.nn.softmax(jnp.concatenate([exact, far], axis=-1), axis=-1)
        out.append(
            _einsum("bhqk,bkhd->bqhd", p[..., :n], v[:, lo:hi], precision)
            + _einsum("bhqj,bjhd->bqhd", p[..., n:], v_sum[:, :seen],
                      precision))
    return jnp.concatenate(out, axis=1)


def _block(x, p, model: dict, precision: str):
    B, T, C = x.shape
    H = int(model["num_attention_heads"])
    eps = float(model["rms_norm_eps"])
    a = _rms_norm(x, p["ln1_g"], eps)
    q, k, v = (_einsum("btc,cd->btd", a, p[n], precision).reshape(
        B, T, H, C // H) for n in ("q_w", "k_w", "v_w"))
    theta = float(model["rope_theta"])
    y = eva_attention(_rotary(q, theta), _rotary(k, theta), v,
                      p["phi"], p["mu"], int(model["window_size"]),
                      int(model["chunk_size"]), precision)
    x = x + _einsum("btc,cd->btd", y.reshape(B, T, C), p["o_w"], precision)
    a = _rms_norm(x, p["ln2_g"], eps)
    gate = _einsum("btc,cf->btf", a, p["gate_w"], precision)
    up = _einsum("btc,cf->btf", a, p["up_w"], precision)
    return x + _einsum("btf,fc->btc", jax.nn.silu(gate) * up, p["down_w"],
                       precision)


def forward_heads(params: dict, tokens, model: dict,
                  precision: str = "float32", remat: bool = False):
    """Logits ``[B, T, num_pred_heads, vocab]`` float32 for token ids
    ``[B, T]``: head ``i`` at position ``t`` predicts byte ``t + 1 + i``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    x = params["wte"][tokens]

    def body(x, layer):
        return _block(x, layer, model, precision), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = lax.scan(body, x, {k: params[k] for k in _STACKED})
    x = _rms_norm(x, params["lnf_g"], float(model["rms_norm_eps"]))
    logits = _einsum("btc,cv->btv", x, params["head_w"], precision)
    return logits.reshape(*tokens.shape, int(model["num_pred_heads"]),
                          int(model["vocab_size"]))


def forward(params: dict, tokens, model: dict, precision: str = "float32",
            remat: bool = False):
    """Next-byte logits ``[B, T, vocab]`` float32 (head 0): what greedy
    decoding reads, and what the serving check compares."""
    return forward_heads(params, tokens, model, precision, remat)[:, :, 0]


def loss(params: dict, tokens, targets, model: dict,
         precision: str = "float32"):
    """Mean next-byte cross-entropy (head 0) over every position."""
    logits = forward(params, tokens, model, precision, remat=True)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(picked)
