"""The second family's plain reference: float32 ``jax.numpy`` at
``highest`` matmul precision over the unstacked layout of ``adapter.py``
beside it, one Python loop over the layers, nothing of the program and
nothing of the GPT-2 reference imported.  (The mathematics is GPT-2's,
because the family wraps the program's GPT: pre-LayerNorm blocks, fused
QKV, tanh-GELU, a tied output head.)  ``precision`` feeds every matrix
product lower-precision operands, for a control."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "fp8")


def _mm(eq: str, a, b, precision: str):
    if precision == "fp8":
        a, b = (x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
                for x in (a, b))
    if precision != "float32":
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    return jnp.einsum(eq, a, b, precision="highest",
                      preferred_element_type=jnp.float32)


def _norm(x, p: dict, name: str, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p[name + ".weight"] \
        + p[name + ".bias"]


def forward(params: dict, tokens, model: dict, precision: str = "float32",
            remat: bool = False):
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    eps, H = float(model["layer_norm_eps"]), int(model["num_attention_heads"])
    B, T = tokens.shape
    x = params["embed.tokens"][tokens] + params["embed.positions"][:T]
    C = x.shape[-1]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(int(model["num_hidden_layers"])):
        at = f"layers.{i}."
        h = _norm(x, params, at + "input_norm", eps)
        qkv = _mm("btc,cd->btd", h, params[at + "attn.qkv.weight"],
                  precision) + params[at + "attn.qkv.bias"]
        q, k, v = (a.reshape(B, T, H, C // H)
                   for a in jnp.split(qkv, 3, axis=-1))
        scores = _mm("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(C // H)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        y = _mm("bhqk,bkhd->bqhd", probs, v, precision).reshape(B, T, C)
        x = x + _mm("btc,cd->btd", y, params[at + "attn.out.weight"],
                    precision) + params[at + "attn.out.bias"]
        h = _norm(x, params, at + "post_norm", eps)
        h = _mm("btc,cd->btd", h, params[at + "mlp.up.weight"],
                precision) + params[at + "mlp.up.bias"]
        h = 0.5 * h * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (h + 0.044715 * h ** 3)))
        x = x + _mm("btd,dc->btc", h, params[at + "mlp.down.weight"],
                    precision) + params[at + "mlp.down.bias"]
    x = _norm(x, params, "final_norm", eps)
    return _mm("btc,vc->btv", x, params["embed.tokens"], precision)


def loss(params: dict, tokens, targets, model: dict,
         precision: str = "float32"):
    logp = jax.nn.log_softmax(forward(params, tokens, model, precision),
                              axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
