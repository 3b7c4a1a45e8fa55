"""Plain reference for the GPT-2 configurations (`gpt2-small` and
`gpt2-large` each name it through `configs/<name>_reference.py`; the
sizes come from the configuration's file): GPT-2's forward pass and loss
in straightforward ``jax.numpy``, float32, matrix products at ``highest``
precision.  No kernels, no cache, no batching tricks, and nothing
imported from the program under test.

It follows the published model (Radford et al. 2019; the Hugging Face
`config.json` that each configuration's file names as its source):
learned position embeddings, pre-LayerNorm blocks, fused QKV, causal
softmax attention scaled by 1/sqrt(head size), tanh-GELU (`gelu_new`),
output head tied to the token embedding.  Departures, both stated in the
configuration file: the vocabulary is padded to 50304 rows and
LayerNorm's epsilon is the configuration's (1e-6, the program's; the
published 1e-5 is listed under `reduced`, with what it moves).

Parameters are one dict of arrays with the layers stacked on a leading
axis (``chipbench/adapters/gpt2.py`` makes them from the seed), so the blocks
run under one ``lax.scan``.

``precision`` re-computes the same mathematics with every matrix product
fed lower-precision operands.  It exists for the control that
``chipbench/check.py`` has to fail: ``bfloat16`` is what the
configuration states, ``fp8`` (e4m3 with one scale per tensor, as a
serving stack would use it) the step below it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = ("float32", "bfloat16", "fp8")
_FP8_MAX = 448.0


@jax.custom_vjp
def _fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _FP8_MAX
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


# straight-through: the cotangent is not squeezed through e4m3 unscaled,
# where every gradient of this model would flush to zero
_fp8.defvjp(lambda a: (_fp8(a), None), lambda _, g: (g,))


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


def _layer_norm(x, gain, bias, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * gain + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, n_head: int, eps: float, precision: str):
    B, T, C = x.shape
    h = _layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
    qkv = _einsum("btc,cd->btd", h, p["qkv_w"], precision) + p["qkv_b"]
    q, k, v = (a.reshape(B, T, n_head, C // n_head)
               for a in jnp.split(qkv, 3, axis=-1))
    scores = _einsum("bqhd,bkhd->bhqk", q, k, precision) \
        / math.sqrt(C // n_head)
    causal = jnp.tril(jnp.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    y = _einsum("bhqk,bkhd->bqhd", probs, v, precision).reshape(B, T, C)
    x = x + _einsum("btc,cd->btd", y, p["proj_w"], precision) + p["proj_b"]
    h = _layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
    h = _gelu_new(_einsum("btc,cd->btd", h, p["fc_w"], precision)
                  + p["fc_b"])
    return x + _einsum("btd,dc->btc", h, p["out_w"], precision) + p["out_b"]


_STACKED = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
            "ln2_g", "ln2_b", "fc_w", "fc_b", "out_w", "out_b")


def forward(params: dict, tokens, model: dict, precision: str = "float32",
            remat: bool = False):
    """Logits ``[B, T, vocab]`` float32 for token ids ``[B, T]``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    eps = float(model["layer_norm_epsilon"])
    T = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][:T]

    def body(x, layer):
        return _block(x, layer, int(model["n_head"]), eps, precision), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = lax.scan(body, x, {k: params[k] for k in _STACKED})
    x = _layer_norm(x, params["lnf_g"], params["lnf_b"], eps)
    return _einsum("btc,vc->btv", x, params["wte"], precision)


def loss(params: dict, tokens, targets, model: dict,
         precision: str = "float32"):
    """Mean next-token cross-entropy over every position, float32."""
    logits = forward(params, tokens, model, precision, remat=True)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(picked)
