"""Seeded weights, made by the benchmark and given to both sides.

The program under test and the plain reference each get the weights of
``make_weights(model, key)`` for the same key: the program through
``BenchModule``'s ``init_params`` (inside its own jitted init, on its own
device, then cast to the type it serves or trains in), the reference
directly in float32.  So neither takes anything the other has made.

Layout: one flat dict, the blocks' tensors stacked on a leading layer
axis.  Values follow GPT-2's initialisation (normal, std 0.02, residual
projections scaled by 1/sqrt(2 * layers)) except that biases and
LayerNorm gains are perturbed too (std 0.02), so that a mistake in any
of them shows in the comparison.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

STD = 0.02


def shapes(model: dict) -> dict:
    L, d = int(model["n_layer"]), int(model["n_embd"])
    V, T = int(model["vocab_size"]), int(model["n_positions"])
    return {
        "wte": (V, d), "wpe": (T, d),
        "ln1_g": (L, d), "ln1_b": (L, d),
        "qkv_w": (L, d, 3 * d), "qkv_b": (L, 3 * d),
        "proj_w": (L, d, d), "proj_b": (L, d),
        "ln2_g": (L, d), "ln2_b": (L, d),
        "fc_w": (L, d, 4 * d), "fc_b": (L, 4 * d),
        "out_w": (L, 4 * d, d), "out_b": (L, d),
        "lnf_g": (d,), "lnf_b": (d,),
    }


def make_weights(model: dict, key) -> dict:
    """Float32 weights from a PRNG key (``module.init_key``); traceable.
    The key is an argument, never a constant of the program: a jitted
    caller compiles once for all seeds, and the device makes the weights
    in one call.  The barrier keeps XLA from generating a stacked tensor
    again inside every consumer that slices a layer out of it."""
    residual = STD / math.sqrt(2 * int(model["n_layer"]))
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(model).items())):
        std = residual if name in ("proj_w", "out_w") else STD
        w = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        out[name] = w + 1.0 if name.endswith("_g") else w
    return jax.lax.optimization_barrier(out)
