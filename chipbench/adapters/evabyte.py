"""The EvaByte family's adapter (``chipbench/README.md`` has the contract):
the module handed to ``Server``, seeded weights for the program and the
plain reference alike, the map to the program's parameter tree, the
positions of a sequence, and the bytes a decode step cannot avoid.

The family is served only: no training job, so no
``train_flops_per_token`` and no ``leaf_norm_axes``; and
``decode_step_bytes(model, live_tokens)`` is left out because a step's
bytes follow the cache ROWS a slot reads, not its positions
(``decode_row_bytes``, which this cell's own readers call).

Weights: one flat dict, the blocks' tensors stacked on a leading layer
axis.  Matrices are normal with the published ``init_std``, the two
residual projections scaled by 1/sqrt(2 * layers); the norms' gains
(added to 1, ``norm_add_unit_offset``) are perturbed (std 0.02) so that
a mistake in them shows; ``phi`` and ``mu`` are normal, clipped to
[-1, 1], times ``head_dim ** -0.5`` (the configuration's ``assumed``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.evabyte import (
    EvaByteConfig, EvaByteLightningModule)

BF16 = 2


def config_of(model: dict) -> EvaByteConfig:
    names = {f.name for f in EvaByteConfig.__dataclass_fields__.values()}
    return EvaByteConfig(**{k: v for k, v in model.items()
                            if k in names - {"dtype"}})


def module(model: dict, seed: int, job: "dict | None" = None):
    return BenchModule(model, seed)


def context(model: dict) -> int:
    """Positions a sequence of the cell may have: the width of the
    check's rows and the bound on the bucket ladder.  ``served_positions``
    (the longest prompt plus the longest answer of the traffic) where
    the configuration gives it, so that the reference does not compute
    20k positions that no request has; the cache is sized for
    ``max_position_embeddings`` either way (models/evabyte.py)."""
    return int(model.get("served_positions",
                         model["max_position_embeddings"]))


class BenchModule(EvaByteLightningModule):

    def __init__(self, model: dict, seed: int):
        super().__init__(config_of(model))
        self.bench_model = dict(model)
        self.bench_seed = int(seed)

    def init_params(self, rng, batch):
        """The program hands its init key in; the weights are a function
        of it (``module.init_key`` tells the reference which key)."""
        return {"params": to_program_tree(
            make_weights(self.bench_model, rng))}


# -- seeded weights ------------------------------------------------------------

def shapes(model: dict) -> dict:
    c = config_of(model)
    L, d, F = c.num_hidden_layers, c.hidden_size, c.intermediate_size
    H, D = c.num_attention_heads, c.head_dim
    return {
        "wte": (c.vocab_size, d),
        "ln1_g": (L, d), "ln2_g": (L, d), "lnf_g": (d,),
        "q_w": (L, d, d), "k_w": (L, d, d), "v_w": (L, d, d),
        "o_w": (L, d, d), "phi": (L, H, D), "mu": (L, H, D),
        "gate_w": (L, d, F), "up_w": (L, d, F), "down_w": (L, F, d),
        "head_w": (d, c.num_pred_heads * c.vocab_size),
    }


def make_weights(model: dict, key) -> dict:
    """Float32 weights from a PRNG key; traceable, the key an argument."""
    c = config_of(model)
    residual = c.init_std / math.sqrt(2 * c.num_hidden_layers)
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(model).items())):
        w = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32)
        if name in ("phi", "mu"):
            w = jnp.clip(w, -1.0, 1.0) * c.head_dim ** -0.5
        elif name.endswith("_g"):
            w = 0.02 * w
        else:
            w = (residual if name in ("o_w", "down_w") else c.init_std) * w
        out[name] = w
    return jax.lax.optimization_barrier(out)


def to_program_tree(w: dict) -> dict:
    """The reference layout (stacked blocks) as ``models/evabyte.py
    EvaByte``'s flax parameter tree."""
    tree = {"wte": {"embedding": w["wte"]},
            "ln_f": {"scale": w["lnf_g"]},
            "lm_head": {"kernel": w["head_w"]}}
    for i in range(w["q_w"].shape[0]):
        tree[f"h{i}"] = {
            "ln1": {"scale": w["ln1_g"][i]},
            "ln2": {"scale": w["ln2_g"][i]},
            "attn": {**{n: {"kernel": w[n + "_w"][i]} for n in "qkvo"},
                     "phi": w["phi"][i], "mu": w["mu"][i]},
            "mlp": {n: {"kernel": w[n + "_w"][i]}
                    for n in ("gate", "up", "down")}}
    return tree


# -- bytes from the shapes ---------------------------------------------------------

def weight_bytes(model: dict) -> int:
    """Every parameter a decode step reads, in bf16: the blocks, the
    final norm, and of the two tables what a next-byte step touches
    (one embedding row a slot is nothing; head 0's columns of the
    output head)."""
    c = config_of(model)
    d = c.hidden_size
    per_block = 4 * d * d + 3 * d * c.intermediate_size + 2 * d \
        + 2 * c.num_attention_heads * c.head_dim
    return BF16 * (c.num_hidden_layers * per_block + d + d * c.vocab_size)


def decode_row_bytes(model: dict, live_rows: float) -> float:
    """Bytes the decode attention cannot avoid reading: the key and the
    value of every cache row the occupied slots see (``live_rows``,
    summed over slots: ``Scheduler.stats()``), in every layer, bf16."""
    c = config_of(model)
    return 2 * BF16 * c.hidden_size * c.num_hidden_layers * live_rows
