"""The Command A+ family's adapter (``chipbench/README.md`` has the
contract): the module handed to ``Server``, seeded weights for the
program and the plain reference alike, the positions of a sequence, and
the operations and bytes of what the family adds.

The family is served only: no training job, so no
``train_flops_per_token`` and no ``leaf_norm_axes``; and
``decode_step_bytes(model, live_tokens)`` is left out because a step's
bytes follow the cache ROWS a slot reads (a ring in three layers of
four) and the experts it hits, which positions do not give
(``decode_row_bytes``, ``expert_bytes``, which this cell's own readers
call).

**Weights by leaf.**  The float32 weights of the cut are 18.9 GB: no
chip holds them at once.  ``make_weights`` therefore returns the KEY (the
contract's second form) and ``chipbench/command_reference.py`` makes each
tensor from ``fold_in`` of it where it applies it (``leaf``).  The
program's parameters are the SAME values, made tensor by tensor from the
same ``leaf`` inside the engine's one jitted init and cast to their
resident type at once (``BenchModule.init_params``), so that no more
than one tensor is float32 at a time.  ``leaf``'s values are what
bfloat16 holds (a published checkpoint's), so the cast loses nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import command_reference as ref
from ray_lightning_tpu.models.command import (
    SLIDING, CommandConfig, CommandLightningModule)
from ray_lightning_tpu.ops.window_attention import visible_scores

BF16 = 2


def config_of(model: dict) -> CommandConfig:
    names = {f.name for f in CommandConfig.__dataclass_fields__.values()}
    return CommandConfig(**{k: v for k, v in model.items()
                            if k in names - {"dtype"}})


def module(model: dict, seed: int, job: "dict | None" = None):
    return BenchModule(model, seed)


def context(model: dict) -> int:
    """Positions a sequence of the cell may have: the width of the
    check's rows, the bound of the bucket ladder and the rows a slot of a
    full layer holds (``served_positions``)."""
    return int(model.get("served_positions",
                         model["max_position_embeddings"]))


def make_weights(model: dict, key):
    """The contract's second form: the key.  The reference makes each
    tensor from it where it applies it (module docstring)."""
    return key


class BenchModule(CommandLightningModule):

    def __init__(self, model: dict, seed: int):
        super().__init__(config_of(model))
        self.bench_model = dict(model)
        self.bench_seed = int(seed)

    def init_params(self, rng, batch):
        """The program hands its init key in; the weights are a function
        of it (``module.init_key`` tells the reference which key)."""
        return {"params": program_tree(self.bench_model, rng)}


def program_tree(model: dict, key, dtype=jnp.bfloat16) -> dict:
    """``models/command.py Command``'s parameter tree in its resident
    types, every tensor ``command_reference.leaf``'s values: bfloat16,
    the routers float32.  A block's routed experts are stacked ``[held,
    ...]`` (one ``leaf`` an expert, under ``vmap``).  ``dtype``: the
    resident type of everything but the routers (tests ask for float32)."""
    z = ref.sizes(model)

    def bf16(name, layer=-1):
        return ref.leaf(model, key, name, layer).astype(dtype)

    def experts(name, layer):
        one = lambda e: ref.leaf(  # noqa: E731
            model, key, name, layer, e).astype(dtype)
        return jax.vmap(one)(z["offset"] + jnp.arange(z["held"]))

    tree = {"wte": {"embedding": bf16("wte")},
            "ln_f": {"scale": bf16("lnf_g")}}
    for i in range(z["L"]):
        tree[f"h{i}"] = {
            "ln": {"scale": bf16("ln_g", i)},
            "attn": {n: {"kernel": bf16(n + "_w", i)} for n in "qkvo"},
            "moe": {
                "router": ref.leaf(model, key, "router_w", i),
                **{n: experts(n + "_w", i) for n in ("gate", "up", "down")},
                **{"shared_" + n: {"kernel": bf16(f"shared_{n}_w", i)}
                   for n in ("gate", "up", "down")}}}
    return tree


# -- operations and bytes from the shapes ---------------------------------------

def kinds_of(model: dict) -> list:
    return list(model["layer_types"][:int(model["num_hidden_layers"])])


def weight_bytes(model: dict) -> int:
    """Every parameter a decode step reads whatever it routes, in bf16:
    attention, the shared experts, the router (float32), the norms, and
    the held rows of the tied table once (the head; an embedding row a
    slot is nothing)."""
    z = ref.sizes(model)
    d, F = z["d"], z["F"]
    attn = 2 * d * z["H"] * z["D"] + 2 * d * z["G"] * z["D"]
    shared = 3 * d * z["shared"] * F
    per_block = BF16 * (attn + shared + d) + 4 * d * z["E"]
    return z["L"] * per_block + BF16 * (d + z["V"] * d)


def expert_bytes(model: dict, experts_hit: float, pairs: float) -> float:
    """Bytes the grouped products cannot avoid: the three matrices of
    every expert HIT (summed over the layers) once, and a row of ``d`` in
    and out for every pair, bf16."""
    z = ref.sizes(model)
    return BF16 * (experts_hit * 3 * z["d"] * z["F"] + pairs * 2 * z["d"])


def expert_flops(model: dict, pairs: float) -> float:
    """Operations of the grouped products for ``pairs`` token-expert
    pairs: three products of ``d x F`` a pair."""
    z = ref.sizes(model)
    return 2.0 * 3 * z["d"] * z["F"] * pairs


def decode_row_bytes(model: dict, live_rows: float) -> float:
    """Bytes the decode attention cannot avoid reading: the key and the
    value of every cache row the occupied slots see.  ``live_rows`` is
    ``Scheduler.stats()``'s, the MEAN over the layers summed over the
    slots (``live_cache_rows``: a ring's rows in a sliding layer, a row a
    position in a full one), so the layers' sum is ``L`` times it."""
    z = ref.sizes(model)
    return 2 * BF16 * z["G"] * z["D"] * z["L"] * live_rows


def prefill_attn_flops(model: dict, length: int) -> float:
    """Operations of the scores a prompt of ``length`` positions cannot
    avoid: q.k and p.v over the visible pairs (the band in a sliding
    layer, the triangle in a full one, not the padded square), every
    query head, every layer."""
    z = ref.sizes(model)
    pairs = sum(visible_scores(length, z["window"] if t == SLIDING else None)
                for t in kinds_of(model))
    return 2.0 * 2 * z["H"] * z["D"] * pairs


__all__ = ["BenchModule", "config_of", "context",
           "decode_row_bytes", "expert_bytes", "expert_flops",
           "make_weights", "module", "prefill_attn_flops", "program_tree",
           "weight_bytes"]
