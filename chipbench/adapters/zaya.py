"""The ZAYA1 family's adapter (``chipbench/README.md`` has the contract):
the module handed to ``Server``, seeded weights for the program and the
plain reference alike, the positions of a sequence, and the operations
and bytes of what the family adds.

The family is served only: no training job, so no
``train_flops_per_token``; and ``decode_step_bytes(model, live_tokens)``
is left out, as in ``adapters/command.py``, because a step's bytes follow
the experts it hits beside the rows it reads (``weight_bytes``,
``expert_bytes``, ``decode_row_bytes``, which this cell's readers call).

**Weights by leaf.**  The program's parameters are made tensor by tensor
from ``chipbench/zaya_reference.py``'s ``leaf`` (``fold_in`` of the key)
inside the engine's one jitted init and cast to their resident type at
once (``BenchModule.init_params``).  ``leaf``'s values are what bfloat16
holds but for ``FLOAT32_LEAVES`` (the router whole, ``tau``, the residual
vectors), which are float32 on both sides: the cast loses nothing.
``make_weights`` is called for the check, after the program's state is
freed: it returns the SAME values made once and kept as bfloat16
(``zaya_reference.hold``, 5.2 GB), which the reference's ``forward`` reads
back as float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import zaya_reference as ref
from ray_lightning_tpu.models.zaya import ZayaConfig, ZayaLightningModule

BF16 = 2
F32 = 4


def config_of(model: dict) -> ZayaConfig:
    names = {f.name for f in ZayaConfig.__dataclass_fields__.values()}
    rope = model.get("rope_parameters", {}).get("hybrid", {})
    flat = {"rope_theta": rope.get("rope_theta"), **model}
    return ZayaConfig(**{k: v for k, v in flat.items()
                         if k in names - {"dtype"} and v is not None})


def module(model: dict, seed: int, job: "dict | None" = None):
    return BenchModule(model, seed)


def context(model: dict) -> int:
    """Positions a sequence of the cell may have: the width of the
    check's rows, the bound of the bucket ladder and the rows a slot
    holds (``served_positions``)."""
    return int(model.get("served_positions",
                         model["max_position_embeddings"]))


def make_weights(model: dict, key):
    """What the reference's ``forward`` is handed: every tensor of the key,
    made once and kept as bfloat16 holds it (module docstring)."""
    return ref.hold(model, key)


def leaf_norm_axes(name: str, array):
    """No training job compares a leaf's norm; all axes."""
    return None


class BenchModule(ZayaLightningModule):

    def __init__(self, model: dict, seed: int):
        super().__init__(config_of(model))
        self.bench_model = dict(model)
        self.bench_seed = int(seed)

    def init_params(self, rng, batch):
        """The program hands its init key in; the weights are a function
        of it (``module.init_key`` tells the reference which key)."""
        return {"params": program_tree(self.bench_model, rng)}


def to_program_tree(tree, model: dict, dtype=jnp.bfloat16) -> dict:
    """``models/zaya.py Zaya``'s parameter tree from ``tree(name, layer=-1,
    expert=None) -> float32 tensor`` in the reference's layout: every
    tensor in ``dtype`` (bfloat16; tests ask for float32), and float32 for
    ``ref.FLOAT32_LEAVES``.  A layer's experts are stacked ``[experts,
    ...]``."""
    z = ref.sizes(model)

    def w(name, layer=-1):
        a = tree(name, layer)
        return a if name in ref.FLOAT32_LEAVES else a.astype(dtype)

    def experts(name, layer):
        return jax.vmap(lambda e: tree(name, layer, e).astype(dtype))(
            jnp.arange(z["E"]))

    out = {"wte": {"embedding": w("wte")}, "ln_f": {"scale": w("lnf_g")}}
    for i in range(z["L"]):
        out[f"h{i}"] = {
            "ln_attn": {"scale": w("ln_attn_g", i)},
            "ln_mlp": {"scale": w("ln_mlp_g", i)},
            "attn": {
                **{n: w(n + "_w", i) for n in ("q", "k", "v1", "v2", "o")},
                **{n: w(n, i) for n in ("conv0_w", "conv0_b", "conv1_w",
                                        "conv1_b", "tau")}},
            **{f"res_{which}": {
                f"{p[0]}_{p[1]}": w(f"res_{which}_{p}", i)
                for p in ("ar", "br", "af", "bf")}
               for which in ("attn", "mlp")},
            "router": {
                **{n: w("router_" + n, i)
                   for n in ("proj", "gamma", "norm", "w1", "w2", "w3")},
                "bias": w("router_b", i)},
            "moe": {n: experts(n + "_w", i)
                    for n in ("gate", "up", "down")}}
    return out


def program_tree(model: dict, key, dtype=jnp.bfloat16) -> dict:
    """The program's parameters from the key: ``zaya_reference.leaf``'s
    values, tensor by tensor."""
    return to_program_tree(
        lambda name, layer=-1, expert=None: ref.leaf(
            model, key, name, layer, expert), model, dtype)


# -- operations and bytes from the shapes ---------------------------------------

def routed_sublayers(model: dict) -> int:
    """Expert sublayers a token passes: one a layer."""
    return ref.sizes(model)["L"]


def head_bytes(model: dict) -> float:
    """Bytes the tied head of one decode run cannot avoid: the table's
    ``vocab x d`` once, bf16.  (The float32 logits, 134 MB at 128 slots,
    are NOT counted: a program that takes the arg-max inside the product's
    epilogue never writes them, and the described compile of the decode
    program holds 27 MB of temporaries in all; one that does write them
    reads a lower share.)"""
    z = ref.sizes(model)
    return float(BF16 * z["V"] * z["d"])


def weight_bytes(model: dict) -> int:
    """Every parameter a decode step reads whatever it routes: attention
    (the five projections, both convolutions, bf16), the norms' gains, the
    table as the head once (the embedding is gathered: a row a slot is
    nothing), and in float32 the routers, ``tau`` and the residual
    vectors."""
    z = ref.sizes(model)
    d, D, C, R = z["d"], z["D"], z["C"], z["R"]
    attn = d * (C + 2 * D) + z["H"] * D * d + 3 * C \
        + (z["H"] + z["G"]) * 2 * D * D
    router = d * R + 1 + R + 2 * R * R + (R + 1) * (z["E"] + 1)
    layer = BF16 * (attn + 2 * d) + F32 * (router + z["G"] + 8 * d)
    return z["L"] * layer + BF16 * (d + d * z["V"])


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
    """Bytes the decode attention cannot avoid reading: the keys' and the
    values' ``G D`` lanes (1,024 B in bf16 at the published widths) of
    every position the occupied slots see, every layer.  ``live_rows`` is
    ``Scheduler.stats()``'s, the mean over the layers summed over the
    slots (a row a position in every layer)."""
    z = ref.sizes(model)
    return BF16 * 2 * z["G"] * z["D"] * z["L"] * live_rows


__all__ = ["BenchModule", "config_of", "context", "decode_row_bytes",
           "expert_bytes", "expert_flops", "head_bytes", "leaf_norm_axes",
           "make_weights", "module", "program_tree",
           "routed_sublayers", "to_program_tree", "weight_bytes"]
