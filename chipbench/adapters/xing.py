"""The Xing4.0 family's adapter (``chipbench/README.md`` has the
contract): the module handed to ``Server``, seeded weights for the program
and the plain reference alike, the positions of a sequence, and the
operations and bytes of what the family adds.

The family is served only: no training job, so no
``train_flops_per_token`` and no ``leaf_norm_axes``; and
``decode_step_bytes(model, live_tokens)`` is left out, as in
``adapters/command.py``, because a step's bytes follow the experts it
hits beside the rows it reads (``weight_bytes``, ``expert_bytes``,
``decode_row_bytes``, which this cell's readers call).

**Weights by leaf.**  The float32 weights of the cut are 16.2 GB: no chip
holds them at once, and nothing holds them beside the program.  The
program's parameters are made tensor by tensor from
``chipbench/xing_reference.py``'s ``leaf`` (``fold_in`` of the key) inside
the engine's one jitted init and cast to their resident type at once
(``BenchModule.init_params``).  ``leaf``'s values are what bfloat16 holds
but for the router, its bias and the hyper-connection parameters, which
are float32 on both sides: the cast loses nothing.  ``make_weights`` is
called for the check, after the program's state is freed: it returns the
SAME values made once and kept as bfloat16 (``xing_reference.hold``,
7.2 GB: all but the table), which the reference's ``forward`` reads back as float32; handed
the bare key, ``forward`` makes every tensor again in every call.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import xing_reference as ref
from ray_lightning_tpu.models.xing import XingConfig, XingLightningModule

BF16 = 2
F32 = 4


def config_of(model: dict) -> XingConfig:
    names = {f.name for f in XingConfig.__dataclass_fields__.values()}
    flat = {**model, **{"rope_" + k: v
                        for k, v in model["rope_scaling"].items()}}
    return XingConfig(**{k: v for k, v in flat.items()
                         if k in names - {"dtype"}})


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


class BenchModule(XingLightningModule):

    def __init__(self, model: dict, seed: int):
        super().__init__(config_of(model))
        self.bench_model = dict(model)
        self.bench_seed = int(seed)

    def init_params(self, rng, batch):
        """The program hands its init key in; the weights are a function
        of it (``module.init_key`` tells the reference which key)."""
        return {"params": program_tree(self.bench_model, rng)}


def program_tree(model: dict, key, dtype=jnp.bfloat16) -> dict:
    """``models/xing.py Xing``'s parameter tree in its resident types,
    every tensor ``xing_reference.leaf``'s values: ``dtype`` (bfloat16;
    tests ask for float32), and float32 for ``ref.FLOAT32_LEAVES``.  A
    block's routed experts are stacked ``[experts, ...]`` (one ``leaf``
    an expert, under ``vmap``); ``ukv_w`` [r, H (dn + dv)] is split into
    the keys' ``uk`` [r, H, dn] and the values' ``uv`` [r, H, dv]."""
    z = ref.sizes(model)
    H, dn, dv = z["H"], z["dn"], z["dv"]

    def w(name, layer=-1):
        a = ref.leaf(model, key, name, layer)
        return a if name in ref.FLOAT32_LEAVES else a.astype(dtype)

    def kernel(name, layer):
        return {"kernel": w(name, layer)}

    def experts(name, layer):
        one = lambda e: ref.leaf(  # noqa: E731
            model, key, name, layer, e).astype(dtype)
        return jax.vmap(one)(jnp.arange(z["E"]))

    def hyper(which, layer):
        return {"hc_" + p: w(f"hc_{which}_{p}", layer)
                for p in ("phi", "b", "a")}

    tree = {"wte": {"embedding": w("wte")}, "ln_f": {"scale": w("lnf_g")},
            "lm_head": w("head_w")}
    for i in range(z["L"]):
        ukv = w("ukv_w", i).reshape(z["r"], H, dn + dv)
        blk = {
            "hc_attn": hyper("attn", i), "hc_mlp": hyper("mlp", i),
            "ln_attn": {"scale": w("ln_attn_g", i)},
            "ln_mlp": {"scale": w("ln_mlp_g", i)},
            "attn": {
                "dq": kernel("dq_w", i), "uq": kernel("uq_w", i),
                "dkv": kernel("dkv_w", i), "o": kernel("o_w", i),
                "q_norm": {"scale": w("q_norm_g", i)},
                "kv_norm": {"scale": w("kv_norm_g", i)},
                "uk": ukv[..., :dn], "uv": ukv[..., dn:]}}
        if i < z["dense"]:
            blk["mlp"] = {n: kernel(f"mlp_{n}_w", i)
                          for n in ("gate", "up", "down")}
        else:
            blk["moe"] = {
                "router": w("router_w", i), "bias": w("router_b", i),
                **{n: experts(n + "_w", i) for n in ("gate", "up", "down")},
                **{"shared_" + n: kernel(f"shared_{n}_w", i)
                   for n in ("gate", "up", "down")}}
        tree[f"h{i}"] = blk
    return tree


# -- operations and bytes from the shapes ---------------------------------------

def _attention_params(z: dict) -> int:
    d, H = z["d"], z["H"]
    return (d * z["rq"] + z["rq"] * H * (z["dn"] + z["dr"])
            + d * (z["r"] + z["dr"]) + z["r"] * H * (z["dn"] + z["dv"])
            + H * z["dv"] * d + z["rq"] + z["r"])


def weight_bytes(model: dict) -> int:
    """Every parameter a decode step reads whatever it routes: attention
    (bf16), the leading dense blocks' MLPs, the shared experts, the norms'
    gains, the head once (the embedding is gathered: a row a slot is
    nothing), and in float32 the routers with their biases and the
    hyper-connection parameters."""
    z = ref.sizes(model)
    d, F, n = z["d"], z["F"], z["n"]
    hyper = 2 * (n * d * n * (n + 2) + n * (n + 2) + 3)
    every = BF16 * (_attention_params(z) + 2 * d) + F32 * hyper
    dense = BF16 * 3 * d * z["F_dense"]
    moe = BF16 * 3 * d * z["shared"] * F + F32 * (d * z["E"] + z["E"])
    n_dense = min(z["dense"], z["L"])
    return z["L"] * every + n_dense * dense + (z["L"] - n_dense) * moe \
        + BF16 * (d + d * z["V"])


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
    """Bytes the decode attention cannot avoid reading: the ONE latent row
    (``kv_lora_rank + qk_rope_head_dim`` values, 1,152 B in bf16 at the
    published widths, whatever the lanes it is padded to in memory) of
    every position the occupied slots see, every layer.  ``live_rows`` is
    ``Scheduler.stats()``'s, the mean over the layers summed over the
    slots (a row a position in every layer)."""
    z = ref.sizes(model)
    return BF16 * (z["r"] + z["dr"]) * z["L"] * live_rows


def prefill_attn_flops(model: dict, length: int) -> float:
    """Operations of the scores a prompt of ``length`` positions cannot
    avoid: q.k over ``dn + dr`` and p.v over ``dv`` for the visible pairs
    (the triangle, not the padded square), every head, every layer."""
    z = ref.sizes(model)
    pairs = length * (length + 1) // 2
    return 2.0 * (z["dn"] + z["dr"] + z["dv"]) * z["H"] * z["L"] * pairs


def stream_bytes(model: dict, tokens: float) -> float:
    """The least a prefill's hyper-connections move: each of the ``2 L``
    sublayers reads the ``hc_mult`` float32 streams of every valid token
    twice (for the coefficients, and to mix them) and writes them once.
    A fused sublayer moves no less; the program moves more."""
    z = ref.sizes(model)
    return 2 * z["L"] * 3 * F32 * z["n"] * z["d"] * tokens


__all__ = ["BenchModule", "config_of", "context", "decode_row_bytes",
           "expert_bytes", "expert_flops", "make_weights", "module",
           "prefill_attn_flops", "program_tree", "stream_bytes",
           "weight_bytes"]
