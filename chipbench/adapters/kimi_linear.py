"""The Kimi Linear family's adapter (``chipbench/README.md`` has the
contract): the module handed to ``Server``, seeded weights for the program
and the plain reference alike, the positions of a sequence, and the
operations and bytes of what the family adds.

The family is served only: no training job, so no
``train_flops_per_token`` and no ``leaf_norm_axes``; and
``decode_step_bytes(model, live_tokens)`` is left out, as in
``adapters/command.py``, because a step's bytes follow the experts it
hits beside the rows it reads and the state it multiplies
(``weight_bytes``, ``expert_bytes``, ``decode_row_bytes``,
``state_bytes``, which this cell's readers call).

**Weights by leaf.**  The program's parameters are made tensor by tensor
from ``chipbench/kimi_linear_reference.py``'s ``leaf`` (``fold_in`` of the
key) inside the engine's one jitted init and cast to their resident type
at once (``BenchModule.init_params``).  ``leaf``'s values are what bfloat16
holds but for ``FLOAT32_LEAVES`` (the router, its bias, and what makes a
KDA layer's decay and beta), which are float32 on both sides: the cast
loses nothing.  ``make_weights`` is called for the check, after the
program's state is freed: it returns the SAME values made once and kept
as bfloat16 (``kimi_linear_reference.hold``, 4.7 GB), which the
reference's ``forward`` reads back as float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import kimi_linear_reference as ref
from ray_lightning_tpu.models.kimi_linear import (
    KimiLinearConfig, KimiLinearLightningModule)

BF16 = 2
F32 = 4
#: positions to a chunk of the chunkwise form (``kda_prefill_flops``)
CHUNK = 64


def config_of(model: dict) -> KimiLinearConfig:
    names = {f.name for f in KimiLinearConfig.__dataclass_fields__.values()}
    lin = model["linear_attn_config"]
    flat = {**model, "kda_layers": tuple(lin["kda_layers"]),
            "full_attn_layers": tuple(lin["full_attn_layers"]),
            "kda_num_heads": lin["num_heads"], "kda_head_dim": lin["head_dim"],
            "short_conv_kernel_size": lin["short_conv_kernel_size"]}
    return KimiLinearConfig(**{k: v for k, v in flat.items()
                               if k in names - {"dtype"}})


def module(model: dict, seed: int, job: "dict | None" = None):
    return BenchModule(model, seed)


def context(model: dict) -> int:
    """Positions a sequence of the cell may have: the width of the
    check's rows, the bound of the bucket ladder and the rows a slot
    holds in a full-attention layer (``served_positions``)."""
    return int(model.get("served_positions", model["model_max_length"]))


def make_weights(model: dict, key):
    """What the reference's ``forward`` is handed: every tensor of the key,
    made once and kept as bfloat16 holds it (module docstring)."""
    return ref.hold(model, key)


class BenchModule(KimiLinearLightningModule):

    def __init__(self, model: dict, seed: int):
        super().__init__(config_of(model))
        self.bench_model = dict(model)
        self.bench_seed = int(seed)

    def init_params(self, rng, batch):
        """The program hands its init key in; the weights are a function
        of it (``module.init_key`` tells the reference which key)."""
        return {"params": program_tree(self.bench_model, rng)}


def program_tree(model: dict, key, dtype=jnp.bfloat16) -> dict:
    """``models/kimi_linear.py KimiLinear``'s parameter tree in its
    resident types, every tensor ``kimi_linear_reference.leaf``'s values:
    ``dtype`` (bfloat16; tests ask for float32), and float32 for
    ``ref.FLOAT32_LEAVES``.  A block's HELD routed experts are stacked
    ``[held, ...]`` (one ``leaf`` an expert by its published number, under
    ``vmap``); ``ukv_w`` [r, H (dn + dv)] is split into the keys' ``uk``
    [r, H, dn] and the values' ``uv`` [r, H, dv]."""
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
        return jax.vmap(one)(z["offset"] + jnp.arange(z["held"]))

    tree = {"wte": {"embedding": w("wte")}, "ln_f": {"scale": w("lnf_g")},
            "lm_head": w("head_w")}
    for i in range(z["L"]):
        blk = {"ln_attn": {"scale": w("ln_attn_g", i)},
               "ln_mlp": {"scale": w("ln_mlp_g", i)}}
        if i in z["kda"]:
            blk["attn"] = {
                **{n: kernel(f"kda_{n}_w", i) for n in "qkvo"},
                **{"conv_" + n: w(f"conv_{n}_w", i) for n in "qkv"},
                "a1": w("a1_w", i), "a2": w("a2_w", i),
                "A_log": w("A_log", i), "dt_bias": w("dt_bias", i),
                "b": w("b_w", i), "g1": kernel("g1_w", i),
                "g2": kernel("g2_w", i), "g_bias": w("g_bias", i),
                "o_norm": w("o_norm_g", i)}
        else:
            ukv = w("ukv_w", i).reshape(z["r"], H, dn + dv)
            blk["attn"] = {
                "q": kernel("mla_q_w", i), "dkv": kernel("dkv_w", i),
                "o": kernel("mla_o_w", i),
                "kv_norm": {"scale": w("kv_norm_g", i)},
                "uk": ukv[..., :dn], "uv": ukv[..., dn:]}
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

def _kda_params(z: dict) -> "tuple[int, int]":
    """``(bfloat16, float32)`` parameters of one KDA sublayer."""
    d, P, K = z["d"], z["P"], z["K"]
    return (4 * d * P + 3 * P * z["taps"] + d * K + K * P + P + K,
            d * K + K * P + z["Hk"] + P + d * z["Hk"])


def _mla_params(z: dict) -> int:
    d, H = z["d"], z["H"]
    return (d * H * (z["dn"] + z["dr"]) + d * (z["r"] + z["dr"]) + z["r"]
            + z["r"] * H * (z["dn"] + z["dv"]) + H * z["dv"] * d)


def head_bytes(model: dict) -> float:
    """Bytes the head of one decode run cannot avoid: the held rows of the
    untied table, ``vocab x d`` once, bf16 (94 MB).  The float32 logits
    are NOT counted, as ``chipbench/adapters/zaya.py head_bytes`` leaves
    them out: a program that writes and re-reads them reads a lower
    share."""
    z = ref.sizes(model)
    return float(BF16 * z["V"] * z["d"])


def weight_bytes(model: dict) -> int:
    """Every parameter a decode step reads whatever it routes: both kinds
    of attention sublayer (a KDA layer's decay and beta parameters in
    float32), the leading dense layers' MLPs, the shared experts, the
    norms' gains, the head once (the embedding is gathered: a row a slot
    is nothing), and in float32 the routers with their biases."""
    z = ref.sizes(model)
    d, F = z["d"], z["F"]
    low, high = _kda_params(z)
    n_dense = min(z["dense"], z["L"])
    return (len(z["kda"]) * (BF16 * low + F32 * high)
            + len(z["full"]) * BF16 * _mla_params(z)
            + z["L"] * BF16 * 2 * d
            + n_dense * BF16 * 3 * d * z["F_dense"]
            + (z["L"] - n_dense) * (BF16 * 3 * d * z["shared"] * F
                                    + F32 * (d * z["E"] + z["E"]))
            + BF16 * (d + d * z["V"]))


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
    (``kv_lora_rank + qk_rope_head_dim`` values, 1,152 B in bf16, whatever
    the lanes it is padded to in memory) of every position the occupied
    slots see, in the full-attention layers only.  ``live_rows`` is
    ``Scheduler.stats()``'s: the mean over ALL the layers summed over the
    slots (a KDA layer counts no row), so times all the layers it is the
    rows read."""
    z = ref.sizes(model)
    return BF16 * (z["r"] + z["dr"]) * z["L"] * live_rows


def prefill_attn_flops(model: dict, length: int) -> float:
    """Operations of the scores a prompt of ``length`` positions cannot
    avoid in the full-attention layers: q.k over ``dn + dr`` and p.v over
    ``dv`` for the visible pairs (the triangle), every head."""
    z = ref.sizes(model)
    pairs = length * (length + 1) // 2
    return 2.0 * (z["dn"] + z["dr"] + z["dv"]) * z["H"] * len(z["full"]) \
        * pairs


def state_bytes(model: dict, slots: float) -> float:
    """Bytes a decode run cannot avoid moving for the KDA layers' state of
    ``slots`` occupied slots: a layer's float32 matrix ``[P, K]`` read
    once and written once, the three earlier positions of the
    convolutions' inputs read and this position's written (``3 P`` float32
    values each), and the stamp read and written.  One read and one write,
    so no implementation can pass 100 % of it."""
    z = ref.sizes(model)
    P = z["P"]
    a_layer = F32 * (2 * P * z["K"] + z["taps"] * 3 * P + 2)
    return float(len(z["kda"]) * a_layer * slots)


def kda_prefill_flops(model: dict, length: int) -> float:
    """Operations of the chunkwise delta rule for a prompt of ``length``
    positions, chunks of ``C`` = 64 (``ceil(length / C)`` of them), a head
    of key width ``K`` and value width ``V`` = ``K``, counted from the
    algorithm and not from what implements it, multiply and add apart:

    - the decayed products inside a chunk, the keys' below the diagonal
      and the queries' on and below it: ``C^2 K`` multiply-adds together,
      ``2 C^2 K`` operations;
    - the unit lower triangular system for the chunk's corrected keys and
      values (``K + V`` columns) by forward substitution: ``C (C - 1) / 2``
      multiply-adds a column, ``C^2 (K + V)`` operations (an
      implementation that inverts the matrix first does more);
    - against the carried state: ``U = U0 - W S`` (``2 C K V``), the
      read-out ``Q~ S`` (``2 C K V``) and ``A^q U`` (``C^2 V``), the
      state's update ``K^^T U`` (``2 C K V``).

    ``C^2 (3 K + 2 V) + 6 C K V`` a chunk a head: 8.9 M at the published
    sizes, times the heads, the chunks and the KDA layers."""
    z = ref.sizes(model)
    K = V = z["K"]
    chunks = -(-int(length) // CHUNK)
    a_chunk = CHUNK * CHUNK * (3 * K + 2 * V) + 6 * CHUNK * K * V
    return float(a_chunk * z["Hk"] * chunks * len(z["kda"]))


__all__ = ["BenchModule", "config_of", "context", "decode_row_bytes",
           "expert_bytes", "expert_flops", "head_bytes", "kda_prefill_flops",
           "make_weights", "module", "prefill_attn_flops", "program_tree",
           "state_bytes", "weight_bytes"]
