"""A second model family, made of new files only: the proof that the
harness takes one (``test_adapter.py`` rehearses a train and a serve cell
of it with no edit of a file that existed).  It is no model of its own:
it wraps the program's ``GPTLightningModule``, but under other key names
(``hidden_size / num_hidden_layers / num_attention_heads /
max_position_embeddings / layer_norm_eps``, as most published configs
spell them) and with an unstacked weight layout (one flat dict, one
entry per layer and tensor), so nothing of the GPT-2 adapter fits it.
The contract it keeps is in ``chipbench/README.md``."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ray_lightning_tpu.core.data import ArrayDataset, DataLoader
from ray_lightning_tpu.models.gpt import GPTConfig, GPTLightningModule

from chipbench import generator

STD = 0.02
# reference-layout tensor of a layer -> its place in the program's tree
_LAYER = {
    "input_norm.weight": ("ln1", "scale"), "input_norm.bias": ("ln1", "bias"),
    "attn.qkv.weight": ("attn", "qkv", "kernel"),
    "attn.qkv.bias": ("attn", "qkv", "bias"),
    "attn.out.weight": ("attn", "proj", "kernel"),
    "attn.out.bias": ("attn", "proj", "bias"),
    "post_norm.weight": ("ln2", "scale"), "post_norm.bias": ("ln2", "bias"),
    "mlp.up.weight": ("mlp", "fc", "kernel"),
    "mlp.up.bias": ("mlp", "fc", "bias"),
    "mlp.down.weight": ("mlp", "out", "kernel"),
    "mlp.down.bias": ("mlp", "out", "bias"),
}


def _sizes(model: dict):
    return (int(model["num_hidden_layers"]), int(model["hidden_size"]),
            int(model["vocab_size"]), int(model["max_position_embeddings"]))


def context(model: dict) -> int:
    return int(model["max_position_embeddings"])


def module(model: dict, seed: int, job: "dict | None" = None):
    return Family2Module(model, seed, job)


class Family2Module(GPTLightningModule):

    def __init__(self, model: dict, seed: int, job: "dict | None" = None):
        job = job or {}
        opt = job.get("optimizer", {})
        L, d, V, T = _sizes(model)
        super().__init__(
            GPTConfig(vocab_size=V, block_size=T, n_layer=L, n_embd=d,
                      n_head=int(model["num_attention_heads"]), remat=False),
            lr=float(opt.get("lr", 3e-4)),
            weight_decay=float(opt.get("weight_decay", 0.01)),
            warmup_steps=int(opt.get("warmup_steps", 10)),
            batch_size=int(job.get("global_batch", 8)))
        self.f2_model, self.f2_seed = dict(model), int(seed)
        self.f2_rows = int(job.get("global_batch", 8)) \
            * int(job.get("steps_per_epoch", 1))
        self.f2_token_ids = int(job.get("token_ids_below", V))

    def init_params(self, rng, batch):
        return {"params": to_program_tree(make_weights(self.f2_model, rng))}

    def train_rows(self):
        return generator.lm_rows(self.f2_rows, self.config.block_size,
                                 self.f2_token_ids, self.f2_seed)

    def train_dataloader(self):
        x, y = self.train_rows()
        return DataLoader(ArrayDataset(np.asarray(x), np.asarray(y)),
                          batch_size=self.batch_size, drop_last=True)


def shapes(model: dict) -> dict:
    L, d, V, T = _sizes(model)
    layer = {"input_norm.weight": (d,), "input_norm.bias": (d,),
             "attn.qkv.weight": (d, 3 * d), "attn.qkv.bias": (3 * d,),
             "attn.out.weight": (d, d), "attn.out.bias": (d,),
             "post_norm.weight": (d,), "post_norm.bias": (d,),
             "mlp.up.weight": (d, 4 * d), "mlp.up.bias": (4 * d,),
             "mlp.down.weight": (4 * d, d), "mlp.down.bias": (d,)}
    out = {"embed.tokens": (V, d), "embed.positions": (T, d),
           "final_norm.weight": (d,), "final_norm.bias": (d,)}
    for i in range(L):
        out.update({f"layers.{i}.{k}": s for k, s in layer.items()})
    return out


def make_weights(model: dict, key) -> dict:
    residual = STD / math.sqrt(2 * int(model["num_hidden_layers"]))
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(model).items())):
        std = residual if name.endswith(("attn.out.weight",
                                         "mlp.down.weight")) else STD
        w = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        out[name] = w + 1.0 if name.endswith("norm.weight") else w
    return out


def to_program_tree(w: dict) -> dict:
    tree = {"wte": {"embedding": w["embed.tokens"]},
            "wpe": w["embed.positions"],
            "ln_f": {"scale": w["final_norm.weight"],
                     "bias": w["final_norm.bias"]}}
    for name, leaf in w.items():
        if not name.startswith("layers."):
            continue
        _, i, rest = name.split(".", 2)
        node = tree.setdefault(f"h{i}", {})
        *path, last = _LAYER[rest]
        for part in path:
            node = node.setdefault(part, {})
        node[last] = leaf
    return tree


def leaf_norm_axes(name: str, array):
    return None     # every tensor of this layout is one leaf of the tree


def train_flops_per_token(model: dict, seq_len: int) -> float:
    L, d, V, _ = _sizes(model)
    return 6.0 * (12 * L * d * d + V * d) + 6.0 * L * int(seq_len) * d


def decode_step_bytes(model: dict, live_tokens: float) -> float:
    L, d, V, T = _sizes(model)
    weights = 2 * (L * (12 * d * d + 13 * d) + V * d + T * d + 2 * d)
    return weights + 2 * 2 * L * d * live_tokens
