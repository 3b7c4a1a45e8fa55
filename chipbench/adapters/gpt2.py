"""The GPT-2 family's adapter: everything the harness has to know about
one model family, and the only file of the harness that knows GPT-2.
Both GPT-2 configurations name it (``"adapter"`` in their files);
``chipbench/README.md`` has the contract that every family's file keeps.

What is here: the module handed to the system under test
(``GPTLightningModule`` such as a user would write, its sizes from the
configuration's file, its weights from the benchmark's seed inside the
program's own jitted init, its training rows from ``generator.lm_rows``);
the seeded weights that the program and the plain reference both get,
and the map from their layout to the program's parameter tree; how many
positions the model has; and the operations and bytes its shapes require,
which ``train_mfu_pct`` and ``tput_decode_roofline`` divide by.

Weights: one flat dict, the blocks' tensors stacked on a leading layer
axis.  Values follow GPT-2's initialisation (normal, std 0.02, residual
projections scaled by 1/sqrt(2 * layers)) except that biases and
LayerNorm gains are perturbed too (std 0.02), so that a mistake in any
of them shows in the comparison.  The program gets them through
``BenchModule.init_params`` (on its own device, then cast to the type it
serves or trains in), the reference directly in float32: neither takes
anything the other has made.
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_lightning_tpu.core.data import ArrayDataset, DataLoader
from ray_lightning_tpu.models.gpt import GPTConfig, GPTLightningModule

from chipbench import generator

STD = 0.02
BF16 = 2


# -- the module --------------------------------------------------------------

def module(model: dict, seed: int, job: "dict | None" = None):
    return BenchModule(model, seed, job)


def context(model: dict) -> int:
    """Positions a sequence may have: the bucket ladder's top, the width
    of the check's rows, the length of a training row."""
    return int(model["n_positions"])


class BenchModule(GPTLightningModule):

    def __init__(self, model: dict, seed: int, job: "dict | None" = None):
        job = job or {}
        opt = job.get("optimizer", {})
        remat = job.get("remat_policy", "off")
        super().__init__(
            GPTConfig(vocab_size=int(model["vocab_size"]),
                      block_size=int(model["n_positions"]),
                      n_layer=int(model["n_layer"]),
                      n_head=int(model["n_head"]),
                      n_embd=int(model["n_embd"]),
                      remat=remat != "off", remat_policy=remat),
            lr=float(opt.get("lr", 3e-4)),
            weight_decay=float(opt.get("weight_decay", 0.01)),
            warmup_steps=int(opt.get("warmup_steps", 10)),
            batch_size=int(job.get("global_batch", 8)))
        self.bench_model = dict(model)
        self.bench_seed = int(seed)
        self.bench_rows = int(job.get("global_batch", 8)) \
            * int(job.get("steps_per_epoch", 1))
        self.bench_token_ids = int(job.get("token_ids_below",
                                           model["vocab_size"]))

    def init_params(self, rng, batch):
        """The program hands its init key in; the weights are a function
        of it (``module.init_key`` tells the reference which key that
        was)."""
        return {"params": to_program_tree(
            make_weights(self.bench_model, rng))}

    def train_rows(self):
        return generator.lm_rows(self.bench_rows, self.config.block_size,
                                 self.bench_token_ids, self.bench_seed)

    def train_dataloader(self):
        t = time.monotonic()
        x, y = self.train_rows()
        self.bench_rows_s = time.monotonic() - t
        return DataLoader(ArrayDataset(np.asarray(x), np.asarray(y)),
                          batch_size=self.batch_size, drop_last=True)


# -- seeded weights ------------------------------------------------------------

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


def to_program_tree(w: dict) -> dict:
    """The reference layout (stacked blocks) as ``models/gpt.py GPT``'s
    flax parameter tree.  Linear: gradients and updates map the same way,
    and so do per-layer norms (``leaf_norm_axes``)."""
    def dense(k, i):
        return {"kernel": w[k + "_w"][i], "bias": w[k + "_b"][i]}

    def norm(k, i):
        return {"scale": w[k + "_g"][i], "bias": w[k + "_b"][i]}

    tree = {"wte": {"embedding": w["wte"]}, "wpe": w["wpe"],
            "ln_f": {"scale": w["lnf_g"], "bias": w["lnf_b"]}}
    for i in range(w["qkv_w"].shape[0]):
        tree[f"h{i}"] = {
            "ln1": norm("ln1", i), "ln2": norm("ln2", i),
            "attn": {"qkv": dense("qkv", i), "proj": dense("proj", i)},
            "mlp": {"fc": dense("fc", i), "out": dense("out", i)}}
    return tree


def leaf_norm_axes(name: str, array):
    """The axes a norm of this reference-layout tensor is taken over, so
    that ``to_program_tree`` of the norms gives one number per leaf of
    the program's tree: all but the layer axis of a stacked tensor, every
    axis (None) of the two embedding tables and the final LayerNorm."""
    stacked = array.ndim >= 2 and name not in ("wte", "wpe")
    return tuple(range(1, array.ndim)) if stacked else None


# -- operations and bytes from the shapes --------------------------------------

def matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix product once per token:
    12 d^2 per block (QKV 3, projection 1, MLP 8) plus the tied
    embedding table, counted once, as the output head."""
    d = int(model["n_embd"])
    return 12 * int(model["n_layer"]) * d * d + int(model["vocab_size"]) * d


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Operations a training step requires per token, forward and
    backward, recomputation not counted: 6 FLOPs per matmul parameter
    (2 forward, 4 backward) plus causal attention's score and value
    products: 12 L T d for the full square, half of it under the causal
    mask, so 6 L T d."""
    return 6.0 * matmul_params(model) + 6.0 * int(model["n_layer"]) \
        * int(seq_len) * int(model["n_embd"])


def weight_bytes(model: dict) -> int:
    """Every parameter in bf16: matmul weights, biases, LayerNorms, both
    embedding tables."""
    L, d = int(model["n_layer"]), int(model["n_embd"])
    per_block = 12 * d * d + 13 * d
    return BF16 * (L * per_block + int(model["vocab_size"]) * d
                   + int(model["n_positions"]) * d + 2 * d)


def kv_bytes_per_token(model: dict) -> int:
    """K and V rows of one position over all layers, bf16."""
    return 2 * BF16 * int(model["n_layer"]) * int(model["n_embd"])


def decode_step_bytes(model: dict, live_tokens: float) -> float:
    """Bytes a decode step cannot avoid moving (decode is bytes-bound:
    every weight and every live cache row is read once per token and
    almost nothing is reused): the weights once, plus the K/V rows of
    every live context position of the occupied slots."""
    return weight_bytes(model) + kv_bytes_per_token(model) * live_tokens
