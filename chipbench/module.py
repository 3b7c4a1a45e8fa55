"""The adapter between the benchmark and the system under test: the one
file that knows the program's module class and its parameter tree.

``BenchModule`` is a ``GPTLightningModule`` such as a user would write:
its sizes come from a configuration file, its weights from the
benchmark's seed (``weights.make_weights``, inside the program's own
jitted init, so the device makes them in one call and nothing is read
or shipped), its training rows from ``generator.lm_rows``.
"""

from __future__ import annotations

import time

import numpy as np

from ray_lightning_tpu.core.data import ArrayDataset, DataLoader
from ray_lightning_tpu.models.gpt import GPTConfig, GPTLightningModule

from chipbench import generator, weights


def init_key(kind: str, seed: int):
    """The key that the program hands to ``init_params`` when it is given
    ``seed=program_seed(seed)``: ``Server`` passes ``PRNGKey(seed)``
    (serve/engine.py), ``Trainer`` the first half of its split
    (core/steps.py ``build_init_fn``).  If the program changes this, the
    comparison with the reference fails loudly rather than quietly."""
    import jax
    key = jax.random.PRNGKey(program_seed(seed))
    return jax.random.split(key)[0] if kind == "train" else key


def program_seed(seed: int) -> int:
    """The driver's seeds pass 2**31; the program takes them unsigned."""
    return int(seed) % 2 ** 32


def to_program_tree(w: dict) -> dict:
    """The reference layout (stacked blocks) as ``models/gpt.py GPT``'s
    flax parameter tree.  Linear: gradients and updates map the same way."""
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


def leaves(tree) -> dict:
    """``{"h0/attn/qkv/kernel": leaf}`` for any pytree of dicts and
    named tuples."""
    import jax
    return {"/".join(str(getattr(k, "key", getattr(k, "name", k)))
                     for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


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
        of it (``init_key`` tells the reference which key that was)."""
        return {"params": to_program_tree(
            weights.make_weights(self.bench_model, rng))}

    def train_rows(self):
        return generator.lm_rows(self.bench_rows, self.config.block_size,
                                 self.bench_token_ids, self.bench_seed)

    def train_dataloader(self):
        t = time.monotonic()
        x, y = self.train_rows()
        self.bench_rows_s = time.monotonic() - t
        return DataLoader(ArrayDataset(np.asarray(x), np.asarray(y)),
                          batch_size=self.batch_size, drop_last=True)
