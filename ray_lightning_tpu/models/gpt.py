"""GPT-style decoder LM — the flagship model family, designed TPU-first.

The reference's only "big model" is pl_bolts ImageGPT consumed as an
opaque import in its sharded example
(reference: examples/ray_ddp_sharded_example.py:8); the BASELINE configs
ask for GPT-2-1.3B multi-host sharded (config #5).  This is a from-scratch
flax implementation shaped for the TPU, not a port of any torch model:

- **MXU-friendly**: all FLOPs live in large batched matmuls
  (qkv/proj/mlp, logits); compute dtype is bfloat16 with fp32 params and
  fp32 softmax accumulation.
- **Static shapes / compiler-friendly**: fixed block size, causal mask
  built with ``jnp`.tril`` at trace time, no data-dependent Python.
- **Remat**: each block can be wrapped in ``jax.checkpoint`` (HBM for
  FLOPs trade, the standard long-sequence lever).
- **Sharding-ready**: ``gpt_partition_rules()`` gives SpmdStrategy
  regex rules for 2-D (data × tensor) or (data × fsdp) meshes; the
  attention core is pluggable (``attention_impl``) so ring attention
  (sequence parallelism) and the pallas flash kernel slot in.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from ray_lightning_tpu.core.data import ArrayDataset, DataLoader
from ray_lightning_tpu.core.module import LightningModule
from ray_lightning_tpu.ops.attention import (  # noqa: F401  (re-export:
    MultiHeadAttention,           # tests and user code import the attention
    dot_product_attention,        # entry points from the model module)
    resolve_attention,
)

# back-compat alias (attention dispatch now lives in ops/attention.py)
_resolve_attention = resolve_attention


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304          # multiple of 128 → clean MXU tiling
    block_size: int = 256
    n_layer: int = 4
    n_head: int = 4
    n_embd: int = 256
    dropout: float = 0.0
    remat: bool = True
    # Which intermediates the block remat SAVES instead of recomputing
    # (jax.checkpoint_policies): "full" = nothing saveable (max memory
    # savings, max recompute); "dots" = keep matmul outputs (recompute
    # only the cheap elementwise chains); "dots_no_batch" = keep only
    # batch-free matmul outputs (≈ params-shaped, tiny);
    # "dots_moe_act" / "dots_moe" = dots plus the named MoE
    # intermediates (ops/moe.py checkpoint_names — measured SLOWER than
    # plain dots on gpt2-moe-8e, kept as documented options);
    # "off" = save everything.  The policy is THE lever of the
    # memory-bound regime (the gpt2-medium walk beside CONFIGS below).
    # ``RLT_REMAT_POLICY`` overrides at model build for A/B sweeps.
    remat_policy: str = "full"
    dtype: Any = jnp.bfloat16        # compute dtype; params stay fp32
    # "auto" | "dot" | "flash" | "ring" | "local" (ops/attention.py;
    # "local" = per-device flash/dot for manual shard_map regions)
    attention_impl: str = "auto"
    # >0: compute the LM loss with chunked_softmax_cross_entropy over this
    # many row chunks instead of full fp32 logits — the memory opt-in for
    # long-seq × large-vocab configs (ops/losses.py); 0 = fused full-vocab
    # loss (faster when the logits fit, measured on v5e)
    chunked_ce: int = 0
    # Mixture-of-Experts (ops/moe.py; beyond reference parity).  >0 swaps
    # the MLP of every ``moe_every``-th block for a routed MoEMLP whose
    # expert weights shard on the ``expert`` mesh axis.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_every: int = 2
    moe_aux_weight: float = 0.01

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head


# Named configs.  "gpt2-1p3b" is the BASELINE #5 target (GPT-2-1.3B class).
CONFIGS = {
    "tiny": GPTConfig(vocab_size=512, block_size=64, n_layer=2, n_head=2,
                      n_embd=64, remat=False),
    # remat off: B=8xT=1024 activations fit a single chip's HBM easily and
    # recompute costs ~20% steps/sec (measured v5e); larger configs below
    # keep remat for memory headroom.
    "gpt2-small": GPTConfig(block_size=1024, n_layer=12, n_head=12,
                            n_embd=768, remat=False),
    # dots_saveable: keep matmul outputs, recompute only elementwise
    # chains — measured +17% steps/s over full remat on v5e (150.3 vs
    # 177.4 ms/step device) and still fits with 6+ GB to spare; policy
    # "off" needs 18.95 GB and OOMs (pre-round walk, one v5e)
    "gpt2-medium": GPTConfig(block_size=1024, n_layer=24, n_head=16,
                             n_embd=1024, remat_policy="dots"),
    # 1.3B class: remat + chunked CE — at T=2048 the full fp32 logits
    # alone would be ~1.6GB/example-batch; the chunked loss streams them
    "gpt2-1p3b": GPTConfig(block_size=2048, n_layer=24, n_head=32,
                           n_embd=2048, chunked_ce=16),
    # MoE variants (beyond parity): routed FFN every other block, expert
    # weights sharded on the `expert` mesh axis (ops/moe.py)
    "moe-tiny": GPTConfig(vocab_size=512, block_size=64, n_layer=2,
                          n_head=2, n_embd=64, remat=False, n_experts=4),
    # dots remat beats BOTH full remat (92.7 ms) and no remat (95.3 ms)
    # here: the dispatch/combine and expert-FFN intermediates are huge,
    # and recomputing their elementwise chains is cheaper than
    # round-tripping them through HBM (pre-round walk, one v5e:
    # 80.1 ms/step, MFU 0.44 → 0.535)
    "gpt2-moe-8e": GPTConfig(block_size=1024, n_layer=12, n_head=12,
                             n_embd=768, n_experts=8,
                             remat_policy="dots"),
}


class MLP(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        h = nn.Dense(4 * cfg.n_embd, dtype=cfg.dtype, name="fc")(x)
        h = nn.gelu(h)
        h = nn.Dense(cfg.n_embd, dtype=cfg.dtype, name="out")(h)
        if cfg.dropout > 0:
            h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        return h


class Block(nn.Module):
    config: GPTConfig
    use_moe: bool = False

    @nn.compact
    def __call__(self, x, deterministic: bool = True, *,
                 decode_cache=None, positions=None, slots=None,
                 page_table=None):
        cfg = self.config
        attn = MultiHeadAttention(
            n_head=cfg.n_head, causal=True, dropout=cfg.dropout,
            dtype=cfg.dtype, attention_impl=cfg.attention_impl,
            name="attn")
        # profiler scopes (telemetry/scopes.py): the flax module names
        # attn / mlp are on the list already; the norms are ln1/ln2/ln_f
        with jax.named_scope("ln"):
            h = nn.LayerNorm(dtype=cfg.dtype, name="ln1")(x)
        new_cache = None
        if decode_cache is not None:
            # serve-plane decode: ``decode_cache`` is the whole resident
            # (k, v) plus this block's layer number; the attention
            # returns the updated buffers alongside its output
            # (ops/attention.py)
            a, new_cache = attn(h, deterministic,
                                decode_cache=decode_cache,
                                positions=positions, slots=slots,
                                page_table=page_table)
        else:
            a = attn(h, deterministic)
        with jax.named_scope("attn"):
            x = x + a    # each residual add goes with its branch
        if self.use_moe:
            from ray_lightning_tpu.ops.moe import MoEMLP
            ffn = MoEMLP(n_experts=cfg.n_experts, d_ff=4 * cfg.n_embd,
                         top_k=cfg.moe_top_k,
                         capacity_factor=cfg.moe_capacity_factor,
                         dtype=cfg.dtype, name="moe")
        else:
            ffn = MLP(cfg, name="mlp")
        with jax.named_scope("ln"):
            h = nn.LayerNorm(dtype=cfg.dtype, name="ln2")(x)
        with jax.named_scope("mlp"):
            x = x + ffn(h, deterministic)
        return x if new_cache is None else (x, new_cache)


def _remat_policy(name: str):
    """jax.checkpoint policy for a config/env name (None = save nothing,
    jax's default — the max-recompute end of the walk).  The canonical
    name → policy mapping lives in core/remat.py ``policy_object`` (the
    planner's ``configure_remat`` machinery shares it); this wrapper
    keeps the ``RLT_REMAT_POLICY`` per-model-build override, which the
    planner pins its sweep to when set (plan/candidates.py
    ``resolve_remat_options``)."""
    from ray_lightning_tpu.core.remat import policy_object
    return policy_object(os.environ.get("RLT_REMAT_POLICY") or name)


class GPT(nn.Module):
    """Decoder-only transformer; ``__call__(tokens) -> logits``.

    ``hidden()`` exposes the pre-head representation so losses can chunk
    the vocab projection (ops/losses.py) instead of materializing the
    full fp32 [B·T, V] logits tensor — at V=50k that tensor dominates
    HBM traffic in the loss.  setup-style so both methods share the
    submodules; param paths are identical to the previous compact form.
    """

    config: GPTConfig

    def setup(self):
        cfg = self.config
        self.wte = nn.Embed(cfg.vocab_size, cfg.n_embd, name="wte",
                            dtype=cfg.dtype)
        self.wpe = self.param("wpe", nn.initializers.normal(0.02),
                              (cfg.block_size, cfg.n_embd))
        block = Block
        if cfg.remat:
            # trade FLOPs for HBM: recompute block activations on
            # backward, keeping whatever the policy marks saveable
            block = nn.remat(Block, static_argnums=(2,),
                             policy=_remat_policy(cfg.remat_policy))
        self.blocks = [
            block(cfg, use_moe=(cfg.n_experts > 0
                                and i % cfg.moe_every == cfg.moe_every - 1),
                  name=f"h{i}")
            for i in range(cfg.n_layer)]
        self.ln_f = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")

    def hidden(self, idx, deterministic: bool = True):
        """Pre-head representation ``[B, T, C]`` in the compute dtype."""
        cfg = self.config
        B, T = idx.shape
        with jax.named_scope("embed"):
            x = self.wte(idx) + self.wpe[:T].astype(cfg.dtype)
        for blk in self.blocks:
            x = blk(x, deterministic)
        with jax.named_scope("ln"):
            return self.ln_f(x)

    @property
    def embedding_table(self):
        return self.wte.embedding

    def __call__(self, idx, deterministic: bool = True):
        x = self.hidden(idx, deterministic)
        # tied output head: attend promotes operands to the compute dtype
        # (bf16 on the MXU, fp32 accumulation implicit on TPU); logits
        # upcast to fp32 only for the loss softmax.
        with jax.named_scope("lm_head"):
            return self.wte.attend(x).astype(jnp.float32)

    def decode(self, tokens, positions, k_caches, v_caches,
               page_table=None, slots=None):
        """One continuous-batching decode step over ``S`` batch slots
        (the serve plane's hot program, ray_lightning_tpu/serve/).

        ``tokens`` [S] int32 — each slot's current token; ``positions``
        [S] int32 — that token's absolute position; ``k_caches`` /
        ``v_caches`` [n_layer, S, L, H*D] — the slot-indexed KV cache
        in its resident layout (serve/kvcache.py).  Writes each token's
        K/V row at ``[layer, slot, position]`` and returns ``(logits
        [S, V] fp32, k_caches, v_caches)``: the buffers that come back
        are the ones that went in, one row a slot and layer newer.
        Traces with STATIC shapes regardless of which slots are live —
        in-flight request insertion/eviction happens by slot index,
        never by re-trace.

        Use through ``configure_decode_model()`` (remat/dropout off);
        MoE configs are rejected by the serve engine (token routing is
        batch-shaped, unsupported in the decode path).  ``page_table``
        ([S, pages_per_slot] int32, serve/fleet/pages.py) rides down to
        ``cached_attention`` for the paged flash-decode kernel; ``None``
        keeps the slot-contiguous layout.  ``slots`` ([B] int32) makes
        ``tokens`` / ``positions`` a batch of B rows that live in cache
        slots ``slots[b]`` and touch no other (the one-row suffix
        program, core/steps.py ``build_suffix_step``); ``None`` is the
        decode step proper: row s is slot s.
        """
        cfg = self.config
        with jax.named_scope("embed"):
            x = self.wte(tokens[:, None])
            x = x + jnp.take(self.wpe, positions,
                             axis=0)[:, None, :].astype(cfg.dtype)
        x, k_caches, v_caches = self._cached_blocks(
            x, positions, k_caches, v_caches, page_table, slots)
        with jax.named_scope("lm_head"):
            logits = self.wte.attend(x).astype(jnp.float32)
        return logits[:, 0], k_caches, v_caches

    def _cached_blocks(self, x, positions, k_caches, v_caches, page_table,
                       slots=None):
        """The blocks over the resident cache, for :meth:`decode` and
        :meth:`verify`.  No layer is sliced out and nothing is stacked
        back: every block gets the whole (donated) buffers and its own
        layer number, writes its rows into them and reads them where
        they lie (ops/attention.py), so the program's cache traffic is
        the rows written and the rows attention reads.  (ROADMAP S4a:
        slicing a layer out and stacking it back made the compiler copy
        and relayout the whole cache every step; PERF.md, PR 25.)"""
        for i, blk in enumerate(self.blocks):
            x, (k_caches, v_caches) = blk(
                x, True, decode_cache=(k_caches, v_caches, i),
                positions=positions, slots=slots, page_table=page_table)
        with jax.named_scope("ln"):
            return self.ln_f(x), k_caches, v_caches

    def verify(self, tokens, positions, k_caches, v_caches,
               page_table=None):
        """Multi-token decode over ``S`` slots — the speculative-decode
        verify forward (core/steps.py ``build_verify_step``).

        ``tokens`` / ``positions`` [S, T] int32 — per slot, the last
        emitted token followed by the k drafted tokens at consecutive
        positions (T = k+1); caches as in :meth:`decode`.  ONE batched
        target forward writes every query's K/V row and scores each
        query under its own position bound (ops/attention.py
        multi-query ``cached_attention``), so the argmax at query j is
        numerically THE token plain decode would emit after accepting
        drafts 1..j — greedy parity is exact by construction, not by
        tolerance.  Rows written for later-rejected drafts are stale
        but masked (never at or below any live query's bound) and are
        overwritten by the next round, which restarts at the first
        corrected position.  Returns ``(logits [S, T, V] fp32,
        k_caches, v_caches)``.
        """
        cfg = self.config
        with jax.named_scope("embed"):
            x = self.wte(tokens)
            # gather clamps out-of-range positions (slots speculating
            # past the cache end read wpe[-1]; their outputs are
            # truncated by the scheduler's max_new cap before anything
            # is emitted)
            x = x + jnp.take(self.wpe, positions, axis=0).astype(cfg.dtype)
        x, k_caches, v_caches = self._cached_blocks(
            x, positions, k_caches, v_caches, page_table)
        with jax.named_scope("lm_head"):
            logits = self.wte.attend(x).astype(jnp.float32)
        return logits, k_caches, v_caches


def gpt_partition_rules(tensor_axis: str = "tensor") -> list[tuple[str, P]]:
    """SpmdStrategy rules for a (data, [fsdp,] tensor) mesh.

    Megatron-style: qkv/fc column-split, proj/out row-split; embeddings
    vocab-split.  XLA inserts the matching all-reduces on ``tensor``
    (riding ICI because tensor is the innermost mesh axis,
    parallel/mesh.py).
    """
    from ray_lightning_tpu.ops.moe import moe_partition_rules
    return moe_partition_rules(tensor_axis=tensor_axis) + [
        (r"wte/embedding", P(tensor_axis, None)),
        (r"attn/qkv/kernel", P(None, tensor_axis)),
        (r"attn/proj/kernel", P(tensor_axis, None)),
        (r"mlp/fc/kernel", P(None, tensor_axis)),
        (r"mlp/out/kernel", P(tensor_axis, None)),
        # no wpe rule: position embeddings fall through to the fsdp
        # fallback — sharded when an fsdp axis exists (at T=2048 C=2048
        # they are 4M params; pinning them replicated was waste),
        # replicated otherwise
    ]


def synthetic_lm_dataset(n: int, block_size: int, vocab_size: int,
                         seed: int = 0) -> ArrayDataset:
    """Deterministic token sequences with learnable structure (each token
    depends on the previous one), so loss decreases measurably fast."""
    rng = np.random.default_rng(seed)
    perm = np.random.default_rng(7).permutation(vocab_size)
    first = rng.integers(0, vocab_size, size=(n, 1))
    seqs = [first]
    for _ in range(block_size):
        # next token = perm[prev] with 10% noise
        nxt = perm[seqs[-1]]
        noise = rng.integers(0, vocab_size, size=(n, 1))
        mask = rng.random((n, 1)) < 0.1
        seqs.append(np.where(mask, noise, nxt))
    toks = np.concatenate(seqs, axis=1).astype(np.int32)
    return ArrayDataset(toks[:, :-1], toks[:, 1:])


class GPTLightningModule(LightningModule):
    """LM training module over :class:`GPT` (next-token cross-entropy)."""

    def __init__(self, config: "GPTConfig | str" = "tiny",
                 lr: float = 3e-4, weight_decay: float = 0.01,
                 warmup_steps: int = 10, dataset_size: int = 256,
                 batch_size: int = 8):
        super().__init__()
        if isinstance(config, str):
            config = CONFIGS[config]
        self.config = config
        self.save_hyperparameters("lr", "weight_decay", "batch_size")
        self.lr = lr
        self.weight_decay = weight_decay
        self.warmup_steps = warmup_steps
        self.dataset_size = dataset_size
        self.batch_size = batch_size

    def configure_model(self):
        return GPT(self.config)

    def configure_remat(self):
        """Planner-plane remat surface (core/remat.py): the GPT policy
        ladder — plus the ``checkpoint_name``-based MoE save lists when
        this config routes experts — with a per-block probe pricing any
        policy from avals alone.  ``apply`` folds a policy back into the
        config the way ``RLT_REMAT_POLICY`` used to per-build ("off"
        drops the ``nn.remat`` wrap entirely, matching the tiny/small
        configs' ``remat=False``)."""
        from ray_lightning_tpu.core import remat as _rm

        policies = list(_rm.POLICY_LADDER)
        if self.config.n_experts > 0:
            policies += list(_rm.MOE_POLICIES)

        def apply(policy: str) -> None:
            if policy not in policies:
                raise ValueError(f"remat policy {policy!r}; this "
                                 f"config's ladder: {policies}")
            cfg = self.config
            self.config = dataclasses.replace(
                cfg, remat=(policy != "off"),
                remat_policy=(policy if policy != "off"
                              else cfg.remat_policy))
            self.model = None   # next setup_model() rebuilds the wrap

        _base_flops: dict = {}   # (use_moe, B, T) -> baseline bwd flops

        def probe(policy: str, batch) -> _rm.RematProbe:
            cfg = self.config
            x = batch[0] if isinstance(batch, (tuple, list)) else batch
            B, T = int(x.shape[0]), int(x.shape[1])
            h = jax.ShapeDtypeStruct((B, T, cfg.n_embd), cfg.dtype)
            n_moe = sum(
                1 for i in range(cfg.n_layer)
                if cfg.n_experts > 0
                and i % cfg.moe_every == cfg.moe_every - 1)
            saved = flops = 0
            for count, use_moe in ((cfg.n_layer - n_moe, False),
                                   (n_moe, True)):
                if count == 0:
                    continue

                def base_fn(p, hh, _moe=use_moe):
                    return Block(cfg, use_moe=_moe).apply(
                        {"params": p}, hh, True)

                params = jax.eval_shape(
                    lambda k, _moe=use_moe: Block(cfg, use_moe=_moe).init(
                        k, jnp.zeros((1, T, cfg.n_embd), cfg.dtype),
                        True)["params"],
                    jax.random.PRNGKey(0))
                key = (use_moe, B, T)
                if key not in _base_flops:
                    _base_flops[key] = _rm.grad_dot_flops(base_fn,
                                                          params, h)
                if policy == "off":
                    fn = base_fn
                else:
                    blk = nn.remat(
                        Block, static_argnums=(2,),
                        policy=_rm.policy_object(policy))(
                            cfg, use_moe=use_moe)

                    def fn(p, hh, _b=blk):
                        return _b.apply({"params": p}, hh, True)

                s, f = _rm.block_cost(fn, base_fn, params, h,
                                      base_flops=_base_flops[key])
                saved += count * s
                flops += count * f
            return _rm.RematProbe(saved_bytes=saved,
                                  recompute_flops=flops,
                                  n_blocks=self.config.n_layer, batch=B)

        return _rm.RematSpec(
            policies=tuple(policies),
            default=(self.config.remat_policy if self.config.remat
                     else "off"),
            apply=apply, probe=probe)

    def configure_decode_model(self):
        """Serve-plane model (serve/engine.py): the SAME param tree as
        the training model — remat off (no backward pass to save memory
        for; kwargs-through-remat is also fragile) and dropout off
        (generation is deterministic)."""
        if self.config.n_experts > 0:
            raise ValueError(
                "serve decode does not support MoE configs: expert "
                "routing is batch-shaped and has no single-token cache "
                "path yet (models/gpt.py GPT.decode)")
        return GPT(dataclasses.replace(self.config, remat=False,
                                       dropout=0.0))

    def configure_draft(self, layers: "int | None" = None):
        """Speculative-decode draft sibling (serve/engine.py): the SAME
        architecture truncated to the first ``layers`` blocks (default
        ``n_layer // 2``), sharing the target's weights — ``wte``,
        ``wpe``, ``h0..h{layers-1}`` and ``ln_f`` are a subtree of the
        target param tree, so the engine derives draft params by path
        with ZERO extra HBM (unless ``RLT_DRAFT_QUANT`` opts into an
        int8 resident copy).  A layer-truncated residual LM is the
        classic self-speculation draft: early blocks carry most of the
        next-token signal, so acceptance is real without any separate
        draft training.  ``layers == n_layer`` is the degenerate
        full-clone draft (acceptance 1.0 — the test fixture for the
        accept-k pattern)."""
        if self.config.n_experts > 0:
            raise ValueError(
                "speculative decode does not support MoE configs: the "
                "draft/verify path rides GPT.decode/verify, which "
                "reject expert routing (configure_decode_model)")
        cfg = self.config
        n = int(layers) if layers else max(1, cfg.n_layer // 2)
        if not 1 <= n <= cfg.n_layer:
            raise ValueError(
                f"draft layers {n} must be in [1, {cfg.n_layer}]")
        return GPT(dataclasses.replace(cfg, n_layer=n, remat=False,
                                       dropout=0.0))

    @property
    def param_dtype(self):
        # bf16-resident params (RLT_BF16_PARAMS=0 opts out): deletes the
        # per-step fp32->bf16 kernel casts (~8.7 ms/step of dtype-convert
        # fusions in the gpt2-small device trace) and halves DDP gradient
        # bytes; the fp32 master copy in the optimizer state
        # (ops/optim.py fp32_master) keeps update precision
        return (jnp.bfloat16
                if os.environ.get("RLT_BF16_PARAMS", "1") != "0" else None)

    def flops_per_step(self):
        """Goodput-plane hook (core/module.py): the FLOPs one optimizer
        step requires over the global batch, from the sizes alone: 6 per
        matmul parameter and token (2 forward, 4 backward; 12 d^2 a
        block plus the tied table as the head), plus causal attention's
        score and value products, 6 L T d a token.  Recomputation is not
        counted.  The trainer's default (every ``dot_general`` of the
        step's jaxpr) cannot see inside the flash kernels, and counts
        the dense fallback's full square where it can."""
        cfg = self.config
        batch = getattr(self.trainer, "_abstract_batch", None)
        if cfg.n_experts > 0 or batch is None:
            return None      # routed FFN / unknown batch: price the jaxpr
        b, t = jax.tree_util.tree_leaves(batch)[0].shape[:2]
        matmul_params = 12 * cfg.n_layer * cfg.n_embd ** 2 \
            + cfg.vocab_size * cfg.n_embd
        return float(b * t) * (6.0 * matmul_params
                               + 6.0 * cfg.n_layer * t * cfg.n_embd)

    def configure_optimizers(self):
        sched = optax.linear_schedule(0.0, self.lr, self.warmup_steps)
        # bf16 first moment (RLT_BF16_MOMENTS=0 opts out): halves mu's
        # HBM traffic in the optimizer update with no measurable quality
        # change on the LM objective (nu stays fp32 — optax exposes only
        # mu_dtype, and the second moment is variance-scale sensitive)
        mu_dtype = (jnp.bfloat16
                    if os.environ.get("RLT_BF16_MOMENTS", "1") != "0"
                    else None)
        tx = optax.adamw(sched, weight_decay=self.weight_decay,
                         b1=0.9, b2=0.95, mu_dtype=mu_dtype)
        if self.param_dtype is not None:
            from ray_lightning_tpu.ops.optim import fp32_master
            tx = fp32_master(tx)
        return tx

    def _loss(self, ctx, batch):
        x, y = batch
        if self.config.chunked_ce > 0:
            # memory-lean loss: never materialize full fp32 logits
            # (ops/losses.py; the opt-in for long-seq × 50k-vocab configs)
            from ray_lightning_tpu.ops.losses import (
                chunked_softmax_cross_entropy)
            h = ctx.apply(x, not ctx.training, method=GPT.hidden)
            # read the tied table from params directly: a second
            # ctx.apply would consume an extra dropout-RNG split and
            # change training trajectories vs the full-vocab path
            table = ctx.params["wte"]["embedding"]
            return chunked_softmax_cross_entropy(
                h, table, y, self.config.chunked_ce)
        if os.environ.get("RLT_FUSED_CE", "1") != "0":
            # default full-vocab loss: bf16-resident logits, fp32
            # accumulation inside the reduction fusions (ops/losses.py
            # fused_lm_cross_entropy — measured win on the v5e headline;
            # RLT_FUSED_CE=0 restores the fp32-logits path)
            from ray_lightning_tpu.ops.losses import fused_lm_cross_entropy
            h = ctx.apply(x, not ctx.training, method=GPT.hidden)
            table = ctx.params["wte"]["embedding"]
            return fused_lm_cross_entropy(h, table, y)
        logits = ctx.apply(x, not ctx.training)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    def training_step(self, ctx, batch):
        loss = self._loss(ctx, batch)
        if self.config.n_experts > 0:
            # routed layers sowed their load-balance losses during the
            # forward pass (mutable collections only flow back to the
            # context under training, core/module.py ctx.apply)
            from ray_lightning_tpu.ops.moe import total_aux_loss
            aux = total_aux_loss(ctx.model_state)
            if aux is not None:
                ctx.log("moe_aux", aux)
                loss = loss + self.config.moe_aux_weight * aux
        ctx.log("loss", loss)
        return loss

    def validation_step(self, ctx, batch):
        ctx.log("val_loss", self._loss(ctx, batch))

    def test_step(self, ctx, batch):
        ctx.log("test_loss", self._loss(ctx, batch))

    def predict_step(self, ctx, batch):
        x = batch[0] if isinstance(batch, (tuple, list)) else batch
        return jnp.argmax(ctx.apply(x, True), axis=-1)

    def _loader(self, seed):
        ds = synthetic_lm_dataset(self.dataset_size, self.config.block_size,
                                  self.config.vocab_size, seed)
        return DataLoader(ds, batch_size=self.batch_size, drop_last=True)

    def train_dataloader(self):
        return self._loader(0)

    def val_dataloader(self):
        return self._loader(1)

    def test_dataloader(self):
        return self._loader(2)

    def predict_dataloader(self):
        return self._loader(3)
