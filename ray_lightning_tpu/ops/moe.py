"""Mixture-of-Experts feed-forward layer with expert parallelism.

Beyond the reference's parity surface (SURVEY.md §2.3 marks EP absent);
built TPU-first rather than ported:

- **Static shapes**: GShard/Switch-style fixed expert *capacity* — every
  expert processes exactly ``capacity`` token slots per group, so the
  whole layer is three einsums XLA can tile onto the MXU.  No dynamic
  gather/scatter, no data-dependent shapes (SURVEY.md's XLA-semantics
  constraint).
- **Expert parallelism as sharding**: expert weights carry a leading
  ``[n_experts, ...]`` dim annotated on the ``expert`` mesh axis
  (``moe_partition_rules``); tokens stay sharded on ``data``.  GSPMD
  lowers the dispatch/combine einsums to the all-to-all over ICI —
  the same "parallelism is an annotation, collectives are compiler
  output" inversion as the rest of ``parallel/strategy.py``.
- **fp32 router**: gate logits/softmax in fp32 (bf16 routing is noisy
  enough to destabilize small models), expert FFN in the compute dtype.

The router sows its load-balance auxiliary loss into the ``losses``
variable collection (overwrite semantics, so the carried value stays a
scalar across steps); :func:`total_aux_loss` folds the collection into
the training loss.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


def _overwrite(prev, new):
    """sow reduce_fn: keep the latest value (no unbounded tuple growth
    when the collection is threaded through successive train steps)."""
    del prev
    return new


class MoEMLP(nn.Module):
    """Drop-in MLP replacement routing each token to ``top_k`` experts.

    Input/output: ``[groups, tokens, d_model]`` (groups = the batch dim;
    capacity is computed per group).  Tokens beyond an expert's capacity
    are *dropped* — their output is zero, and the surrounding residual
    connection passes them through unchanged (the standard Switch
    behavior).
    """

    n_experts: int
    d_ff: int
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        del deterministic  # routing is deterministic; no dropout inside
        G, S, M = x.shape
        E, k = self.n_experts, self.top_k
        if not 1 <= k <= E:
            raise ValueError(f"top_k={k} must be in [1, {E}]")
        capacity = min(S, int(math.ceil(self.capacity_factor * k * S / E)))

        router = self.param("router", nn.initializers.normal(0.02), (M, E),
                            jnp.float32)
        w1 = self.param("w1", nn.initializers.lecun_normal(),
                        (E, M, self.d_ff), jnp.float32)
        w2 = self.param("w2", nn.initializers.lecun_normal(),
                        (E, self.d_ff, M), jnp.float32)

        gate_logits = jnp.einsum("gsm,me->gse", x.astype(jnp.float32), router)
        probs = jax.nn.softmax(gate_logits, axis=-1)          # [G,S,E] fp32

        gate_vals, gate_idx = jax.lax.top_k(probs, k)         # [G,S,k]
        if k > 1:
            gate_vals = gate_vals / (
                jnp.sum(gate_vals, axis=-1, keepdims=True) + 1e-9)
        # k == 1 keeps the RAW top-1 probability (Switch-Transformer
        # style): renormalizing would pin the combine weight at 1.0 and
        # sever the router's gradient path through the task loss.

        # Fill expert slots choice-by-choice; the per-expert position
        # counter carries across choices so a token's 2nd-choice expert
        # sees slots already taken by other tokens' 1st choices.
        # (A compute-dtype [G,S,E,cap] chain was tried in round 5 —
        # exact by disjointness — and measured 80.08 ms/step, identical
        # to this fp32 chain: the expert-bwd drag fusions' bytes are
        # einsum operand traffic, not chain dtype; see the README's
        # round-5 MoE rejected-experiment note.)
        dispatch = jnp.zeros((G, S, E, capacity), dtype=x.dtype)
        combine = jnp.zeros((G, S, E, capacity), dtype=jnp.float32)
        taken = jnp.zeros((G, 1, E), dtype=jnp.int32)
        for i in range(k):
            onehot = jax.nn.one_hot(gate_idx[..., i], E,
                                    dtype=jnp.int32)          # [G,S,E]
            pos = jnp.cumsum(onehot, axis=1) - 1 + taken      # slot index
            taken = taken + jnp.sum(onehot, axis=1, keepdims=True)
            keep = onehot * (pos < capacity)                  # overflow drop
            slot = jax.nn.one_hot(jnp.clip(pos, 0, capacity - 1), capacity,
                                  dtype=jnp.float32)          # [G,S,E,cap]
            d_i = keep.astype(jnp.float32)[..., None] * slot
            dispatch = dispatch + d_i.astype(x.dtype)
            combine = combine + gate_vals[..., i, None, None] * d_i

        # Switch load-balance loss: E * sum_e(frac_tokens_e * mean_prob_e);
        # 1.0 at perfect balance, grows as routing collapses.
        first = jax.nn.one_hot(gate_idx[..., 0], E, dtype=jnp.float32)
        frac = jnp.mean(first, axis=(0, 1))
        mean_prob = jnp.mean(probs, axis=(0, 1))
        aux = E * jnp.sum(frac * mean_prob)
        self.sow("losses", "moe_aux", aux, reduce_fn=_overwrite,
                 init_fn=lambda: jnp.zeros((), jnp.float32))

        # dispatch → expert FFN → combine: three MXU einsums.  With w1/w2
        # sharded on the expert axis and tokens on data, GSPMD inserts the
        # token all-to-all around the FFN automatically.  The big
        # intermediates carry checkpoint_names so remat policies can save
        # them selectively (models/gpt.py "dots_moe_act"/"dots_moe") —
        # measured round 5: BOTH save-lists lose to plain "dots"
        # (81.97 / 83.12 vs 80.08 ms/step; the HBM round-trip of the
        # saved tensors exceeds the recompute it removes), so they exist
        # as documented rejected options, not defaults.
        from jax.ad_checkpoint import checkpoint_name as name
        dispatch = name(dispatch, "moe_dispatch")
        xe = jnp.einsum("gsec,gsm->egcm", dispatch, x)
        h = jnp.einsum("egcm,emh->egch", xe, w1.astype(self.dtype))
        h = name(nn.gelu(h), "moe_hact")
        out = jnp.einsum("egch,ehm->egcm", h, w2.astype(self.dtype))
        # the tag sits on the bf16-cast combine (the tensor the einsum
        # consumes), not the fp32 original — saving double-width bytes
        # would pessimize the save-list option for no consumer
        return jnp.einsum("gsec,egcm->gsm",
                          name(combine.astype(self.dtype), "moe_combine"),
                          out)


def moe_partition_rules(expert_axis: str = "expert",
                        tensor_axis: str = "tensor"):
    """SpmdStrategy rules for MoE parameters (prepend to the model's own
    rules).  Expert dim sharded on ``expert``; within each expert the FFN
    is Megatron-split on ``tensor``; the router stays replicated (it is
    tiny and every data shard needs it)."""
    from jax.sharding import PartitionSpec as P
    return [
        (r"moe/w1$", P(expert_axis, None, tensor_axis)),
        (r"moe/w2$", P(expert_axis, tensor_axis, None)),
        (r"moe/router$", P()),
    ]


def total_aux_loss(model_state) -> "jax.Array | None":
    """Sum every sown ``losses`` leaf (one per MoE layer), or None if the
    model has no loss-sowing layers."""
    tree = (model_state or {}).get("losses")
    if not tree:
        return None
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return None
    total = leaves[0]
    for leaf in leaves[1:]:
        total = total + leaf
    return total


# -- dropless serving layer (models/command.py, models/xing.py) ---------------
#
# The capacity router above drops tokens and computes every expert for
# ``capacity`` slots.  Serving wants neither: every pair a token chose is
# computed, and only the pairs.  What is here is also told which experts
# it HOLDS (``offset`` and the leading dimension of the weights): it
# routes over all the published experts and computes the part of the
# result its own experts give; a pair routed to an absent expert adds
# nothing.  On one chip there is no exchange, and nothing stands in for
# the absent chips.  The router is a sigmoid over all the published
# experts (``sigmoid_topk``), with a family's optional selection bias
# (added to the scores to CHOOSE, never to weigh) and scaling factor;
# ``ExpertLayer`` is the flax layer both families build: router, routed
# experts, shared experts, and what it counts for ``SERVE_COUNTERS``.  A
# family whose router is not that (models/zaya.py: an MLP that reads the
# previous layer's router, a softmax, ONE choice a token, which may be "no
# expert": ``mlp_softmax_top1``) owns its expert sublayer and calls
# ``dropless_experts`` itself; an index past the held experts is a pair
# that lands nowhere, the case ``offset`` / ``held`` already make.
#
# "Dropless" has no smaller static bound than ``T * k`` rows: every token
# may put all its ``k`` pairs on held experts.  A decode batch pays that
# bound (256 rows); a prompt does not.  Its ``T * k`` pairs are sorted,
# the held ones first and by expert, and the sorted order is cut into
# PIECES of ``C`` rows (``_piece_rows``); piece ``j`` holds sorted places
# ``[jC, (j + 1)C)`` and runs only when ``pairs > jC``, so a chip that
# holds an eighth of the experts moves about an eighth of the rows; one
# that holds them all owes the whole bound and pays it in ONE pass.

#: tiles (rows, contraction, columns) of the megablox grouped product on
#: the TPU, by whether a call is a decode batch's few rows or a prompt's.
#: One product [rows, 4096] x 16 x [4096, 4096] by hand on the v5e
#: (builder's chip run, PR 33; PERF.md section 6), ms a call.  256 rows
#: (32 tokens), 34 pairs on 12 experts: (128, 512, 1024) 0.751, (128,
#: 1024, 1024) 0.739, (128, 1024, 2048) 0.710, (128, 2048, 1024) 0.742,
#: (256, 1024, 1024) 0.811; ``jax.lax.ragged_dot`` 1.073; the hit
#: experts' bytes' time 0.49.  65,536 rows (8192 tokens), 8,297 pairs on
#: 16: (256, 1024, 1024) 3.005, (512, 1024, 1024) 3.154, (512, 512, 1024)
#: 3.346, larger tiles do not fit VMEM; ``ragged_dot`` 6.439; one dense
#: product of as many rows 1.580.
_GMM_TILING_FEW = (128, 1024, 2048)
_GMM_TILING_MANY = (256, 1024, 1024)
#: models/xing.py's experts are 64 of [3584, 1024] / [1024, 3584] (3584 =
#: 3.5 x 1024: a contraction tile of 1024 leaves a masked half tile).  One
#: product by hand on the v5e (builder's chip run, PR 36; PERF.md section
#: 6), ms a call, ~0.76 of each dispatch; a tile of a whole 3584 side does
#: not fit VMEM.  256 rows (64 tokens) on 63 of 64 experts, their bytes'
#: time 0.565: in (128, 512, 1024) 1.329, (128, 1792, 1024) 1.343, (128,
#: 1024, 1024) 1.382, (128, 1792, 512) 1.476; out (128, 1024, 1792) 1.345,
#: (128, 1024, 2048) 1.361, (128, 1024, 512) 1.387; ``ragged_dot`` 1.70 /
#: 1.72.  2,048 rows (a piece of an 8192-token prompt) on all 64: in (128,
#: 1792, 1024) 1.555, (128, 512, 1024) 1.566, (256, 1792, 1024) 1.569,
#: (128, 1024, 1024) 1.640, (256, 1024, 1024) 1.705, (512, 512, 1024)
#: 2.114; out (128, 1024, 2048) 1.382, (256, 1024, 1792) 1.384, (128, 1024,
#: 1792) 1.443, (256, 1024, 2048) 1.475, (512, 1024, 1792) 1.968;
#: ``ragged_dot`` 2.33 / 2.31.  The way out is within noise of the two
#: constants above and takes them; the way in has the one gap that was
#: read (a piece's rows: 1.555 against ``_GMM_TILING_MANY``'s 1.705), and
#: ``_GMM_TILING_FEW``'s 2048 columns are wider than its 1024.
_GMM_TILING_3584_IN = (128, 1792, 1024)
#: sorted rows up to which the layer is one piece (where ``_grouped_dot``
#: changes tilings), and the share of the sorted rows a piece holds
#: beyond.  The layer alone by hand on the v5e (builder's chip runs, PR
#: 34; PERF.md section 6), 7168 tokens of which 6,900 exist, 6,773 pairs
#: on 16 of 128 experts, ms a call: all 57,344 rows moved (PR 33's layer)
#: 17.90; pieces of a quarter (14,336 rows, one runs) 11.52, an eighth
#: (7,168, one) 9.64, a sixteenth (3,584, two) 9.54, a thirty-second
#: (1,792, four) 10.16.  A sixteenth never moves more rows than an eighth
#: and moves fewer where the pairs pass an eighth by a little: 8192
#: tokens, 8,257 pairs: 20.47 as it was, an eighth 13.76 (two pieces), a
#: sixteenth 12.42 (three); 6144 tokens, 5,897 pairs: 15.94, 8.53, 8.49.
#: Past a sixteenth the pieces' own cost shows.  Every pair held (7168
#: tokens on 16 of 16 experts, 57,344 pairs, all sixteen pieces): 53.79
#: as it was, 59.03 in pieces (an eighth 58.64): what the shape's choice
#: costs a chip that holds all its experts.  ``xing4-serve-doc8k`` is the
#: cell that does (64 of 64 experts of [3584, 1024], 4 a token; a piece is
#: a run of the by-expert order, so it meets 4-5 experts' groups of ~450-
#: 560 rows and not all 64): in pieces, by hand, ms a call (PR 36 | PR 37,
#: a trace): 7168 tokens 35.31 | 36.97, 8192 39.97 | 41.60, 9216 44.69 |
#: 46.48, the scatter-add alone 27.5 / 31.2 / 35.2: such a chip takes ONE
#: pass (``_in_one_pass`` has its numbers).  A decode batch 1.959, 1.962.
_ONE_PIECE_ROWS = 1024
_PIECE_SHARE = 16


def sigmoid_topk(h, router_w, top_k: int, bias=None, scale: float = 1.0):
    """``(idx [T, k] int32, w [T, k] float32)``: the ``k`` largest of
    ``sigmoid(h @ router_w)`` over ALL the router's outputs, and their
    weights normalised to sum to 1 (``norm_topk_prob``), times ``scale``
    (``routed_scaling_factor``).  ``bias`` [E] float32 (``noaux_tc``'s
    ``e_score_correction_bias``) is added to the scores to CHOOSE the
    ``k``; the weights are the chosen experts' scores without it.  With
    neither, the operations are what they were without the arguments.
    float32 throughout, the product at ``highest``: a rounding that swaps
    the k-th and the (k+1)-th score swaps an expert."""
    with jax.named_scope("moe_route"):
        r = jnp.einsum("td,de->te", h.astype(jnp.float32),
                       router_w.astype(jnp.float32), precision="highest")
        s = jax.nn.sigmoid(r)
        if bias is None:
            top, idx = jax.lax.top_k(s, top_k)
        else:
            _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
            top = jnp.take_along_axis(s, idx, axis=-1)
        w = top / jnp.sum(top, axis=-1, keepdims=True)
        return idx, w if scale == 1.0 else w * scale


def mlp_softmax_top1(h, prev, down_w, gamma, norm_g, w1, w2, w3, bias,
                     eps: float):
    """``(idx [T, 1] int32, w [T, 1] float32, s [T, R] float32)``: an MLP
    router that reads the router of the layer before it (the ZAYA1
    router, arXiv:2511.17127).  ``r = h down_w`` ([d, R]); ``s = r +
    gamma * prev`` (``prev`` [T, R]: the SAME tokens' ``s`` of the
    previous layer's router, None before the first; ``gamma`` a scalar);
    ``p = softmax(w3 gelu(w2 gelu(w1 n(s))))`` with ``n`` an RMSNorm of
    gain ``norm_g``, ``w1``, ``w2`` [R, R] and ``w3`` [R, E + 1], the
    gelu exact.  The LAST output is "no expert": ``idx`` is the largest
    of ``p + bias`` (``bias`` [E + 1] chooses and never weighs), in
    ``[0, E]``, where ``E`` lands on no held expert
    (:func:`dropless_experts`' ``held``), and ``w`` the chosen output's
    ``p`` as it is (one a token: nothing to normalise).  ``s`` goes to the
    next layer's router.  float32 throughout, every product at
    ``highest``: the best two of 17 may lie a rounding apart, and a swap
    replaces a token's whole expert."""
    f32 = jnp.float32
    with jax.named_scope("moe_route"):
        def dot(a, b):
            return jnp.einsum("tr,re->te", a, b.astype(f32),
                              precision="highest")

        s = dot(h.astype(f32), down_w)
        if prev is not None:
            s = s + gamma.astype(f32) * prev
        n = s * jax.lax.rsqrt(
            jnp.mean(jnp.square(s), axis=-1, keepdims=True) + eps) \
            * norm_g.astype(f32)
        a = jax.nn.gelu(dot(n, w1), approximate=False)
        a = jax.nn.gelu(dot(a, w2), approximate=False)
        p = jax.nn.softmax(dot(a, w3), axis=-1)
        idx = jnp.argmax(p + bias.astype(f32), axis=-1)[:, None]
        return (idx.astype(jnp.int32),
                jnp.take_along_axis(p, idx, axis=-1), s)


def _grouped_dot(rows, weights, sizes, impl: str):
    """``rows[group g's rows] @ weights[g]``, accumulated in float32 and
    returned in ``rows``' type (the next product reads it so, and a
    float32 copy would double the scratch of a call's rows: a decode
    batch's ``T * k``, or one piece of a prompt's, about as many as the
    pairs that land here); rows past the groups' total are undefined."""
    if impl == "gmm":
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        return gmm(rows, weights, sizes, rows.dtype,
                   gmm_tiling(*rows.shape))
    return jax.lax.ragged_dot(rows, weights, sizes,
                              preferred_element_type=jnp.float32
                              ).astype(rows.dtype)


#: tiles by contraction width and by whether a call's rows pass a decode
#: batch's (``_ONE_PIECE_ROWS``), where the two 4096-wide constants were
#: read behind.  3584 or 1024 wide and past it is a prompt of
#: models/xing.py in ONE pass (``_in_one_pass``): 28,672 / 32,768 / 36,864
#: rows in 64 whole groups of ~450-560.  One product by hand on the v5e
#: (builder's chip run, PR 37; a trace's kernel events; PERF.md section
#: 6), ms a call at 7168 / 8192 / 9216 tokens.  In, [rows, 3584] x 64 x
#: [3584, 1024] (its operations' time 1.07 / 1.22 / 1.37): (256, 3584, 512)
#: 1.852 / 1.997 / 2.152, (128, 3584, 512) 1.861 / 2.017 / 2.198, (256,
#: 3584, 256) 2.016 / 2.196 / 2.373, (256, 1792, 1024) 2.200 / 2.397 /
#: 2.592, (256, 3584, 640) 2.213 / 2.404 / 2.590, (512, 3584, 256) 2.452 /
#: 2.620 / 2.768, ``_GMM_TILING_3584_IN`` (128, 1792, 1024) 3.179 / 3.520
#: / 3.873 (an expert's 7.3 MB read again for each of ~350 row tiles); at
#: 8192 (256, 1024, 1024) 2.740, (256, 512, 1024) 2.636, (512, 1024, 1024)
#: 2.953; a whole 3584 with 768 or 1024 columns does not fit VMEM.  Out,
#: [rows, 1024] x 64 x [1024, 3584]: (64, 1024, 3584) 1.867 / 2.027 /
#: 2.184, (128, 1024, 1792) 1.937 / 2.094 / 2.271, (256, 1024, 1792)
#: 1.993 / 2.147 / 2.299, (64, 1024, 1792) 1.988 / 2.159 / 2.327, (128,
#: 1024, 1280) 2.092 / 2.265 / 2.462, (512, 1024, 1792) 2.326 / 2.463 /
#: 2.601, ``_GMM_TILING_MANY`` (256, 1024, 1024) 2.300 / 2.485 / 2.663;
#: (128 / 256, 1024, 3584) do not fit.  The way in changes a 9216 layer
#: by 3.4 ms of 14.0, the way out by 0.5.
#: models/zaya.py's experts are 16 of [2048, 2048] both ways, ONE a token:
#: a decode batch's 128 rows in groups of ~8 and a prompt's up to 1024 in
#: groups of ~60, neither past ``_ONE_PIECE_ROWS``.  One product by hand on
#: the v5e (builder's chip run, PR 41; PERF.md section 6), ms a call, calls
#: queued back to back (~0.2 ms is the host's floor a call; 16 experts'
#: bytes' time 0.164).  128 rows: (128, 2048, 1024) 0.213, (128, 512, 2048)
#: 0.215, ``_GMM_TILING_FEW`` (128, 1024, 2048) 0.216, (128, 1024, 1024)
#: 0.231, (64, 1024, 2048) 0.231, ``ragged_dot`` 0.260; (128, 2048, 2048)
#: does not fit VMEM.  1024 rows: (128, 2048, 1024) 0.264, (256, 1024,
#: 2048) 0.278, (256, 1024, 1024) 0.304, ``_GMM_TILING_FEW`` 0.305, (128,
#: 1024, 1024) 0.325, (512, 1024, 1024) 0.434, ``ragged_dot`` 0.495.
_GMM_TILING_BY_WIDTH = {(3584, False): _GMM_TILING_3584_IN,
                        (3584, True): (256, 3584, 512),
                        (1024, True): (64, 1024, 3584),
                        (2048, False): (128, 2048, 1024)}


def gmm_tiling(m: int, k: int) -> tuple:
    """The megablox tiles (rows, contraction, columns) for ``[m, k] x
    [groups, k, n]``, from the shapes handed in: few rows or many
    (``_ONE_PIECE_ROWS``), as the two constants say, the 4096-wide
    products among them; the measured choices where the contraction is
    3584, 2048 up to a decode batch's rows, or 1024 past them."""
    many = m > _ONE_PIECE_ROWS
    tiling = _GMM_TILING_BY_WIDTH.get(
        (k, many), _GMM_TILING_MANY if many else _GMM_TILING_FEW)
    return (min(tiling[0], m),) + tiling[1:]


def grouped_dot_impl(impl: "str | None" = None) -> str:
    """``gmm`` (megablox, the TPU) or ``ragged`` (``jax.lax.ragged_dot``,
    everywhere else), from what the code can observe."""
    if impl is not None:
        return impl
    return "gmm" if jax.devices()[0].platform == "tpu" else "ragged"


def _gated_products(rows, weights, sizes, impl: str):
    """``down (silu(gate rows) * up rows)``, each product grouped by
    ``sizes``: what of the layer lies under ``moe_experts``."""
    gate_w, up_w, down_w = weights
    with jax.named_scope("moe_experts"):
        a = jax.nn.silu(_grouped_dot(rows, gate_w, sizes, impl)) \
            * _grouped_dot(rows, up_w, sizes, impl)
        return _grouped_dot(a, down_w, sizes, impl)


def _piece_rows(rows: int) -> int:
    """Rows of one piece for ``rows`` sorted pairs: all of them up to
    ``_GMM_TILING_FEW``'s bound, else the share ``_PIECE_SHARE`` rounded
    up to whole row tiles."""
    if rows <= _ONE_PIECE_ROWS:
        return rows
    tile = _GMM_TILING_MANY[0]
    return -(-rows // (_PIECE_SHARE * tile)) * tile


def dropless_experts(h, idx, w, gate_w, up_w, down_w, *, offset: int = 0,
                     valid=None, impl: "str | None" = None,
                     published: "int | None" = None):
    """What the held experts add: ``sum_{e chosen, held} w_e E_e(h)``
    with ``E(h) = down (silu(gate h) * up h)``.

    ``h`` [T, d] in the compute dtype; ``idx``, ``w`` [T, k] from
    :func:`sigmoid_topk`; ``gate_w``, ``up_w`` [held, d, F], ``down_w``
    [held, F, d]: experts ``offset .. offset + held`` of the ``published``
    ones (None: not said, and never taken for all of them); ``valid`` [T]
    bool: tokens that exist (a bucket's padding routes nowhere).  Returns
    ``(y [T, d] float32, pairs, experts_hit, rows)``: the token-expert
    pairs computed here, the held experts with at least one, and the rows
    pushed through the grouped products (int32 scalars; ``rows`` a Python
    int where it is the shape's).

    The ``T * k`` pairs are sorted by expert, the ones that land here
    first.  Up to ``_ONE_PIECE_ROWS`` of them (a decode batch) are ONE
    piece: every row gathered, the three products run as grouped products
    over the sorted rows (scope ``moe_experts``), the result sent back by
    the inverse permutation and summed per token under its weights (scope
    ``moe_route``: everything that is not a product).  A prompt's rows
    are cut into pieces of ``_piece_rows`` sorted positions, and piece
    ``j`` runs only when ``pairs > j * C`` (a loop of ``ceil(pairs / C)``
    trips around one set of three grouped products): it gathers its own
    ``C`` rows, its group sizes are the parts of ``sizes`` inside it, and
    its weighted rows are added into their tokens' float32 rows.  Where
    every published expert is held (``held == published``) every pair of
    every token that exists lands here, pieces save no row, and a prompt
    too is ONE pass (:func:`_in_one_pass`).  Every pair that lands here
    is computed whatever their number: nothing has a capacity and
    nothing is dropped."""
    T, d = h.shape
    k = idx.shape[1]
    held = gate_w.shape[0]
    impl = grouped_dot_impl(impl)
    with jax.named_scope("moe_route"):
        local = idx - offset
        here = (local >= 0) & (local < held)
        if valid is not None:
            here = here & valid[:, None]
        expert = jnp.where(here, local, held).reshape(T * k)
        order = jnp.argsort(expert, stable=True)
        sizes = jnp.sum(expert[:, None] == jnp.arange(held)[None, :],
                        axis=0, dtype=jnp.int32)
    weights = (gate_w, up_w, down_w)
    if held == published and T * k > _ONE_PIECE_ROWS:
        return _in_one_pass(h, order, sizes, here, w, weights, impl)
    C = _piece_rows(T * k)
    if C < T * k:
        return _in_pieces(h, order, sizes, jnp.where(here, w, 0.0), C,
                          weights, impl)
    with jax.named_scope("moe_route"):
        rows = jnp.take(h, order // k, axis=0)              # [T*k, d]
    y = _gated_products(rows, weights, sizes, impl)
    with jax.named_scope("moe_route"):
        pairs = jnp.sum(sizes)
        y = jnp.where((jnp.arange(T * k) < pairs)[:, None], y, 0)
        back = jnp.zeros((T * k,), jnp.int32).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32))
        y = jnp.take(y, back, axis=0).reshape(T, k, d)
        out = jnp.einsum("tk,tkd->td", jnp.where(here, w, 0.0), y,
                         preferred_element_type=jnp.float32)
    return out, pairs, jnp.sum(sizes > 0, dtype=jnp.int32), T * k


#: the layer with every expert held (64 of 64 of [3584, 1024], 4 a token,
#: 3 % of the bucket padding), by hand on the v5e, device ms a call from
#: a trace at 7168 / 8192 / 9216 tokens (builder's chip runs, PR 37;
#: PERF.md section 6): in pieces 34.77 / 39.15 / 43.74 (36.97 / 41.60 /
#: 46.48 on another machine), ONE pass 8.14 / 9.09 / 10.01 (11.32 / 12.57 /
#: 14.04 before ``_GMM_TILING_BY_WIDTH``).  The pass at 9216: the three
#: products 6.5 and ``silu * up`` 0.23; the gather in 0.40 (the HBM's
#: rate), the four gathers back 2.03, the select, weigh and sum 0.58, the
#: inverse permutation 0.17, the sort 0.04, sizes and the rest ~0.3.
def _in_one_pass(h, order, sizes, here, w, weights, impl: str):
    """:func:`dropless_experts` past one piece's rows where every
    published expert is held: a decode batch's path at a prompt's size.
    ONE gather into the by-expert order, the three grouped products over
    whole expert groups, the way back by the inverse permutation as
    gathers (a token's ``k`` rows, choice by choice: ``[T, d]`` each,
    where a ``[T, k, d]`` view would pad ``k`` to a tile's sublanes); no
    loop and no scatter-add.  ``here`` [T, k] the pairs computed (all of
    a token that exists); the sum over a token's ``k`` rows is float32
    arithmetic on the float32 ``w`` (a multiply and a sum: an einsum
    would round ``w`` to bfloat16 on the MXU), and a row past the pairs
    (undefined, :func:`_grouped_dot`) is selected away there and never
    multiplied.  Every index is a permutation's: ``mode="clip"`` saves
    the fill's select, a pass over all the rows.  The products take whole
    row tiles, as a piece is: a bucket that is not (the cells' are) runs
    a last tile's rows of token 0 past the pairs."""
    T, d = h.shape
    k = w.shape[1]
    tile = _GMM_TILING_MANY[0]
    with jax.named_scope("moe_route"):
        rows = jnp.take(h, jnp.pad(order, (0, -(T * k) % tile)) // k,
                        axis=0, mode="clip")        # [T*k in tiles, d]
    y = _gated_products(rows, weights, sizes, impl)
    with jax.named_scope("moe_route"):
        back = jnp.zeros((T * k,), jnp.int32).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32)).reshape(T, k)
        out = sum(jnp.where(
            here[:, j, None],
            jnp.take(y, back[:, j], axis=0, mode="clip").astype(jnp.float32)
            * w[:, j, None], 0.0) for j in range(k))
    return (out, jnp.sum(sizes), jnp.sum(sizes > 0, dtype=jnp.int32),
            T * k)


def _in_pieces(h, order, sizes, w, C: int, weights, impl: str):
    """:func:`dropless_experts` past one piece.  ``order`` [T * k] the
    sorted pairs' places in ``w`` [T, k] float32 (0 where a pair is not
    computed here), ``sizes`` [held].  The loop itself lies under neither
    finer scope, so that a trace's event for it, if it has one, is counted
    under none; its body's operations lie under theirs."""
    T, d = h.shape
    k = w.shape[1]
    with jax.named_scope("moe_route"):
        pairs = jnp.sum(sizes)
        ends = jnp.cumsum(sizes)
        starts = ends - sizes
        # (a slice that ran past the end would be moved back inside)
        order = jnp.pad(order, (0, -order.shape[0] % C))
        w = w.reshape(T * k)

    def piece(j, out):
        lo = j * C
        with jax.named_scope("moe_route"):
            at = jax.lax.dynamic_slice(order, (lo,), (C,))
            token = at // k
            rows = jnp.take(h, token, axis=0, mode="clip")
            part = jnp.clip(jnp.minimum(ends, lo + C)
                            - jnp.maximum(starts, lo), 0)
        y = _gated_products(rows, weights, part, impl)
        with jax.named_scope("moe_route"):
            live = (lo + jnp.arange(C) < pairs)[:, None]
            y = jnp.where(live, y, 0).astype(jnp.float32) \
                * jnp.take(w, at, mode="clip")[:, None]
            return out.at[token].add(y)

    n = (pairs + C - 1) // C
    out = jax.lax.fori_loop(0, n, piece, jnp.zeros((T, d), jnp.float32))
    return out, pairs, jnp.sum(sizes > 0, dtype=jnp.int32), n * C


# -- the layer both served families build -------------------------------------

#: the int32 accumulator a model with an ``ExpertLayer`` keeps beside its
#: serve state (serve/engine.py ``stats()['counters']``,
#: ``module.serve_counters``): runs, pairs computed here, experts hit and
#: rows pushed through the grouped products, for decode runs and prefills
SERVE_COUNTERS = ("decode_runs", "decode_moe_pairs",
                  "decode_moe_experts_hit", "decode_moe_rows",
                  "prefill_runs", "prefill_moe_pairs",
                  "prefill_moe_experts_hit", "prefill_moe_rows")


def split_counters(k_caches):
    """``(arrays, counters)``: a serve state's arrays, and the accumulator
    that rides behind them where the engine made one."""
    if len(k_caches) and k_caches[-1].ndim == 1:
        return tuple(k_caches[:-1]), k_caches[-1]
    return tuple(k_caches), None


def count_run(counters, first: int, pairs, hit, rows) -> tuple:
    """``(counters',)`` with one run and its three counts added at
    entries ``first .. first + 4`` (0: a decode run; 4: a prefill);
    ``()`` where there is no accumulator."""
    if counters is None:
        return ()
    add = jnp.stack([jnp.ones((), jnp.int32), pairs, hit, rows])
    return (counters.at[first:first + 4].add(add.astype(counters.dtype)),)


class ExpertLayer(nn.Module):
    """The routed experts held here and the shared experts.  ``h`` [T,
    d] float32 (the norm's output: the router reads it so).  Returns
    ``(y [T, d] float32, (pairs, experts_hit, rows))``.

    ``width``: one expert's; ``held`` of ``published`` experts from
    ``offset``; ``top_k`` a token; ``n_shared`` shared experts as ONE
    gated product of width ``n_shared * width``, whose output is divided
    by ``n_shared``.  ``select_bias``: a float32 parameter ``bias``
    [published] chooses with the scores (:func:`sigmoid_topk`);
    ``scale``: the routed weights' factor."""

    d: int
    width: int
    held: int
    published: int
    top_k: int
    n_shared: int
    offset: int = 0
    select_bias: bool = False
    scale: float = 1.0
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h, valid=None):
        d, F, held, n = self.d, self.width, self.held, self.n_shared
        init = nn.initializers.normal(self.init_std)
        router = self.param("router", init, (d, self.published),
                            jnp.float32)
        gate = self.param("gate", init, (held, d, F))
        up = self.param("up", init, (held, d, F))
        down = self.param("down", init, (held, F, d))
        bias = self.param("bias", nn.initializers.zeros, (self.published,),
                          jnp.float32) if self.select_bias else None
        idx, w = sigmoid_topk(h, router, self.top_k, bias, self.scale)
        hc = h.astype(self.dtype)
        y, *counts = dropless_experts(
            hc, idx, w, gate.astype(self.dtype), up.astype(self.dtype),
            down.astype(self.dtype), offset=self.offset, valid=valid,
            published=self.published)
        with jax.named_scope("moe_shared"):
            def dense(width, name):
                return nn.Dense(width, use_bias=False, dtype=self.dtype,
                                name=name, kernel_init=init)

            a = nn.silu(dense(n * F, "shared_gate")(hc)) \
                * dense(n * F, "shared_up")(hc)
            shared = dense(d, "shared_down")(a).astype(jnp.float32)
            y = y + shared / n
        return y, tuple(counts)
