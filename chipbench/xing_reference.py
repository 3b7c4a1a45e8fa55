"""Plain reference for the Xing4.0 configurations (`xing4-29b-a4b` names it
through `configs/xing4-29b-a4b_reference.py`; the sizes come from the
configuration's file): the forward pass in straightforward ``jax.numpy``,
float32, matrix products at ``highest`` precision.  No kernel, no cache,
no absorbed path (keys and values are expanded from the latent at EVERY
position), no sort, no grouped product, the Sinkhorn loop written out,
and nothing imported from the program under test.

The model (``model_type`` ``xing4_0``; the configuration's file names the
published ``config.json`` and, under ``assumed``, each point it cannot
confirm).  ``X`` [n, d] the ``n = hc_mult`` residual streams of a token,
no bias anywhere.  ``n(x) = x / sqrt(mean(x^2) + eps) * g``; gated MLPs
``W_down (silu(W_gate h) * W_up h)``::

    X_0 = the token's embedding, n times
    each sublayer F (attention, then the dense MLP in the first
    first_k_dense_replace blocks and the experts after), with float32
    parameters phi [n d, n + n + n n], b [n + n + n n], a [3]
    (manifold-constrained hyper-connections, arXiv:2512.24880, over
    hyper-connections, arXiv:2409.19606):
      x~     = vec(X) / sqrt(mean(vec(X)^2) + eps)
      z      = x~ phi                       (columns: pre | post | res)
      H_pre  = sigmoid(a_0 z_pre + b_pre)                       [n]
      H_post = 2 sigmoid(a_1 z_post + b_post)                   [n]
      M_0    = exp(clip(a_2 mat(z_res) + b_res, clamp_min, clamp_max))
      hc_sinkhorn_iters times: every row of M divided by its sum +
      hc_eps, then every column by its; H_res = the result    [n, n]
      y      = F(n(H_pre X))
      X'     = H_res X + H_post^T y
    out: n_f(sum of the streams), logits = W_head x, the head untied.

    Attn: c_q = n(W_dq h); [q_nope | q_rope] = W_uq c_q a head (dn | dr)
          [c_kv | k_r] = W_dkv h (r | dr); c_kv <- n(c_kv)
          q_rope, k_r rotated, pairs (2j, 2j+1) interleaved, by YaRN's
          frequencies (``yarn_inv_freq``); k_r is one for all heads
          [k_nope | v] = W_ukv c_kv a head (dn | dv)
          scores (q_nope . k_nope + q_rope . k_r) * s, causal softmax,
          W_o [heads of p . v];  s = (dn + dr)^-0.5 (0.1 ln factor + 1)^2
    MoE:  s = sigmoid(W_r h) over the experts; T = the k largest of
          s + bias (the bias chooses and does not weigh);
          w_e = routed_scaling_factor * s_e / sum_T s;
          MoE(h) = sum_{e in T} w_e E_e(h) + E_shared(h)

The multi-token-prediction module is not part of the next-token forward.

**Weights** are not arrays handed in: ``forward`` takes the PRNG key and
makes every tensor from ``fold_in`` of it where it is applied (``leaf``),
so that the float32 weights of one expert, not of the model (16.2 GB),
are live at a time: the expert blocks run under one ``lax.scan``, a
block's experts under another.  ``chipbench/adapters/xing.py`` makes the
program's parameters from the same ``leaf``, tensor by tensor.  The
values are those of a bfloat16 checkpoint (``as_published``) but for the
router, its bias and the hyper-connection parameters, which are float32
on both sides: the program's resident cast is exact, and what the
comparison reads is the arithmetic, not a second set of weights.  Because
bfloat16 holds them, ``hold`` can make them once and keep them at 7.2 GB,
and ``forward`` takes that in the key's place and computes the same
numbers: the check calls ``forward`` once a request, and from the key
every call made all 4.05B values again (PERF.md section 6, PR 36).

``precision`` re-computes the same mathematics with every matrix product
fed lower-precision operands, for the control that ``chipbench/check.py``
has to fail: ``bfloat16`` is what the configuration states, ``fp8`` (e4m3
with one scale per tensor) the step below it.  The router's product and
the hyper-connections' stay float32 in every precision, as they are in
the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = ("float32", "bfloat16", "fp8")
_FP8_MAX = 448.0
#: a block's tensors, in the order their keys are folded in; the table,
#: the final norm's gain and the head are layer "-1"
LEAVES = ("hc_attn_phi", "hc_attn_b", "hc_attn_a", "ln_attn_g",
          "dq_w", "q_norm_g", "uq_w", "dkv_w", "kv_norm_g", "ukv_w", "o_w",
          "hc_mlp_phi", "hc_mlp_b", "hc_mlp_a", "ln_mlp_g",
          "mlp_gate_w", "mlp_up_w", "mlp_down_w",
          "router_w", "router_b", "gate_w", "up_w", "down_w",
          "shared_gate_w", "shared_up_w", "shared_down_w")
GLOBAL_LEAVES = ("wte", "lnf_g", "head_w")
#: float32 on both sides, never rounded to what bfloat16 holds
FLOAT32_LEAVES = ("router_w", "router_b", "hc_attn_phi", "hc_attn_b",
                  "hc_attn_a", "hc_mlp_phi", "hc_mlp_b", "hc_mlp_a")
#: query rows to a block of scores, so that a row of 10,240 positions fits
QUERY_BLOCK = 512
#: a routed expert's down-projection beside the shared expert's (``leaf``)
ROUTED_DOWN = 0.25


# -- sizes ---------------------------------------------------------------------

def sizes(model: dict) -> dict:
    rope = model["rope_scaling"]
    return {
        "L": int(model["num_hidden_layers"]),
        "dense": int(model["first_k_dense_replace"]),
        "d": int(model["hidden_size"]),
        "F_dense": int(model["intermediate_size"]),
        "F": int(model["moe_intermediate_size"]),
        "H": int(model["num_attention_heads"]),
        "rq": int(model["q_lora_rank"]), "r": int(model["kv_lora_rank"]),
        "dn": int(model["qk_nope_head_dim"]),
        "dr": int(model["qk_rope_head_dim"]),
        "dv": int(model["v_head_dim"]),
        "V": int(model["vocab_size"]),
        "E": int(model["n_routed_experts"]),
        "k": int(model["num_experts_per_tok"]),
        "shared": int(model["n_shared_experts"]),
        "scale": float(model["routed_scaling_factor"]),
        "n": int(model["hc_mult"]),
        "iters": int(model["hc_sinkhorn_iters"]),
        "hc_eps": float(model["hc_eps"]),
        "clamp": (float(model["mhc_h_res_clamp_min"]),
                  float(model["mhc_h_res_clamp_max"])),
        "eps": float(model["rms_norm_eps"]),
        "theta": float(model["rope_theta"]),
        "factor": float(rope["factor"]),
        "original": int(rope["original_max_position_embeddings"]),
        "beta_fast": float(rope["beta_fast"]),
        "beta_slow": float(rope["beta_slow"]),
        "mscale_all_dim": float(rope["mscale_all_dim"]),
    }


def leaf_shape(model: dict, name: str) -> tuple:
    """The shape of one tensor as ``leaf`` makes it.  ``gate_w`` / ``up_w``
    / ``down_w`` are ONE expert's; ``ukv_w`` [r, H * (dn + dv)] has head
    ``h``'s columns at ``h (dn + dv)``: ``dn`` of its keys, then ``dv`` of
    its values; ``uq_w`` [rq, H * (dn + dr)] likewise (``dn | dr``);
    ``dkv_w`` [d, r + dr] (the latent, then the shared key)."""
    z = sizes(model)
    d, F, n, H = z["d"], z["F"], z["n"], z["H"]
    c = n * (n + 2)
    hc = {"phi": (n * d, c), "b": (c,), "a": (3,)}
    if name.startswith("hc_"):
        return hc[name.rsplit("_", 1)[1]]
    return {
        "wte": (z["V"], d), "lnf_g": (d,), "head_w": (d, z["V"]),
        "ln_attn_g": (d,), "ln_mlp_g": (d,),
        "dq_w": (d, z["rq"]), "q_norm_g": (z["rq"],),
        "uq_w": (z["rq"], H * (z["dn"] + z["dr"])),
        "dkv_w": (d, z["r"] + z["dr"]), "kv_norm_g": (z["r"],),
        "ukv_w": (z["r"], H * (z["dn"] + z["dv"])),
        "o_w": (H * z["dv"], d),
        "mlp_gate_w": (d, z["F_dense"]), "mlp_up_w": (d, z["F_dense"]),
        "mlp_down_w": (z["F_dense"], d),
        "router_w": (d, z["E"]), "router_b": (z["E"],),
        "gate_w": (d, F), "up_w": (d, F), "down_w": (F, d),
        "shared_gate_w": (d, z["shared"] * F),
        "shared_up_w": (d, z["shared"] * F),
        "shared_down_w": (z["shared"] * F, d),
    }[name]


def leaf(model: dict, key, name: str, layer=-1, expert=None):
    """Float32 tensor ``name`` of block ``layer`` (or the model's own:
    ``wte``, ``lnf_g``, ``head_w``), of routed expert ``expert`` for the
    three expert matrices.  Traceable in ``key``, ``layer`` and
    ``expert``.  Matrices are normal with ``init_std`` (0.02), the
    projections back into the streams (``o_w``, ``mlp_down_w``,
    ``down_w``, ``shared_down_w``) scaled by 1/sqrt(2 * layers), and a
    ROUTED expert's ``down_w`` by ``ROUTED_DOWN`` (1/4) besides: each of a
    token's four experts weighs 2 x ~1/4, so at the shared expert's scale
    the routed part is as large as everything else a block adds, and the
    four are independent random functions where a trained model's experts
    near a routing tie resemble one another.  With every expert held, a
    fourth expert swapped by bfloat16 rounding of the router's input then
    moved a logit by up to 3.8 of a spread of 1.2 and 5-7 % of the served
    tokens were off the float32 reference's best, in the program and in
    the reference computed in bfloat16 alike (PERF.md section 2, PR 36);
    quartered, the same swaps move a logit by a fourteenth of that.  Norm
    gains are 1 + 0.02 n so that a mistake in them shows.  The router has
    the same 0.02 (its scores spread by ~1.2 before the sigmoid) and its
    selection bias 0.1 n: several gaps between neighbouring scores, so it
    changes what is chosen for most tokens.

    The hyper-connections are seeded so that their dynamic part shows:
    ``phi`` is normal with 1 / sqrt(n d), so ``z = x~ phi`` is of unit
    spread from token to token; the scalars ``a`` are (1, 1, 0.75); ``b``
    is 0.5 n for ``pre`` and ``post``, and for ``res`` 2 on the diagonal +
    n, with 29 added to its first ROW: that row's entries lie at 29-33
    before the clip at 30, which therefore binds for most tokens among
    entries that compete with one another under the row's normalisation
    (an implementation without the clip, without the dynamic part, or
    with fewer iterations of a matrix spread over e^+-3 gives other
    coefficients).

    Every tensor but ``FLOAT32_LEAVES`` is rounded to what bfloat16 holds
    (``as_published``)."""
    if isinstance(key, dict):
        return _held(model, key, name, layer, expert)
    z = sizes(model)
    std = float(model.get("init_std", 0.02))
    names = LEAVES + GLOBAL_LEAVES
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 1),
                           names.index(name))
    if expert is not None:
        k = jax.random.fold_in(k, expert)
    w = jax.random.normal(k, leaf_shape(model, name), jnp.float32)
    n = z["n"]
    if name.endswith("_phi"):
        return w / math.sqrt(n * z["d"])
    if name.endswith("_a"):
        return jnp.asarray([1.0, 1.0, 0.75], jnp.float32)
    if name.startswith("hc_"):          # the biases b
        res = w[2 * n:].reshape(n, n) + 2.0 * jnp.eye(n)
        res = res.at[0].add(29.0)
        return jnp.concatenate([0.5 * w[:2 * n], res.reshape(-1)])
    if name == "router_b":
        return 0.1 * w
    if name.endswith("_g"):
        w = 1.0 + 0.02 * w
    elif name in ("o_w", "mlp_down_w", "down_w", "shared_down_w"):
        w = (std / math.sqrt(2 * z["L"])) * w
        if name == "down_w":
            w = ROUTED_DOWN * w
    else:
        w = std * w
    return w if name in FLOAT32_LEAVES else as_published(w)


#: the leaves that only a leading dense block has, and only an expert block
DENSE_LEAVES = ("mlp_gate_w", "mlp_up_w", "mlp_down_w")
EXPERT_LEAVES = ("router_w", "router_b", "gate_w", "up_w", "down_w",
                 "shared_gate_w", "shared_up_w", "shared_down_w")
ROUTED_LEAVES = ("gate_w", "up_w", "down_w")


def _blocks_with(name: str, z: dict) -> tuple:
    """``(first, count)`` of the blocks that have tensor ``name``."""
    dense = min(z["dense"], z["L"])
    if name in DENSE_LEAVES:
        return 0, dense
    if name in EXPERT_LEAVES:
        return dense, z["L"] - dense
    return 0, z["L"]


def hold(model: dict, key) -> dict:
    """Every tensor of ``leaf`` but the embedding table, made ONCE from
    ``key`` and kept: ``{name: [blocks that have it, (experts,) ...]}`` in
    bfloat16, which holds ``leaf``'s values exactly (``as_published``),
    and float32 for ``FLOAT32_LEAVES``; ``"key"`` for what is not kept.
    7.16 GB at the published sizes, where float32 would be 16.2.
    ``forward`` takes it in the key's place and computes the same numbers
    (``leaf`` hands back the kept tensor as float32); made from the key,
    ``forward`` makes every tensor again for every request it is handed,
    4.05B normal values a call.  For a chip that holds nothing else: the
    check runs after the program's state is freed.  The table (0.94 GB,
    gathered once a call) is left out so that the control's two forwards
    fit beside the rest (described compile: 7.16 GB + 8.87 of
    temporaries, of 16.91)."""
    z = sizes(model)

    def resident(name, layer=-1, expert=None):
        a = leaf(model, key, name, layer, expert)
        return a if name in FLOAT32_LEAVES else a.astype(jnp.bfloat16)

    held = {"key": key, "lnf_g": resident("lnf_g"),
            "head_w": resident("head_w")}
    for name in LEAVES:
        first, count = _blocks_with(name, z)
        if not count:
            continue
        if name in ROUTED_LEAVES:
            def of_block(layer, name=name):
                return lax.map(lambda e: resident(name, layer, e),
                               jnp.arange(z["E"]))
        else:
            def of_block(layer, name=name):
                return resident(name, layer)
        held[name] = lax.map(of_block, jnp.arange(first, first + count))
    return held


def _held(model: dict, held: dict, name: str, layer, expert):
    if name not in held:
        return leaf(model, held["key"], name, layer, expert)
    a = held[name]
    if name not in GLOBAL_LEAVES:
        a = a[layer - _blocks_with(name, sizes(model))[0]]
    if expert is not None:
        a = a[expert]
    return a.astype(jnp.float32)


def as_published(w):
    """Float32 values that bfloat16 holds exactly: a published checkpoint
    is bfloat16, and a reference is run on the values the server loads.
    ``reduce_precision`` and not a cast there and back: the compiler may
    drop such a pair inside a fusion (PERF.md section 2)."""
    return lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)


# -- arithmetic ----------------------------------------------------------------

def _fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _FP8_MAX
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _operand(a, precision: str):
    if precision == "float32":
        return a
    if precision == "fp8":
        a = _fp8(a)
    return a.astype(jnp.bfloat16)


def _einsum(eq: str, a, b, precision: str):
    return jnp.einsum(eq, _operand(a, precision), _operand(b, precision),
                      precision="highest",
                      preferred_element_type=jnp.float32)


def rms_norm(x, gain, eps: float):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * gain


def yarn_inv_freq(z: dict):
    """[dr / 2] float32: pair ``j`` of the rotated ``dr`` dimensions turns
    by this a position.  ``theta ** (-2j / dr)`` for the pairs that turn
    more than ``beta_fast`` times over the original positions, that over
    ``factor`` for those that turn fewer than ``beta_slow`` times, and a
    linear ramp over the pair numbers between the two (the lower bound
    rounded down, the upper up) blends the rest."""
    dr = z["dr"]
    plain = z["theta"] ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)

    def pair_that_turns(times):
        return dr * math.log(z["original"] / (times * 2 * math.pi)) \
            / (2 * math.log(z["theta"]))

    low = max(math.floor(pair_that_turns(z["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(z["beta_slow"])), dr - 1)
    ramp = jnp.clip((jnp.arange(dr // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain / z["factor"] * ramp + plain * (1.0 - ramp)


def softmax_scale(z: dict) -> float:
    m = 0.1 * z["mscale_all_dim"] * math.log(z["factor"]) + 1.0 \
        if z["factor"] > 1 else 1.0
    return (z["dn"] + z["dr"]) ** -0.5 * m * m


def rotary(x, inv_freq):
    """Interleaved rotary embedding of ``x`` [B, T, H, D] at positions
    0..T-1: the pair ``(x[2j], x[2j + 1])`` turns by ``t * inv_freq[j]``."""
    T = x.shape[1]
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def causal_attention(q, k, v, scale: float, precision: str):
    """``q``, ``k`` [B, T, H, dq], ``v`` [B, T, H, dv]: query block by
    query block, each against the keys up to its own last row."""
    B, T, H, _ = q.shape
    out = []
    for lo in range(0, T, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, T)
        s = _einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi],
                    precision) * scale
        seen = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out.append(_einsum("bhqk,bkhd->bqhd", p, v[:, :hi], precision))
    return jnp.concatenate(out, axis=1)


def attention(h, model: dict, key, layer, precision: str = "float32"):
    """Latent attention of ``h`` [B, T, d], keys and values expanded
    from the latent at every position."""
    z = sizes(model)
    B, T, _ = h.shape
    H, r, dn, dr, dv = z["H"], z["r"], z["dn"], z["dr"], z["dv"]

    def w(name):
        return leaf(model, key, name, layer)

    c_q = rms_norm(_einsum("btd,de->bte", h, w("dq_w"), precision),
                   w("q_norm_g"), z["eps"])
    q = _einsum("bte,ef->btf", c_q, w("uq_w"), precision) \
        .reshape(B, T, H, dn + dr)
    kv = _einsum("btd,de->bte", h, w("dkv_w"), precision)
    c_kv = rms_norm(kv[..., :r], w("kv_norm_g"), z["eps"])
    inv_freq = yarn_inv_freq(z)
    q_rope = rotary(q[..., dn:], inv_freq)
    k_rope = rotary(kv[:, :, None, r:], inv_freq)
    up = _einsum("btc,cf->btf", c_kv, w("ukv_w"), precision) \
        .reshape(B, T, H, dn + dv)
    k = jnp.concatenate(
        [up[..., :dn], jnp.broadcast_to(k_rope, (B, T, H, dr))], axis=-1)
    q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    y = causal_attention(q, k, up[..., dn:], softmax_scale(z), precision)
    return _einsum("bte,ed->btd", y.reshape(B, T, H * dv), w("o_w"),
                   precision)


def route(h, router_w, router_b, z: dict):
    """``[..., E]`` combine weights: ``scale * s_e / sum_T s`` on the
    ``k`` experts whose ``s + bias`` is largest, 0 elsewhere.  Float32
    in every precision."""
    s = jax.nn.sigmoid(jnp.einsum("...d,de->...e", h, router_w,
                                  precision="highest"))
    _, idx = lax.top_k(s + router_b, z["k"])
    picked = jax.nn.one_hot(idx, z["E"], dtype=s.dtype)     # [..., k, E]
    chosen = jnp.sum(picked, axis=-2)                       # [..., E] 0 / 1
    return z["scale"] * s * chosen / jnp.sum(s * chosen, axis=-1,
                                             keepdims=True)


def _gated(h, gate_w, up_w, down_w, precision: str):
    a = jax.nn.silu(_einsum("...d,df->...f", h, gate_w, precision)) \
        * _einsum("...d,df->...f", h, up_w, precision)
    return _einsum("...f,fd->...d", a, down_w, precision)


def routed_sum(rows, weights, experts, matrices, rooms,
               precision: str = "float32"):
    """``sum_e weights[:, e] * E_e(rows)`` over ``experts``, a loop over
    them.  ``rows`` [N, d]; ``weights`` [N, E], 0 where a token did not
    choose an expert; ``matrices(e)`` the three of expert ``e``.
    ``rooms`` (ascending): where at most ``rooms[i]`` tokens chose an
    expert, only those go through it, in the smallest such room; where
    more than the largest did, every token does, under the mask.  Either
    way exact: no token is left out."""
    N = rows.shape[0]

    def one(acc, e):
        mats = matrices(e)
        we = jnp.take(weights, e, axis=-1)                  # [N]

        def chosen_only(room):
            def run(acc):
                at = jnp.nonzero(we > 0, size=room, fill_value=N)[0]
                y = _gated(jnp.take(rows, at, axis=0, mode="fill",
                                    fill_value=0.0), *mats, precision)
                share = jnp.take(we, at, mode="fill", fill_value=0.0)
                return acc.at[at].add(share[:, None] * y, mode="drop")
            return run

        def every_token(acc):
            return acc + we[:, None] * _gated(rows, *mats, precision)

        chose = jnp.sum(we > 0)
        tight = sum((chose > room).astype(jnp.int32) for room in rooms)
        return lax.switch(tight, [chosen_only(r) for r in rooms]
                          + [every_token], acc), None

    return lax.scan(one, jnp.zeros_like(rows), experts)[0]


def expert_rooms(N: int, z: dict) -> tuple:
    """The rooms of ``routed_sum`` for ``N`` tokens: one and a half times
    an expert's even share (or a sixteenth of the tokens), then four times
    that: the check pads a request to the context with one repeated token,
    a fifth of its rows, and about a quarter of a block's experts share
    those (by hand on the chip: PERF.md section 6, PR 36)."""
    if N <= 64:
        return (N,)
    room = max(N // 16, 3 * N * z["k"] // (2 * z["E"]))
    return tuple(r for r in (room, 4 * room) if r < N)


def experts(h, model: dict, key, layer, precision: str = "float32"):
    """The routed experts' weighted sum and the shared expert."""
    z = sizes(model)
    w = route(h, leaf(model, key, "router_w", layer),
              leaf(model, key, "router_b", layer), z)
    rows = h.reshape(-1, h.shape[-1])
    N = rows.shape[0]
    routed = routed_sum(
        rows, w.reshape(N, -1), jnp.arange(z["E"]),
        lambda e: [leaf(model, key, n, layer, e)
                   for n in ("gate_w", "up_w", "down_w")],
        expert_rooms(N, z), precision).reshape(h.shape)
    shared = _gated(h, *(leaf(model, key, "shared_" + m, layer)
                         for m in ("gate_w", "up_w", "down_w")), precision)
    return routed + shared


def dense_mlp(h, model: dict, key, layer, precision: str = "float32"):
    return _gated(h, *(leaf(model, key, "mlp_" + m, layer)
                       for m in ("gate_w", "up_w", "down_w")), precision)


def hyper_coefficients(X, phi, b, a, z: dict, iters=None):
    """``(H_pre [..., n], H_post [..., n], H_res [..., n, n])`` from the
    streams ``X`` [..., n, d].  ``iters``: the Sinkhorn iterations (the
    configuration's; a test asks for fewer)."""
    n = z["n"]
    x = X.reshape(X.shape[:-2] + (-1,))
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                      + z["eps"])
    c = jnp.einsum("...x,xc->...c", x, phi, precision="highest")
    pre = jax.nn.sigmoid(a[0] * c[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * c[..., n:2 * n] + b[n:2 * n])
    res = (a[2] * c[..., 2 * n:] + b[2 * n:]).reshape(c.shape[:-1] + (n, n))
    m = jnp.exp(jnp.clip(res, *z["clamp"]))
    for _ in range(z["iters"] if iters is None else iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + z["hc_eps"])  # rows
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + z["hc_eps"])  # columns
    return pre, post, m


def sublayer(X, F, model: dict, key, layer, which: str):
    """``X' = H_res X + H_post^T F(n(H_pre X))`` with sublayer ``which``'s
    (``attn`` / ``mlp``) hyper-connection and norm."""
    z = sizes(model)
    pre, post, res = hyper_coefficients(
        X, *(leaf(model, key, f"hc_{which}_{p}", layer)
             for p in ("phi", "b", "a")), z)
    h = jnp.sum(pre[..., :, None] * X, axis=-2)
    y = F(rms_norm(h, leaf(model, key, f"ln_{which}_g", layer), z["eps"]))
    return jnp.sum(res[..., :, :, None] * X[..., None, :, :], axis=-2) \
        + post[..., :, None] * y[..., None, :]


def block(X, model: dict, key, layer, is_dense: bool,
          precision: str = "float32"):
    """One block on the streams ``X`` [B, T, n, d]; ``layer`` may be
    traced, ``is_dense`` (a leading dense block) not."""
    X = sublayer(X, lambda h: attention(h, model, key, layer, precision),
                 model, key, layer, "attn")
    mlp = dense_mlp if is_dense else experts
    return sublayer(X, lambda h: mlp(h, model, key, layer, precision),
                    model, key, layer, "mlp")


def forward(key, tokens, model: dict, precision: str = "float32",
            remat: bool = False):
    """Next-token logits ``[B, T, vocab]`` float32 for token ids ``[B,
    T]``.  ``key``: the PRNG key every weight is made from, or what
    ``hold`` made of it once."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    z = sizes(model)
    x = leaf(model, key, "wte")[tokens]
    X = jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (z["n"], z["d"]))
    for layer in range(min(z["dense"], z["L"])):
        X = block(X, model, key, layer, True, precision)

    def body(X, layer):
        return block(X, model, key, layer, False, precision), None

    if remat:
        body = jax.checkpoint(body)
    if z["L"] > z["dense"]:
        X, _ = lax.scan(body, X, jnp.arange(z["dense"], z["L"]))
    x = rms_norm(jnp.sum(X, axis=-2), leaf(model, key, "lnf_g"), z["eps"])
    return _einsum("btd,dv->btv", x, leaf(model, key, "head_w"), precision)


def loss(key, tokens, targets, model: dict, precision: str = "float32"):
    """Mean next-token cross-entropy over every position."""
    logits = forward(key, tokens, model, precision, remat=True)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(picked)
