"""Plain reference for the Kimi Linear configurations (`kimi-linear-48b-a3b`
names it through `configs/kimi-linear-48b-a3b_reference.py`; the sizes come
from the configuration's file): the forward pass in straightforward
``jax.numpy``, float32, matrix products at ``highest`` precision.  No
kernel, no cache, no state carried between calls, the delta rule's
recurrence POSITION BY POSITION under one ``lax.scan`` (never the
chunkwise form the program's prefill takes: the two are independent), no
absorbed latent path, no sort, no grouped product, and nothing imported
from the program under test.

The model (``model_type`` ``kimi_linear``, arXiv:2510.26692; the
configuration's file names the published ``config.json`` and, under
``assumed``, each point it cannot confirm, marked **(A)** here).  ``d`` =
hidden size, ``H`` heads of ``K = V`` = ``linear_attn_config.head_dim`` in a
KDA layer, ``P = H K``, no bias on a projection.  ``n(x) = x / sqrt(mean(x^2)
+ eps) * g``.  Pre-norm, plain residual: ``x <- x + Attn(n(x))``, ``x <- x +
FFN(n(x))``::

    KDA (layers ``kda_layers``, 1-based), u = n(x):
      q~ = u W_q, k~ = u W_k, v~ = u W_v                 (d -> P each)
      (A) short convolution: depthwise, causal, kernel 4, no bias, one set
      of weights each for q, k and v, SiLU after it:
        c_t = sum_{j=0..3} w[:, j] * z~_{t-3+j}, zeros before position 0
      a head: q_t = L2norm(silu(c^q_t)) K^-1/2  (A: the scale, as the public
              flash-linear-attention kernel applies it),
              k_t = L2norm(silu(c^k_t)), v_t = silu(c^v_t);
              L2norm(x) = x / sqrt(sum x^2 + 1e-6)
      (A) decay, a channel of the key: g_t = -exp(A_log_h) softplus((u W_a1)
          W_a2 + dt_bias), W_a1 d -> K, W_a2 K -> P, A_log [H], dt_bias [P];
          alpha_t = exp(g_t) in (0, 1)^K a head
      beta_t = sigmoid(u W_b)                             (d -> H)
      S_-1 = 0 [K, V] a head;  S' = Diag(alpha_t) S_{t-1};
      S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t
      W_o [ norm_head(o_t) * sigmoid((u W_g1) W_g2 + b_g) ]   (A: the bias b_g;
          norm_head over a head's V values with a learned [V] gain; W_g1 d ->
          K, W_g2 K -> P, W_o P -> d)
      the state and everything that makes g, beta and the update is
      float32 in every ``precision``.
    MLA without positions (layers ``full_attn_layers``), u = n(x):
      q = u W_q a head (q_nope dn | q_pe dr); kv = u W_kva (r + dr):
      c = n(kv[:r]), k_pe = kv[r:], shared by all heads, NEITHER rotated
      (``mla_use_nope``); [k_nope | v] = c W_kvb a head (dn | dv)
      scores (q_nope . k_nope + q_pe . k_pe) / sqrt(dn + dr) (A), causal
      softmax, W_o [heads of p . v]
    FFN: layer 1 (``first_k_dense_replace``) a gated MLP ``W_down
      (silu(W_gate h) * W_up h)`` of ``intermediate_size``; after it
      s = sigmoid(W_r h) over ALL ``num_experts_published`` outputs
      (float32); T = the k largest of s + bias (the bias chooses and does
      not weigh); w_e = routed_scaling_factor * s_e / sum_T s;
      sum_{e in T, e held here} w_e E_e(h) + E_shared(h): of the experts,
      ``num_experts`` are held here from ``expert_offset``; a pair routed
      to an absent expert adds nothing, in program and reference alike.
    out: logits = n_f(x) W_head, rows 0..vocab_size of the untied table.

**Weights** are tensors of a PRNG key (``leaf``: ``fold_in`` of it), seeded
so that every mechanism shows in a logit, and what bfloat16 holds but for
``FLOAT32_LEAVES`` (the router and its bias, and everything that makes a
KDA layer's decay and beta), which are float32 on both sides: the
program's resident cast loses nothing.  ``hold`` makes them once and keeps
them as bfloat16 (4.7 GB at the cut; float32 would be 9.5), and
``forward`` takes that in the key's place and computes the same numbers.

``precision`` re-computes the same mathematics with every matrix product's
operands lowered, for the control that ``chipbench/check.py`` has to fail:
``bfloat16`` is what the configuration states, ``fp8`` (e4m3 with one scale
per tensor) the step below it.  The router's product, the gate's and
beta's, and the recurrence stay float32 in every precision, as they are in
the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.xing_reference import as_published, routed_sum

PRECISIONS = ("float32", "bfloat16", "fp8")
_FP8_MAX = 448.0
#: the tensors of a KDA sublayer, of a latent one, of a leading dense
#: layer's MLP and of an expert layer
KDA_LEAVES = ("kda_q_w", "kda_k_w", "kda_v_w", "conv_q_w", "conv_k_w",
              "conv_v_w", "a1_w", "a2_w", "A_log", "dt_bias", "b_w", "g1_w",
              "g2_w", "g_bias", "o_norm_g", "kda_o_w")
MLA_LEAVES = ("mla_q_w", "dkv_w", "kv_norm_g", "ukv_w", "mla_o_w")
DENSE_LEAVES = ("mlp_gate_w", "mlp_up_w", "mlp_down_w")
EXPERT_LEAVES = ("router_w", "router_b", "gate_w", "up_w", "down_w",
                 "shared_gate_w", "shared_up_w", "shared_down_w")
#: a block's tensors, in the order their keys are folded in; the table,
#: the final norm's gain and the head are layer "-1"
LEAVES = ("ln_attn_g",) + KDA_LEAVES + MLA_LEAVES + ("ln_mlp_g",) \
    + DENSE_LEAVES + EXPERT_LEAVES
GLOBAL_LEAVES = ("wte", "lnf_g", "head_w")
#: float32 on both sides, never rounded to what bfloat16 holds
FLOAT32_LEAVES = ("router_w", "router_b", "a1_w", "a2_w", "A_log", "dt_bias",
                  "b_w")
ROUTED_LEAVES = ("gate_w", "up_w", "down_w")
#: query rows to a block of scores, so that a row of 5,248 positions fits
QUERY_BLOCK = 512
#: a routed expert's down-projection beside the shared expert's
#: (``xing_reference.ROUTED_DOWN``'s lesson: eight experts a token at ~0.3
#: each; a swap of the eighth by a rounding must not move a logit by more
#: than the arithmetic under test does)
ROUTED_DOWN = 0.25
#: the selection bias beside scores ~0.004 apart: it re-orders neighbours
#: without deciding which eighth of the experts (the held ones) is chosen
ROUTER_BIAS = 0.02
#: the spread of what the decay's and the output gate's second matrices add
#: before the softplus / the sigmoid
DECAY_SPREAD = 0.5
GATE_SPREAD = 1.0


# -- sizes ---------------------------------------------------------------------

def sizes(model: dict) -> dict:
    lin = model["linear_attn_config"]
    return {
        "L": int(model["num_hidden_layers"]),
        "dense": int(model["first_k_dense_replace"]),
        "kda": tuple(int(i) - 1 for i in lin["kda_layers"]),
        "full": tuple(int(i) - 1 for i in lin["full_attn_layers"]),
        "d": int(model["hidden_size"]),
        "F_dense": int(model["intermediate_size"]),
        "F": int(model["moe_intermediate_size"]),
        "Hk": int(lin["num_heads"]), "K": int(lin["head_dim"]),
        "P": int(lin["num_heads"]) * int(lin["head_dim"]),
        "taps": int(lin["short_conv_kernel_size"]),
        "H": int(model["num_attention_heads"]),
        "r": int(model["kv_lora_rank"]),
        "dn": int(model["qk_nope_head_dim"]),
        "dr": int(model["qk_rope_head_dim"]),
        "dv": int(model["v_head_dim"]),
        "V": int(model["vocab_size"]),
        "E": int(model.get("num_experts_published", model["num_experts"])),
        "held": int(model["num_experts"]),
        "offset": int(model.get("expert_offset", 0)),
        "k": int(model["num_experts_per_token"]),
        "shared": int(model["num_shared_experts"]),
        "scale": float(model["routed_scaling_factor"]),
        "eps": float(model["rms_norm_eps"]),
    }


def share_of(model: dict, part: int, parts: int) -> dict:
    """The model of share ``part`` of ``parts``: its experts of the
    published ``num_experts_published``."""
    E = sizes(model)["E"]
    return {**model, "num_experts_published": E, "num_experts": E // parts,
            "expert_offset": part * (E // parts)}


def leaf_shape(model: dict, name: str) -> tuple:
    """The shape of one tensor as ``leaf`` makes it.  ``gate_w`` / ``up_w``
    / ``down_w`` are ONE expert's; a convolution's [P, taps] holds the tap
    on ``z_{t-3}`` first and the tap on ``z_t`` last; ``mla_q_w`` [d, H *
    (dn + dr)] has head ``h``'s columns at ``h (dn + dr)`` (``dn | dr``),
    ``ukv_w`` [r, H * (dn + dv)] likewise (``dn | dv``), ``dkv_w`` [d, r +
    dr] the latent, then the shared key."""
    z = sizes(model)
    d, F, P, K, H = z["d"], z["F"], z["P"], z["K"], z["H"]
    return {
        "wte": (z["V"], d), "lnf_g": (d,), "head_w": (d, z["V"]),
        "ln_attn_g": (d,), "ln_mlp_g": (d,),
        "kda_q_w": (d, P), "kda_k_w": (d, P), "kda_v_w": (d, P),
        "conv_q_w": (P, z["taps"]), "conv_k_w": (P, z["taps"]),
        "conv_v_w": (P, z["taps"]),
        "a1_w": (d, K), "a2_w": (K, P), "A_log": (z["Hk"],),
        "dt_bias": (P,), "b_w": (d, z["Hk"]),
        "g1_w": (d, K), "g2_w": (K, P), "g_bias": (P,),
        "o_norm_g": (K,), "kda_o_w": (P, d),
        "mla_q_w": (d, H * (z["dn"] + z["dr"])),
        "dkv_w": (d, z["r"] + z["dr"]), "kv_norm_g": (z["r"],),
        "ukv_w": (z["r"], H * (z["dn"] + z["dv"])),
        "mla_o_w": (H * z["dv"], d),
        "mlp_gate_w": (d, z["F_dense"]), "mlp_up_w": (d, z["F_dense"]),
        "mlp_down_w": (z["F_dense"], d),
        "router_w": (d, z["E"]), "router_b": (z["E"],),
        "gate_w": (d, F), "up_w": (d, F), "down_w": (F, d),
        "shared_gate_w": (d, z["shared"] * F),
        "shared_up_w": (d, z["shared"] * F),
        "shared_down_w": (z["shared"] * F, d),
    }[name]


def leaf(model: dict, key, name: str, layer=-1, expert=None):
    """Float32 tensor ``name`` of block ``layer`` (0-based; or the model's
    own: ``wte``, ``lnf_g``, ``head_w``), of routed expert ``expert`` (its
    published number) for the three expert matrices.  Traceable in
    ``key``, ``layer`` and ``expert``.

    Matrices are normal with ``init_std`` (0.02), the projections back
    into the stream (``kda_o_w``, ``mla_o_w``, ``mlp_down_w``, ``down_w``,
    ``shared_down_w``) scaled by 1/sqrt(2 * layers), a ROUTED expert's
    ``down_w`` by ``ROUTED_DOWN`` besides.  Norm gains 1 + 0.02 n.  The
    convolutions' taps n / 2: four of comparable size, so a tap left out
    or taken in the other order shows.  **The decay is seeded so that the
    state at position 3,000 is neither zero nor only the last chunk**:
    ``A_log`` = log of uniform(1, 16) a head, ``dt_bias`` the inverse
    softplus of log-uniform(0.001, 0.1) a channel, so a channel forgets
    over 0.6 to 1,000 positions; ``a1_w`` at ``init_std`` and ``a2_w``
    ``DECAY_SPREAD`` n / sqrt(K), so that a token moves its channels'
    rates by a factor of e^+-0.5; ``b_w`` at ``init_std`` (beta mostly in
    0.27-0.73).  The output gate: ``g1_w`` at ``init_std``, ``g2_w``
    ``GATE_SPREAD`` n / sqrt(K), ``g_bias`` 0.1 n.  The router 0.02 like
    the rest with every column centred over its inputs (a direction that
    every token shares then favours no expert: PR 41's second seeding),
    its selection bias ``ROUTER_BIAS`` n.

    Every tensor but ``FLOAT32_LEAVES`` is rounded to what bfloat16 holds
    (``as_published``), the draw itself first and every product before a
    sum, so that the program's init and ``hold``, two programs, make the
    same numbers whatever their compilers fuse."""
    if isinstance(key, dict):
        return _held(model, key, name, layer, expert)
    z = sizes(model)
    std = float(model.get("init_std", 0.02))
    names = LEAVES + GLOBAL_LEAVES
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 1),
                           names.index(name))
    if expert is not None:
        k = jax.random.fold_in(k, expert)
    shape = leaf_shape(model, name)
    if name in ("A_log", "dt_bias"):
        u = jax.random.uniform(k, shape, jnp.float32)
        if name == "A_log":
            return jnp.log(1.0 + 15.0 * u)
        rate = jnp.exp(math.log(0.001) + math.log(100.0) * u)
        return jnp.log(jnp.expm1(rate))
    w = as_published(jax.random.normal(k, shape, jnp.float32))
    back = std / math.sqrt(2 * z["L"])
    if name == "router_w":
        # (rounded again, so that two programs' sums make one number)
        return std * as_published(w - jnp.mean(w, axis=0, keepdims=True))
    if name in FLOAT32_LEAVES:
        return {"router_b": ROUTER_BIAS, "a1_w": std, "b_w": std,
                "a2_w": DECAY_SPREAD / math.sqrt(z["K"])}[name] * w
    if name.endswith("_g"):
        w = 1.0 + as_published(0.02 * w)
    elif name.startswith("conv_"):
        w = 0.5 * w
    elif name == "g2_w":
        w = (GATE_SPREAD / math.sqrt(z["K"])) * w
    elif name == "g_bias":
        w = 0.1 * w
    elif name == "down_w":
        w = (ROUTED_DOWN * back) * w
    elif name in ("kda_o_w", "mla_o_w", "mlp_down_w", "shared_down_w"):
        w = back * w
    else:
        w = std * w
    return as_published(w)


def layers_with(name: str, z: dict) -> tuple:
    """The blocks (0-based) that have tensor ``name``."""
    every = tuple(range(z["L"]))
    if name in KDA_LEAVES:
        return tuple(i for i in every if i in z["kda"])
    if name in MLA_LEAVES:
        return tuple(i for i in every if i in z["full"])
    if name in DENSE_LEAVES:
        return every[:z["dense"]]
    if name in EXPERT_LEAVES:
        return every[z["dense"]:]
    return every


def hold(model: dict, key) -> dict:
    """Every tensor of ``leaf``, made ONCE from ``key`` and kept: ``{name:
    [blocks that have it, (held experts,) ...]}`` in bfloat16, which holds
    ``leaf``'s values exactly, and float32 for ``FLOAT32_LEAVES``.
    ``forward`` takes it in the key's place and computes the same numbers.
    For a chip that holds nothing else: the check runs after the program's
    state is freed."""
    z = sizes(model)

    def resident(name, layer=-1, expert=None):
        a = leaf(model, key, name, layer, expert)
        return a if name in FLOAT32_LEAVES else a.astype(jnp.bfloat16)

    held = {"key": key, **{n: resident(n) for n in GLOBAL_LEAVES}}
    for name in LEAVES:
        at = layers_with(name, z)
        if not at:
            continue
        if name in ROUTED_LEAVES:
            def of_block(layer, name=name):
                return lax.map(lambda e: resident(name, layer, e),
                               z["offset"] + jnp.arange(z["held"]))
        else:
            def of_block(layer, name=name):
                return resident(name, layer)
        held[name] = lax.map(of_block, jnp.asarray(at))
    return held


def _held(model: dict, held: dict, name: str, layer, expert):
    z = sizes(model)
    a = held[name]
    if name not in GLOBAL_LEAVES:
        a = a[layers_with(name, z).index(int(layer))]
    if expert is not None:
        a = a[expert - z["offset"]]
    return a.astype(jnp.float32)


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


def _exact(eq: str, a, b):
    return jnp.einsum(eq, a, b, precision="highest")


def rms_norm(x, gain, eps: float):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * gain


def l2_norm(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + 1e-6)


def short_conv(z, w):
    """``c_t = sum_j w[:, j] z_{t - taps + 1 + j}``, zeros before position
    0.  ``z`` [B, T, P], ``w`` [P, taps]."""
    T, taps = z.shape[1], w.shape[1]
    padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + T] * w[:, j] for j in range(taps))


def delta_rule(q, k, v, g, beta):
    """The recurrence, a position at a time from a zero state.  ``q``,
    ``k``, ``g`` [B, T, H, K], ``v`` [B, T, H, V], ``beta`` [B, T, H].
    Returns ``o`` [B, T, H, V]."""
    B, T, H, K = k.shape

    def position(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[..., :, None] * S
        seen = jnp.einsum("bhkv,bhk->bhv", S, k, precision="highest")
        S = S + (beta[..., None, None] * k[..., :, None]) \
            * (v - seen)[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q, precision="highest")

    _, o = lax.scan(position, jnp.zeros((B, H, K, v.shape[-1]), jnp.float32),
                    tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def kda(u, model: dict, key, layer: int, precision: str = "float32"):
    """Kimi Delta Attention of ``u`` [B, T, d] (module docstring)."""
    z = sizes(model)
    B, T, _ = u.shape
    H, K = z["Hk"], z["K"]

    def w(name):
        return leaf(model, key, name, layer)

    def heads(name):
        c = short_conv(_einsum("btd,dp->btp", u, w(f"kda_{name}_w"),
                               precision), w(f"conv_{name}_w"))
        return jax.nn.silu(c).reshape(B, T, H, K)

    q = l2_norm(heads("q")) * K ** -0.5
    k, v = l2_norm(heads("k")), heads("v")
    rate = _exact("btk,kp->btp", _exact("btd,dk->btk", u, w("a1_w")),
                  w("a2_w")) + w("dt_bias")
    g = -jnp.exp(w("A_log"))[:, None] \
        * jax.nn.softplus(rate).reshape(B, T, H, K)
    beta = jax.nn.sigmoid(_exact("btd,dh->bth", u, w("b_w")))
    o = rms_norm(delta_rule(q, k, v, g, beta), w("o_norm_g"), z["eps"])
    gate = _einsum("btk,kp->btp",
                   _einsum("btd,dk->btk", u, w("g1_w"), precision),
                   w("g2_w"), precision) + w("g_bias")
    return _einsum("btp,pd->btd",
                   o.reshape(B, T, H * K) * jax.nn.sigmoid(gate),
                   w("kda_o_w"), precision)


def causal_attention(q, k, v, scale: float, precision: str):
    """``q``, ``k`` [B, T, H, dq], ``v`` [B, T, H, dv]: query block by
    query block, each against the keys up to its own last row."""
    T = q.shape[1]
    out = []
    for lo in range(0, T, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, T)
        s = _einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi],
                    precision) * scale
        seen = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out.append(_einsum("bhqk,bkhd->bqhd", p, v[:, :hi], precision))
    return jnp.concatenate(out, axis=1)


def latent_attention(u, model: dict, key, layer: int,
                     precision: str = "float32"):
    """Latent attention of ``u`` [B, T, d] with nothing rotated, keys and
    values expanded from the latent at every position."""
    z = sizes(model)
    B, T, _ = u.shape
    H, r, dn, dr, dv = z["H"], z["r"], z["dn"], z["dr"], z["dv"]

    def w(name):
        return leaf(model, key, name, layer)

    q = _einsum("btd,de->bte", u, w("mla_q_w"), precision) \
        .reshape(B, T, H, dn + dr)
    kv = _einsum("btd,de->bte", u, w("dkv_w"), precision)
    c = rms_norm(kv[..., :r], w("kv_norm_g"), z["eps"])
    up = _einsum("btc,cf->btf", c, w("ukv_w"), precision) \
        .reshape(B, T, H, dn + dv)
    k = jnp.concatenate(
        [up[..., :dn], jnp.broadcast_to(kv[:, :, None, r:], (B, T, H, dr))],
        axis=-1)
    y = causal_attention(q, k, up[..., dn:], (dn + dr) ** -0.5, precision)
    return _einsum("bte,ed->btd", y.reshape(B, T, H * dv), w("mla_o_w"),
                   precision)


def route(h, router_w, router_b, z: dict):
    """``[..., E]`` combine weights: ``scale * s_e / sum_T s`` on the
    ``k`` experts whose ``s + bias`` is largest, 0 elsewhere.  Float32
    in every precision."""
    s = jax.nn.sigmoid(_exact("...d,de->...e", h, router_w))
    _, idx = lax.top_k(s + router_b, z["k"])
    chosen = jnp.sum(jax.nn.one_hot(idx, z["E"], dtype=s.dtype), axis=-2)
    return z["scale"] * s * chosen / jnp.sum(s * chosen, axis=-1,
                                             keepdims=True)


def _gated(h, gate_w, up_w, down_w, precision: str):
    a = jax.nn.silu(_einsum("...d,df->...f", h, gate_w, precision)) \
        * _einsum("...d,df->...f", h, up_w, precision)
    return _einsum("...f,fd->...d", a, down_w, precision)


def moe_parts(h, model: dict, key, layer: int, precision: str = "float32"):
    """``(routed, shared)``: what the HELD experts add for the tokens
    routed to them (``xing_reference.routed_sum``: a loop over them, each
    under a combine weight that is 0 where a token did not choose it),
    and the shared expert."""
    z = sizes(model)
    w = route(h, leaf(model, key, "router_w", layer),
              leaf(model, key, "router_b", layer), z)
    rows = h.reshape(-1, h.shape[-1])
    N = rows.shape[0]
    room = max(N // 16, 3 * N * z["k"] // (2 * z["E"]))
    routed = routed_sum(
        rows, w.reshape(N, -1), z["offset"] + jnp.arange(z["held"]),
        lambda e: [leaf(model, key, n, layer, e)
                   for n in ("gate_w", "up_w", "down_w")],
        (N,) if N <= 64 else tuple(r for r in (room, 4 * room) if r < N),
        precision).reshape(h.shape)
    shared = _gated(h, *(leaf(model, key, "shared_" + m, layer)
                         for m in ("gate_w", "up_w", "down_w")), precision)
    return routed, shared


def block(x, model: dict, key, layer: int, precision: str = "float32"):
    """One block on ``x`` [B, T, d]; ``layer`` is a Python int (the
    layers differ in kind)."""
    z = sizes(model)
    u = rms_norm(x, leaf(model, key, "ln_attn_g", layer), z["eps"])
    attn = kda if layer in z["kda"] else latent_attention
    x = x + attn(u, model, key, layer, precision)
    u = rms_norm(x, leaf(model, key, "ln_mlp_g", layer), z["eps"])
    if layer < z["dense"]:
        return x + _gated(u, *(leaf(model, key, "mlp_" + m, layer)
                               for m in ("gate_w", "up_w", "down_w")),
                          precision)
    return x + sum(moe_parts(u, model, key, layer, precision))


def forward(key, tokens, model: dict, precision: str = "float32",
            remat: bool = False):
    """Next-token logits ``[B, T, vocab]`` float32 for token ids ``[B,
    T]``.  ``key``: the PRNG key every weight is made from, or what
    ``hold`` made of it once."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    z = sizes(model)
    x = leaf(model, key, "wte")[tokens]
    for layer in range(z["L"]):
        x = block(x, model, key, layer, precision)
    x = rms_norm(x, leaf(model, key, "lnf_g"), z["eps"])
    return _einsum("btd,dv->btv", x, leaf(model, key, "head_w"), precision)


def loss(key, tokens, targets, model: dict, precision: str = "float32"):
    """Mean next-token cross-entropy over every position."""
    logits = forward(key, tokens, model, precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(picked)
