"""Plain reference for the Command A+ configurations (`command-a-plus`
names it through `configs/command-a-plus_reference.py`; the sizes come
from the configuration's file): the forward pass in straightforward
``jax.numpy``, float32, matrix products at ``highest`` precision.  No
kernel, no cache, no sort, no grouped product, and nothing imported from
the program under test.

The model (``model_type`` ``cohere2_moe``; the configuration's file names
the published ``config.json``).  ``x`` the residual, ``d`` its width, no
bias anywhere, one norm a block which attention and experts both read
(``use_parallel_block``)::

    h  = LN(x) = (x - mean(x)) / sqrt(var(x) + eps) * g
    x' = x + Attn_l(h) + MoE(h)

    Attn: q = Wq h in [H, D]; k = Wk h, v = Wv h in [G, D]; query head i
          reads K/V head i // (H / G); scores q.k / sqrt(D), softmax, Wo.
          sliding_attention: rotary on q and k over all D dimensions,
          pairs (2j, 2j+1) interleaved (rope_gptj); key j is seen by
          query i iff i - window < j <= i.
          full_attention: no positional transform; key j seen iff j <= i.
    MoE:  r = Wr h in R^E (E the PUBLISHED number of experts);
          s = sigmoid(r); T = the k largest of s; w_e = s_e / sum_T s
          E_e(h) = Wdown_e (silu(Wgate_e h) * Wup_e h)
          MoE(h) = sum_{e in T, e held here} w_e E_e(h)
                   + (1 / n_shared) sum_j S_j(h)
    out:  LN_f, logits = logit_scale * table^T x, the table tied.

**The chip's share** (the configuration's ``deployment``): the router
keeps its published width ``num_experts_published`` and its experts per
token; of the experts, ``num_experts`` are held here, the first of them
``expert_offset`` (0); a pair routed to an absent expert adds nothing,
here and in the program alike.  ``vocab_size`` rows of the table are
held.  ``share_of(model, part, parts)`` gives the model of one of
``parts`` equal shares, and ``moe_parts`` the two summands apart, so
that a test can add every share's routed part to ONE shared part and
meet the uncut layer.

**Weights** are not an argument: ``forward`` is handed the PRNG key and
makes every tensor from ``fold_in`` of it where it is applied
(``leaf``), so that the float32 weights of one expert, not of the model
(18.9 GB), are live at a time: blocks run under one ``lax.scan``, a
block's held experts under another.  ``chipbench/adapters/command.py``
makes the program's parameters from the same ``leaf``, tensor by tensor.
The values are those of a bfloat16 checkpoint (``as_published``): the
program's resident cast is exact, and what the comparison reads is the
arithmetic, not a second set of weights.

``precision`` re-computes the same mathematics with every matrix product
fed lower-precision operands, for the control that ``chipbench/check.py``
has to fail: ``bfloat16`` is what the configuration states, ``fp8`` (e4m3
with one scale per tensor) the step below it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = ("float32", "bfloat16", "fp8")
_FP8_MAX = 448.0
#: a block's tensors, in the order their keys are folded in; the table
#: and the final norm's gain are layer "-1"
LEAVES = ("ln_g", "q_w", "k_w", "v_w", "o_w", "router_w",
          "gate_w", "up_w", "down_w",
          "shared_gate_w", "shared_up_w", "shared_down_w")
GLOBAL_LEAVES = ("wte", "lnf_g")
#: query rows to a block of scores, so that a row of 8960 positions fits
QUERY_BLOCK = 512


# -- sizes ---------------------------------------------------------------------

def sizes(model: dict) -> dict:
    d, F = int(model["hidden_size"]), int(model["intermediate_size"])
    H, G = int(model["num_attention_heads"]), int(model["num_key_value_heads"])
    D = int(model["head_dim"])
    return {
        "L": int(model["num_hidden_layers"]), "d": d, "F": F, "H": H,
        "G": G, "D": D, "V": int(model["vocab_size"]),
        "E": int(model.get("num_experts_published", model["num_experts"])),
        "held": int(model["num_experts"]),
        "offset": int(model.get("expert_offset", 0)),
        "k": int(model["num_experts_per_tok"]),
        "shared": int(model["num_shared_experts"]),
        "window": int(model["sliding_window"]),
    }


def share_of(model: dict, part: int, parts: int) -> dict:
    """The model of share ``part`` of ``parts``: its experts of the
    published ``num_experts_published``."""
    E = sizes(model)["E"]
    return {**model, "num_experts_published": E, "num_experts": E // parts,
            "expert_offset": part * (E // parts)}


def leaf_shape(model: dict, name: str) -> tuple:
    """The shape of one tensor as ``leaf`` makes it.  ``gate_w`` /
    ``up_w`` / ``down_w`` are ONE expert's; the shared experts' lie side
    by side (``shared_gate_w`` [d, n_shared * F]: columns ``j F .. (j +
    1) F`` are shared expert ``j``'s)."""
    z = sizes(model)
    d, F, n = z["d"], z["F"], z["shared"]
    return {
        "wte": (z["V"], d), "lnf_g": (d,), "ln_g": (d,),
        "q_w": (d, z["H"] * z["D"]), "k_w": (d, z["G"] * z["D"]),
        "v_w": (d, z["G"] * z["D"]), "o_w": (z["H"] * z["D"], d),
        "router_w": (d, z["E"]),
        "gate_w": (d, F), "up_w": (d, F), "down_w": (F, d),
        "shared_gate_w": (d, n * F), "shared_up_w": (d, n * F),
        "shared_down_w": (n * F, d),
    }[name]


def leaf(model: dict, key, name: str, layer=-1, expert=None):
    """Float32 tensor ``name`` of block ``layer`` (or the model's own:
    ``wte``, ``lnf_g``), of routed expert ``expert`` (its PUBLISHED
    number) for the three expert matrices.  Traceable in ``key``,
    ``layer`` and ``expert``.  Matrices are normal with ``init_std``
    (0.02), the projections back into the residual (``o_w``, ``down_w``,
    ``shared_down_w``) scaled by 1/sqrt(2 * layers); norm gains are 1 +
    0.02 n so that a mistake in them shows.  The router has the same
    0.02: its 128 scores then have a spread of ~1.3 before the sigmoid,
    not saturated.  Every tensor but the router is rounded to what
    bfloat16 holds (``as_published``)."""
    z = sizes(model)
    std = float(model.get("init_std", 0.02))
    names = LEAVES + GLOBAL_LEAVES
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 1),
                           names.index(name))
    if expert is not None:
        k = jax.random.fold_in(k, expert)
    w = jax.random.normal(k, leaf_shape(model, name), jnp.float32)
    if name.endswith("_g"):
        w = 1.0 + 0.02 * w
    elif name in ("o_w", "down_w", "shared_down_w"):
        w = (std / math.sqrt(2 * z["L"])) * w
    else:
        w = std * w
    return w if name == "router_w" else as_published(w)


def as_published(w):
    """Float32 values that bfloat16 holds exactly: a published checkpoint
    is bfloat16, and a reference is run on the values the server loads.
    (The router's stay float32 on both sides.)  With float32 values
    that only the program rounded, 2.2 % of the token ids chose other
    experts in the FIRST block, whose router reads nothing but the
    token's own table row, and an answer that repeats such a token
    replays the swap at every position: PERF.md section 2, seed
    1908811543.)  ``reduce_precision`` and not a cast there and back: the
    compiler may drop such a pair inside a fusion (it did, on the chip)."""
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


def layer_norm(x, gain, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * gain


def rotary(x, theta: float):
    """Interleaved rotary embedding (``rope_gptj``) of ``x`` [B, T, H, D]
    at positions 0..T-1 over all D dimensions: the pair ``(x[2j], x[2j +
    1])`` turns by ``t * theta ** (-2j / D)``."""
    T, D = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def attention(q, k, v, full, window: int, precision: str):
    """``q`` [B, T, H, D], ``k``, ``v`` [B, T, G, D]; ``full`` a (traced)
    bool: causal over everything, or causal under a band of ``window``
    keys that counts the query's own position.  K/V group by group and
    query block by query block."""
    B, T, H, D = q.shape
    G = k.shape[2]
    per = H // G
    qb = min(T, QUERY_BLOCK)
    pad = -T % qb
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = (T + pad) // qb
    # [G, nq, B, qb, per, D]
    q = q.reshape(B, nq, qb, G, per, D).transpose(3, 1, 0, 2, 4, 5)
    k, v = (a.transpose(2, 0, 1, 3) for a in (k, v))       # [G, B, T, D]
    key_at = jnp.arange(T)

    def group(args):
        qg, kg, vg = args

        def block(args):
            i, qi = args                                 # qi [B, qb, per, D]
            at = i * qb + jnp.arange(qb)
            s = _einsum("bqhd,bkd->bhqk", qi, kg, precision) / math.sqrt(D)
            seen = (key_at[None, :] <= at[:, None]) & (
                full | (key_at[None, :] > at[:, None] - window))
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return _einsum("bhqk,bkd->bqhd", p, vg, precision)

        return lax.map(block, (jnp.arange(nq), qg))       # [nq, B, qb, per, D]

    out = lax.map(group, (q, k, v))                   # [G, nq, B, qb, per, D]
    out = out.transpose(2, 1, 3, 0, 4, 5).reshape(B, T + pad, H, D)
    return out[:, :T]


def route(h, router_w, z: dict, precision: str):
    """``[..., E]`` combine weights: ``s_e / sum_T s`` on the ``k``
    largest sigmoid scores of a token, 0 elsewhere."""
    s = jax.nn.sigmoid(_einsum("...d,de->...e", h, router_w, precision))
    top, idx = lax.top_k(s, z["k"])
    w = top / jnp.sum(top, axis=-1, keepdims=True)
    picked = jax.nn.one_hot(idx, z["E"], dtype=s.dtype)   # [..., k, E]
    return jnp.einsum("...k,...ke->...e", w, picked, precision="highest")


def _gated(h, gate_w, up_w, down_w, precision: str):
    a = jax.nn.silu(_einsum("...d,df->...f", h, gate_w, precision)) \
        * _einsum("...d,df->...f", h, up_w, precision)
    return _einsum("...f,fd->...d", a, down_w, precision)


def routed_sum(rows, weights, experts, matrices, room: int,
               precision: str = "float32"):
    """``sum_e weights[:, e] * E_e(rows)`` over ``experts`` ([n] their
    published numbers), a loop over them.  ``rows`` [N, d]; ``weights``
    [N, E], 0 where a token did not choose an expert; ``matrices(e)``
    the three of expert ``e``.

    An expert is chosen by about N * k / E of the N tokens.  Where at
    most ``room`` of them chose it, only those go through it: the same
    sum for a sixteenth of the products at the published sizes (the
    float32 reference of one 8960-token request took 5.7 s on the chip
    with every token through each of the 16 experts).  Where more did,
    every token does, under the mask.  Either way exact: no token is left
    out."""
    N = rows.shape[0]

    def one(acc, e):
        mats = matrices(e)
        we = jnp.take(weights, e, axis=-1)                  # [N]

        def chosen_only(_):
            at = jnp.nonzero(we > 0, size=room, fill_value=N)[0]
            y = _gated(jnp.take(rows, at, axis=0, mode="fill",
                                fill_value=0.0), *mats, precision)
            share = jnp.take(we, at, mode="fill", fill_value=0.0)
            return jnp.zeros_like(rows).at[at].add(share[:, None] * y,
                                                   mode="drop")

        def every_token(_):
            return we[:, None] * _gated(rows, *mats, precision)

        return acc + lax.cond(jnp.sum(we > 0) <= room, chosen_only,
                              every_token, None), None

    return lax.scan(one, jnp.zeros_like(rows), experts)[0]


def moe_parts(h, model: dict, key, layer, precision: str = "float32"):
    """``(routed, shared)``: what the held experts add for the tokens
    routed to them (``routed_sum``: a loop over the held experts, each
    weighted by a combine weight that is 0 where a token did not choose
    it), and the shared experts' mean."""
    z = sizes(model)
    w = route(h, leaf(model, key, "router_w", layer), z, precision)
    rows = h.reshape(-1, h.shape[-1])
    N = rows.shape[0]
    routed = routed_sum(
        rows, w.reshape(N, -1), z["offset"] + jnp.arange(z["held"]),
        lambda e: [leaf(model, key, n, layer, e)
                   for n in ("gate_w", "up_w", "down_w")],
        N if N <= 64 else max(N // 8, 2 * N * z["k"] // z["E"]),
        precision).reshape(h.shape)
    F, n = z["F"], z["shared"]
    gate, up, down = (leaf(model, key, "shared_" + m, layer)
                      for m in ("gate_w", "up_w", "down_w"))
    shared = sum(_gated(h, gate[:, j * F:(j + 1) * F],
                        up[:, j * F:(j + 1) * F],
                        down[j * F:(j + 1) * F], precision)
                 for j in range(n)) / n
    return routed, shared


def block(x, model: dict, key, layer, full, precision: str = "float32"):
    """One parallel block on ``x`` [B, T, d]; ``layer`` and ``full``
    (whether it is a ``full_attention`` layer) may be traced."""
    z = sizes(model)
    B, T, _ = x.shape
    h = layer_norm(x, leaf(model, key, "ln_g", layer),
                   float(model["layer_norm_eps"]))
    q = _einsum("btd,de->bte", h, leaf(model, key, "q_w", layer),
                precision).reshape(B, T, z["H"], z["D"])
    k, v = (_einsum("btd,de->bte", h, leaf(model, key, n, layer),
                    precision).reshape(B, T, z["G"], z["D"])
            for n in ("k_w", "v_w"))
    theta = float(model["rope_theta"])
    q = jnp.where(full, q, rotary(q, theta))
    k = jnp.where(full, k, rotary(k, theta))
    y = attention(q, k, v, full, z["window"], precision)
    a = _einsum("bte,ed->btd", y.reshape(B, T, z["H"] * z["D"]),
                leaf(model, key, "o_w", layer), precision)
    routed, shared = moe_parts(h, model, key, layer, precision)
    return x + a + routed + shared


def is_full(model: dict):
    """[L] bool: which blocks are ``full_attention``."""
    return jnp.asarray([t == "full_attention" for t in
                        model["layer_types"][:sizes(model)["L"]]])


def forward(key, tokens, model: dict, precision: str = "float32",
            remat: bool = False):
    """Next-token logits ``[B, T, vocab]`` float32 for token ids ``[B,
    T]`` below ``vocab_size``.  ``key``: the PRNG key every weight is
    made from."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    table = leaf(model, key, "wte")
    x = table[tokens]

    def body(x, at):
        layer, full = at
        return block(x, model, key, layer, full, precision), None

    if remat:
        body = jax.checkpoint(body)
    L = sizes(model)["L"]
    x, _ = lax.scan(body, x, (jnp.arange(L), is_full(model)))
    x = layer_norm(x, leaf(model, key, "lnf_g"),
                   float(model["layer_norm_eps"]))
    return float(model.get("logit_scale", 1.0)) \
        * _einsum("btd,vd->btv", x, table, precision)


def loss(key, tokens, targets, model: dict, precision: str = "float32"):
    """Mean next-token cross-entropy over every position."""
    logits = forward(key, tokens, model, precision, remat=True)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(picked)
