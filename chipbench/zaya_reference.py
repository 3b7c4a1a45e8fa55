"""Plain reference for the ZAYA1 configurations (`zaya1-8b` names it through
`configs/zaya1-8b_reference.py`; the sizes come from the configuration's
file): the forward pass in straightforward ``jax.numpy``, float32, matrix
products at ``highest`` precision.  No kernel, no cache and no tail (both
convolutions and the value's shift run over the whole sequence), no sort,
no grouped product, rotary by slices, and nothing imported from the
program under test.

The model (``model_type`` ``zaya``; the configuration's file names the
published ``config.json`` and, under ``assumed``, each point marked **(A)**
that it cannot confirm, with the source of the reading: the sibling
catalog row ``ZAYA1-base`` and arXiv:2510.04476 / arXiv:2511.17127).
``d`` the hidden size, ``H`` query heads over ``G`` K/V heads of ``D``,
``g(h) = h // (H / G)``; ``n(x) = x / sqrt(mean(x^2) + eps) * g``; no bias
on a projection; everything "at t - 1" is zero at t = 0::

    each of the L layers, two sublayers F (attention, then the experts)
    with learned float32 vectors a_r, b_r, a_f, b_f [d] (A):
      x' = (x + b_r) * a_r + (F(n(x)) + b_f) * a_f

    Attn (compressed convolutional attention), u = n(x):
      q~ = u W_q [H D], k~ = u W_k [G D]
      v_t = [u_t W_v1 ; u_{t-1} W_v2]    (A: K/V head 0 holds the
            position's own value, head 1 the previous position's)
      z = [q~ ; k~]; two causal convolutions of kernel 2 (A):
        a_t = w0[:, 0] * z_{t-1} + w0[:, 1] * z_t + b0   (depthwise)
        c_t^(h) = W1^(h)[0] a_{t-1}^(h) + W1^(h)[1] a_t^(h) + b1^(h)
                  per head of the H + G, W1^(h)[j] [D, D]
      m_q^(h) = (q~^(h) + k~^(g(h))) / 2                  (A: q-k mean)
      m_k^(g) = (mean_{h in g} q~^(h) + k~^(g)) / 2
      q = c_q + m_q, k = c_k + m_k
      q <- sqrt(D) q / |q|, k <- tau_g sqrt(D) k / |k| per head  (A)
      rotary on the first D partial_rotary_factor lanes of each head of q
      and k: the pair (j, j + rot / 2) turns by t theta^(-2j / rot)
      causal softmax of q . k / sqrt(D), head h against K/V head g(h);
      W_o [H D, d]
    MoE, u = n(x), s_{-1} = 0 (A):
      s_l = u W_d + gamma_l s_{l-1}           (R = router_hidden_size)
      p = softmax(W_3 gelu(W_2 gelu(W_1 n(s_l))))  (E + 1 outputs; the gelu
          exact; the LAST output is "no expert")
      e = argmax(p + b)     (the bias chooses and does not weigh)
      MoE(u) = p_e D_e (silu(G_e u) * U_e u) for e < E, 0 for e = E
    out: logits = E n_f(x), the embedding table tied.

**Weights** are not arrays handed in: ``forward`` takes the PRNG key and
makes every tensor from ``fold_in`` of it where it is applied (``leaf``),
or what ``hold`` made of the key once (bfloat16, 5.2 GB at the published
sizes: the check calls ``forward`` once a request).
``chipbench/adapters/zaya.py`` makes the program's parameters from the same
``leaf``.  The values are those of a bfloat16 checkpoint (``as_published``)
but for ``FLOAT32_LEAVES`` (the router whole, ``tau``, the residual
vectors), which are float32 on both sides.

``precision`` re-computes the same mathematics with every matrix product
fed lower-precision operands, for the control that ``chipbench/check.py``
has to fail: ``bfloat16`` is what the configuration states, ``fp8`` (e4m3
with one scale per tensor) the step below it.  The router stays float32
in every precision, as it is in the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.xing_reference import as_published, routed_sum

PRECISIONS = ("float32", "bfloat16", "fp8")
_FP8_MAX = 448.0
#: a layer's tensors, in the order their keys are folded in; the table and
#: the final norm's gain are layer "-1"
LEAVES = ("ln_attn_g", "q_w", "k_w", "v1_w", "v2_w", "conv0_w", "conv0_b",
          "conv1_w", "conv1_b", "tau", "o_w",
          "res_attn_ar", "res_attn_br", "res_attn_af", "res_attn_bf",
          "ln_mlp_g", "router_proj", "router_gamma", "router_norm",
          "router_w1", "router_w2", "router_w3", "router_b",
          "gate_w", "up_w", "down_w",
          "res_mlp_ar", "res_mlp_br", "res_mlp_af", "res_mlp_bf")
GLOBAL_LEAVES = ("wte", "lnf_g")
#: float32 on both sides, never rounded to what bfloat16 holds
FLOAT32_LEAVES = tuple(n for n in LEAVES
                       if n.startswith(("router_", "res_")) or n == "tau")
ROUTED_LEAVES = ("gate_w", "up_w", "down_w")
#: query rows to a block of scores
QUERY_BLOCK = 512
#: a routed expert's down-projection beside the attention's output
#: projection (``leaf``): with ONE expert a token, a swap of the best two
#: router outputs replaces the sublayer's whole output
ROUTED_DOWN = 0.25
#: the spread of the router's last matrix: 17 logits ~1.5-2 apart end to end
ROUTER_OUT = 1.2
#: the selection bias, beside probabilities of ~1/17
ROUTER_BIAS = 0.01
#: the residual merges' biases ``b_r``, ``b_f``: forty vectors every token
#: shares.  At 0.02 (ISSUE 41's) they were 5.7 of norm beside an embedding of
#: 0.9, the routers read much the same vector from every token, and with
#: ``W_2`` / ``W_3`` uncentred 66 of 160 experts were hit a decode run where
#: a trained, balanced router hits ~157: nine runs on the chip read
#: 15,219-19,095 tokens/s by how many their seed hit (PERF.md section 6)
RESIDUAL_BIAS = 0.002


# -- sizes ---------------------------------------------------------------------

def sizes(model: dict) -> dict:
    H, G, D = (int(model["num_attention_heads"]),
               int(model["num_key_value_heads"]), int(model["head_dim"]))
    return {
        "L": int(model["num_hidden_layers"]), "d": int(model["hidden_size"]),
        "H": H, "G": G, "D": D, "C": (H + G) * D,
        "rot": int(D * float(model["partial_rotary_factor"])),
        "theta": float(model.get("rope_theta") or model[
            "rope_parameters"]["hybrid"]["rope_theta"]),
        "eps": float(model["rms_norm_eps"]),
        "E": int(model["num_experts"]),
        "F": int(model["moe_intermediate_size"]),
        "R": int(model["router_hidden_size"]),
        "V": int(model["vocab_size"]),
    }


def leaf_shape(model: dict, name: str) -> tuple:
    """The shape of one tensor as ``leaf`` makes it.  ``gate_w`` / ``up_w``
    / ``down_w`` are ONE expert's; ``conv0_w`` [C, 2] holds the tap on
    ``z_{t-1}`` then the tap on ``z_t``; ``conv1_w`` [H + G, 2, D, D] head
    ``h``'s two [D, D] matrices, applied to a ROW (``a W``)."""
    z = sizes(model)
    d, D, C, R, F = z["d"], z["D"], z["C"], z["R"], z["F"]
    if name.startswith("res_"):
        return (d,)
    return {
        "wte": (z["V"], d), "lnf_g": (d,), "ln_attn_g": (d,),
        "ln_mlp_g": (d,),
        "q_w": (d, z["H"] * D), "k_w": (d, z["G"] * D), "v1_w": (d, D),
        "v2_w": (d, D), "o_w": (z["H"] * D, d),
        "conv0_w": (C, 2), "conv0_b": (C,),
        "conv1_w": (z["H"] + z["G"], 2, D, D), "conv1_b": (C,),
        "tau": (z["G"],),
        "router_proj": (d, R), "router_gamma": (), "router_norm": (R,),
        "router_w1": (R, R), "router_w2": (R, R),
        "router_w3": (R, z["E"] + 1), "router_b": (z["E"] + 1,),
        "gate_w": (d, F), "up_w": (d, F), "down_w": (F, d),
    }[name]


def leaf(model: dict, key, name: str, layer=-1, expert=None):
    """Float32 tensor ``name`` of layer ``layer`` (or the model's own:
    ``wte``, ``lnf_g``), of expert ``expert`` for the three expert
    matrices.  Traceable in ``key``, ``layer`` and ``expert``.

    Seeded so that every mechanism shows in a logit and the router
    DECIDES.  Matrices are normal with ``init_std`` (0.02); the
    projections back into the stream (``o_w``, ``down_w``) scaled by
    1/sqrt(2 L), an expert's ``down_w`` by ``ROUTED_DOWN`` besides (one
    expert a token: a swap of the router's best two replaces the whole
    sublayer's output, ``xing_reference.ROUTED_DOWN``'s lesson).  Norm
    gains 1 + 0.02 n.  The convolutions' taps are of comparable size, and
    their output comparable to the q-k mean beside it: ``conv0_w`` 0.5 +
    0.25 n a tap, ``conv1_w`` n / sqrt(2 D), both biases 0.05 n.  ``tau``
    1 + 0.1 n inside [0.8, 1.2]; the residual scales 1 + 0.15 n inside
    [0.7, 1.3], their biases ``RESIDUAL_BIAS`` n.  The router: ``router_proj`` at
    ``init_std`` (``u W_d`` of spread ~0.9), ``gamma`` 0.5 + 0.1 n, its
    norm's gain 1 + 0.02 n, ``W_1``, ``W_2`` n / sqrt(R) (unit
    pre-activations), ``W_3`` ``ROUTER_OUT`` n / sqrt(R), the last two
    with every column centred over its inputs (a BALANCED router, as the
    balancing bias makes a trained one: the 17 outputs each win ~1/17 of
    the tokens) (matrices at
    ``init_std`` through three layers give 17 logits ~1e-4 apart and
    routing by rounding noise), the bias ``ROUTER_BIAS`` n beside
    probabilities of ~1/17.

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
    # (rounded where drawn: a compiler may fold the scale below into the
    # draw's own last product in one program and not in another, and the
    # program's init and ``hold`` are two programs; no fold crosses this)
    w = as_published(
        jax.random.normal(k, leaf_shape(model, name), jnp.float32))
    back = std / math.sqrt(2 * z["L"])

    def about(centre, spread):
        # ``centre + spread n`` with the product rounded BEFORE the sum,
        # so that no compiler's fused multiply-add makes another number
        # of it: the program's init and ``hold`` are two programs
        return centre + as_published(spread * w)

    if name in ("res_attn_ar", "res_attn_af", "res_mlp_ar", "res_mlp_af"):
        w = jnp.clip(about(1.0, 0.15), 0.7, 1.3)
    elif name.startswith("res_"):
        w = RESIDUAL_BIAS * w
    elif name == "tau":
        w = jnp.clip(about(1.0, 0.1), 0.8, 1.2)
    elif name == "router_gamma":
        w = about(0.5, 0.1)
    elif name == "router_w1":
        w = (1.0 / math.sqrt(z["R"])) * w
    elif name in ("router_w2", "router_w3"):
        # every column sums to ~0 over its inputs: a gelu's output has a
        # positive mean, and through a column that does not sum to zero it
        # is an offset on that output that every token shares (rounded
        # again so that two programs' sums make the same numbers)
        w = as_published(w - jnp.mean(w, axis=0, keepdims=True))
        w = ((ROUTER_OUT if name == "router_w3" else 1.0)
             / math.sqrt(z["R"])) * w
    elif name == "router_b":
        w = ROUTER_BIAS * w
    elif name.endswith("_g") or name == "router_norm":
        w = about(1.0, 0.02)
    elif name == "conv0_w":
        w = about(0.5, 0.25)
    elif name in ("conv0_b", "conv1_b"):
        w = 0.05 * w
    elif name == "conv1_w":
        w = (1.0 / math.sqrt(2 * z["D"])) * w
    elif name == "o_w":
        w = back * w
    elif name == "down_w":
        w = (ROUTED_DOWN * back) * w
    else:
        w = std * w
    return w if name in FLOAT32_LEAVES else as_published(w)


def hold(model: dict, key) -> dict:
    """Every tensor of ``leaf``, made ONCE from ``key`` and kept: ``{name:
    [layers, (experts,) ...]}`` in bfloat16, which holds ``leaf``'s values
    exactly (``as_published``), and float32 for ``FLOAT32_LEAVES``.  5.2 GB
    at the published sizes where float32 would be 10.4.  ``forward`` takes
    it in the key's place and computes the same numbers (``leaf`` hands
    back the kept tensor as float32).  For a chip that holds nothing else:
    the check runs after the program's state is freed."""
    z = sizes(model)

    def resident(name, layer=-1, expert=None):
        a = leaf(model, key, name, layer, expert)
        return a if name in FLOAT32_LEAVES else a.astype(jnp.bfloat16)

    held = {name: resident(name) for name in GLOBAL_LEAVES}
    for name in LEAVES:
        if name in ROUTED_LEAVES:
            def of_layer(layer, name=name):
                return lax.map(lambda e: resident(name, layer, e),
                               jnp.arange(z["E"]))
        else:
            def of_layer(layer, name=name):
                return resident(name, layer)
        held[name] = lax.map(of_layer, jnp.arange(z["L"]))
    return held


def _held(model: dict, held: dict, name: str, layer, expert):
    a = held[name]
    if name not in GLOBAL_LEAVES:
        a = a[layer]
    if expert is not None:
        a = a[expert]
    return a.astype(jnp.float32)


# -- arithmetic ----------------------------------------------------------------

def _fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _FP8_MAX
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _operand(a, precision: str):
    """``a`` as a product's operand in ``precision``: the float32 values
    that bfloat16 holds (``as_published``: exact products, float32
    accumulation, as the MXU's), after e4m3 for ``fp8``."""
    if precision == "float32":
        return a
    if precision == "fp8":
        a = _fp8(a)
    return as_published(a)


def _einsum(eq: str, a, b, precision: str):
    return jnp.einsum(eq, _operand(a, precision), _operand(b, precision),
                      precision="highest",
                      preferred_element_type=jnp.float32)


def rms_norm(x, gain, eps: float):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * gain


def before(x):
    """``x`` [B, T, ...] one position later: zeros at position 0."""
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def rotary(x, z: dict):
    """Rotate-half rotary on the first ``rot`` of the ``D`` values of
    every head of ``x`` [B, T, n, D] at positions 0..T-1; the rest as it
    is."""
    rot, half = z["rot"], z["rot"] // 2
    inv = z["theta"] ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def causal_attention(q, k, v, precision: str):
    """``q`` [B, T, H, D] against ``k``, ``v`` [B, T, G, D], query head
    ``h`` reading K/V head ``h // (H / G)``: query block by query block,
    each against the keys up to its own last row; scores over sqrt(D)."""
    B, T, H, D = q.shape
    G = k.shape[2]
    q = q.reshape(B, T, G, H // G, D)
    out = []
    for lo in range(0, T, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, T)
        s = _einsum("bqgpd,bkgd->bgpqk", q[:, lo:hi], k[:, :hi],
                    precision) / math.sqrt(D)
        seen = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out.append(_einsum("bgpqk,bkgd->bqgpd", p, v[:, :hi], precision))
    return jnp.concatenate(out, axis=1).reshape(B, T, H * D)


def mix(q_, k_, model: dict, key, layer, precision: str = "float32",
        *, mean: bool = True):
    """``(q, k)`` [B, T, H, D], [B, T, G, D] from the projections ``q~``
    [B, T, H D] and ``k~`` [B, T, G D]: both convolutions, the q-k mean,
    the norm and the temperature (no rotary yet).  ``mean=False`` leaves
    the q-k mean out, for the test that it must show in a logit."""
    z = sizes(model)
    B, T, _ = q_.shape
    H, G, D = z["H"], z["G"], z["D"]

    def w(name):
        return leaf(model, key, name, layer)

    zz = jnp.concatenate([q_, k_], axis=-1)
    w0 = w("conv0_w")
    a = w0[:, 0] * before(zz) + w0[:, 1] * zz + w("conv0_b")
    ah = a.reshape(B, T, H + G, D)
    w1 = w("conv1_w")
    c = _einsum("btnd,nde->btne", before(ah), w1[:, 0], precision) \
        + _einsum("btnd,nde->btne", ah, w1[:, 1], precision) \
        + w("conv1_b").reshape(H + G, D)
    qh = q_.reshape(B, T, G, H // G, D)
    kh = k_.reshape(B, T, G, D)
    if mean:
        m_q = (qh + kh[:, :, :, None]) / 2
        m_k = (jnp.mean(qh, axis=3) + kh) / 2
        c = c + jnp.concatenate([m_q.reshape(B, T, H, D), m_k], axis=2)

    def unit(x):
        return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-12)

    q = math.sqrt(D) * unit(c[:, :, :H])
    k = math.sqrt(D) * w("tau")[:, None] * unit(c[:, :, H:])
    return q, k


def attention(u, model: dict, key, layer, precision: str = "float32",
              **switches):
    """Compressed convolutional attention of ``u`` [B, T, d].
    ``switches``: ``mix``'s, and ``shift`` (False: the value's second head
    reads its own position)."""
    z = sizes(model)
    B, T, _ = u.shape

    def w(name):
        return leaf(model, key, name, layer)

    def proj(name):
        return _einsum("btd,de->bte", u, w(name), precision)

    shift = switches.pop("shift", True)
    q, k = mix(proj("q_w"), proj("k_w"), model, key, layer, precision,
               **switches)
    v2 = proj("v2_w")
    v = jnp.stack([proj("v1_w"), before(v2) if shift else v2], axis=2)
    y = causal_attention(rotary(q, z), rotary(k, z), v, precision)
    return _einsum("bte,ed->btd", y, w("o_w"), precision)


def route(u, s_prev, model: dict, key, layer):
    """``(weights [..., E], s, p [..., E + 1])``: ``p_e`` on the expert
    chosen, 0 elsewhere (all 0 where "no expert" was), the router's state,
    and the softmax itself.  Float32 in every precision."""
    z = sizes(model)

    def w(name):
        return leaf(model, key, "router_" + name, layer)

    def dot(a, b):
        return jnp.einsum("...r,re->...e", a, b, precision="highest")

    s = dot(u, w("proj")) + w("gamma") * s_prev
    a = jax.nn.gelu(dot(rms_norm(s, w("norm"), z["eps"]), w("w1")),
                    approximate=False)
    a = jax.nn.gelu(dot(a, w("w2")), approximate=False)
    p = jax.nn.softmax(dot(a, w("w3")), axis=-1)
    chosen = jnp.argmax(p + w("b"), axis=-1)
    picked = jax.nn.one_hot(chosen, z["E"] + 1, dtype=p.dtype)
    return (p * picked)[..., :z["E"]], s, p


def expert_rooms(N: int, z: dict) -> tuple:
    """The rooms of ``routed_sum`` for ``N`` tokens, one expert each: one
    and a half times an expert's even share, then four times that; past
    the largest every token goes through the expert under the mask."""
    if N <= 64:
        return (N,)
    room = 3 * N // (2 * z["E"])
    return tuple(r for r in (room, 4 * room) if r < N)


def experts(u, s_prev, model: dict, key, layer,
            precision: str = "float32"):
    """``(p_e E_e(u) of the chosen expert, s)``: the chosen expert's
    tokens only go through it (``xing_reference.routed_sum``)."""
    z = sizes(model)
    weights, s, _ = route(u, s_prev, model, key, layer)
    rows = u.reshape(-1, u.shape[-1])
    N = rows.shape[0]
    y = routed_sum(
        rows, weights.reshape(N, -1), jnp.arange(z["E"]),
        lambda e: [leaf(model, key, n, layer, e)
                   for n in ("gate_w", "up_w", "down_w")],
        expert_rooms(N, z), precision)
    return y.reshape(u.shape), s


def merge(x, y, model: dict, key, layer, which: str):
    """``(x + b_r) * a_r + (y + b_f) * a_f`` of sublayer ``which``."""
    a_r, b_r, a_f, b_f = (leaf(model, key, f"res_{which}_{n}", layer)
                          for n in ("ar", "br", "af", "bf"))
    return (x + b_r) * a_r + (y + b_f) * a_f


def layer_forward(x, s, model: dict, key, layer,
                  precision: str = "float32"):
    """One layer on the stream ``x`` [B, T, d] with the router state ``s``
    [B, T, R] of the layer before; ``layer`` may be traced."""
    z = sizes(model)
    u = rms_norm(x, leaf(model, key, "ln_attn_g", layer), z["eps"])
    x = merge(x, attention(u, model, key, layer, precision), model, key,
              layer, "attn")
    u = rms_norm(x, leaf(model, key, "ln_mlp_g", layer), z["eps"])
    y, s = experts(u, s, model, key, layer, precision)
    return merge(x, y, model, key, layer, "mlp"), s


def forward(key, tokens, model: dict, precision: str = "float32",
            remat: bool = False):
    """Next-token logits ``[B, T, vocab]`` float32 for token ids ``[B,
    T]``.  ``key``: the PRNG key every weight is made from, or what
    ``hold`` made of it once."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    z = sizes(model)
    table = leaf(model, key, "wte")
    x = table[tokens]

    def body(carry, layer):
        return layer_forward(*carry, model, key, layer, precision), None

    if remat:
        body = jax.checkpoint(body)
    s0 = jnp.zeros(x.shape[:-1] + (z["R"],), jnp.float32)
    (x, _), _ = lax.scan(body, (x, s0), jnp.arange(z["L"]))
    x = rms_norm(x, leaf(model, key, "lnf_g"), z["eps"])
    return _einsum("btd,vd->btv", x, table, precision)


def loss(key, tokens, targets, model: dict, precision: str = "float32"):
    """Mean next-token cross-entropy over every position."""
    logits = forward(key, tokens, model, precision, remat=True)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(picked)
