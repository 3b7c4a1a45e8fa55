"""Pallas kernel tests (interpret mode on CPU, compiled on TPU).

Mirrors the reference's numeric-assertion style (weights-changed /
accuracy floors, reference: tests/utils.py:174-210) but at the kernel
level: flash output and gradients must match the naive attention to
tight fp32 tolerances.
"""

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.gpt import dot_product_attention
from ray_lightning_tpu.ops import flash_decode
from ray_lightning_tpu.ops.flash_attention import flash_attention

#: the module: the package's ``flash_attention`` function shadows its name
fa = sys.modules["ray_lightning_tpu.ops.flash_attention"]


def _rand_qkv(b=2, t=128, h=2, d=32, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t", [64, 128, 256])
def test_flash_forward_matches_naive(causal, t):
    q, k, v = _rand_qkv(t=t)
    out = flash_attention(q, k, v, causal=causal, dtype=jnp.float32,
                          block_q=64, block_k=64)
    ref = dot_product_attention(q, k, v, causal=causal, dtype=jnp.float32)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_tri_decode_exact_for_all_indices():
    """The triangular-grid decode must be EXACT on every backend: the
    float sqrt is only an estimate (TPU's sqrt misrounds, e.g. i=6 →
    2.99999976) and the integer correction must land every index on the
    true (qi, kb) pair — a misdecode silently corrupts causal attention
    at T>=2048 where the tri path is default-on."""
    from ray_lightning_tpu.ops.flash_attention import (_tri_decode,
                                                       _tri_decode_rev)
    n = 64                                   # up to 64x64 block grids
    idx = jnp.arange(n * (n + 1) // 2)
    qi, kb = jax.jit(_tri_decode)(idx)
    expect = [(q, c) for q in range(n) for c in range(q + 1)]
    np.testing.assert_array_equal(np.asarray(qi), [e[0] for e in expect])
    np.testing.assert_array_equal(np.asarray(kb), [e[1] for e in expect])

    ki, qi2 = jax.jit(lambda i: _tri_decode_rev(i, n))(idx)
    # every (ki, qi2) pair covers the qi>=ki triangle exactly once,
    # contiguously per ki group, qi descending from n-1
    seen = list(zip(np.asarray(ki).tolist(), np.asarray(qi2).tolist()))
    assert sorted(seen) == sorted(
        (k, q) for k in range(n) for q in range(k, n))
    for a, b in zip(seen, seen[1:]):
        assert (b[0] == a[0] and b[1] == a[1] - 1) or \
            (b[0] == a[0] - 1 and b[1] == n - 1)


def test_flash_uneven_blocks():
    # T=96 forces the block picker to halve down to a divisor
    q, k, v = _rand_qkv(t=96)
    out = flash_attention(q, k, v, causal=True, dtype=jnp.float32)
    ref = dot_product_attention(q, k, v, causal=True, dtype=jnp.float32)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grads_match_naive(causal):
    q, k, v = _rand_qkv(t=128)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, dtype=jnp.float32,
                            block_q=64, block_k=64)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        o = dot_product_attention(q, k, v, causal=causal, dtype=jnp.float32)
        return jnp.sum(jnp.sin(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch")


def test_flash_under_jit_and_bf16():
    q, k, v = _rand_qkv(t=128, dtype=jnp.bfloat16)

    @jax.jit
    def f(q, k, v):
        return flash_attention(q, k, v, causal=True)

    out = f(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_gpt_attention_impl_flash_trains(tmp_path):
    # end-to-end: tiny GPT with attention_impl="flash" takes a step
    from ray_lightning_tpu import Trainer
    from ray_lightning_tpu.models.gpt import GPTConfig, GPTLightningModule

    cfg = GPTConfig(vocab_size=128, block_size=64, n_layer=1, n_head=2,
                    n_embd=32, remat=False, attention_impl="flash")
    module = GPTLightningModule(cfg, dataset_size=16, batch_size=4)
    trainer = Trainer(max_steps=2, max_epochs=1, enable_checkpointing=False,
                      num_sanity_val_steps=0, limit_val_batches=0,
                      log_every_n_steps=1,
                      # a directory of its own: under xdist another
                      # worker's fit logs into the default one meanwhile
                      default_root_dir=str(tmp_path))
    trainer.fit(module)
    assert np.isfinite(float(trainer.callback_metrics["loss"]))


# -- head-packed single-block kernels (the production path at T<=1024) ------
#
# _head_pack engages when 128//d divides h; the default test shapes
# (h=2, d=32 → pack=4 ∤ 2) never hit it, so these cases pin the packed
# forward AND backward explicitly — a regression here would otherwise
# ship under a green suite while being the path the headline runs.

_PACKED_SHAPES = [
    (4, 32),    # pack=4 divides h=4
    (2, 64),    # pack=2 divides h=2 (the gpt2 head_dim)
    (2, 128),   # pack=1, d == lane width
]


@pytest.mark.parametrize("h,d", _PACKED_SHAPES)
@pytest.mark.parametrize("causal", [True, False])
def test_packed_forward_matches_naive(h, d, causal):
    from ray_lightning_tpu.ops.flash_attention import _head_pack
    assert _head_pack(d, h) > 0
    q, k, v = _rand_qkv(t=128, h=h, d=d)
    out = flash_attention(q, k, v, causal=causal, dtype=jnp.float32)
    ref = dot_product_attention(q, k, v, causal=causal, dtype=jnp.float32)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("h,d", _PACKED_SHAPES)
def test_packed_grads_match_naive(h, d):
    q, k, v = _rand_qkv(t=128, h=h, d=d)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, dtype=jnp.float32)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        o = dot_product_attention(q, k, v, causal=True, dtype=jnp.float32)
        return jnp.sum(jnp.sin(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch")


def test_odd_head_count_falls_back_to_folded():
    """h=3 with d=64 (pack=2 ∤ 3) must take the folded path and still be
    correct — the dispatch seam between the two layouts."""
    from ray_lightning_tpu.ops.flash_attention import _head_pack
    assert _head_pack(64, 3) == 0
    q, k, v = _rand_qkv(t=128, h=3, d=64)
    out = flash_attention(q, k, v, causal=True, dtype=jnp.float32)
    ref = dot_product_attention(q, k, v, causal=True, dtype=jnp.float32)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("h,d", [(4, 32), (2, 64)])
def test_packed_triangular_multiblock(h, d):
    """Multi-block causal with square blocks engages the PACKED
    triangular-grid kernels (transpose-free [B,T,C] layout at T>=2048
    in production; forced here with small blocks) — forward and grads
    must match the XLA reference."""
    assert fa._head_pack(d, h) > 0
    assert fa._select_family(256, h, d, True, 64, 64).lse == "packed"
    q, k, v = _rand_qkv(t=256, h=h, d=d)

    out = flash_attention(q, k, v, causal=True, dtype=jnp.float32,
                          block_q=64, block_k=64)
    ref = dot_product_attention(q, k, v, causal=True, dtype=jnp.float32)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, dtype=jnp.float32,
                            block_q=64, block_k=64)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        o = dot_product_attention(q, k, v, causal=True, dtype=jnp.float32)
        return jnp.sum(jnp.sin(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch")


# -- causal staircase subtiling (the round-4 single-block fast path) --------
#
# _sub_block auto-engages at T>=512 (the production headline runs
# T=1024, sub=256); these tests lower the module's _STAIRCASE_SUB so
# the staircase math is pinned at CI-friendly shapes, and one case
# pins the constant's own threshold.


# (2,64)/(3,64): packed/folded with the sm_scale fold (1/8 is a power
# of two); (4,32): packed WITHOUT the fold (1/√32 has a non-trivial
# mantissa) so the `not fold` scaling branches are covered too.
@pytest.mark.parametrize("h,d", [(2, 64), (3, 64), (4, 32)])
def test_staircase_single_block_matches_full(h, d, monkeypatch):
    """Staircase on (sub=32 at T=128) must match staircase off bit-for-
    bit on dq/dv and to fp tolerance elsewhere, and match the XLA
    reference — for BOTH the head-packed and the folded fused kernels."""
    q, k, v = _rand_qkv(t=128, h=h, d=d)

    def loss(attn):
        def f(q, k, v):
            o = attn(q, k, v)
            return jnp.sum(jnp.sin(o))
        return f

    flash = loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, dtype=jnp.float32))
    ref = loss(lambda q, k, v: dot_product_attention(
        q, k, v, causal=True, dtype=jnp.float32))

    assert fa._sub_block(128, True) == 0          # 128 < 2 x 256
    v_off = flash(q, k, v)
    g_off = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)

    monkeypatch.setattr(fa, "_STAIRCASE_SUB", 32)
    assert fa._sub_block(128, True) == 32
    v_on = flash(q, k, v)
    g_on = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)

    np.testing.assert_allclose(v_on, v_off, atol=1e-5, rtol=1e-5)
    for a, b, name in zip(g_on, g_off, "qkv"):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5,
                                   err_msg=f"d{name} staircase vs full")
    g_ref = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_on, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} staircase vs ref")


def test_staircase_auto_threshold():
    """The constant as shipped: off below T=512, sub=256 at T in [512,
    1024] (single-block territory), irrelevant past 1024 where the tiled
    tri grid takes over — and off for non-causal always."""
    from ray_lightning_tpu.ops.flash_attention import _sub_block
    assert _sub_block(128, True) == 0
    assert _sub_block(256, True) == 0
    assert _sub_block(512, True) == 256
    assert _sub_block(1024, True) == 256
    assert _sub_block(1024, False) == 0


def test_rowres_gates_factor_head_width():
    """The row-resident VMEM budgets were measured at w=128; wide heads
    (d >= 256 pack to w=d) must cap t·w, not t alone (ADVICE r4 #3)."""
    def fam(t, d):
        return fa._select_family(t, 1, d, True, 512, 512)

    assert fam(8192, 128).fwd == "rowres"          # the measured point
    assert fam(8192, 256).fwd == "tri_packed"      # 2x resident k/v
    assert fam(4096, 256).fwd == "rowres"          # same t*w budget
    assert fam(2048, 128).bwd == "rowres"
    assert fam(2048, 256).bwd == "tri_packed"
    assert fam(1024, 256).bwd == "rowres"


def test_staircase_non_causal_unaffected(monkeypatch):
    """Non-causal single block must ignore the staircase entirely."""
    monkeypatch.setattr(fa, "_STAIRCASE_SUB", 32)
    q, k, v = _rand_qkv(t=128, h=2, d=64)
    out = flash_attention(q, k, v, causal=False, dtype=jnp.float32)
    ref = dot_product_attention(q, k, v, causal=False, dtype=jnp.float32)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("sm_scale", [None, 0.1])  # fold and no-fold
@pytest.mark.parametrize("rowres", ["1", "0"])
def test_rowres_backward_matches_reference(rowres, sm_scale, monkeypatch):
    """The row-resident fused triangular backward (default at
    multi-block causal T<=2048) and the grid-tri pair it replaces must
    BOTH match the reference — ``rowres="0"`` reaches the pair the way
    a long sequence does, by a t·w over the budgets (lowered here), and
    keeps that path covered.  sm_scale=0.1 (not a power of
    two) exercises the no-fold scaling branches, checked against the
    full-precision einsum recipe directly (the XLA helper hardwires
    1/sqrt(d))."""
    if rowres == "0":
        monkeypatch.setattr(fa, "_ROWRES_FWD_BUDGET", 0)
        monkeypatch.setattr(fa, "_ROWRES_BWD_BUDGET", 0)
    fam = fa._select_family(256, 2, 64, True, 64, 64)
    assert (fam.fwd, fam.bwd) == (("rowres", "rowres") if rowres == "1"
                                  else ("tri_packed", "tri_packed"))
    q, k, v = _rand_qkv(t=256, h=2, d=64)
    scale = sm_scale if sm_scale is not None else 64 ** -0.5

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, dtype=jnp.float32,
                            sm_scale=sm_scale, block_q=64, block_k=64)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        mask = np.tril(np.ones((256, 256), bool))
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return jnp.sum(jnp.sin(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} rowres={rowres}")


def test_fwd_rowres_with_grid_tri_backward(monkeypatch):
    """The 2048 < T <= 8192 production combination: row-resident FORWARD
    (whose lse ships in the packed [B, H/pack, T, pack] layout) feeding
    the grid-tri backward.  Forced at small T by lowering only the
    backward's budget — a layout drift between the two would break grads
    here."""
    monkeypatch.setattr(fa, "_ROWRES_BWD_BUDGET", 0)
    fam = fa._select_family(256, 2, 64, True, 64, 64)
    assert (fam.fwd, fam.bwd, fam.lse) == ("rowres", "tri_packed", "packed")
    q, k, v = _rand_qkv(t=256, h=2, d=64)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, dtype=jnp.float32,
                            block_q=64, block_k=64)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        o = dot_product_attention(q, k, v, causal=True, dtype=jnp.float32)
        return jnp.sum(jnp.sin(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} fwd-rowres+tri-bwd")


# -- which family a geometry takes (ops/flash_attention.py _select_family) --

#: (t, h, d, causal, block_q, block_k) -> (fwd, bwd, lse, bq, bk, sub):
#: the geometries the benchmark's cells lower, and one on each side of
#: every gate.  ``sub`` is the staircase sub-block a one-block kernel
#: runs with.
_FAMILIES = {
    "train_cell": ((1024, 12, 64, True, None, None),
                   ("packed", "packed", "packed", 1024, 1024, 256)),
    "gpt2l_prefill_256": ((256, 20, 64, True, None, None),
                          ("packed", "packed", "packed", 256, 256, 0)),
    "gpt2l_prefill_512": ((512, 20, 64, True, None, None),
                          ("packed", "packed", "packed", 512, 512, 256)),
    "gpt2l_prefill_1024": ((1024, 20, 64, True, None, None),
                           ("packed", "packed", "packed", 1024, 1024, 256)),
    "evabyte_window": ((2048, 32, 128, True, None, None),
                       ("rowres", "rowres", "packed", 512, 512, 0)),
    "causal_4096": ((4096, 12, 64, True, None, None),
                    ("rowres", "tri_packed", "packed", 512, 512, 0)),
    "causal_16384": ((16384, 12, 64, True, None, None),
                     ("tri_packed", "tri_packed", "packed", 512, 512, 0)),
    "wide_heads_4096": ((4096, 4, 256, True, None, None),
                        ("rowres", "tri_packed", "packed", 512, 512, 0)),
    "unpackable_1024": ((1024, 8, 96, True, None, None),
                        ("rect", "fused", "folded", 1024, 1024, 256)),
    "unpackable_2048": ((2048, 8, 96, True, None, None),
                        ("tri", "tri", "folded", 512, 512, 0)),
    "odd_heads_1024": ((1024, 3, 64, True, None, None),
                       ("rect", "fused", "folded", 1024, 1024, 256)),
    "bidirectional_512": ((512, 12, 64, False, None, None),
                          ("packed", "packed", "packed", 512, 512, 0)),
    "bidirectional_2048": ((2048, 12, 64, False, None, None),
                           ("rect", "rect", "folded", 512, 512, 0)),
    "unequal_blocks": ((1024, 12, 64, True, 256, 512),
                       ("rect", "rect", "folded", 256, 512, 0)),
    "explicit_tiles_1024": ((1024, 12, 64, True, 512, 512),
                            ("rowres", "rowres", "packed", 512, 512, 0)),
}


@pytest.mark.parametrize("case", list(_FAMILIES))
def test_select_family(case):
    """Which kernels a geometry lowers, forward and backward, and the
    layout of the ``lse`` that passes between them — and that the
    forward really writes that layout (shapes only: nothing runs)."""
    (t, h, d, causal, block_q, block_k), expect = _FAMILIES[case]
    fam = fa._select_family(t, h, d, causal, block_q, block_k)
    single = fam.bq == t and fam.bk == t
    sub = fa._sub_block(t, causal) if single else 0
    assert tuple(fam) + (sub,) == expect

    x = jax.ShapeDtypeStruct((1, t, h * d), jnp.bfloat16)
    _, lse = jax.eval_shape(
        lambda q, k, v: fa._fwd(q, k, v, h, causal, d ** -0.5, block_q,
                                block_k, True), x, x, x)
    if fam.lse == "packed":
        pack = fa._head_pack(d, h)
        assert lse.shape == (1, h // pack, t, pack)
    else:
        assert lse.shape == (h, t, 1)


def test_kernels_read_no_environment_but_decode_impl():
    """The kernel a program lowers follows from its shapes and from
    ``RLT_DECODE_IMPL`` (the explicit request for a decode kernel),
    never from another variable of the process: a name read from the
    environment at trace time is in no program's name, no cache key's
    visible inputs and no ledger line."""
    import ast
    import inspect

    from ray_lightning_tpu.ops import eva_attention
    reads = {}
    for mod in (fa, flash_decode, eva_attention):
        src = inspect.getsource(mod)
        found = [ast.literal_eval(n.args[0]) for n in ast.walk(ast.parse(src))
                 if isinstance(n, ast.Call)
                 and ast.unparse(n.func) in ("os.environ.get", "os.getenv")]
        # every mention is one of those calls: no subscript, no alias
        assert src.count("environ") + src.count("getenv") == len(found)
        reads[mod.__name__.rsplit(".", 1)[-1]] = found
    assert reads == {"flash_attention": [], "eva_attention": [],
                     "flash_decode": ["RLT_DECODE_IMPL"]}


# -- decode kernel tier (ops/flash_decode.py) ------------------------------


#: the resident cache is [n_layer, S, L, H*D] and every call reads ONE
#: layer of it: the tier runs on layer 1 of 3, so an index_map that
#: forgets the layer's offset (or adds the wrong one) reads random rows
N_LAYER, LAYER = 3, 1


def _rand_decode(s=4, L=256, h=2, d=32, seed=3, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (s, 1, h, d), dtype)
    kc = jax.random.normal(ks[1], (N_LAYER, s, L, h * d), dtype)
    vc = jax.random.normal(ks[2], (N_LAYER, s, L, h * d), dtype)
    return q, kc, vc


def _decode(impl, q, kc, vc, pos, dtype=jnp.float32, page_table=None,
            slots=None):
    from ray_lightning_tpu.ops.attention import cached_attention
    return cached_attention(q, kc, vc, jnp.asarray(pos, jnp.int32),
                            layer=LAYER, slots=slots, dtype=dtype,
                            impl=impl, page_table=page_table)


def _einsum_ref(q, kc, vc, pos, dtype=jnp.float32):
    """The plain mathematics, written here and not in the package: the
    masked einsum over layer LAYER of the cache, heads unpacked to
    ``[S, L, H, D]``."""
    s, _, h, d = q.shape
    k = kc[LAYER].reshape(s, -1, h, d)
    v = vc[LAYER].reshape(s, -1, h, d)
    scores = jnp.einsum("sqhd,slhd->shql", q, k,
                        preferred_element_type=jnp.float32) / np.sqrt(d)
    valid = jnp.arange(k.shape[1])[None, :] <= jnp.asarray(pos)[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("shql,slhd->sqhd", probs, v)


#: the decode-parity tier: every caller of ops/flash_decode.py's shared
#: body x the geometries that stress its layout (heads on sublanes, the
#: packed columns on lanes) x positions on the edges of a block and of
#: the cache x both dtypes, against the dense einsum written above.
#: Blocks are 64 rows of a 256-row cache: four a slot.
_BK, _L, _SLOTS = 64, 256, 4
_GEOMETRIES = {
    "h20d64": (20, 64),      # gpt2-large: H no sublane multiple, D half a vreg
    "h32d128": (32, 128),    # EvaByte: whole-vreg heads
    "tiny": (2, 32),         # the tier's old shape
}
_POSITIONS = {
    "first": [0] * _SLOTS,                     # one live row a slot
    "block_end": [_BK - 1] * _SLOTS,           # a block exactly full
    "block_start": [_BK] * _SLOTS,             # one row into the next
    "last": [_L - 1] * _SLOTS,                 # the cache's last row
    "mixed": [0, _BK - 1, _BK, _L - 1],
    "ragged": [0, 17, 128, 255],               # the tier's old cases
    "straddle": [_BK - 1, _BK, 2 * _BK + 1, 255],
    "late": [5, 100, 200, 255],
}
#: EvaByte's cache at a tiny size: a window of 128 exact rows and one
#: summary row per 4 of 512 positions; a position is no row there
_WINDOW, _CHUNK, _EVA_POSITIONS = 128, 4, 512
_BARS = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@functools.lru_cache(maxsize=2)
def _decode_case(h, d, dtype):
    return _rand_decode(s=_SLOTS, L=_L, h=h, d=d, dtype=dtype)


#: the ``paged`` caller: logical page p of slot s lies at physical page
#: _PERM[s * n + p] of the layer; ``slots``: rows in cache slots 3 and 1
_PERM = np.random.default_rng(7).permutation(_SLOTS * (_L // _BK))
_PICK = np.array([3, 1])


def _scatter_pages(c):
    pages = c.reshape(N_LAYER, len(_PERM), _BK, c.shape[-1])
    return jnp.zeros_like(pages).at[:, _PERM].set(pages).reshape(c.shape)


@functools.lru_cache(maxsize=None)
def _parity_call(caller, dtype):
    """``f(q, kc, vc, pos)`` of one caller, jitted once a shape: the
    positions are an argument, so their cases share a compilation."""
    from ray_lightning_tpu.ops.eva_attention import eva_cached_attention
    if caller == "eva":
        return jax.jit(lambda q, kc, vc, pos: eva_cached_attention(
            q, kc, vc, pos, layer=LAYER, window=_WINDOW, chunk=_CHUNK,
            dtype=dtype, impl="flash_decode"))
    if caller == "slots":
        return jax.jit(lambda q, kc, vc, pos: _decode(
            "flash_decode", q[_PICK], kc, vc, pos[_PICK], dtype=dtype,
            slots=jnp.asarray(_PICK, jnp.int32)))
    if caller == "paged":
        table = jnp.asarray(_PERM.reshape(_SLOTS, -1), jnp.int32)
        return jax.jit(lambda q, kc, vc, pos: _decode(
            "paged", q, _scatter_pages(kc), _scatter_pages(vc), pos,
            dtype=dtype, page_table=table))
    impl = "dense" if caller == "dense" else "flash_decode"
    return jax.jit(lambda q, kc, vc, pos: _decode(impl, q, kc, vc, pos,
                                                  dtype=dtype))


def _eva_ref(q, kc, vc, pos, dtype):
    """The two-range bound over layer LAYER, written here: rows up to
    ``pos % window`` of the exact part, the summaries of every earlier
    window."""
    s, _, h, d = q.shape
    k = kc[LAYER].reshape(s, -1, h, d)
    v = vc[LAYER].reshape(s, -1, h, d)
    scores = jnp.einsum("sqhd,slhd->shql", q, k,
                        preferred_element_type=jnp.float32) / np.sqrt(d)
    pos = np.asarray(pos)[:, None]
    row = np.arange(k.shape[1])[None, :]
    far = (pos // _WINDOW) * (_WINDOW // _CHUNK)
    seen = (row <= pos % _WINDOW) | (
        (row >= _WINDOW) & (row < _WINDOW + far))
    scores = jnp.where(seen[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("shql,slhd->sqhd", probs, v)


@pytest.mark.parametrize("dtype", list(_BARS), ids=["f32", "bf16"])
@pytest.mark.parametrize("positions", list(_POSITIONS))
@pytest.mark.parametrize("geometry", list(_GEOMETRIES))
@pytest.mark.parametrize("caller", ["dense", "flat", "paged", "slots", "eva"])
def test_decode_parity(monkeypatch, caller, geometry, positions, dtype):
    """One body under every call: ``flat`` (``flash_decode``), ``paged``
    (a PERMUTED page table over a cache whose pages are permuted to
    match, so a walk that ignores the table reads other rows), ``slots``
    (two rows in cache slots of their own choosing, through the paged
    kernel), ``eva`` (``eva_decode``: the two-range bound) and the
    ``dense`` einsum of the package, each on layer LAYER of the stacked
    cache against the plain mathematics above."""
    monkeypatch.setattr(flash_decode, "_BLOCK_K", _BK)
    q, kc, vc = _decode_case(*_GEOMETRIES[geometry], dtype)
    pos = np.asarray(_POSITIONS[positions], np.int32)
    if caller == "eva":
        # the cache's window + positions / chunk rows; the positions
        # spread over the four windows, on the same edges and beside them
        rows = _WINDOW + _EVA_POSITIONS // _CHUNK
        kc, vc = kc[:, :, :rows], vc[:, :, :rows]
        pos = pos * (_EVA_POSITIONS // _L) + pos % 2
        ref = _eva_ref(q, kc, vc, pos, dtype)
    else:
        ref = _einsum_ref(q, kc, vc, pos, dtype)
        if caller == "slots":
            ref = ref[_PICK]
    out = _parity_call(caller, dtype)(q, kc, vc, pos)
    assert out.shape == ref.shape and out.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=_BARS[dtype], rtol=_BARS[dtype])


@pytest.mark.parametrize("dtype", list(_BARS), ids=["f32", "bf16"])
@pytest.mark.parametrize("caller", ["flat", "slots"])
def test_decode_over_a_cache_the_block_does_not_tile(monkeypatch, caller,
                                                     dtype):
    """232 rows a slot under blocks of 64 (PR 42).  ``flat`` reads three
    whole blocks and a fourth whose last 24 rows lie past the array (the
    interpreter fills them with NaN: the body masks the value rows);
    slots at row 0, the last whole block's last row, the ragged block's
    first and the cache's last.  ``slots`` walks pages, which have to
    tile: 29 pages of 8 rows, as before."""
    monkeypatch.setattr(flash_decode, "_BLOCK_K", _BK)
    L = 232
    q, kc, vc = _rand_decode(s=_SLOTS, L=L, dtype=dtype)
    pos = np.asarray([0, 3 * _BK - 1, 3 * _BK, L - 1], np.int32)
    ref = _einsum_ref(q, kc, vc, pos, dtype)
    with flash_decode.record_decode_kernels() as lowered:
        if caller == "flat":
            out = _decode("flash_decode", q, kc, vc, pos, dtype=dtype)
        else:
            ref = ref[_PICK]
            out = _decode("flash_decode", q[_PICK], kc, vc, pos[_PICK],
                          dtype=dtype, slots=jnp.asarray(_PICK, jnp.int32))
    assert lowered == {"flash_decode": [[_BK, 4, 40] if caller == "flat"
                                        else [8, 29, 8]]}
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=_BARS[dtype], rtol=_BARS[dtype])


@pytest.mark.parametrize("impl", ["dense", "flash_decode", "paged"])
def test_decode_rows_in_named_slots(impl):
    """``slots``: a batch of rows that live in cache slots of their own
    choosing (the one-row suffix program) reads exactly what the full
    batch reads at those slots, with and without a page table."""
    from ray_lightning_tpu.serve.fleet.pages import identity_page_table
    q, kc, vc = _rand_decode()
    pos = np.array([0, 17, 128, 255])
    table = jnp.asarray(identity_page_table(4, 256, 64)) \
        if impl == "paged" else None
    full = _einsum_ref(q, kc, vc, pos)
    pick = jnp.asarray([3, 1], jnp.int32)
    out = _decode(impl, q[pick], kc, vc, pos[np.asarray(pick)],
                  page_table=None if table is None else table[pick],
                  slots=pick)
    np.testing.assert_allclose(out, full[pick], atol=2e-5, rtol=2e-5)


def test_flash_decode_single_slot():
    q, kc, vc = _rand_decode(s=1, L=128)
    out = _decode("flash_decode", q, kc, vc, [63])
    np.testing.assert_allclose(out, _einsum_ref(q, kc, vc, [63]),
                               atol=2e-5, rtol=2e-5)


def test_paged_decode_bitwise_equal_to_flat():
    """The paged variant under the identity table (the slot-contiguous
    cache) is the slot-contiguous kernel at the same block size, bit for
    bit, at positions on and around page boundaries."""
    from ray_lightning_tpu.ops.flash_decode import flash_decode_attention
    from ray_lightning_tpu.serve.fleet.pages import identity_page_table
    page = 64
    q, kc, vc = _rand_decode(s=4, L=256)
    table = jnp.asarray(identity_page_table(4, 256, page))
    pos = [page - 1, page, 2 * page + 1, 255]
    out = _decode("paged", q, kc, vc, pos, page_table=table)
    flat = flash_decode_attention(
        q, kc, vc, jnp.asarray(pos, jnp.int32), layer=LAYER,
        dtype=jnp.float32, block_k=page)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(flat))


def test_dense_decode_fully_masked_no_nan():
    """satellite pin: the dense path masks with NEG_INF (-1e30), not
    finfo.min — a fully-masked row (position -1: nothing valid yet)
    softmaxes to finite uniform weights instead of NaN, and position 0
    reduces to exactly v[:, 0]."""
    q, kc, vc = _rand_decode(s=2, L=64)
    out = _decode("dense", q, kc, vc, [-1, 0])
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(out[1, 0].reshape(-1), vc[LAYER, 1, 0],
                               atol=2e-5, rtol=2e-5)
