"""Pallas kernel tests (interpret mode on CPU, compiled on TPU).

Mirrors the reference's numeric-assertion style (weights-changed /
accuracy floors, reference: tests/utils.py:174-210) but at the kernel
level: flash output and gradients must match the naive attention to
tight fp32 tolerances.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.gpt import dot_product_attention
from ray_lightning_tpu.ops.flash_attention import flash_attention


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
    from ray_lightning_tpu.ops.flash_attention import _head_pack, _use_tri
    assert _head_pack(d, h) > 0
    assert _use_tri(True, 64, 64, 4)
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
# T=1024, sub=256); these tests force small sub sizes via RLT_FLASH_SUB
# so the staircase math is pinned at CI-friendly shapes, and one case
# pins the auto default at its threshold.


# (2,64)/(3,64): packed/folded with the sm_scale fold (1/8 is a power
# of two); (4,32): packed WITHOUT the fold (1/√32 has a non-trivial
# mantissa) so the `not fold` scaling branches are covered too.
@pytest.mark.parametrize("h,d", [(2, 64), (3, 64), (4, 32)])
def test_staircase_single_block_matches_full(h, d, monkeypatch):
    """Staircase on (sub=32 at T=128) must match staircase off bit-for-
    bit on dq/dv and to fp tolerance elsewhere, and match the XLA
    reference — for BOTH the head-packed and the folded fused kernels."""
    from ray_lightning_tpu.ops.flash_attention import _sub_block
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

    monkeypatch.setenv("RLT_FLASH_SUB", "0")
    assert _sub_block(128, True) == 0
    v_off = flash(q, k, v)
    g_off = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)

    monkeypatch.setenv("RLT_FLASH_SUB", "32")
    assert _sub_block(128, True) == 32
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


def test_staircase_auto_threshold(monkeypatch):
    """The auto default: off below T=512, sub=256 at T in [512, 1024]
    (single-block territory), irrelevant past 1024 where the tiled tri
    grid takes over — and off for non-causal always."""
    from ray_lightning_tpu.ops.flash_attention import _sub_block
    monkeypatch.delenv("RLT_FLASH_SUB", raising=False)
    assert _sub_block(128, True) == 0
    assert _sub_block(256, True) == 0
    assert _sub_block(512, True) == 256
    assert _sub_block(1024, True) == 256
    assert _sub_block(1024, False) == 0


def test_staircase_env_malformed_warns_and_defaults(monkeypatch):
    """A typo'd opt-out like RLT_FLASH_SUB=off must warn and fall back
    to the auto default instead of crashing at trace time
    (ADVICE r4 #4)."""
    from ray_lightning_tpu.ops.flash_attention import _sub_block
    monkeypatch.setenv("RLT_FLASH_SUB", "off")
    with pytest.warns(UserWarning, match="RLT_FLASH_SUB"):
        assert _sub_block(512, True) == 256   # the auto default
    monkeypatch.setenv("RLT_FLASH_SUB", "")
    assert _sub_block(512, True) == 256       # empty: silent default


def test_rowres_gates_factor_head_width(monkeypatch):
    """The row-resident VMEM budgets were measured at w=128; wide heads
    (d >= 256 pack to w=d) must cap t·w, not t alone (ADVICE r4 #3)."""
    from ray_lightning_tpu.ops.flash_attention import (
        _use_row_resident, _use_row_resident_fwd)
    monkeypatch.delenv("RLT_FLASH_ROWRES", raising=False)
    assert _use_row_resident_fwd(8192, 128)        # the measured point
    assert not _use_row_resident_fwd(8192, 256)    # 2x resident k/v
    assert _use_row_resident_fwd(4096, 256)        # same t*w budget
    assert _use_row_resident(2048, 128)
    assert not _use_row_resident(2048, 256)
    assert _use_row_resident(1024, 256)
    monkeypatch.setenv("RLT_FLASH_ROWRES", "0")
    assert not _use_row_resident_fwd(1024, 128)


def test_staircase_non_causal_unaffected(monkeypatch):
    """Non-causal single block must ignore RLT_FLASH_SUB entirely."""
    monkeypatch.setenv("RLT_FLASH_SUB", "32")
    q, k, v = _rand_qkv(t=128, h=2, d=64)
    out = flash_attention(q, k, v, causal=False, dtype=jnp.float32)
    ref = dot_product_attention(q, k, v, causal=False, dtype=jnp.float32)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("sm_scale", [None, 0.1])  # fold and no-fold
@pytest.mark.parametrize("rowres", ["1", "0"])
def test_rowres_backward_matches_reference(rowres, sm_scale, monkeypatch):
    """The row-resident fused triangular backward (default at
    multi-block causal T<=2048) and the grid-tri pair it replaces must
    BOTH match the reference — the env A/B pins the dispatch seam and
    keeps the fallback path covered.  sm_scale=0.1 (not a power of
    two) exercises the no-fold scaling branches, checked against the
    full-precision einsum recipe directly (the XLA helper hardwires
    1/sqrt(d))."""
    from ray_lightning_tpu.ops.flash_attention import (_head_pack,
                                                       _use_row_resident)
    monkeypatch.setenv("RLT_FLASH_ROWRES", rowres)
    assert _use_row_resident(256) == (rowres == "1")
    assert _head_pack(64, 2) > 0
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
    the grid-tri backward.  Forced at small T by disabling only the
    backward gate — a layout drift between the two would break grads
    here."""
    import sys
    fa = sys.modules["ray_lightning_tpu.ops.flash_attention"]
    monkeypatch.setattr(fa, "_use_row_resident", lambda t, w=128: False)
    assert fa._use_row_resident_fwd(256)
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


@pytest.mark.parametrize("impl", ["dense", "flash_decode", "paged"])
def test_decode_impls_match_unpacked_einsum(impl):
    """Every decode path reads layer LAYER of the packed, stacked cache
    and agrees with the einsum over its unpacked ``[S, L, H, D]`` view,
    across ragged positions including 0 and the cache's last row."""
    from ray_lightning_tpu.serve.fleet.pages import identity_page_table
    q, kc, vc = _rand_decode()
    pos = [0, 17, 128, 255]
    table = jnp.asarray(identity_page_table(4, 256, 64)) \
        if impl == "paged" else None
    out = _decode(impl, q, kc, vc, pos, page_table=table)
    np.testing.assert_allclose(out, _einsum_ref(q, kc, vc, pos),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", ["dense", "flash_decode", "paged"])
def test_decode_rows_in_named_slots(impl):
    """``slots``: a batch of rows that live in cache slots of their own
    choosing (the one-row suffix program) reads exactly what the full
    batch reads at those slots."""
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


def test_flash_decode_matches_dense_ragged():
    """Length-aware kernel vs the masked dense einsum across ragged
    per-slot positions — including position 0 (single valid index) and
    the last index of the cache."""
    q, kc, vc = _rand_decode()
    pos = [0, 17, 128, 255]
    ref = _decode("dense", q, kc, vc, pos)
    out = _decode("flash_decode", q, kc, vc, pos)
    assert out.shape == ref.shape == q.shape
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_decode_single_slot():
    q, kc, vc = _rand_decode(s=1, L=128)
    ref = _decode("dense", q, kc, vc, [63])
    out = _decode("flash_decode", q, kc, vc, [63])
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_decode_bf16_tolerance():
    """bf16 caches (the serve plane's storage dtype) stay within bf16
    rounding of the dense reference."""
    q, kc, vc = _rand_decode(dtype=jnp.bfloat16)
    pos = [5, 100, 200, 255]
    ref = _decode("dense", q, kc, vc, pos, dtype=jnp.bfloat16)
    out = _decode("flash_decode", q, kc, vc, pos, dtype=jnp.bfloat16)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32),
                               atol=2e-2, rtol=2e-2)


def test_paged_decode_page_boundary_straddle():
    """The paged variant (identity page table — slot-contiguous cache)
    must agree with dense at positions ON and AROUND page boundaries,
    where an off-by-one in the table walk or the logical-position
    masking would surface, and agree bitwise with the slot-contiguous
    kernel at matching block size."""
    from ray_lightning_tpu.ops.flash_decode import flash_decode_attention
    from ray_lightning_tpu.serve.fleet.pages import identity_page_table
    page = 64
    q, kc, vc = _rand_decode(s=4, L=256)
    table = jnp.asarray(identity_page_table(4, 256, page))
    pos = [page - 1, page, 2 * page + 1, 255]
    ref = _decode("dense", q, kc, vc, pos)
    out = _decode("paged", q, kc, vc, pos, page_table=table)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
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
