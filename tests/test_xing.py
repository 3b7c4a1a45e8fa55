"""Xing4.0 (models/xing.py, ops/latent_attention.py, ops/flash_decode.py
``mla_decode``, ops/moe.py ``ExpertLayer``) against its plain reference
(chipbench/xing_reference.py), at a tiny size on the CPU: width 64, 4
heads (16 | 8 of query and key, 16 of value) over a latent of 32 + 8, 4
streams, one dense block and two expert blocks of 8 experts, 2 a token,
everything in float32.

Tolerance: the two sides are the same mathematics written twice in
float32 (the reference expands keys and values at every position, loops
over the experts under a mask and runs Sinkhorn on ``[..., n, n]``; the
program has an absorbed decode path over a cache of one array, sorted
pairs in grouped products and Sinkhorn streams-major), so they differ by
summation order only: logits spread by about 1.5, three blocks leave a few
1e-7 of that, and ``ATOL = 2e-5`` leaves room, while a wrong row, mask,
position, frequency, expert, coefficient or weight moves a logit by 1e-3 or
more (``test_an_altered_residual_path_moves_the_logits`` says by how much
for the three hyper-connection faults).
"""

from __future__ import annotations

import dataclasses
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import xing_reference as ref
from chipbench.adapters import xing as adapter
from ray_lightning_tpu.models.xing import (
    SERVE_COUNTERS, Xing, XingLightningModule, sinkhorn)
from ray_lightning_tpu.ops import flash_decode
from ray_lightning_tpu.ops import latent_attention as la
from ray_lightning_tpu.ops import moe
from ray_lightning_tpu.ops import window_attention as wa
from ray_lightning_tpu.parallel.strategy import DataParallelStrategy
from ray_lightning_tpu.serve.buckets import pad_to_bucket
from ray_lightning_tpu.serve.engine import ServeEngine
from ray_lightning_tpu.serve.kvcache import KVCacheSpec
from tests import serve_ahead

ATOL = 2e-5
ROPE = dict(type="yarn", factor=4, original_max_position_embeddings=16,
            beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)
MODEL = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
             moe_intermediate_size=32, num_hidden_layers=3,
             first_k_dense_replace=1, num_attention_heads=4,
             q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
             num_experts_per_tok=2, n_shared_experts=1,
             routed_scaling_factor=2, hc_mult=4, hc_sinkhorn_iters=20,
             hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
             rms_norm_eps=1e-6, rope_theta=10000, rope_scaling=ROPE,
             max_position_embeddings=64, served_positions=64)
CFG = dataclasses.replace(adapter.config_of(MODEL), dtype=jnp.float32)
KEY = jax.random.PRNGKey(5)
SLOTS, POSITIONS, ROW = 3, 64, 128
PUBLISHED = adapter.config_of({
    "rope_scaling": dict(ROPE, factor=64,
                         original_max_position_embeddings=4096)})


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def _time_limit(request):
    """``@pytest.mark.limit(seconds)``: each test's own time limit."""
    mark = request.node.get_closest_marker("limit")
    if mark is None:
        yield
        return

    def late(signum, frame):
        raise TimeoutError(f"over its limit of {mark.args[0]} s")

    was = signal.signal(signal.SIGALRM, late)
    signal.alarm(int(mark.args[0]))
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, was)


@pytest.fixture(scope="module")
def params():
    return adapter.program_tree(MODEL, KEY, jnp.float32)


class _Module(XingLightningModule):
    """The module a user would hand to ``Server``, in float32 and with
    the reference's weights."""

    def __init__(self):
        super().__init__(CFG)

    def init_params(self, rng, batch):
        return {"params": adapter.program_tree(MODEL, KEY, jnp.float32)}


@pytest.fixture(scope="module")
def engine():
    return ServeEngine(_Module(), DataParallelStrategy(),
                       buckets=(32,), slots=SLOTS, max_seq_len=POSITIONS,
                       seed=0).setup()


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 256, (n,)).astype(np.int32)


_FORWARD = jax.jit(lambda tokens: ref.forward(KEY, tokens, MODEL))


def _full(tokens):
    """The reference's logits at every position of ``tokens``: one
    compiled program for every length (causal, so zeros behind the
    sequence change nothing before them)."""
    row = np.zeros((1, POSITIONS), np.int32)
    row[0, :len(tokens)] = tokens
    return np.asarray(_FORWARD(row))[0, :len(tokens)]


@pytest.mark.limit(120)
@pytest.mark.parametrize("T", [8, 29])
def test_forward_matches_reference(params, T):
    seq = _tokens(T, T)
    got = Xing(CFG).apply({"params": params}, jnp.asarray(seq)[None])
    np.testing.assert_allclose(np.asarray(got)[0], _full(seq), atol=ATOL)


@pytest.mark.limit(120)
@pytest.mark.parametrize("T", [29, 50])
def test_a_prompt_past_one_pieces_rows_is_one_pass_through_the_model(
        params, monkeypatch, T):
    """Every expert is held (8 of 8), so ``ExpertLayer`` hands
    ``dropless_experts`` the fact and a prompt whose ``T x 2`` sorted rows
    pass one piece's (put at 32 here: this size has no 1024) runs ONE
    pass, no scatter-add in the whole forward: the reference's logits;
    told nothing, the same layer runs its pieces and reads the same."""
    monkeypatch.setattr(moe, "_ONE_PIECE_ROWS", 32)
    monkeypatch.setattr(moe, "_GMM_TILING_MANY", (2, 1024, 1024))
    assert moe._piece_rows(T * 2) < T * 2
    seq = jnp.asarray(_tokens(T, T))[None]
    net = Xing(CFG)
    forward = lambda p, t: net.apply({"params": p}, t)  # noqa: E731
    got = forward(params, seq)
    np.testing.assert_allclose(np.asarray(got)[0], _full(np.asarray(seq)[0]),
                               atol=ATOL)
    assert "scatter-add" not in str(jax.make_jaxpr(forward)(params, seq))
    told = moe.dropless_experts
    monkeypatch.setattr(moe, "dropless_experts", lambda *a, published, **kw:
                        told(*a, **kw))
    # (a function of its own: a trace is cached by the function traced)
    pieces = lambda p, t: net.apply({"params": p}, t)  # noqa: E731
    assert "scatter-add" in str(jax.make_jaxpr(pieces)(params, seq))
    np.testing.assert_allclose(np.asarray(pieces(params, seq)),
                               np.asarray(got), atol=ATOL)


@pytest.mark.limit(240)
@pytest.mark.parametrize("impl", ["flash_decode", "dense"])
def test_prefill_then_decode_is_the_absorbed_path_against_the_expanded(
        params, monkeypatch, impl):
    """Three prompts at three slots, then 36 decode steps each
    teacher-forced along its sequence, through the ONE array of latent
    rows: blocks of 16 rows, so the slots cross two block edges and read
    more than 2 x a block.  The reference expands keys and values at
    every position of the full forward; the decode never does."""
    monkeypatch.setenv("RLT_DECODE_IMPL", impl)
    monkeypatch.setattr(flash_decode, "_LATENT_BLOCK_K", 16)
    net = Xing(CFG)
    prefill = jax.jit(lambda p, k, v, t, n, s: net.apply(
        {"params": p}, t, n, s, k, v, method="prefill"))
    decode = jax.jit(lambda p, k, v, t, at: net.apply(
        {"params": p}, t, at, k, v, method="decode"))
    spec = KVCacheSpec(n_layer=3, slots=SLOTS, max_seq_len=POSITIONS,
                       width=ROW, rows=POSITIONS, paired=False,
                       counters=len(SERVE_COUNTERS))
    k, v = spec.state(jnp.zeros, jnp.float32)
    assert v == () and len(k) == 2
    lengths, buckets = (5, 13, 21), (16, 16, 32)
    seqs = [_tokens(40 + i, n + 36) for i, n in enumerate(lengths)]
    want = [_full(s) for s in seqs]
    for slot, (n, b) in enumerate(zip(lengths, buckets)):
        logits, k, v = prefill(params, k, v,
                               pad_to_bucket(seqs[slot][:n], b),
                               np.int32(n), np.int32(slot))
        np.testing.assert_allclose(np.asarray(logits), want[slot][n - 1],
                                   atol=ATOL)
    for step in range(36):
        at = np.asarray([n + step for n in lengths], np.int32)
        toks = np.asarray([s[t] for s, t in zip(seqs, at)], np.int32)
        logits, k, v = decode(params, k, v, toks, at)
        for slot in range(SLOTS):
            np.testing.assert_allclose(
                np.asarray(logits)[slot], want[slot][at[slot]], atol=ATOL)
    assert k[0].shape == (3, SLOTS, POSITIONS, ROW) and v == ()
    counted = dict(zip(SERVE_COUNTERS, np.asarray(k[-1])))
    assert counted["prefill_runs"] == 3 and counted["decode_runs"] == 36
    # two expert blocks, 2 a token, every expert held: every pair counts
    assert counted["decode_moe_pairs"] == 36 * SLOTS * 2 * 2
    assert counted["prefill_moe_pairs"] == sum(lengths) * 2 * 2


# -- the residual path ---------------------------------------------------------

def _coefficients(T=24, iters=None, **z_over):
    z = {**ref.sizes(MODEL), **z_over}
    X = jax.random.normal(jax.random.PRNGKey(2), (T, 4, 64), jnp.float32)
    return ref.hyper_coefficients(
        X, *(ref.leaf(MODEL, KEY, f"hc_mlp_{p}", 1)
             for p in ("phi", "b", "a")), z, iters)


@pytest.mark.limit(60)
def test_h_res_is_doubly_stochastic_after_20_iterations_and_not_after_2():
    """Over 24 tokens at the seeded scalars and biases: the median token's
    sums are 1 to 1e-4 after 20 iterations (the worst token's, a matrix
    spread over e^+-3, to 1e-2) and off by over 1e-2 after 2 (the rows':
    the columns came last)."""
    for iters in (20, 2):
        _, _, res = _coefficients(iters=iters)
        off = jnp.max(jnp.abs(jnp.sum(res, axis=-1) - 1.0), axis=-1)
        cols = jnp.max(jnp.abs(jnp.sum(res, axis=-2) - 1.0))
        assert float(cols) < 1e-5
        if iters == 20:
            assert float(jnp.median(off)) < 1e-4 and float(off.max()) < 1e-2
        else:
            assert float(jnp.median(off)) > 1e-2
    # the program's own loop, streams-major, is the reference's
    z = ref.sizes(MODEL)
    m = jnp.exp(jax.random.normal(jax.random.PRNGKey(4), (24, 4, 4)) * 2)
    want = m
    for _ in range(20):
        want = want / (want.sum(-1, keepdims=True) + z["hc_eps"])
        want = want / (want.sum(-2, keepdims=True) + z["hc_eps"])
    got = sinkhorn(jnp.moveaxis(m, 0, -1), 20, z["hc_eps"])
    np.testing.assert_allclose(np.moveaxis(np.asarray(got), -1, 0), want,
                               atol=1e-6)


@pytest.mark.limit(60)
def test_the_seeded_coefficients_move_from_token_to_token():
    """How far the dynamic part moves them at the seeded scalars and
    biases: ``H_pre`` over tokens by a standard deviation of ~0.2 around
    ~0.5, ``H_res``'s entries by ~0.1; and the clip binds in the first
    row for most tokens."""
    pre, post, res = _coefficients(T=200)
    assert 0.1 < float(jnp.std(pre, axis=0).mean()) < 0.3
    assert 0.2 < float(jnp.std(post, axis=0).mean()) < 0.6
    assert float(jnp.std(res, axis=0).mean()) > 0.05
    unclipped = _coefficients(T=200, clamp=(-1e9, 1e9))[2]
    moved = jnp.max(jnp.abs(unclipped - res), axis=(-1, -2))
    assert float(jnp.mean(moved > 1e-2)) > 0.5


@pytest.mark.limit(120)
@pytest.mark.parametrize("fault", ["no_dynamic_part", "no_clip",
                                   "two_iterations", "eps_left_out"])
def test_an_altered_residual_path_moves_the_logits(params, fault):
    """The program with one fault against the sound reference: each moves
    a logit by far more than the tolerance (by 1e-3 or more where ATOL is
    2e-5; ``hc_eps`` itself is below it and must not)."""
    cfg, tree = CFG, params
    if fault == "no_dynamic_part":
        def still(path, a):
            return jnp.zeros_like(a) if path[-1].key == "hc_a" else a
        tree = jax.tree_util.tree_map_with_path(still, params)
    elif fault == "no_clip":
        cfg = dataclasses.replace(CFG, mhc_h_res_clamp_max=1e9)
    elif fault == "two_iterations":
        cfg = dataclasses.replace(CFG, hc_sinkhorn_iters=2)
    else:
        cfg = dataclasses.replace(CFG, hc_eps=0.0)
    seq = _tokens(7, 24)
    got = Xing(cfg).apply({"params": tree}, jnp.asarray(seq)[None])
    off = float(np.max(np.abs(np.asarray(got)[0] - _full(seq))))
    if fault == "eps_left_out":
        assert off < ATOL
    else:
        assert off > 50 * ATOL, off


# -- positions -------------------------------------------------------------------

@pytest.mark.limit(60)
def test_yarn_frequencies_and_scale_against_numbers_by_hand():
    """The published sizes: 64 rotated dimensions, theta 10000, factor 64
    over 4096 positions, beta 32 / 1.  Pair j turns 4096 theta^(-2j/64) /
    2 pi times: 32 times at j = 10.47, once at j = 22.51, so pairs 0-10
    keep theta^(-2j/64), pairs 23-31 take it over 64, and pair 11 lies
    1/13 of the way: 10000^(-22/64) (12/13 + 1/(13 x 64)) = 0.0389765.
    The scale: 192^-0.5 (0.1 ln 64 + 1)^2 = 0.0721688 x 2.0047 = 0.14468."""
    f = np.asarray(PUBLISHED.inv_freq())
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 64, rtol=1e-6)
    np.testing.assert_allclose(f[11], 0.0389765, rtol=1e-5)
    np.testing.assert_allclose(f[17], plain[17] * (6 / 13 + 7 / 13 / 64),
                               rtol=1e-5)
    assert abs(PUBLISHED.softmax_scale - 0.14468) < 1e-5
    # the reference computes both apart from the program
    z = ref.sizes({**MODEL, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                   "rope_scaling": dict(
                       ROPE, factor=64,
                       original_max_position_embeddings=4096)})
    np.testing.assert_allclose(np.asarray(ref.yarn_inv_freq(z)), f,
                               rtol=1e-6)
    assert abs(ref.softmax_scale(z) - PUBLISHED.softmax_scale) < 1e-9


@pytest.mark.limit(60)
def test_rotary_with_given_frequencies_is_the_references():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 11, 3, 8), jnp.float32)
    f = CFG.inv_freq()
    got = wa.rotary_interleaved(x, jnp.arange(11), CFG.rope_theta, f)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.rotary(x, f)), atol=1e-6)
    # and left out, the frequencies are theta's as they were
    plain = 10000.0 ** (-jnp.arange(0, 8, 2, dtype=jnp.float32) / 8)
    np.testing.assert_array_equal(
        np.asarray(wa.rotary_interleaved(x, jnp.arange(11), 10000.0)),
        np.asarray(wa.rotary_interleaved(x, jnp.arange(11), 10000.0,
                                         plain)))


# -- the router --------------------------------------------------------------------

@pytest.mark.limit(60)
def test_the_routers_bias_chooses_and_does_not_weigh_and_the_factor_is_2():
    h = jax.random.normal(jax.random.PRNGKey(6), (40, 64), jnp.float32)
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(7), (64, 8), jnp.float32)
    s = np.asarray(jax.nn.sigmoid(h @ w))
    plain_idx, plain_w = moe.sigmoid_topk(h, w, 2)
    np.testing.assert_array_equal(np.asarray(plain_idx),
                                  np.argsort(-s, axis=-1)[:, :2])
    np.testing.assert_allclose(np.asarray(plain_w).sum(-1), 1.0, atol=1e-6)
    # a bias that lifts expert 5 over everything: always chosen, and its
    # weight is still its own score's share of the two scores
    bias = jnp.zeros((8,)).at[5].set(10.0)
    idx, wt = moe.sigmoid_topk(h, w, 2, bias, 2.0)
    idx, wt = np.asarray(idx), np.asarray(wt)
    assert (idx[:, 0] == 5).all()
    chosen = np.take_along_axis(s, idx, axis=-1)
    np.testing.assert_allclose(wt, 2.0 * chosen / chosen.sum(-1,
                                                             keepdims=True),
                               atol=1e-6)
    assert (np.asarray(plain_idx) != idx).any()
    # the reference's dense form of the same
    z = {**ref.sizes(MODEL)}
    dense = np.asarray(ref.route(h, w, bias, z))
    np.testing.assert_allclose(np.take_along_axis(dense, idx, axis=-1), wt,
                               atol=1e-6)
    assert ((dense > 0).sum(-1) == 2).all()


@pytest.mark.limit(60)
def test_without_bias_and_factor_the_router_is_the_program_it_was():
    h = jax.ShapeDtypeStruct((40, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 8), jnp.float32)
    was = str(jax.make_jaxpr(lambda h, w: moe.sigmoid_topk(h, w, 2))(h, w))
    now = str(jax.make_jaxpr(
        lambda h, w: moe.sigmoid_topk(h, w, 2, None, 1.0))(h, w))
    assert was == now and "add" not in was.split("top_k")[0]


@pytest.mark.limit(60)
@pytest.mark.parametrize("m,k,want", [
    (256, 4096, (128, 1024, 2048)),
    (65536, 4096, (256, 1024, 1024)),
    (64, 4096, (64, 1024, 2048)),
    (256, 3584, (128, 1792, 1024)),
    (1024, 3584, (128, 1792, 1024)),
    (4096, 3584, (256, 3584, 512)),
    (36864, 3584, (256, 3584, 512)),
    (256, 1024, (128, 1024, 2048)),
    (1024, 1024, (128, 1024, 2048)),
    (4096, 1024, (64, 1024, 3584)),
    (36864, 1024, (64, 1024, 3584))])
def test_the_grouped_products_tiles_follow_the_shapes(m, k, want):
    assert moe.gmm_tiling(m, k) == want


# -- the cache and its kernel ------------------------------------------------------

@pytest.mark.limit(60)
def test_a_state_of_one_array_and_the_pairs_as_they_were():
    """A latent row a position: ONE array a kind, no values' side; 1,152 B
    of values a row a layer at the published widths, 1,280 B as it lies
    (640 lanes: ``XingConfig.row_width``).  GPT-2's, EvaByte's and
    Command's descriptions are what they were."""
    assert PUBLISHED.row_width == 640 and CFG.row_width == ROW \
        and 2 * (PUBLISHED.kv_lora_rank + PUBLISHED.qk_rope_head_dim) == 1152
    block = jax.ShapeDtypeStruct((1, 1, 9984, 640), jnp.bfloat16)
    spec = KVCacheSpec.from_capture([(block,)] * 5, slots=64,
                                    max_seq_len=9984, counters=8)
    assert spec == KVCacheSpec(n_layer=5, slots=64, max_seq_len=9984,
                               width=640, rows=9984, counters=8,
                               paired=False)
    assert spec.own_state and spec.shapes == ((5, 64, 9984, 640),) \
        and spec.shape == (5, 64, 9984, 640)
    assert spec.nbytes() == 1280 * 5 * 64 * 9984
    k, v = spec.state(jax.ShapeDtypeStruct, jnp.bfloat16)
    assert v == () and len(k) == 2 and k[0].shape == spec.shape \
        and k[1].shape == (8,) and k[1].dtype == jnp.int32
    bare = KVCacheSpec.from_capture([(block,)], 2, 9984)
    k, v = bare.state(jax.ShapeDtypeStruct, jnp.bfloat16)
    assert v == () and len(k) == 1
    # pairs, handed in as avals or as the captured tuples: as they were
    row = jax.ShapeDtypeStruct((1, 8, 64), jnp.bfloat16)
    own = jax.ShapeDtypeStruct((1, 1, 96, 64), jnp.bfloat16)
    for entries in ([row, row], [(row, row), (row, row)]):
        gpt = KVCacheSpec.from_capture(entries, slots=4, max_seq_len=64)
        assert gpt == KVCacheSpec(n_layer=2, slots=4, max_seq_len=64,
                                  width=64) and gpt.paired
        assert gpt.nbytes() == 2 * 2 * 2 * 4 * 64 * 64
    eva = KVCacheSpec.from_capture([(own, own)] * 2, 4, 256)
    assert eva == KVCacheSpec(n_layer=2, slots=4, max_seq_len=256, width=64,
                              rows=96)
    ks, vs = eva.state(jnp.zeros, jnp.bfloat16)
    assert isinstance(ks, jax.Array) and ks.shape == vs.shape
    mixed = KVCacheSpec.from_capture(
        [(jax.ShapeDtypeStruct((1, 1, r, 32), jnp.bfloat16),) * 2
         for r in (8, 8, 8, 56)], 4, 56, counters=8)
    assert mixed.kinds == ((3, 8), (1, 56)) and mixed.paired
    assert mixed.nbytes() == 2 * 2 * 32 * 4 * (3 * 8 + 56)
    ks, vs = mixed.state(jnp.zeros, jnp.bfloat16)
    assert len(ks) == 3 and len(vs) == 2


@pytest.mark.limit(120)
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("L", [64, 72], ids=["tiled", "ragged"])
def test_latent_decode_call_against_a_dense_einsum(monkeypatch, dtype, atol,
                                                   L):
    """``mla_decode`` under the interpreter: 4 heads read ONE row of 40
    whose first 32 lanes are the value; slots at position 0, at a block's
    last row and its first, mid-cache and at the last row.  ``ragged``: 72
    rows in blocks of 16, so the fifth block's last 8 rows lie past the
    array (9,984 rows in blocks of 1,024, PR 42): the slots at the last
    whole block's last row, the ragged block's first and the cache's
    last; one fetch serves keys and values, so the mask is on the block's
    value lanes."""
    monkeypatch.setattr(flash_decode, "_LATENT_BLOCK_K", 16)
    S, H, C, r = 5, 4, 40, 32
    q, rows = (jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32)
               for i, shape in enumerate([(S, H, C), (2, S, L, C)]))
    at = jnp.asarray([0, 15, 16, 37, 63] if L == 64
                     else [0, 63, 64, 37, 71], jnp.int32)
    cache = rows.astype(dtype)
    with flash_decode.record_decode_kernels() as lowered:
        got = la.cached_attention(q.astype(dtype), cache, at, layer=1,
                                  value_dim=r, sm_scale=0.31, dtype=dtype,
                                  impl="flash_decode")
    assert lowered == {"mla_decode": [[16, 4, 16] if L == 64
                                      else [16, 5, 8]]}
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert got.shape == (S, H, r) and got.dtype == dtype
    own = cache[1].astype(jnp.float32)
    s = jnp.einsum("shc,slc->shl", q.astype(dtype).astype(jnp.float32),
                   own) * 0.31
    seen = jnp.arange(L)[None, :] <= at[:, None]
    p = jax.nn.softmax(jnp.where(seen[:, None, :], s, -jnp.inf), axis=-1)
    want = jnp.einsum("shl,slc->shc", p, own[..., :r])
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=atol)
    dense = la.cached_attention(q.astype(dtype), cache, at, layer=1,
                                value_dim=r, sm_scale=0.31, dtype=dtype,
                                impl="dense")
    np.testing.assert_allclose(np.asarray(dense, np.float32), want,
                               atol=atol)


@pytest.mark.limit(60)
def test_prefill_attention_with_two_widths_against_the_references():
    q, k = (jax.random.normal(jax.random.PRNGKey(i), (1, 40, 4, 24))
            for i in (1, 2))
    v = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 4, 16))
    got = la.causal_attention(q, k, v, sm_scale=0.2, dtype=jnp.float32)
    want = ref.causal_attention(q, k, v, 0.2, "float32")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.limit(120)
def test_weights_by_leaf_are_the_references_and_float32_where_they_say():
    tree = adapter.program_tree(MODEL, KEY)
    bf = lambda a: a.astype(jnp.bfloat16)    # noqa: E731
    blk = tree["h2"]
    assert blk["moe"]["router"].dtype == blk["moe"]["bias"].dtype \
        == blk["hc_attn"]["hc_phi"].dtype == blk["hc_mlp"]["hc_b"].dtype \
        == blk["hc_mlp"]["hc_a"].dtype == jnp.float32
    assert blk["moe"]["gate"].dtype == blk["attn"]["uk"].dtype \
        == tree["lm_head"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(blk["moe"]["gate"][5]),
        np.asarray(bf(ref.leaf(MODEL, KEY, "gate_w", 2, 5))))
    ukv = ref.leaf(MODEL, KEY, "ukv_w", 2).reshape(32, 4, 32)
    np.testing.assert_array_equal(np.asarray(blk["attn"]["uk"]),
                                  np.asarray(bf(ukv[..., :16])))
    np.testing.assert_array_equal(np.asarray(blk["attn"]["uv"]),
                                  np.asarray(bf(ukv[..., 16:])))
    # what bfloat16 holds: the resident cast loses nothing
    w = ref.leaf(MODEL, KEY, "uq_w", 1)
    np.testing.assert_array_equal(np.asarray(bf(w).astype(jnp.float32)),
                                  np.asarray(w))
    # the first row of b_res lies at the clip
    b = np.asarray(blk["hc_attn"]["hc_b"])[8:].reshape(4, 4)
    assert b[0].min() > 26 and b[1:].max() < 6
    assert "mlp" in tree["h0"] and "moe" not in tree["h0"]
    # the program's own init has the same tree and the same types
    made = XingLightningModule("tiny").init_params(
        KEY, np.zeros((1, 8), np.int32))["params"]
    same = jax.tree_util.tree_map(
        lambda a, b: a.dtype == b.dtype and a.ndim == b.ndim, made, tree)
    assert all(jax.tree_util.tree_leaves(same))


@pytest.mark.limit(120)
@pytest.mark.parametrize("precision", ["float32", "fp8"])
def test_the_reference_on_held_weights_is_the_reference_on_the_key(precision):
    """``hold`` makes every tensor once and keeps it as bfloat16 holds it
    (the check's ``make_weights``): the logits are the key's, every
    ``leaf`` comes back as it was made, and the float32 leaves stay so."""
    held = jax.jit(lambda k: ref.hold(MODEL, k))(KEY)
    assert set(held) == (set(ref.LEAVES + ref.GLOBAL_LEAVES) - {"wte"}) \
        | {"key"}
    assert held["router_w"].dtype == held["hc_mlp_phi"].dtype == jnp.float32
    assert held["down_w"].shape == (2, 8, 32, 64) \
        and held["down_w"].dtype == held["head_w"].dtype == jnp.bfloat16 \
        and held["mlp_up_w"].shape[0] == 1 and held["o_w"].shape[0] == 3
    for name, layer, expert in (("wte", -1, None), ("mlp_gate_w", 0, None),
                                ("router_b", 1, None), ("down_w", 2, 5),
                                ("hc_attn_b", 2, None), ("ukv_w", 0, None)):
        # to an ulp: a constant folded into another inside one program
        np.testing.assert_allclose(
            np.asarray(ref.leaf(MODEL, held, name, layer, expert)),
            np.asarray(ref.leaf(MODEL, KEY, name, layer, expert)),
            rtol=3e-7, atol=0)
    tokens = jnp.asarray(_tokens(11, 40))[None]
    got = jax.jit(lambda w: ref.forward(w, tokens, MODEL, precision))(held)
    want = jax.jit(lambda w: ref.forward(w, tokens, MODEL, precision))(KEY)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=ATOL if precision == "float32" else 0.02)


@pytest.mark.limit(60)
@pytest.mark.parametrize("rooms,branch", [((40,), 0), ((6, 40), 1),
                                          ((3, 6), 2)])
def test_the_references_expert_loop_is_exact_in_every_room(rooms, branch):
    """``routed_sum`` sends an expert's tokens through the smallest room
    that takes them and every token under the mask where none does (the
    check's padding hands a few experts a fifth of a request): the same
    sum as each expert over every token."""
    z = ref.sizes(MODEL)
    h = jax.random.normal(jax.random.PRNGKey(9), (40, z["d"]))
    w = ref.route(h, ref.leaf(MODEL, KEY, "router_w", 2),
                  ref.leaf(MODEL, KEY, "router_b", 2), z)
    mats = lambda e: [ref.leaf(MODEL, KEY, n, 2, e)  # noqa: E731
                      for n in ("gate_w", "up_w", "down_w")]
    chose = (np.asarray(w) > 0).sum(0)
    assert sum(int(chose.max() > r) for r in rooms) == branch
    got = ref.routed_sum(h, w, jnp.arange(z["E"]), mats, rooms)
    want = sum(w[:, e, None] * ref._gated(h, *mats(e), "float32")
               for e in range(z["E"]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))
    assert ref.expert_rooms(10240, ref.sizes(
        {**MODEL, "n_routed_experts": 64, "num_experts_per_tok": 4})) \
        == (960, 3840)
    assert ref.expert_rooms(40, z) == (40,)


# -- through the engine's own programs ---------------------------------------------

@pytest.mark.limit(120)
def test_engine_serves_the_reference_tokens_over_one_array(engine):
    spec = engine.kv_spec
    assert not spec.paired and spec.own_state \
        and spec.shapes == ((3, SLOTS, POSITIONS, ROW),)
    assert spec.nbytes(4) == 4 * ROW * 3 * SLOTS * POSITIONS
    assert isinstance(engine._k, tuple) and len(engine._k) == 2 \
        and engine._v == () and engine._k[-1].dtype == jnp.int32
    assert engine.stats()["decode_kernel"] == "dense"
    before = engine.stats()["counters"]
    seq = _tokens(11, 50)
    want = _full(seq).argmax(-1)
    got = [engine.prefill(1, pad_to_bucket(seq[:19], 32), 19, 32)]
    toks, at = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    for t in range(19, 45):
        toks[1], at[1] = seq[t], t
        got.append(int(engine.decode(toks, at)[1]))
    assert got == [int(x) for x in want[18:45]]
    after = engine.stats()["counters"]
    assert after["prefill_runs"] - before["prefill_runs"] == 1
    assert after["decode_runs"] - before["decode_runs"] == 26
    assert after["decode_moe_pairs"] - before["decode_moe_pairs"] \
        == 26 * SLOTS * 2 * 2
    assert after["decode_moe_rows"] - before["decode_moe_rows"] \
        == 26 * SLOTS * 2 * 2
    assert after["prefill_moe_pairs"] - before["prefill_moe_pairs"] \
        == 19 * 2 * 2
    assert sum(engine.stats()["retraces"].values()) == 0


@pytest.mark.limit(240)
@pytest.mark.parametrize("name", ["freed_slot", "no_decode"])
def test_decode_ahead_serves_what_the_blocking_order_serves(engine, name):
    """serve/worker.py ``_run_ahead`` over a state of one array: equal
    tokens and, at every step, equal rows where a live slot can read."""
    prompts = [_tokens(20 + i, n) for i, n in
               enumerate((5, 13, 21, 9, 27, 16))]
    got = serve_ahead.check_equal_and_counted(
        engine, prompts, name, lambda pos: np.arange(pos))
    assert engine.stats()["counters"]["decode_runs"] > sum(got["decoded"])
    assert sum(engine.stats()["retraces"].values()) == 0


@pytest.mark.limit(60)
@pytest.mark.parametrize("what", ["paged", "kvship", "spec", "engine",
                                  "suffix", "paged_kernel"])
def test_refusals_name_the_reason(params, monkeypatch, what):
    from ray_lightning_tpu.serve import Server
    from ray_lightning_tpu.serve.fleet.pages import PageConfig
    from ray_lightning_tpu.serve.spec import SpecConfig
    module = XingLightningModule(CFG)
    paged = PageConfig(enabled=True, page_size=8)
    if what in ("paged", "kvship", "spec"):
        kw, match = {
            "paged": ({"paged": paged}, "paged= is refused for Xing"),
            "kvship": ({"kvship": True}, "kvship= is refused for Xing"),
            "spec": ({"spec": SpecConfig(enabled=True, k=2)},
                     "spec= is refused for Xing")}[what]
        with pytest.raises(ValueError, match=match):
            Server(module, buckets=(16,), max_batch_slots=2,
                   max_seq_len=POSITIONS, platform="cpu", **kw)
        return
    if what == "engine":
        with pytest.raises(ValueError, match="own kind of cache rows"):
            ServeEngine(module, DataParallelStrategy(), buckets=(16,),
                        slots=2, max_seq_len=POSITIONS, paged=paged).setup()
        return
    net = Xing(CFG)
    k = (jnp.zeros((3, 2, POSITIONS, ROW), jnp.float32),)
    z = jnp.zeros((2,), jnp.int32)
    if what == "suffix":
        with pytest.raises(ValueError, match="suffix program"):
            net.apply({"params": params}, z[:1], z[:1], k, (),
                      method="decode", slots=z[:1])
    else:
        monkeypatch.setenv("RLT_DECODE_IMPL", "paged")
        with pytest.raises(ValueError, match="one array of rows"):
            net.apply({"params": params}, z, z, k, (), method="decode")


@pytest.mark.limit(60)
def test_live_rows_are_a_row_a_position():
    module = XingLightningModule(CFG)
    assert module.live_cache_rows(0) == 1 \
        and module.live_cache_rows(8299) == 8300


@pytest.mark.limit(240)
def test_server_on_the_cpu_serves_the_references_tokens():
    """``Server(module, platform="cpu").start()``: a worker process, the
    scheduler, a bucketed prefill and the decode program queued ahead,
    in bfloat16 on seeded weights.  A served greedy token is the
    reference's best wherever the reference's margin over its second is
    wider than bfloat16's noise on these logits (0.03 of a spread of
    0.16)."""
    from ray_lightning_tpu.serve import Server
    server = Server(adapter.module(MODEL, 0), checkpoint=None,
                    buckets=(16, 32), max_batch_slots=2,
                    max_seq_len=POSITIONS, seed=5, platform="cpu",
                    telemetry=False)
    server.start()
    try:
        prompts = [_tokens(60 + i, n) for i, n in enumerate((9, 20, 14))]
        reqs = [server.submit(p, max_new_tokens=12) for p in prompts]
        outs = [r.result(120) for r in reqs]
        stats = server.stats()
    finally:
        server.shutdown(graceful=False)
    assert stats["scheduler"]["live_rows"] > 0
    assert sum(stats["workers"][0]["retraces"].values()) == 0
    checked = 0
    for p, out in zip(prompts, outs):
        out = np.asarray(out)
        assert out.shape == (12,)
        logits = _full(np.concatenate([p, out[:-1]]))[len(p) - 1:]
        top = np.sort(logits, axis=-1)
        sure = top[:, -1] - top[:, -2] > 0.03
        assert (logits.argmax(-1)[sure] == out[sure]).all()
        checked += int(sure.sum())
    assert checked >= 8
