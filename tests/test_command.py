"""Command A+ (models/command.py, ops/moe.py ``dropless_experts``,
ops/window_attention.py) against its plain reference
(chipbench/command_reference.py), at a tiny size on the CPU: width 64, 8
query heads on 2 K/V heads of 16, window 8, two periods of three sliding
layers and a full one, 16 published experts of which 4 are held, 4 a
token, 2 shared, everything in float32.

Tolerance: the two sides are the same mathematics written twice in
float32 (the reference with a loop over the held experts under a mask and
attention group by group, the program with sorted pairs, grouped products
and, served, a ring and a row per position), so they differ by summation
order only: logits spread by about 1.3, eight layers leave a few 1e-7 of
that, and ``ATOL = 2e-5`` leaves room while a wrong row, mask, position,
expert or weight moves a logit by 1e-2 or more.  A routing flip would
move one too: the router is float32 on both sides and no pair of scores
at this size lies within rounding of another.
"""

from __future__ import annotations

import dataclasses
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import command_reference as ref
from chipbench.adapters import command as adapter
from ray_lightning_tpu.models.command import (
    SERVE_COUNTERS, Command, CommandLightningModule)
from ray_lightning_tpu.ops import flash_decode as _fd
from ray_lightning_tpu.ops import moe
from ray_lightning_tpu.ops import window_attention as wa
from ray_lightning_tpu.parallel.strategy import DataParallelStrategy
from ray_lightning_tpu.serve.buckets import pad_to_bucket
from ray_lightning_tpu.serve.engine import ServeEngine
from ray_lightning_tpu.serve.kvcache import KVCacheSpec
from tests import serve_ahead

ATOL = 2e-5
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
MODEL = dict(vocab_size=256, hidden_size=64, intermediate_size=48,
             num_hidden_layers=8, num_attention_heads=8,
             num_key_value_heads=2, head_dim=16, num_experts=4,
             num_experts_published=16, expert_offset=0,
             num_experts_per_tok=4, num_shared_experts=2, sliding_window=8,
             layer_types=PERIOD * 2, rope_theta=50000, layer_norm_eps=1e-5,
             logit_scale=1, max_position_embeddings=64, served_positions=56)
CFG = dataclasses.replace(adapter.config_of(MODEL), dtype=jnp.float32)
#: one period, for the tests that go through the engine's own programs
MODEL4 = {**MODEL, "num_hidden_layers": 4}
CFG4 = dataclasses.replace(adapter.config_of(MODEL4), dtype=jnp.float32)
KEY = jax.random.PRNGKey(3)
SLOTS = 3
WINDOW, POSITIONS = 8, 56


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


class _Module(CommandLightningModule):
    """The module a user would hand to ``Server``, in float32 and with
    the reference's weights."""

    def __init__(self):
        super().__init__(CFG4)

    def init_params(self, rng, batch):
        return {"params": adapter.program_tree(MODEL4, KEY, jnp.float32)}


@pytest.fixture(scope="module")
def engine():
    return ServeEngine(_Module(), DataParallelStrategy(),
                       buckets=(32,), slots=SLOTS, max_seq_len=POSITIONS,
                       seed=0).setup()


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 256, (n,)).astype(np.int32)


def _full(tokens, model=MODEL):
    return np.asarray(ref.forward(KEY, jnp.asarray(tokens)[None], model))[0]


@pytest.mark.limit(120)
@pytest.mark.parametrize("T", [8, 29])
def test_forward_matches_reference(params, T):
    """At the window and past it (the band cuts; the full layers do
    not)."""
    seq = _tokens(T, T)
    got = Command(CFG).apply({"params": params}, jnp.asarray(seq)[None])
    np.testing.assert_allclose(np.asarray(got)[0], _full(seq), atol=ATOL)


@pytest.mark.limit(240)
def test_prefill_then_decode_through_both_caches(params, monkeypatch):
    """Two periods; three prompts (inside the window, past it, two
    windows) at three slots, then 20 decode steps each teacher-forced
    along its sequence: every ring wraps, and the logits are the
    reference's at every step, with the ``gqa_decode`` kernel under the
    interpreter (the engine's test below takes the dense path)."""
    monkeypatch.setenv("RLT_DECODE_IMPL", "flash_decode")
    net = Command(CFG)
    prefill = jax.jit(lambda p, k, v, t, n, s: net.apply(
        {"params": p}, t, n, s, k, v, method="prefill"))
    decode = jax.jit(lambda p, k, v, t, at: net.apply(
        {"params": p}, t, at, k, v, method="decode"))
    spec = KVCacheSpec(n_layer=8, slots=SLOTS, max_seq_len=POSITIONS,
                       width=32, kinds=((6, WINDOW), (2, POSITIONS)),
                       counters=len(SERVE_COUNTERS))
    k, v = spec.state(jnp.zeros, jnp.float32)
    lengths, buckets = (5, 13, 21), (16, 16, 32)
    seqs = [_tokens(40 + i, n + 20) for i, n in enumerate(lengths)]
    want = [_full(s) for s in seqs]
    for slot, (n, b) in enumerate(zip(lengths, buckets)):
        logits, k, v = prefill(params, k, v,
                               pad_to_bucket(seqs[slot][:n], b),
                               np.int32(n), np.int32(slot))
        np.testing.assert_allclose(np.asarray(logits), want[slot][n - 1],
                                   atol=ATOL)
    for step in range(20):
        at = np.asarray([n + step for n in lengths], np.int32)
        toks = np.asarray([s[t] for s, t in zip(seqs, at)], np.int32)
        logits, k, v = decode(params, k, v, toks, at)
        for slot in range(SLOTS):
            np.testing.assert_allclose(
                np.asarray(logits)[slot], want[slot][at[slot]], atol=ATOL)
    assert k[0].shape == (6, SLOTS, WINDOW, 32) \
        and k[1].shape == (2, SLOTS, POSITIONS, 32)
    counted = dict(zip(SERVE_COUNTERS, np.asarray(k[-1])))
    assert counted["prefill_runs"] == 3 and counted["decode_runs"] == 20


def _layer_inputs(T=24):
    x = 1.5 * jax.random.normal(jax.random.PRNGKey(9), (T, 64), jnp.float32)
    return ref.layer_norm(x, ref.leaf(MODEL, KEY, "ln_g", 2), 1e-5)


def _held(model, layer, dtype=jnp.float32):
    z = ref.sizes(model)
    return [jnp.stack([ref.leaf(model, KEY, n, layer, z["offset"] + e)
                       for e in range(z["held"])]).astype(dtype)
            for n in ("gate_w", "up_w", "down_w")]


@pytest.mark.limit(120)
def test_every_share_of_the_experts_adds_up_to_the_uncut_layer():
    """The guide's share test: what each of four chips' experts adds
    for the tokens routed to them, summed, plus the shared experts ONCE,
    is the uncut layer (all 16 experts held) of the reference; in the
    reference's own shares and in the program's dropless layer alike."""
    h = _layer_inputs()
    uncut = {**MODEL, "num_experts": 16, "expert_offset": 0}
    routed, shared = ref.moe_parts(h, uncut, KEY, 2)
    whole = np.asarray(routed + shared)
    idx, w = moe.sigmoid_topk(h, ref.leaf(MODEL, KEY, "router_w", 2), 4)
    ref_sum = prog_sum = 0.0
    for part in range(4):
        share = ref.share_of(MODEL, part, 4)
        assert share["num_experts"] == 4 \
            and share["expert_offset"] == 4 * part
        ref_sum = ref_sum + ref.moe_parts(h, share, KEY, 2)[0]
        y, *_ = moe.dropless_experts(h, idx, w, *_held(share, 2),
                                     offset=4 * part)
        prog_sum = prog_sum + y
    # (the layer's output is small at this size: the tolerance is a
    # share of its largest entry, not of a logit's spread)
    tol = 1e-4 * np.abs(whole).max()
    np.testing.assert_allclose(np.asarray(ref_sum + shared), whole, atol=tol)
    np.testing.assert_allclose(np.asarray(prog_sum + shared), whole,
                               atol=tol)
    # and one share alone is not the layer, nor are the shared experts
    # counted once a share
    assert np.abs(np.asarray(y + shared) - whole).max() > 1000 * tol
    assert np.abs(np.asarray(prog_sum + 4 * shared) - whole).max() \
        > 1000 * tol


@pytest.mark.limit(60)
def test_pairs_and_experts_hit_against_a_count_by_hand():
    """``pairs`` are the token-expert pairs whose expert is held here
    and whose token exists; ``experts_hit`` the held experts with one;
    ``rows`` what went through the grouped products: all ``T * k`` where
    the layer is one piece, the pieces that ran beyond."""
    h = _layer_inputs()
    idx, w = moe.sigmoid_topk(h, ref.leaf(MODEL, KEY, "router_w", 2), 4)
    valid = jnp.arange(24) < 19
    _, pairs, hit, rows = moe.dropless_experts(
        h, idx, w, *_held(MODEL, 2), offset=0, valid=valid)
    chosen = np.asarray(idx)[:19]
    assert int(pairs) == int((chosen < 4).sum()) > 0
    assert int(hit) == len(set(chosen[chosen < 4].tolist()))
    assert rows == 24 * 4
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    # a share that holds experts nobody chose computes nothing
    none = jnp.full_like(idx, 9)
    y, pairs, hit, _ = moe.dropless_experts(h, none, w, *_held(MODEL, 2))
    assert int(pairs) == 0 and int(hit) == 0 and not np.asarray(y).any()
    # 300 tokens' 1200 sorted rows are past one piece (1024): pieces of
    # 256 rows (a sixteenth, in whole tiles of 256), as many as the pairs
    # of the first 280 tokens on experts 4-11 need
    h = _layer_inputs(300)
    idx, w = moe.sigmoid_topk(h, ref.leaf(MODEL, KEY, "router_w", 2), 4)
    half = {**MODEL, "num_experts": 8, "expert_offset": 4}
    _, pairs, hit, rows = moe.dropless_experts(
        h, idx, w, *_held(half, 2), offset=4, valid=jnp.arange(300) < 280)
    chosen = np.asarray(idx)[:280]
    mine = chosen[(chosen >= 4) & (chosen < 12)]
    assert int(pairs) == len(mine) and 2 * 256 < len(mine) <= 3 * 256
    assert int(hit) == len(set(mine.tolist())) == 8
    assert int(rows) == 3 * 256


#: the cases the pieces create, at 4 choices a token of 16 experts: tokens
#: (so 4 x as many sorted rows: past one piece, in pieces of 256), experts
#: held and the first of them, tokens that exist, pieces that run
PIECES = {
    "pairs_under_one_piece": (320, 3, 0, None, 1),
    "pairs_past_the_first_piece": (320, 8, 4, None, 3),
    "every_pair_on_a_held_expert": (320, 16, 0, None, 5),
    "no_pair_at_all": (320, 8, 0, 0, 0),
    "valid_cuts_the_buckets_tail": (320, 8, 0, 100, 1),
    "an_edge_inside_an_experts_group": (384, 6, 10, None, 3),
}


@pytest.mark.limit(120)
@pytest.mark.parametrize("case", list(PIECES))
def test_the_layer_in_pieces_against_the_references_expert_loop(case):
    """A prompt's sorted rows are cut into pieces and as many run as the
    pairs need: whatever their number, the layer is the reference's loop
    over the held experts (``moe_parts``), zero at a token that does not
    exist."""
    T, held, offset, length, pieces = PIECES[case]
    model = {**MODEL, "num_experts": held, "expert_offset": offset}
    h = _layer_inputs(T)
    C = moe._piece_rows(T * 4)
    assert C == 256 < T * 4
    valid = None if length is None else jnp.arange(T) < length
    routed = ref.moe_parts(h, model, KEY, 2)[0]
    want = routed if valid is None else jnp.where(valid[:, None], routed, 0)
    idx, w = moe.sigmoid_topk(h, ref.leaf(MODEL, KEY, "router_w", 2), 4)
    y, pairs, hit, rows = jax.jit(
        lambda h, idx, w: moe.dropless_experts(
            h, idx, w, *_held(model, 2), offset=offset, valid=valid))(
                h, idx, w)
    chosen = np.asarray(idx)[:length] - offset
    mine = chosen[(chosen >= 0) & (chosen < held)]
    assert int(pairs) == len(mine) and int(rows) == pieces * C
    assert (pieces - 1) * C < len(mine) <= pieces * C
    assert int(hit) == len(set(mine.tolist()))
    if case == "every_pair_on_a_held_expert":
        assert len(mine) == T * 4
    if case == "an_edge_inside_an_experts_group":
        ends = np.cumsum(np.bincount(mine, minlength=held))
        assert C not in ends and ends[0] < C < ends[-1]
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               atol=1e-4 * float(jnp.abs(routed).max()))


#: the cases of ONE pass (every published expert held, ``T * 4`` sorted
#: rows past one piece): tokens, tokens that exist, an expert that the
#: router's bias keeps every token from choosing
ONE_PASS = {
    "every_token_exists": (320, None, None),
    "valid_cuts_the_buckets_tail": (320, 290, None),
    "fewer_pairs_than_one_piece": (320, 100, None),
    "no_token_exists": (320, 0, None),
    "an_expert_gets_no_pair": (384, 350, 5),
    "a_bucket_that_is_not_whole_row_tiles": (333, 300, None),
}


def _jaxpr_of_the_layer(T, model, **kw):
    z = ref.sizes(model)
    return str(jax.make_jaxpr(
        lambda h, idx, w: moe.dropless_experts(
            h, idx, w, *_held(model, 2), offset=z["offset"], impl="ragged",
            **kw))(
                _layer_inputs(T), jnp.zeros((T, 4), jnp.int32),
                jnp.ones((T, 4), jnp.float32)))


@pytest.mark.limit(120)
@pytest.mark.parametrize("case", list(ONE_PASS))
def test_the_layer_in_one_pass_against_the_loop_and_the_pieces(case):
    """Told that it holds every published expert, the layer takes a
    prompt's rows in ONE pass (one gather in, whole expert groups, one
    gather back): the reference's loop over the experts (``moe_parts``),
    a dense sum over the experts from the pairs themselves, and the
    in-pieces result on the same inputs, to float32 rounding; zero at a
    token that does not exist; ``rows`` is ``T * k``."""
    T, length, unchosen = ONE_PASS[case]
    model = {**MODEL, "num_experts": 16, "expert_offset": 0}
    h = _layer_inputs(T)
    assert moe._ONE_PIECE_ROWS < T * 4
    valid = None if length is None else jnp.arange(T) < length
    bias = None if unchosen is None else \
        jnp.zeros((16,)).at[unchosen].set(-10.0)
    idx, w = moe.sigmoid_topk(h, ref.leaf(MODEL, KEY, "router_w", 2), 4,
                              bias)
    held = _held(model, 2)

    def layer(**kw):
        return jax.jit(lambda h, idx, w: moe.dropless_experts(
            h, idx, w, *held, valid=valid, impl="ragged", **kw))(h, idx, w)

    y, pairs, hit, rows = layer(published=16)
    pieces, p_pairs, p_hit, p_rows = layer()
    exists = T if length is None else length
    chosen = np.asarray(idx)[:exists]
    # every pair of every token that exists lands here
    assert int(pairs) == int(p_pairs) == 4 * exists and rows == T * 4
    assert int(hit) == int(p_hit) == len(set(chosen.reshape(-1).tolist()))
    assert int(p_rows) == -(-4 * exists // 256) * 256
    if unchosen is not None:
        assert int(hit) == 15 and unchosen not in chosen
    # the dense form: each expert over every token under its weight
    gated = lambda e: (jax.nn.silu(h @ held[0][e])  # noqa: E731
                       * (h @ held[1][e])) @ held[2][e]
    dense = sum(jnp.sum(jnp.where(idx == e, w, 0.0), -1)[:, None] * gated(e)
                for e in range(16))
    if valid is not None:
        dense = jnp.where(valid[:, None], dense, 0)
    tol = 3e-4 * float(jnp.abs(dense).max()) if exists else 0.0
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), atol=tol)
    np.testing.assert_allclose(np.asarray(y), np.asarray(pieces), atol=tol)
    if unchosen is None:
        routed = ref.moe_parts(h, model, KEY, 2)[0]
        if valid is not None:
            routed = jnp.where(valid[:, None], routed, 0)
        np.testing.assert_allclose(np.asarray(y), np.asarray(routed),
                                   atol=tol)
    if not exists:
        assert not np.asarray(y).any()


@pytest.mark.limit(60)
@pytest.mark.parametrize("held,published,loop", [
    (16, 16, False), (16, None, True), (8, 16, True), (4, 16, True)])
def test_one_pass_only_where_every_published_expert_is_held(held, published,
                                                            loop):
    """The piece count follows from ``held`` and ``published``: one pass
    (no ``while``, no ``scatter-add``, a multiply and a sum where the
    decode batch has its ``dot_general``) only where they are equal;
    fewer held, or ``published`` not said, and a prompt's rows run in
    pieces as they did; a decode batch's jaxpr is the same whatever is
    said."""
    model = {**MODEL, "num_experts": held, "expert_offset": 0}
    kw = {} if published is None else {"published": published}
    text = _jaxpr_of_the_layer(320, model, **kw)
    assert ("while" in text) == loop and ("scatter-add" in text) == loop
    assert text.count("ragged_dot_general") == 3
    if not loop:
        assert " dot_general" not in text
    assert _jaxpr_of_the_layer(64, model, **kw) \
        == _jaxpr_of_the_layer(64, model)
    # the layer both families build hands the fact down
    layer = moe.ExpertLayer(d=64, width=48, held=held, published=16,
                            top_k=4, n_shared=1, dtype=jnp.float32)
    h = _layer_inputs(320)
    params = layer.init(KEY, h)
    text = str(jax.make_jaxpr(lambda p, h: layer.apply(p, h))(params, h))
    assert ("while" in text) == (held < 16)
    _, (_, _, rows) = layer.apply(params, h)
    assert (int(rows) == 320 * 4) == (held == 16)


@pytest.mark.limit(120)
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("ring", [True, False])
@pytest.mark.parametrize("rows,block", [(32, None), (40, 16)],
                         ids=["one_block", "ragged"])
def test_grouped_decode_call_against_plain_attention(monkeypatch, dtype,
                                                     atol, ring, rows, block):
    """``gqa_decode`` (ops/flash_decode.py's shared body with ``group``)
    under the interpreter against attention written out head by head:
    query head i reads K/V head i // 4; a ring is read whole once it has
    wrapped.  ``ragged``: 40 rows in blocks of 16, the third block's last
    8 rows past the array (the full layer's 8,960 rows in blocks of 512,
    PR 42), a slot at the last whole block's last row and one at the
    cache's last (a ring: wrapped, so every row of the ragged block is
    read)."""
    if block:
        monkeypatch.setattr(_fd, "_GROUPED_BLOCK_K", block)
    S, H, G, D = 3, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (S, 1, H, D), dtype)
    kc = jax.random.normal(ks[1], (2, S, rows, G * D), dtype)
    vc = jax.random.normal(ks[2], (2, S, rows, G * D), dtype)
    pos = jnp.asarray([0, 31 if block else 13, 45 if ring else rows - 1])
    with _fd.record_decode_kernels() as lowered:
        got = wa.cached_attention(q, kc, vc, pos, layer=1, ring=ring,
                                  dtype=dtype, impl="flash_decode")
    assert lowered == {"gqa_decode": [[16, 3, 8] if block else [32, 1, 32]]}
    seen = np.minimum(np.asarray(pos), rows - 1) + 1
    for s in range(S):
        for h in range(H):
            g = h // (H // G)
            kk = np.asarray(kc[1, s, :seen[s], g * D:(g + 1) * D],
                            np.float32)
            vv = np.asarray(vc[1, s, :seen[s], g * D:(g + 1) * D],
                            np.float32)
            sc = kk @ np.asarray(q[s, 0, h], np.float32) / np.sqrt(D)
            p = np.exp(sc - sc.max())
            np.testing.assert_allclose(
                np.asarray(got[s, 0, h], np.float32),
                (p / p.sum()) @ vv, atol=atol)


@pytest.mark.limit(60)
@pytest.mark.parametrize("window", [None, 8, 5])
def test_banded_prefill_against_a_masked_dense_one(window):
    """K/V heads repeated to one a query head and a mask written out."""
    B, T, H, G, D = 2, 21, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, G, D))
    v = jax.random.normal(ks[2], (B, T, G, D))
    got = wa.banded_attention(q, k, v, window=window, dtype=jnp.float32)
    kr, vr = (jnp.repeat(a, H // G, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / np.sqrt(D)
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    seen = (j <= i) & (True if window is None else j > i - window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        jnp.einsum("bhqk,bkhd->bqhd", p, vr)), atol=2e-6)
    assert wa.visible_scores(T, window) == int(seen.sum())


@pytest.mark.limit(60)
def test_rotary_is_the_references_and_a_ring_holds_the_last_window():
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 11, 2, 16))
    np.testing.assert_allclose(
        np.asarray(wa.rotary_interleaved(x, jnp.arange(11), 50000.0)),
        np.asarray(ref.rotary(x, 50000.0)), atol=1e-6)
    for length in (3, 8, 9, 21, 32):
        rows = np.asarray(wa.ring_rows(np.int32(length), 8, 32))
        for r, p in enumerate(rows):
            last = max((q for q in range(length) if q % 8 == r), default=r)
            assert p == last, (length, r)


@pytest.mark.limit(120)
def test_weights_by_leaf_are_the_references_bit_for_bit():
    """The program's tree (tensor by tensor, the routed experts under
    ``vmap``, cast to bfloat16, the routers float32) against the
    reference's ``leaf``: the reference's float32 values are what
    bfloat16 holds (``as_published``), so the cast loses nothing and the
    two sides compute on the same weights."""
    tree = adapter.program_tree(MODEL4, KEY)
    blk = tree["h3"]
    raw = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731

    def bf(a):
        assert a.dtype == jnp.float32
        cast = np.asarray(a.astype(jnp.bfloat16).astype(jnp.float32))
        np.testing.assert_array_equal(cast, np.asarray(a))
        return cast

    assert blk["moe"]["router"].dtype == jnp.float32
    np.testing.assert_array_equal(
        raw(blk["moe"]["router"]), raw(ref.leaf(MODEL4, KEY, "router_w", 3)))
    for name, leaf in (("gate", "gate_w"), ("up", "up_w"),
                       ("down", "down_w")):
        assert blk["moe"][name].dtype == jnp.bfloat16
        for e in range(4):
            np.testing.assert_array_equal(
                raw(blk["moe"][name][e]),
                bf(ref.leaf(MODEL4, KEY, leaf, 3, e)))
        np.testing.assert_array_equal(
            raw(blk["moe"]["shared_" + name]["kernel"]),
            bf(ref.leaf(MODEL4, KEY, "shared_" + leaf, 3)))
    for n in "qkvo":
        np.testing.assert_array_equal(
            raw(blk["attn"][n]["kernel"]),
            bf(ref.leaf(MODEL4, KEY, n + "_w", 3)))
    np.testing.assert_array_equal(raw(tree["wte"]["embedding"]),
                                  bf(ref.leaf(MODEL4, KEY, "wte")))
    np.testing.assert_array_equal(raw(blk["ln"]["scale"]),
                                  bf(ref.leaf(MODEL4, KEY, "ln_g", 3)))
    # the second share's experts are other experts
    other = adapter.program_tree(ref.share_of(MODEL4, 1, 4), KEY)
    np.testing.assert_array_equal(
        raw(other["h3"]["moe"]["gate"][0]),
        bf(ref.leaf(MODEL4, KEY, "gate_w", 3, 4)))
    # and a module's own init keeps the routers float32 as well
    made = CommandLightningModule("tiny").init_params(
        KEY, np.zeros((1, 8), np.int32))["params"]
    assert made["h0"]["moe"]["router"].dtype == jnp.float32 \
        and made["h0"]["moe"]["gate"].dtype == jnp.bfloat16


@pytest.mark.limit(120)
def test_engine_serves_the_reference_tokens_and_counts_on_the_device(engine):
    """The engine's own programs over a cache of two kinds and the
    accumulator behind it: greedy tokens equal the reference's argmax,
    the counters are the runs', nothing retraces."""
    spec = engine.kv_spec
    assert spec.kinds == ((3, WINDOW), (1, POSITIONS)) and spec.own_state
    assert spec.shapes == ((3, SLOTS, WINDOW, 32), (1, SLOTS, POSITIONS, 32))
    assert spec.nbytes(4) == 2 * 4 * 32 * SLOTS * (3 * WINDOW + POSITIONS)
    with pytest.raises(ValueError, match="no one shape"):
        spec.shape
    assert isinstance(engine._k, tuple) and len(engine._k) == 3 \
        and len(engine._v) == 2 and engine._k[-1].dtype == jnp.int32
    assert engine.stats()["decode_kernel"] == "dense"
    before = engine.stats()["counters"]
    seq = _tokens(11, 50)
    want = _full(seq, MODEL4).argmax(-1)
    got = [engine.prefill(1, pad_to_bucket(seq[:19], 32), 19, 32)]
    toks, at = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    for t in range(19, 45):
        toks[1], at[1] = seq[t], t
        got.append(int(engine.decode(toks, at)[1]))
    assert got == [int(x) for x in want[18:45]]
    after = engine.stats()["counters"]
    assert after["prefill_runs"] - before["prefill_runs"] == 1
    assert after["decode_runs"] - before["decode_runs"] == 26
    # a token's four choices land on the 4 held of 16 experts ~1 time a
    # layer: pairs of a decode run over 3 slots x 4 layers, experts hit
    # at most 4 a layer
    pairs = after["decode_moe_pairs"] - before["decode_moe_pairs"]
    hit = after["decode_moe_experts_hit"] - before["decode_moe_experts_hit"]
    assert 0 < hit <= 26 * 4 * 4 and hit <= pairs <= 26 * 4 * 4 * SLOTS
    # one piece at these sizes: every sorted row goes through the
    # products, 4 a token, four layers a run
    assert after["decode_moe_rows"] - before["decode_moe_rows"] \
        == 26 * 4 * 4 * SLOTS
    assert after["prefill_moe_rows"] - before["prefill_moe_rows"] \
        == 4 * 4 * 32
    assert sum(engine.stats()["retraces"].values()) == 0


@pytest.mark.limit(120)
def test_the_engine_records_the_blocks_of_the_ring_and_of_the_full_layer(
        monkeypatch):
    """``stats()["decode_blocks"]`` (PR 42): the ring's 8 rows are one
    block, the full layer's 56 rows three blocks of 16 and a ragged one
    of 8; ``decode_kernel`` names the kernel once, as it did.  Greedy
    tokens past row 48 (the ragged block) are the reference's."""
    monkeypatch.setenv("RLT_DECODE_IMPL", "flash_decode")
    monkeypatch.setattr(_fd, "_GROUPED_BLOCK_K", 16)
    eng = ServeEngine(_Module(), DataParallelStrategy(), buckets=(32,),
                      slots=SLOTS, max_seq_len=POSITIONS, seed=0).setup()
    stats = eng.stats()
    assert stats["decode_kernel"] == "gqa_decode"
    assert stats["decode_blocks"] == {"gqa_decode": [[8, 1, 8], [16, 4, 8]]}
    seq = _tokens(11, 56)
    want = _full(seq, MODEL4).argmax(-1)
    got = [eng.prefill(1, pad_to_bucket(seq[:30], 32), 30, 32)]
    toks, at = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    for t in range(30, 55):
        toks[1], at[1] = seq[t], t
        got.append(int(eng.decode(toks, at)[1]))
    assert got == [int(x) for x in want[29:55]]


@pytest.mark.limit(240)
@pytest.mark.parametrize("name", ["freed_slot", "no_decode"])
def test_decode_ahead_serves_what_the_blocking_order_serves(engine, name):
    """serve/worker.py ``_run_ahead`` over two kinds of state: equal
    tokens and, at every step, equal rows where a live slot can read (a
    ring's rows so far, a full layer's positions so far).  A decode
    queued ahead and dropped still ran: the device counted it."""
    def live_rows(pos):
        # (the ring's row of the position about to be decoded is the
        # decode ahead's to overwrite)
        ring = [r for r in range(min(pos, WINDOW)) if r != pos % WINDOW]
        return [np.asarray(ring, np.int64), np.arange(pos)]

    prompts = [_tokens(20 + i, n) for i, n in
               enumerate((5, 13, 21, 9, 27, 16))]
    got = serve_ahead.check_equal_and_counted(engine, prompts, name,
                                              live_rows)
    counted = engine.stats()["counters"]["decode_runs"]
    assert counted > sum(got["decoded"])
    assert sum(engine.stats()["retraces"].values()) == 0


@pytest.mark.limit(60)
@pytest.mark.parametrize("what", ["paged", "kvship", "spec", "suffix",
                                  "paged_kernel"])
def test_refusals_name_the_reason(params, monkeypatch, what):
    from ray_lightning_tpu.serve import Server
    from ray_lightning_tpu.serve.fleet.pages import PageConfig
    from ray_lightning_tpu.serve.spec import SpecConfig
    module = CommandLightningModule(CFG)
    if what in ("paged", "kvship", "spec"):
        kw = {"paged": {"paged": PageConfig(enabled=True, page_size=8)},
              "kvship": {"kvship": True},
              "spec": {"spec": SpecConfig(enabled=True, k=2)}}[what]
        match = "draft" if what == "spec" else "wrapped ring"
        with pytest.raises(ValueError, match=match):
            Server(module, buckets=(16,), max_batch_slots=2,
                   max_seq_len=POSITIONS, platform="cpu", **kw)
        return
    net = Command(CFG)
    spec = KVCacheSpec(n_layer=8, slots=2, max_seq_len=POSITIONS, width=32,
                       kinds=((6, WINDOW), (2, POSITIONS)))
    k, v = spec.state(jnp.zeros, jnp.float32)
    z = jnp.zeros((2,), jnp.int32)
    if what == "suffix":
        with pytest.raises(ValueError, match="suffix program"):
            net.apply({"params": params}, z[:1], z[:1], k, v,
                      method="decode", slots=z[:1])
    else:
        monkeypatch.setenv("RLT_DECODE_IMPL", "paged")
        with pytest.raises(ValueError, match="ring of window rows"):
            net.apply({"params": params}, z, z, k, v, method="decode")


@pytest.mark.limit(60)
def test_live_rows_are_the_mean_over_the_layers():
    module = CommandLightningModule(CFG)
    assert module.live_cache_rows(0) == 1
    assert module.live_cache_rows(7) == 8
    assert module.live_cache_rows(39) == (3 * 8 + 40) / 4
    big = adapter.module({**MODEL, "sliding_window": 4096,
                          "served_positions": 8960,
                          "max_position_embeddings": 200000}, 0)
    assert big.live_cache_rows(6655) == (3 * 4096 + 6656) / 4


@pytest.mark.limit(60)
def test_a_cache_of_one_kind_is_the_two_bare_arrays_it_was():
    """``gpt2`` (a row per position) and EvaByte (a model's own rows):
    the same shapes and bytes as before the cache went by kind, and the
    state a program takes is the two arrays, no tuple around them."""
    k = jax.ShapeDtypeStruct((1, 8, 64), jnp.bfloat16)
    gpt = KVCacheSpec.from_capture([k, k], slots=4, max_seq_len=64)
    assert gpt == KVCacheSpec(n_layer=2, slots=4, max_seq_len=64, width=64)
    own = jax.ShapeDtypeStruct((1, 1, 96, 64), jnp.bfloat16)
    eva = KVCacheSpec.from_capture([own, own], 4, 256)
    assert eva == KVCacheSpec(n_layer=2, slots=4, max_seq_len=256, width=64,
                              rows=96)
    for spec, shape in ((gpt, (2, 4, 64, 64)), (eva, (2, 4, 96, 64))):
        assert spec.shape == shape and spec.shapes == (shape,)
        assert spec.nbytes() == 2 * 2 * int(np.prod(shape))
        ks, vs = spec.state(jnp.zeros, jnp.bfloat16)
        assert isinstance(ks, jax.Array) and ks.shape == vs.shape == shape
    assert not gpt.own_state and eva.own_state
    mixed = KVCacheSpec.from_capture(
        [jax.ShapeDtypeStruct((1, 1, r, 32), jnp.bfloat16)
         for r in (8, 8, 8, 56)], 4, 56, counters=6)
    assert mixed.kinds == ((3, 8), (1, 56)) and mixed.rows is None
    assert mixed.nbytes() == 2 * 2 * 32 * 4 * (3 * 8 + 56)


@pytest.mark.limit(120)
@pytest.mark.parametrize("family", ["gpt2", "evabyte"])
def test_the_one_kind_engines_build_the_cache_they_built(family):
    if family == "gpt2":
        from ray_lightning_tpu.models.gpt import GPTConfig, GPTLightningModule
        module = GPTLightningModule(GPTConfig(
            vocab_size=128, block_size=64, n_layer=2, n_head=2, n_embd=64))
        want = (2, 2, 64, 64)
    else:
        from ray_lightning_tpu.models.evabyte import EvaByteLightningModule
        module = EvaByteLightningModule("tiny")
        want = (2, 2, 32 + 256 // 4, 64)
    eng = ServeEngine(module, DataParallelStrategy(), buckets=(16,), slots=2,
                      max_seq_len=64, seed=0).setup()
    assert eng.kv_spec.shape == want and not eng.kv_spec.kinds
    assert isinstance(eng._k, jax.Array) \
        and eng._k.shape == eng._v.shape == want
    itemsize = eng._k.dtype.itemsize
    assert eng.kv_spec.nbytes(itemsize) == 2 * int(np.prod(want)) * itemsize
    assert "counters" not in eng.stats()


# -- what Mosaic accepts, at the published widths (nothing runs) ----------------

@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.limit(240)
@pytest.mark.parametrize("kernel", ["gqa_decode", "splash", "gmm",
                                    "gmm_in_pieces", "gmm_in_one_pass"])
def test_kernels_compile_for_v5e_at_the_published_widths(monkeypatch, v5e,
                                                         kernel):
    """128 query heads on 8 K/V heads of 128, experts of 4096 x 4096,
    bf16: the grouped decode call over a ring of 4096 rows at 32 slots,
    jax's splash attention under the band (a shorter prompt than the
    cell's: the tables of an 8192 mask are the slow part), and megablox'
    grouped product inside the dropless layer at a decode batch (one
    piece) and at a prompt's rows (4096 tokens: the three products with
    a prompt's tiles in the body of a loop over pieces of 2048 rows, and
    under half the temporaries of the layer that moved all 32,768); and
    models/xing.py's layer, 64 of 64 experts of 3584 x 1024 held, 4 a
    token, at its cell's 9216 bucket: one pass, so one ``gmm`` call a
    product and none in the body of a loop."""
    from ray_lightning_tpu.ops import flash_decode

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    monkeypatch.setattr(flash_decode, "_use_interpret", lambda: False)
    if kernel == "gqa_decode":
        fn = lambda q, k, v, at: wa.cached_attention(  # noqa: E731
            q, k, v, at, layer=2, ring=True, impl="flash_decode")
        args = (on_chip((32, 1, 128, 128)), on_chip((3, 32, 4096, 1024)),
                on_chip((3, 32, 4096, 1024)), on_chip((32,), jnp.int32))
    elif kernel == "splash":
        monkeypatch.setattr(wa, "select_prefill_kernel",
                            lambda T, D: "splash")
        fn = lambda q, k, v: wa.banded_attention(  # noqa: E731
            q, k, v, window=1024)
        args = (on_chip((1, 2048, 128, 128)), on_chip((1, 2048, 8, 128)),
                on_chip((1, 2048, 8, 128)))
    elif kernel == "gmm_in_one_pass":
        T = 9216
        fn = lambda h, i, w, g, u, d, ok: moe.dropless_experts(  # noqa: E731
            h, i, w, g, u, d, valid=ok, impl="gmm", published=64)[0]
        args = (on_chip((T, 3584)), on_chip((T, 4), jnp.int32),
                on_chip((T, 4), jnp.float32), on_chip((64, 3584, 1024)),
                on_chip((64, 3584, 1024)), on_chip((64, 1024, 3584)),
                on_chip((T,), jnp.bool_))
    else:
        T = 32 if kernel == "gmm" else 4096
        fn = lambda h, i, w, g, u, d, ok: moe.dropless_experts(  # noqa: E731
            h, i, w, g, u, d, valid=ok, impl="gmm")[0]
        args = (on_chip((T, 4096)), on_chip((T, 8), jnp.int32),
                on_chip((T, 8), jnp.float32), on_chip((16, 4096, 4096)),
                on_chip((16, 4096, 4096)), on_chip((16, 4096, 4096)),
                on_chip((T,), jnp.bool_))
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert {"gqa_decode": "gqa_decode", "splash": "splash_mqa_fwd",
            "gmm": "gmm", "gmm_in_pieces": "gmm",
            "gmm_in_one_pass": "gmm"}[kernel] in text
    if kernel == "gmm_in_pieces":
        assert moe._piece_rows(T * 8) == 2048 > moe._ONE_PIECE_ROWS
        assert "while/body/moe_experts/jit(gmm)/pallas_call" in text
        # PR 33's layer at this size, compiled the same way: 805,951,488
        assert compiled.memory_analysis().temp_size_in_bytes < 805_951_488 / 2
    if kernel == "gmm_in_one_pass":
        assert T * 4 > moe._ONE_PIECE_ROWS
        assert text.count('custom_call_target="tpu_custom_call"') == 3
        assert "moe_experts/jit(gmm)/pallas_call" in text \
            and "while/body/moe_experts" not in text \
            and "while/body/moe_route" not in text
        # this tree's own described compile (PR 37): 529,611,264, the
        # products' output beside its four gathered parts
        assert compiled.memory_analysis().temp_size_in_bytes < 560_000_000


@pytest.mark.limit(60)
@pytest.mark.parametrize("room", [3, 24])
def test_the_references_expert_loop_is_exact_with_room_or_without(room):
    """``routed_sum`` sends only the tokens that chose an expert through
    it where they fit ``room`` and every token under the mask where they
    do not: the same sum as each expert over every token."""
    h = _layer_inputs()
    w = ref.route(h, ref.leaf(MODEL, KEY, "router_w", 2), ref.sizes(MODEL),
                  "float32")
    mats = lambda e: [ref.leaf(MODEL, KEY, n, 2, e)  # noqa: E731
                      for n in ("gate_w", "up_w", "down_w")]
    assert int((np.asarray(w)[:, :4] > 0).sum(0).max()) > 3
    got = ref.routed_sum(h, w, jnp.arange(4), mats, room)
    want = sum(w[:, e, None] * ref._gated(h, *mats(e), "float32")
               for e in range(4))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))
