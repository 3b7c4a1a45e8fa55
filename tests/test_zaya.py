"""ZAYA1 (models/zaya.py, ops/moe.py ``mlp_softmax_top1`` and
``dropless_experts`` under the model's own ``Experts``, serve/kvcache.py's
tail, ops/flash_decode.py ``gqa_decode`` at 4 query heads a K/V head)
against its
plain reference (chipbench/zaya_reference.py), at a tiny size on the CPU:
width 64, 4 query heads over 2 K/V heads of 16 (8 of them rotated), 3
layers of 4 experts and a "no expert" output, a router of 16, everything
in float32.

Tolerance: the two sides are the same mathematics written twice in
float32 (the reference convolves and shifts the whole sequence by head,
rotates by slices and loops over the experts under a mask; the program
works on packed rows, rotates by a signed permutation, decodes through a
cache of 32 lanes a row and a tail of two generations a slot, and sorts
its pairs into grouped products), so they differ by summation order only:
logits spread by about 0.16, three layers leave a few 1e-7 of that, and
``ATOL = 2e-5`` leaves room, while a dropped tap, mean, shift, depth
state, temperature, residual scale or skip output moves a logit by 1e-3
or more (``test_an_altered_mechanism_moves_the_logits`` says by how much).
"""

from __future__ import annotations

import dataclasses
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import zaya_reference as ref
from chipbench.adapters import zaya as adapter
from ray_lightning_tpu.core import steps
from ray_lightning_tpu.models import zaya
from ray_lightning_tpu.models.zaya import (
    SERVE_COUNTERS, Zaya, ZayaLightningModule)
from ray_lightning_tpu.ops import flash_decode, moe
from ray_lightning_tpu.ops import window_attention as wa
from ray_lightning_tpu.parallel.strategy import DataParallelStrategy
from ray_lightning_tpu.serve.buckets import pad_to_bucket
from ray_lightning_tpu.serve.engine import ServeEngine
from ray_lightning_tpu.serve.kvcache import KVCacheSpec
from tests import serve_ahead

ATOL = 2e-5
MODEL = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             cca_time0=2, cca_time1=2, partial_rotary_factor=0.5,
             rope_parameters={"hybrid": {"rope_theta": 10000}},
             rms_norm_eps=1e-5, num_experts=4, num_experts_per_tok=1,
             moe_intermediate_size=32, router_hidden_size=16,
             max_position_embeddings=64, served_positions=64)
CFG = dataclasses.replace(adapter.config_of(MODEL), dtype=jnp.float32)
KEY = jax.random.PRNGKey(5)
SLOTS, POSITIONS, ROW, TAIL = 5, 64, 32, 2 * 96 + 16


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


@pytest.fixture(scope="module")
def held():
    return jax.jit(lambda k: ref.hold(MODEL, k))(KEY)


class _Module(ZayaLightningModule):
    """The module a user would hand to ``Server``, in float32 and with
    the reference's weights."""

    def __init__(self):
        super().__init__(CFG)

    def init_params(self, rng, batch):
        return {"params": adapter.program_tree(MODEL, KEY, jnp.float32)}


@pytest.fixture(scope="module")
def engine():
    return ServeEngine(_Module(), DataParallelStrategy(),
                       buckets=(16, 32), slots=3, max_seq_len=POSITIONS,
                       seed=0).setup()


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 256, (n,)).astype(np.int32)


_FORWARD = jax.jit(lambda w, tokens: ref.forward(w, tokens, MODEL))


def _full(tokens, weights=KEY):
    """The reference's logits at every position of ``tokens``: one
    compiled program for every length (causal, so zeros behind the
    sequence change nothing before them)."""
    row = np.zeros((1, POSITIONS), np.int32)
    row[0, :len(tokens)] = tokens
    return np.asarray(_FORWARD(weights, row))[0, :len(tokens)]


@pytest.mark.limit(120)
@pytest.mark.parametrize("T", [8, 29])
def test_forward_matches_reference(params, T):
    tokens = _tokens(T, 2 * T).reshape(2, T)
    got = Zaya(CFG).apply({"params": params}, tokens)
    want = np.stack([_full(row) for row in tokens])
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL)


# -- prefill, then decode through cache and tail ---------------------------------

def _programs():
    net = Zaya(CFG)
    prefill = jax.jit(lambda p, k, v, t, n, s: net.apply(
        {"params": p}, t, n, s, k, v, method="prefill"))
    decode = jax.jit(lambda p, k, v, t, at: net.apply(
        {"params": p}, t, at, k, v, method="decode"))
    spec = KVCacheSpec(n_layer=3, slots=SLOTS, max_seq_len=POSITIONS,
                       width=ROW, counters=len(SERVE_COUNTERS),
                       tail=(2, TAIL))
    return prefill, decode, spec


#: a prompt's ``length`` in its bucket of 16: at the bucket's end, one
#: short of it, well inside it, and prompts of 1 and 2 tokens (the tail's
#: zeros: position 0 has nothing before it)
LENGTHS = (16, 15, 5, 1, 2)


@pytest.mark.limit(240)
@pytest.mark.parametrize("impl", ["flash_decode", "dense"])
def test_prefill_then_decode_through_cache_and_tail_is_the_full_forward(
        params, monkeypatch, impl):
    """Five prompts at five slots, then 36 decode steps each
    teacher-forced along its sequence: every step reads the other
    generation of its slot's tail and a cache of 32 lanes a row (blocks
    of 16 rows: the slots cross two block edges), and its logits are the
    reference's full forward at that position.  The reference has no
    cache and no tail."""
    monkeypatch.setenv("RLT_DECODE_IMPL", impl)
    monkeypatch.setattr(flash_decode, "_GROUPED_BLOCK_K", 16)
    prefill, decode, spec = _programs()
    k, v = spec.state(jnp.zeros, jnp.float32)
    assert len(k) == 3 and len(v) == 1 and k[1].shape == (3, SLOTS, 2, TAIL)
    seqs = [_tokens(40 + i, n + 36) for i, n in enumerate(LENGTHS)]
    want = [_full(s) for s in seqs]
    for slot, n in enumerate(LENGTHS):
        logits, k, v = prefill(params, k, v, pad_to_bucket(seqs[slot][:n], 16),
                               np.int32(n), np.int32(slot))
        np.testing.assert_allclose(np.asarray(logits), want[slot][n - 1],
                                   atol=ATOL)
    for step in range(36):
        at = np.asarray([n + step for n in LENGTHS], np.int32)
        toks = np.asarray([s[t] for s, t in zip(seqs, at)], np.int32)
        logits, k, v = decode(params, k, v, toks, at)
        for slot in range(SLOTS):
            np.testing.assert_allclose(
                np.asarray(logits)[slot], want[slot][at[slot]], atol=ATOL,
                err_msg=f"slot {slot} step {step}")
    counted = dict(zip(SERVE_COUNTERS, np.asarray(k[-1])))
    assert counted["prefill_runs"] == 5 and counted["decode_runs"] == 36
    # one pair a token a layer, less the tokens that chose no expert
    assert 0 < counted["decode_moe_pairs"] < 36 * SLOTS * 3
    assert counted["decode_moe_rows"] == 36 * SLOTS * 3
    assert 0 < counted["prefill_moe_pairs"] <= sum(LENGTHS) * 3


@pytest.mark.limit(120)
def test_a_decode_run_twice_at_one_position_reads_and_writes_the_same(
        params):
    """serve/worker.py drops a decode it queued ahead and queues the
    plan's own at the same positions: the second run must not read the
    tail the first one wrote as its own predecessor.  Two generations a
    slot make the step idempotent; and a decode from position 0 with
    nothing prefilled reads zeros whatever the tail holds."""
    prefill, decode, spec = _programs()
    k, v = spec.state(jnp.zeros, jnp.float32)
    seq = _tokens(7, 12)
    want = _full(seq)
    _, k, v = prefill(params, k, v, pad_to_bucket(seq[:6], 16), np.int32(6),
                      np.int32(2))
    toks, at = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    toks[2], at[2] = seq[6], 6
    # slot 0 decodes from position 0 over a tail full of garbage
    k = (k[0], k[1].at[:, 0].set(7.0)) + k[2:]
    toks[0] = seq[0]
    once, k1, v1 = decode(params, k, v, toks, at)
    twice, k2, v2 = decode(params, k1, v1, toks, at)
    np.testing.assert_array_equal(np.asarray(once), np.asarray(twice))
    for a, b in zip(k1[:2] + v1, k2[:2] + v2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(once)[2], want[6], atol=ATOL)
    np.testing.assert_allclose(np.asarray(once)[0], want[0], atol=ATOL)
    toks[2], at[2] = seq[7], 7
    toks[0], at[0] = seq[1], 1
    after, _, _ = decode(params, k2, v2, toks, at)
    np.testing.assert_allclose(np.asarray(after)[2], want[7], atol=ATOL)
    np.testing.assert_allclose(np.asarray(after)[0], want[1], atol=ATOL)


# -- every mechanism shows in a logit --------------------------------------------

def _altered(held, fault: str):
    """The reference's weights with one mechanism taken out."""
    w = dict(held)
    if fault == "tap0":              # the first convolution's look at t - 1
        w["conv0_w"] = held["conv0_w"].at[:, :, 0].set(0)
    elif fault == "tap1":            # the second's
        w["conv1_w"] = held["conv1_w"].at[:, :, 0].set(0)
    elif fault == "gamma":           # the router's depth state
        w["router_gamma"] = jnp.zeros_like(held["router_gamma"])
    elif fault == "tau":
        w["tau"] = jnp.ones_like(held["tau"])
    elif fault == "residual_scale":
        w["res_attn_af"] = jnp.ones_like(held["res_attn_af"])
    elif fault == "skip_output":     # "no expert" is never chosen
        w["router_b"] = held["router_b"].at[:, -1].set(-2.0)
    return w


@pytest.mark.limit(120)
@pytest.mark.parametrize("fault", ["tap0", "tap1", "qk_mean", "shift",
                                   "gamma", "tau", "residual_scale",
                                   "skip_output"])
def test_an_altered_mechanism_moves_the_logits(params, held, monkeypatch,
                                               fault):
    """The program's logits lie within ``ATOL`` of the reference as it is
    and 10x ``ATOL`` or more from the reference with one mechanism taken
    out: the comparison would catch a model that left it out."""
    seq = _tokens(3, 40)
    got = np.asarray(Zaya(CFG).apply({"params": params}, seq[None]))[0]
    np.testing.assert_allclose(got, _full(seq, held), atol=ATOL)
    if fault in ("qk_mean", "shift"):
        real = ref.attention
        switch = {"qk_mean": {"mean": False}, "shift": {"shift": False}}
        monkeypatch.setattr(
            ref, "attention",
            lambda *a, **kw: real(*a, **kw, **switch[fault]))
        row = np.zeros((1, POSITIONS), np.int32)
        row[0, :40] = seq
        wrong = np.asarray(ref.forward(held, row, MODEL))[0, :40]
    else:
        wrong = _full(seq, _altered(held, fault))
    moved = float(np.abs(got - wrong).max())
    print(f"{fault}: a logit moves by {moved:.2e}")
    assert moved > 10 * ATOL, moved


@pytest.mark.limit(60)
def test_the_seeded_router_decides_and_some_tokens_choose_no_expert(held):
    """The best two of ``p + b`` lie within 1e-3 of one another for few
    (token, layer) pairs, every expert and the skip output are chosen,
    and the depth state moves the choice."""
    z = ref.sizes(MODEL)
    u = jax.random.normal(jax.random.PRNGKey(1), (4096, 64))
    s = jnp.zeros((4096, z["R"]))
    close, chosen = [], []
    for layer in range(3):
        _, s, p = ref.route(u, s, MODEL, held, layer)
        scored = np.sort(np.asarray(p + held["router_b"][layer]), axis=-1)
        close.append(scored[:, -1] - scored[:, -2] < 1e-3)
        chosen.append(np.argmax(np.asarray(p + held["router_b"][layer]), -1))
    assert np.mean(close) < 0.02
    assert set(np.concatenate(chosen)) == set(range(5))
    assert 0.02 < np.mean(np.concatenate(chosen) == 4) < 0.6


@pytest.mark.limit(60)
def test_the_routers_bias_chooses_and_does_not_weigh(held):
    u = jax.random.normal(jax.random.PRNGKey(2), (64, 64))
    w = {n: held["router_" + n][1] for n in
         ("proj", "gamma", "norm", "w1", "w2", "w3", "b")}
    prev = jax.random.normal(jax.random.PRNGKey(3), (64, 16))

    def run(bias, prev=prev):
        return moe.mlp_softmax_top1(
            u, prev, w["proj"], w["gamma"], w["norm"], w["w1"], w["w2"],
            w["w3"], bias, 1e-5)

    _, s_ref, p = ref.route(u, prev, MODEL, held, 1)
    idx, wt, s = run(w["b"])
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(idx)[:, 0], np.argmax(np.asarray(p + w["b"]), -1))
    np.testing.assert_allclose(
        np.asarray(wt)[:, 0],
        np.take_along_axis(np.asarray(p), np.asarray(idx), -1)[:, 0],
        atol=1e-7)
    # a bias that pins the choice to output 2 leaves the weight p_2
    pinned, wt2, _ = run(jnp.zeros(5).at[2].set(9.0))
    assert (np.asarray(pinned) == 2).all()
    np.testing.assert_allclose(np.asarray(wt2)[:, 0], np.asarray(p)[:, 2],
                               atol=1e-7)
    # the first layer has no state before it
    _, _, s0 = run(w["b"], None)
    np.testing.assert_allclose(
        np.asarray(s0), np.asarray(u) @ np.asarray(w["proj"]), atol=1e-5)


@pytest.mark.limit(60)
def test_partial_rotary_against_numbers_written_out_by_hand():
    """D = 8, 4 lanes rotated (pairs (0, 2) and (1, 3)), theta 100: pair j
    turns by t 100^(-j/2), lanes 4-7 pass."""
    x = jnp.arange(1.0, 9.0).reshape(1, 1, 1, 8)
    got = np.asarray(zaya.partial_rotary(
        jnp.tile(x, (1, 3, 1, 1)), jnp.arange(3), 100.0, 4))
    np.testing.assert_allclose(got[0, 0, 0], np.arange(1.0, 9.0), atol=1e-6)
    for t in (1, 2):
        a0, a1 = t * 1.0, t * 0.1
        want = [1 * np.cos(a0) - 3 * np.sin(a0), 2 * np.cos(a1) - 4 * np.sin(a1),
                3 * np.cos(a0) + 1 * np.sin(a0), 4 * np.cos(a1) + 2 * np.sin(a1),
                5, 6, 7, 8]
        np.testing.assert_allclose(got[0, t, 0], want, atol=1e-5)
    # and the reference's slices say the same at the tiny sizes
    y = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 3, 16))
    np.testing.assert_allclose(
        np.asarray(zaya.partial_rotary(y, jnp.arange(9), 10000.0, 8)),
        np.asarray(ref.rotary(y, ref.sizes(MODEL))), atol=1e-6)
    assert adapter.config_of({}).rotary_dim == 64


# -- the state -------------------------------------------------------------------

@pytest.mark.limit(60)
def test_a_spec_with_a_tail():
    published = adapter.config_of({})
    row = jax.ShapeDtypeStruct((1, 16, 256), jnp.bfloat16)
    block = jax.ShapeDtypeStruct((1, 1, 2, published.tail_width), jnp.float32)
    spec = KVCacheSpec.from_capture([(row, row, block)] * 10, 128, 3328,
                                    counters=8)
    assert spec.tail == (2, 2688) and spec.paired and spec.own_state \
        and spec.rows is None
    assert spec.shapes == ((10, 128, 3328, 256),)
    assert spec.tail_shape == (10, 128, 2, 2688)
    rows, tails = 10 * 128 * 3328, 10 * 128 * 2 * 2688 * 4
    # 1,024 B a position a layer in bfloat16, and the tails in float32
    assert spec.nbytes() == 1024 * rows + tails
    k, v = spec.state(jax.ShapeDtypeStruct, jnp.bfloat16)
    assert [a.shape for a in k] == [(10, 128, 3328, 256),
                                    (10, 128, 2, 2688), (8,)]
    assert [a.dtype for a in k] == [jnp.bfloat16, jnp.float32, jnp.int32]
    assert [a.shape for a in v] == [(10, 128, 3328, 256)]
    for other in (jax.ShapeDtypeStruct((1, 1, 2, 8), jnp.float32),
                  jax.ShapeDtypeStruct(block.shape, jnp.bfloat16)):
        with pytest.raises(ValueError, match="differing shapes"):
            KVCacheSpec.from_capture(
                [(row, row, block), (row, row, other)], 2, 64)


def _family_spec(name):
    from ray_lightning_tpu.models.command import CommandLightningModule
    from ray_lightning_tpu.models.evabyte import EvaByteLightningModule
    from ray_lightning_tpu.models.gpt import GPTLightningModule
    from ray_lightning_tpu.models.xing import XingLightningModule
    module = {"gpt": GPTLightningModule, "evabyte": EvaByteLightningModule,
              "command": CommandLightningModule,
              "xing": XingLightningModule}[name]("tiny")
    return _program_texts(module)


def _program_texts(module, slots=3, positions=64, bucket=16):
    """``(spec, {"decode" | "prefill": jaxpr text})`` of a module's serve
    programs, built as serve/engine.py builds them."""
    module.setup_model()
    net = module.configure_decode_model()
    dummy = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
    made = jax.eval_shape(net.init, jax.random.PRNGKey(0), dummy)["params"]
    _, cap = jax.eval_shape(
        lambda p, t: net.apply({"params": p}, t, True, mutable=["kv_cache"]),
        made, dummy)
    captured = steps.kv_layer_pairs(cap["kv_cache"])
    spec = KVCacheSpec.from_capture(
        captured, slots, positions,
        counters=len(getattr(module, "serve_counters", ())))
    k, v = spec.state(jax.ShapeDtypeStruct, captured[0][0].dtype)
    ints = jax.ShapeDtypeStruct((slots,), jnp.int32)
    one = jax.ShapeDtypeStruct((), jnp.int32)
    # (a jaxpr's text names the ambient matmul precision: the parent's
    # were traced under none, not under this file's ``highest``)
    with jax.default_matmul_precision(None):
        return spec, {
            "decode": str(jax.make_jaxpr(steps.build_decode_step(module))(
                made, k, v, ints, ints)),
            "prefill": str(jax.make_jaxpr(
                steps.build_prefill_step(module, bucket))(
                    made, k, v, dummy, one, one))}


#: the state the four families' specs described before a spec could hold
#: a tail (PR 41's parent, 08ba10a): a tail is a new case beside these,
#: not a change to them.  (Their programs' jaxpr texts were compared with
#: the parent's by hand, CHANGES.md: ops/moe.py's layer is untouched.)
PARENTS = {
    "gpt": dict(rows=None, kinds=(), counters=0, paired=True),
    "evabyte": dict(kinds=(), counters=0, paired=True),
    "command": dict(rows=None, counters=8, paired=True),
    "xing": dict(rows=64, kinds=(), counters=8, paired=False),
}


@pytest.mark.limit(120)
@pytest.mark.parametrize("family", sorted(PARENTS))
def test_the_four_families_specs_are_the_parents(family):
    spec, texts = _family_spec(family)
    assert spec.tail == () and spec.tail_shape is None
    for name, value in PARENTS[family].items():
        assert getattr(spec, name) == value, name
    k, v = spec.state(lambda shape, dtype: (shape, np.dtype(dtype).name),
                      jnp.bfloat16)
    if family in ("gpt", "evabyte"):
        assert k == v and len(k) == 2 and len(k[0]) == 4   # the bare arrays
    else:
        assert k[-1] == ((8,), "int32") and len(k) == len(spec.shapes) + 1
    assert all("pjit" in t or "dot_general" in t for t in texts.values())


@pytest.mark.limit(120)
@pytest.mark.parametrize("at", [0, 15, 16, 63])
def test_gqa_decode_at_4_query_heads_a_kv_head_against_a_dense_einsum(
        monkeypatch, at):
    """The shared grouped call under the interpreter at H = 8, G = 2 over
    rows of 2 x 16 lanes, blocks of 16 rows: position 0, a block's last
    row, the next block's first, the cache's last row."""
    monkeypatch.setattr(flash_decode, "_GROUPED_BLOCK_K", 16)
    S, H, G, D, L = 3, 8, 2, 16, 64
    keys = jax.random.split(jax.random.PRNGKey(at), 3)
    q = jax.random.normal(keys[0], (S, 1, H, D))
    k, v = (jax.random.normal(kk, (2, S, L, G * D)) for kk in keys[1:])
    positions = jnp.asarray([at, max(at - 1, 0), at // 2], jnp.int32)
    got = {impl: np.asarray(wa.cached_attention(
        q, k, v, positions, layer=1, ring=False, dtype=jnp.float32,
        impl=impl)) for impl in ("flash_decode", "dense")}
    np.testing.assert_allclose(got["flash_decode"], got["dense"], atol=2e-5)
    s = jnp.einsum("sgpd,slgd->sgpl", q.reshape(S, G, H // G, D),
                   k[1].reshape(S, L, G, D)) / 4.0
    seen = jnp.arange(L)[None, :] <= positions[:, None]
    p = jax.nn.softmax(jnp.where(seen[:, None, None], s, -jnp.inf), -1)
    want = jnp.einsum("sgpl,slgd->sgpd", p, v[1].reshape(S, L, G, D))
    np.testing.assert_allclose(got["dense"].reshape(S, G, H // G, D),
                               np.asarray(want), atol=2e-5)


def _narrow_row_case(L, dtype, S=5, H=8, G=2, D=16):
    keys = jax.random.split(jax.random.PRNGKey(L), 3)
    q = jax.random.normal(keys[0], (S, 1, H, D), dtype)
    k, v = (jax.random.normal(kk, (2, S, L, G * D), dtype)
            for kk in keys[1:])
    return q, k, v


@pytest.mark.limit(120)
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("ring", [False, True])
def test_gqa_decode_over_a_cache_its_block_does_not_tile(monkeypatch, dtype,
                                                         atol, ring):
    """72 rows a slot in blocks of 16: four whole blocks and a fifth of
    which 8 rows lie past the array (the cell's 3,328 rows in blocks of
    512, PR 42).  Slots at row 0, the last whole block's last row, the
    ragged block's first row, the cache's last row and mid-cache; a ring
    that has wrapped reads all 72 rows at every slot."""
    monkeypatch.setattr(flash_decode, "_GROUPED_BLOCK_K", 16)
    L = 72
    q, k, v = _narrow_row_case(L, dtype)
    positions = jnp.asarray([0, 63, 64, 71, 30], jnp.int32) \
        + (100 if ring else 0)
    with flash_decode.record_decode_kernels() as lowered:
        got = wa.cached_attention(q, k, v, positions, layer=1, ring=ring,
                                  dtype=dtype, impl="flash_decode")
    assert lowered == {"gqa_decode": [[16, 5, 8]]}
    want = wa.cached_attention(q, k, v, positions, layer=1, ring=ring,
                               dtype=dtype, impl="dense")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


@pytest.mark.limit(60)
def test_what_lies_past_the_array_is_nan_here_and_the_result_is_finite(
        monkeypatch):
    """The interpreter fills what a block reads past its array with NaN
    (a 13-row array in 8-row blocks: rows 13-15), so a value row left
    unmasked fails loudly on the CPU: 0 x NaN is NaN.  Every slot at the
    cache's last row reads the ragged block whole."""
    from jax.experimental import pallas as pl

    def copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    seen = pl.pallas_call(
        copy, grid=(2,), in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
        interpret=True)(jnp.ones((13, 128), jnp.float32))
    assert np.isnan(np.asarray(seen[13:])).all() \
        and (np.asarray(seen[:13]) == 1).all()
    monkeypatch.setattr(flash_decode, "_GROUPED_BLOCK_K", 16)
    q, k, v = _narrow_row_case(72, jnp.float32)
    got = wa.cached_attention(q, k, v, jnp.full((5,), 71, jnp.int32),
                              layer=1, ring=False, dtype=jnp.float32,
                              impl="flash_decode")
    assert np.isfinite(np.asarray(got)).all()


def _selects_by_shape(jaxpr, found=None):
    """Output shapes of every ``select_n`` in a jaxpr and the jaxprs
    under it (a kernel's body, the branches of its ``cond``s)."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "select_n":
            found.append(eqn.outvars[0].aval.shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _selects_by_shape(sub, found)
    return found


@pytest.mark.limit(60)
def test_a_cache_the_blocks_tile_emits_no_mask_on_the_value_block(
        monkeypatch):
    """``cache_rows % block_k == 0`` is a Python branch: the kernel of a
    shape that tiles holds no select over a ``[block, lanes]`` value
    block; the ragged one holds exactly one."""
    monkeypatch.setattr(flash_decode, "_GROUPED_BLOCK_K", 16)

    def value_selects(L):
        q, k, v = _narrow_row_case(L, jnp.float32)
        jaxpr = jax.make_jaxpr(lambda *a: flash_decode.grouped_decode_attention(
            *a, layer=1))(q, k, v, jnp.zeros((5,), jnp.int32))
        return _selects_by_shape(jaxpr.jaxpr).count((16, 32))

    assert value_selects(64) == 0 and value_selects(72) == 1


# -- the expert sublayer, handed its routing ---------------------------------------

@pytest.mark.limit(120)
@pytest.mark.parametrize("T", [24, 1500])
def test_the_experts_handed_their_routing_against_a_loop_over_tokens(T):
    """models/zaya.py ``Experts``: indices in [0, E], where ``E`` lands on
    no expert.  24 tokens are one piece; 1500 are past it and every expert
    is held, so the layer takes ONE pass.  ops/moe.py ``ExpertLayer`` is
    the parent's: it makes its own router and takes no routing."""
    import inspect
    cfg = dataclasses.replace(CFG, dtype=jnp.float32)
    d, E = cfg.hidden_size, cfg.num_experts
    layer = zaya.Experts(cfg)
    keys = jax.random.split(jax.random.PRNGKey(T), 4)
    h = jax.random.normal(keys[0], (T, d))
    idx = jax.random.randint(keys[1], (T, 1), 0, E + 1)
    w = jax.random.uniform(keys[2], (T, 1))
    variables = layer.init(keys[3], h, idx, w)
    # weights large enough that a missing expert shows
    variables = jax.tree_util.tree_map(lambda a: a * 10.0, variables)
    p = variables["params"]
    assert sorted(p) == ["down", "gate", "up"]
    valid = jnp.arange(T) % 7 != 3
    y, (pairs, hit, rows) = layer.apply(variables, h, idx, w, valid)
    want = np.zeros((T, d), np.float32)
    for t in range(T):
        e = int(idx[t, 0])
        if e < E and bool(valid[t]):
            a = jax.nn.silu(h[t] @ p["gate"][e]) * (h[t] @ p["up"][e])
            want[t] = float(w[t, 0]) * np.asarray(a @ p["down"][e])
    assert np.abs(want).max() > 1e-3
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    here = (np.asarray(idx)[:, 0] < E) & np.asarray(valid)
    assert int(pairs) == here.sum() and int(hit) == E and int(rows) == T
    assert list(inspect.signature(moe.ExpertLayer.__call__).parameters) \
        == ["self", "h", "valid"]


@pytest.mark.limit(60)
@pytest.mark.parametrize("m,k,want", [
    (128, 2048, (128, 2048, 1024)),      # a decode batch, one a token
    (64, 2048, (64, 2048, 1024)),
    (1024, 2048, (128, 2048, 1024)),     # the longest prompt's bucket
    (4096, 2048, (256, 1024, 1024))])    # past it: not read, the default
def test_the_grouped_products_tiles_at_2048_wide(m, k, want):
    assert moe.gmm_tiling(m, k) == want


# -- the weights -------------------------------------------------------------------

@pytest.mark.limit(120)
def test_weights_by_leaf_are_the_references_and_float32_where_they_say():
    tree = jax.jit(lambda k: adapter.program_tree(MODEL, k))(KEY)
    init = jax.eval_shape(Zaya(CFG).init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    assert jax.tree_util.tree_structure(tree) \
        == jax.tree_util.tree_structure(init)
    resident = zaya.resident(jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.float32), init))
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(tree)[0],
            jax.tree_util.tree_leaves(resident)):
        assert a.dtype == b.dtype and a.shape == b.shape, path
    blk = tree["h1"]
    assert blk["router"]["w3"].dtype == jnp.float32 \
        and blk["attn"]["tau"].dtype == jnp.float32 \
        and blk["res_mlp"]["a_f"].dtype == jnp.float32 \
        and blk["attn"]["conv1_w"].dtype == jnp.bfloat16 \
        and blk["moe"]["down"].shape == (4, 32, 64)
    # bfloat16 holds the seeded values: the resident cast loses nothing
    for name, layer, got in (("q_w", 1, blk["attn"]["q"]),
                             ("conv0_w", 1, blk["attn"]["conv0_w"]),
                             ("wte", -1, tree["wte"]["embedding"])):
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(ref.leaf(MODEL, KEY, name, layer)))
    np.testing.assert_array_equal(
        np.asarray(blk["moe"]["up"][3].astype(jnp.float32)),
        np.asarray(ref.leaf(MODEL, KEY, "up_w", 1, 3)))


@pytest.mark.limit(120)
@pytest.mark.parametrize("precision", ["float32", "fp8"])
def test_the_reference_on_held_weights_is_the_reference_on_the_key(
        held, precision):
    tokens = _tokens(9, 48).reshape(2, 24)
    a = ref.forward(KEY, tokens, MODEL, precision)
    b = ref.forward(held, tokens, MODEL, precision)
    # the same weights to the bit; two programs' summation orders
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    for name, layer, e in (("router_w3", 2, None), ("res_mlp_bf", 0, None),
                           ("ln_mlp_g", 1, None), ("down_w", 1, 3)):
        np.testing.assert_array_equal(
            np.asarray(ref.leaf(MODEL, KEY, name, layer, e)),
            np.asarray(ref.leaf(MODEL, held, name, layer, e)))
    assert held["gate_w"].dtype == jnp.bfloat16 \
        and held["router_w1"].dtype == jnp.float32


# -- the engine and the server -----------------------------------------------------

@pytest.mark.limit(120)
def test_engine_serves_the_reference_tokens_through_cache_and_tail(engine):
    spec = engine.kv_spec
    assert spec.paired and spec.own_state and spec.tail == (2, TAIL) \
        and spec.shapes == ((3, 3, POSITIONS, ROW),)
    assert spec.nbytes(4) == 2 * 4 * ROW * 3 * 3 * POSITIONS \
        + 4 * 3 * 3 * 2 * TAIL
    assert len(engine._k) == 3 and len(engine._v) == 1 \
        and engine._k[1].dtype == jnp.float32 \
        and engine._k[-1].dtype == jnp.int32
    assert engine.stats()["decode_kernel"] == "dense"
    before = engine.stats()["counters"]
    seq = _tokens(11, 50)
    want = _full(seq).argmax(-1)
    got = [engine.prefill(1, pad_to_bucket(seq[:19], 32), 19, 32)]
    toks, at = np.zeros(3, np.int32), np.zeros(3, np.int32)
    for t in range(19, 45):
        toks[1], at[1] = seq[t], t
        got.append(int(engine.decode(toks, at)[1]))
    assert got == [int(x) for x in want[18:45]]
    after = engine.stats()["counters"]
    assert after["prefill_runs"] - before["prefill_runs"] == 1
    assert after["decode_runs"] - before["decode_runs"] == 26
    assert after["decode_moe_rows"] - before["decode_moe_rows"] == 26 * 3 * 3
    assert sum(engine.stats()["retraces"].values()) == 0


@pytest.mark.limit(120)
def test_the_engine_records_the_blocks_its_decode_program_reads(monkeypatch):
    """``stats()["decode_blocks"]`` (PR 42): 64 rows a slot in blocks of
    24 are two whole blocks and a ragged one of 16, the same at all three
    layers, so one triple; ``decode_kernel`` is the string it was.  Greedy
    tokens past row 48 (the ragged block) are the reference's."""
    monkeypatch.setenv("RLT_DECODE_IMPL", "flash_decode")
    monkeypatch.setattr(flash_decode, "_GROUPED_BLOCK_K", 24)
    eng = ServeEngine(_Module(), DataParallelStrategy(), buckets=(32,),
                      slots=3, max_seq_len=POSITIONS, seed=0).setup()
    stats = eng.stats()
    assert stats["decode_kernel"] == "gqa_decode"
    assert stats["decode_blocks"] == {"gqa_decode": [[24, 3, 16]]}
    seq = _tokens(11, 64)
    want = _full(seq).argmax(-1)
    got = [eng.prefill(1, pad_to_bucket(seq[:30], 32), 30, 32)]
    toks, at = np.zeros(3, np.int32), np.zeros(3, np.int32)
    for t in range(30, 63):
        toks[1], at[1] = seq[t], t
        got.append(int(eng.decode(toks, at)[1]))
    assert got == [int(x) for x in want[29:63]]


@pytest.mark.limit(240)
@pytest.mark.parametrize("name", ["freed_slot", "no_decode", "idle_gap"])
def test_decode_ahead_serves_what_the_blocking_order_serves(engine, name):
    """serve/worker.py ``_run_ahead`` over rows and a tail: equal tokens
    and, at every step, equal rows where a live slot can read and an
    equal generation of its tail (the one its next decode reads)."""
    prompts = [_tokens(20 + i, n) for i, n in
               enumerate((5, 13, 21, 9, 27, 16))]
    got = serve_ahead.check_equal_and_counted(
        engine, prompts, name,
        lambda pos: [np.arange(pos), [(pos - 1) % 2]])
    assert engine.stats()["counters"]["decode_runs"] > sum(got["decoded"])
    assert sum(engine.stats()["retraces"].values()) == 0


@pytest.mark.limit(60)
@pytest.mark.parametrize("what", ["paged", "kvship", "spec", "engine",
                                  "suffix"])
def test_refusals_name_the_reason(params, what):
    from ray_lightning_tpu.serve import Server
    from ray_lightning_tpu.serve.fleet.pages import PageConfig
    from ray_lightning_tpu.serve.spec import SpecConfig
    module = ZayaLightningModule(CFG)
    paged = PageConfig(enabled=True, page_size=8)
    if what in ("paged", "kvship", "spec"):
        kw, match = {
            "paged": ({"paged": paged}, "paged= is refused for Zaya"),
            "kvship": ({"kvship": True}, "kvship= is refused for Zaya"),
            "spec": ({"spec": SpecConfig(enabled=True, k=2)},
                     "spec= is refused for Zaya")}[what]
        with pytest.raises(ValueError, match=match):
            Server(module, buckets=(16,), max_batch_slots=2,
                   max_seq_len=POSITIONS, platform="cpu", **kw)
        return
    if what == "engine":
        with pytest.raises(ValueError, match="own kind of cache rows"):
            ServeEngine(module, DataParallelStrategy(), buckets=(16,),
                        slots=2, max_seq_len=POSITIONS, paged=paged).setup()
        return
    spec = KVCacheSpec(n_layer=3, slots=2, max_seq_len=POSITIONS, width=ROW,
                       tail=(2, TAIL))
    k, v = spec.state(jnp.zeros, jnp.float32)
    z = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="suffix program"):
        Zaya(CFG).apply({"params": params}, z[:1], z[:1], k, v,
                        method="decode", slots=z[:1])


@pytest.mark.limit(60)
def test_live_rows_are_a_row_a_position():
    module = ZayaLightningModule(CFG)
    assert module.live_cache_rows(0) == 1 \
        and module.live_cache_rows(3327) == 3328


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
        prompts = [_tokens(60 + i, n) for i, n in enumerate((9, 20, 1))]
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
