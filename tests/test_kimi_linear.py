"""Kimi Linear (models/kimi_linear.py, ops/kda.py, serve/kvcache.py's
``SlotState`` / ``states``, ops/latent_attention.py without positions,
ops/moe.py ``ExpertLayer`` with 4 of 8 experts held) against its plain
reference (chipbench/kimi_linear_reference.py), at a tiny size on the
CPU: width 64, five layers KDA KDA KDA MLA KDA (the first with the dense
MLP), 4 KDA heads of 8 (32 channels: not the width over the heads), a
latent of 32 + 8, 2 of 8 experts a token, everything in float32.

Tolerance: the two sides are the same mathematics written twice in
float32.  The reference runs the delta rule a position at a time from a
zero state over the whole sequence; the program takes a prompt through
the chunkwise form (64 positions a chunk: triangular systems inside,
the state carried between), then decodes a position a step out of a
slot's matrix, ring and stamp, through an absorbed latent query and a
sorted grouped product.  They differ by summation order: logits spread
by about 0.16, five layers and ~200 positions of a carried state leave
a few 1e-6 of that, ``ATOL = 3e-5`` leaves room, and a dropped tap,
decay, beta, gate or stamp moves a logit by 1e-3 or more
(``test_an_altered_mechanism_moves_the_logits``).
"""

from __future__ import annotations

import dataclasses
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import kimi_linear_reference as ref
from chipbench.adapters import kimi_linear as adapter
from ray_lightning_tpu.core import steps
from ray_lightning_tpu.models import kimi_linear
from ray_lightning_tpu.models.kimi_linear import (
    RING, SERVE_COUNTERS, KimiLinear, KimiLinearLightningModule)
from ray_lightning_tpu.ops import kda, moe
from ray_lightning_tpu.parallel.strategy import DataParallelStrategy
from ray_lightning_tpu.serve.buckets import pad_to_bucket
from ray_lightning_tpu.serve.engine import ServeEngine
from ray_lightning_tpu.serve.kvcache import KVCacheSpec, SlotState
from ray_lightning_tpu.serve.scheduler import Scheduler
from tests import serve_ahead

ATOL = 3e-5
MODEL = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=5, first_k_dense_replace=1,
    linear_attn_config=dict(kda_layers=[1, 2, 3, 5], full_attn_layers=[4],
                            num_heads=4, head_dim=8,
                            short_conv_kernel_size=4),
    num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, num_experts=4,
    num_experts_published=8, expert_offset=0, num_experts_per_token=2,
    num_shared_experts=1, routed_scaling_factor=2.446, rms_norm_eps=1e-5,
    model_max_length=256, served_positions=256)
CFG = dataclasses.replace(adapter.config_of(MODEL), dtype=jnp.float32)
KEY = jax.random.PRNGKey(5)
SLOTS, POSITIONS, ROW, P, K = 4, 256, 128, 32, 8
STATES = ((4, (P, K), "float32"), (4, (RING, 3 * P), "float32"),
          (4, (), "int32"))


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


class _Module(KimiLinearLightningModule):
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


def _spec(slots=SLOTS):
    return KVCacheSpec(n_layer=5, slots=slots, max_seq_len=POSITIONS,
                       width=ROW, kinds=((1, POSITIONS),),
                       counters=len(SERVE_COUNTERS), paired=False,
                       states=STATES)


def _programs():
    net = KimiLinear(CFG)
    prefill = jax.jit(lambda p, k, v, t, n, s: net.apply(
        {"params": p}, t, n, s, k, v, method="prefill"))
    decode = jax.jit(lambda p, k, v, t, at: net.apply(
        {"params": p}, t, at, k, v, method="decode"))
    return prefill, decode, _spec()


# -- (a) the program against the reference's full forward ----------------------------

@pytest.mark.limit(240)
@pytest.mark.parametrize("T", [8, 150])
def test_forward_matches_reference(params, T):
    """Whole sequences: under a chunk, and two chunks and a part."""
    tokens = _tokens(T, 2 * T).reshape(2, T)
    got = KimiLinear(CFG).apply({"params": params}, tokens)
    want = np.stack([_full(row) for row in tokens])
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL)


@pytest.mark.limit(600)
@pytest.mark.parametrize("impl,steps_", [("dense", 2 * kda.CHUNK + 3),
                                         ("flash_decode", 5),
                                         ("kda_decode", 5)])
def test_prefill_then_decode_through_state_and_cache_is_the_full_forward(
        params, monkeypatch, impl, steps_):
    """Prompts of 77, 1 and 64 tokens in buckets of 128 (a length that is
    neither the bucket nor a multiple of the chunk; one token; one whole
    chunk), then two chunks and three decode steps each, teacher-forced:
    every step multiplies its slot's matrices, turns the ring and reads
    the latent rows, and its logits are the reference's full forward at
    that position.  The reference has no state, no ring and no cache."""
    if impl == "kda_decode":
        # the Pallas call for the state too, interpreted (what the TPU
        # takes; the CPU's default is the plain step)
        monkeypatch.setattr(kda, "decode_kernel", lambda: True)
        impl = "dense"
    monkeypatch.setenv("RLT_DECODE_IMPL", impl)
    prefill, decode, spec = _programs()
    k, v = spec.state(jnp.zeros, jnp.float32)
    assert [a.shape for a in k] == [
        (1, SLOTS, POSITIONS, ROW), (4, SLOTS, P, K),
        (4, SLOTS, RING, 3 * P), (4, SLOTS), (8,)] and v == ()
    lengths = (77, 1, 64)
    seqs = [_tokens(40 + i, n + steps_) for i, n in enumerate(lengths)]
    want = [_full(s) for s in seqs]
    for slot, n in enumerate(lengths):
        logits, k, v = prefill(params, k, v,
                               pad_to_bucket(seqs[slot][:n], 128),
                               np.int32(n), np.int32(slot))
        np.testing.assert_allclose(np.asarray(logits), want[slot][n - 1],
                                   atol=ATOL)
        np.testing.assert_array_equal(np.asarray(k[3])[:, slot], n - 1)
    for step in range(steps_):
        # (slot 3 is dead: position 0, as a plan gives it)
        at = np.asarray([n + step for n in lengths] + [0], np.int32)
        toks = np.asarray([s[t] for s, t in zip(seqs, at)] + [0], np.int32)
        logits, k, v = decode(params, k, v, toks, at)
        for slot in range(3):
            np.testing.assert_allclose(
                np.asarray(logits)[slot], want[slot][at[slot]], atol=ATOL,
                err_msg=f"slot {slot} step {step}")
    counted = dict(zip(SERVE_COUNTERS, np.asarray(k[-1])))
    assert counted["prefill_runs"] == 3 and counted["decode_runs"] == steps_
    # four expert layers, 2 of 8 a token, half of them held
    assert 0 < counted["decode_moe_pairs"] < steps_ * SLOTS * 4 * 2
    np.testing.assert_array_equal(np.asarray(k[3])[:, :3],
                                  [at[:3]] * 4)


# -- (b) the chunkwise form against the recurrence ---------------------------------

def _kda_inputs(T, seed=0, fastest=1.6, B=2, H=3, width=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = kimi_linear.unit(jax.random.normal(ks[0], (B, T, H, width))) \
        * width ** -0.5
    k = kimi_linear.unit(jax.nn.silu(
        jax.random.normal(ks[1], (B, T, H, width))))
    v = jax.nn.silu(jax.random.normal(ks[2], (B, T, H, width)))
    g = -jnp.exp(jax.random.uniform(
        ks[3], (B, T, H, width), minval=np.log(0.001),
        maxval=np.log(fastest)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    state = jax.random.normal(ks[5], (B, H, width, width))
    return q, k, v, g, beta, state


@pytest.mark.limit(120)
@pytest.mark.parametrize("T", [1, 63, 64, 65, 200])
def test_the_chunkwise_form_is_the_recurrence(T):
    """From a carried-in state, output and state after the last position:
    the two formulations of ops/kda.py differ by summation order (the
    state's entries are of order 1)."""
    args = _kda_inputs(T, seed=T)
    o, S = jax.jit(kda.kda_chunked)(*args)
    o2, S2 = jax.jit(kda.kda_recurrent)(*args)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o2), atol=5e-6)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S2), atol=5e-6)
    assert np.abs(np.asarray(S2)).max() > 0.3


@pytest.mark.limit(120)
def test_a_channel_that_forgets_a_chunk_over_stays_finite():
    """A decay of 3 a position is exp(-192) over a chunk: a product of
    exp(G_r) and exp(-G_i) taken apart over the chunk would be inf * 0."""
    args = _kda_inputs(130, seed=3, fastest=3.0)
    o, S = jax.jit(kda.kda_chunked)(*args)
    o2, S2 = jax.jit(kda.kda_recurrent)(*args)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(np.asarray(o), np.asarray(o2), atol=5e-6)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S2), atol=5e-6)


@pytest.mark.limit(120)
def test_positions_with_no_decay_and_no_beta_leave_the_state_alone():
    """What a bucket's padding is given: the state after 100 positions of
    which 37 are real is the state after those 37, to the bit of a
    product with 1 and a sum with 0."""
    q, k, v, g, beta, state = _kda_inputs(100, seed=5)
    real = jnp.arange(100) < 37
    g = jnp.where(real[None, :, None, None], g, 0.0)
    beta = jnp.where(real[None, :, None], beta, 0.0)
    _, S = jax.jit(kda.kda_chunked)(q, k, v, g, beta, state)
    _, S2 = jax.jit(kda.kda_chunked)(q[:, :37], k[:, :37], v[:, :37],
                                     g[:, :37], beta[:, :37], state)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S2), atol=1e-6)
    o, S3 = kda.kda_step(q[:, 50], k[:, 50], v[:, 50], g[:, 50],
                         beta[:, 50], state)
    np.testing.assert_array_equal(np.asarray(S3), np.asarray(state))


@pytest.mark.limit(120)
@pytest.mark.parametrize("layer", [0, 2])
def test_the_decode_call_is_the_step_in_place(layer):
    """``kda_decode`` (interpreted here; compiled for the described v5e in
    tests/test_chip_compile.py) against ``kda_step`` over the resident
    ``[layers, S, H V, K]`` array: the layer's blocks take the step's
    values (to a sum's order along the lanes), every other layer's are
    left as they lie, to the bit."""
    q, k, v, g, beta, _ = _kda_inputs(5, seed=9, B=1)
    q, k, v, g, beta = (x[0] for x in (q, k, v, g, beta))     # 5 slots
    state = jax.random.normal(jax.random.PRNGKey(2), (3, 5, 3 * 16, 16))
    want_o, want = kda.kda_step(q, k, v, g, beta,
                                state[layer].reshape(5, 3, 16, 16))
    o, got = jax.jit(lambda *a: kda.kda_decode(*a, layer=layer),
                     donate_argnums=(5,))(q, k, v, g, beta, state + 0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[layer]),
                               np.asarray(want).reshape(5, 48, 16),
                               atol=1e-6)
    for other in set(range(3)) - {layer}:
        np.testing.assert_array_equal(np.asarray(got[other]),
                                      np.asarray(state[other]))
    with pytest.raises(ValueError, match="does not hold layer"):
        kda.kda_decode(q, k, v, g, beta, state, layer=3)


# -- (c) a step run twice, (d) a slot used again -----------------------------------

@pytest.mark.limit(120)
def test_a_decode_run_twice_at_one_position_gives_the_state_and_logits_of_once(
        params):
    """serve/worker.py drops a decode it queued ahead and queues the
    plan's own at the same positions.  The matrix is multiplied, not
    written by position: the stamp says it stands at ``t`` already, the
    second run leaves it and reads the first run's values out.  A dead
    slot's dummy step (position 0 over a state that stands elsewhere)
    moves nothing but ring row 0, which the slot's next prefill
    rewrites."""
    prefill, decode, spec = _programs()
    k, v = spec.state(jnp.zeros, jnp.float32)
    seq = _tokens(7, 16)
    want = _full(seq)
    for slot in (1, 2):
        _, k, v = prefill(params, k, v, pad_to_bucket(seq[:6], 16),
                          np.int32(6), np.int32(slot))
    toks, at = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    toks[2], at[2] = seq[6], 6           # slot 1 is dead: position 0
    once, k1, v1 = decode(params, k, v, toks, at)
    twice, k2, v2 = decode(params, k1, v1, toks, at)
    np.testing.assert_array_equal(np.asarray(once), np.asarray(twice))
    for a, b in zip(k1[:-1], k2[:-1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(once)[2], want[6], atol=ATOL)
    # the dead slot's matrices and stamps are the prefill's
    np.testing.assert_array_equal(np.asarray(k2[1])[:, 1],
                                  np.asarray(k[1])[:, 1])
    np.testing.assert_array_equal(np.asarray(k2[3])[:, 1], 5)
    np.testing.assert_array_equal(np.asarray(k2[3])[:, 2], 6)
    # ... and a third run a position on moves on from the one state
    toks[2], at[2] = seq[7], 7
    after, _, _ = decode(params, k2, v2, toks, at)
    np.testing.assert_allclose(np.asarray(after)[2], want[7], atol=ATOL)


@pytest.mark.limit(240)
@pytest.mark.parametrize("every", [1, 3])
def test_a_forced_miss_serves_the_tokens_of_a_run_without_one(engine, every):
    """``ServeWorker`` with the decode in flight dropped before every
    (third) plan: that plan's own decode runs at the positions the
    dropped one ran at, and the served tokens are those of the
    undisturbed run."""
    prompts = [_tokens(20 + i, n) for i, n in enumerate((5, 13, 21, 9))]

    def run(drop_every):
        engine._k, engine._v = engine._kv_init()
        sched = Scheduler(buckets=engine.buckets, slots=engine.slots,
                          max_seq_len=engine.max_seq_len)
        worker = serve_ahead.worker_on(engine)
        reqs = [sched.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, (9, 6, 8, 7))]
        aheads = []
        for step in range(200):
            plan = sched.plan()
            if plan is None:
                break
            if drop_every and step % drop_every == 0:
                worker._drop_ahead(engine, wait=True)
            result = worker.serve_step(plan)
            sched.apply(plan, result)
            aheads.append(result["timing"].get("ahead"))
        assert all(r.done() for r in reqs)
        return [r.result(1).tolist() for r in reqs], aheads

    want, plain = run(0)
    got, forced = run(every)
    assert got == want
    assert forced.count("miss") > plain.count("miss") + 3
    for p, out in zip(prompts, want):
        full = _full(np.concatenate([p, out[:-1]]))[len(p) - 1:]
        assert full.argmax(-1).tolist() == out


@pytest.mark.limit(120)
def test_a_slot_freed_mid_answer_and_prefilled_again_serves_a_fresh_slots_logits(
        params):
    """Nothing clears a freed slot: the next prefill writes matrix, ring
    and stamp whole, a dead slot's steps in between notwithstanding."""
    prefill, decode, spec = _programs()
    first, second = _tokens(31, 40), _tokens(32, 20)

    def at_slot_1(value):
        # (a fresh array a call: a dispatch may still be reading the last)
        return np.asarray([0, value, 0, 0], np.int32)

    def serve(k, v, seq, n, steps_):
        out = [prefill(params, k, v, pad_to_bucket(seq[:n], 32),
                       np.int32(n), np.int32(1))]
        k, v = out[0][1:]
        for t in range(n, n + steps_):
            out.append(decode(params, k, v, at_slot_1(seq[t]), at_slot_1(t)))
            k, v = out[-1][1:]
        return [np.asarray(o[0]) for o in out], k, v

    k, v = spec.state(jnp.zeros, jnp.float32)
    _, k, v = serve(k, v, first, 29, 8)
    for _ in range(2):                    # freed: two dummy steps
        _, k, v = decode(params, k, v, at_slot_1(0), at_slot_1(0))
    again, _, _ = serve(k, v, second, 11, 6)
    fresh, _, _ = serve(*spec.state(jnp.zeros, jnp.float32), second, 11, 6)
    for a, b in zip(again, fresh):
        np.testing.assert_array_equal(a[1] if a.ndim == 2 else a,
                                      b[1] if b.ndim == 2 else b)


# -- every mechanism shows in a logit --------------------------------------------

def _altered(held, fault: str):
    """The reference's weights with one mechanism taken out."""
    w = dict(held)
    if fault == "tap":               # the convolution's look at t - 3
        w["conv_k_w"] = held["conv_k_w"].at[:, :, 0].set(0)
    elif fault == "decay":           # every channel forgets alike
        w["A_log"] = jnp.zeros_like(held["A_log"])
    elif fault == "decay_gate":      # the decay no longer reads the token
        w["a2_w"] = jnp.zeros_like(held["a2_w"])
    elif fault == "beta":
        w["b_w"] = jnp.zeros_like(held["b_w"])
    elif fault == "gate_bias":
        w["g_bias"] = jnp.zeros_like(held["g_bias"])
    elif fault == "head_norm":
        w["o_norm_g"] = jnp.ones_like(held["o_norm_g"])
    elif fault == "k_pe":            # the latent layer's shared key
        w["dkv_w"] = held["dkv_w"].at[:, :, 32:].set(0)
    elif fault == "router_bias":
        w["router_b"] = jnp.zeros_like(held["router_b"])
    return w


@pytest.mark.limit(120)
@pytest.mark.parametrize("fault", ["tap", "decay", "decay_gate", "beta",
                                   "gate_bias", "head_norm", "k_pe",
                                   "router_bias"])
def test_an_altered_mechanism_moves_the_logits(held, fault):
    seq = _tokens(13, 120)
    want = _full(seq, held)
    got = _full(seq, _altered(held, fault))
    assert np.abs(got - want).max() > 30 * ATOL, np.abs(got - want).max()


@pytest.mark.limit(240)
def test_bfloat16_against_float32_holds_where_an_fp8_product_fails():
    """The program in bfloat16 (its resident types, as the chip runs it)
    against the float32 reference: products' operands rounded to 8 bits
    of mantissa move a logit by up to ~0.01 of a spread of 0.16 here
    (largest read 0.0096 over four seeds); the reference with its
    operands in fp8 moves one by 0.1 and more.  ``0.025`` lies between."""
    module = adapter.module(MODEL, 0)
    net = module.configure_model()
    tree = jax.jit(lambda k: adapter.program_tree(MODEL, k))(KEY)
    seq = _tokens(17, 150)
    want = _full(seq)
    got = np.asarray(net.apply({"params": tree}, seq[None]))[0]
    low = np.asarray(ref.forward(KEY, seq[None], MODEL, "fp8"))[0]
    assert np.abs(got - want).max() < 0.025 < np.abs(low - want).max()


# -- (e) the shares add up -----------------------------------------------------------

@pytest.mark.limit(120)
def test_every_share_of_the_experts_adds_up_to_the_uncut_layer():
    """The guide's share test: what each of eight chips' experts adds for
    the tokens routed to them (offsets 0, 1, ..., 7 of the 8 published
    here; 0, 32, ..., 224 of 256 at the cell's size), summed, plus the
    shared expert ONCE, is the uncut layer of the reference; in the
    reference's own shares and in the program's dropless layer alike."""
    h = jax.random.normal(jax.random.PRNGKey(3), (24, 64))
    uncut = {**MODEL, "num_experts": 8, "expert_offset": 0}
    routed, shared = ref.moe_parts(h, uncut, KEY, 2)
    whole = np.asarray(routed + shared)
    idx, w = moe.sigmoid_topk(
        h, ref.leaf(MODEL, KEY, "router_w", 2), 2,
        ref.leaf(MODEL, KEY, "router_b", 2), 2.446)
    ref_sum = prog_sum = 0.0
    for part in range(8):
        share = ref.share_of(MODEL, part, 8)
        assert share["num_experts"] == 1 and share["expert_offset"] == part
        ref_sum = ref_sum + ref.moe_parts(h, share, KEY, 2)[0]
        mats = [ref.leaf(MODEL, KEY, n, 2, part)[None]
                for n in ("gate_w", "up_w", "down_w")]
        y, *_ = moe.dropless_experts(h, idx, w, *mats, offset=part,
                                     published=8)
        prog_sum = prog_sum + y
    tol = 1e-4 * np.abs(whole).max()
    np.testing.assert_allclose(np.asarray(ref_sum + shared), whole, atol=tol)
    np.testing.assert_allclose(np.asarray(prog_sum + shared), whole,
                               atol=tol)
    assert np.abs(np.asarray(y + shared) - whole).max() > 100 * tol
    assert np.abs(np.asarray(prog_sum + 8 * shared) - whole).max() \
        > 100 * tol


# -- (f) the refusals ------------------------------------------------------------------

@pytest.mark.limit(60)
@pytest.mark.parametrize("what", ["paged", "kvship", "spec", "engine",
                                  "suffix"])
def test_refusals_name_the_reason(params, what):
    from ray_lightning_tpu.serve import Server
    from ray_lightning_tpu.serve.fleet.pages import PageConfig
    from ray_lightning_tpu.serve.spec import SpecConfig
    module = KimiLinearLightningModule(CFG)
    paged = PageConfig(enabled=True, page_size=8)
    if what in ("paged", "kvship", "spec"):
        kw, match = {
            "paged": ({"paged": paged},
                      "paged= is refused for KimiLinear.*state.*not kept"),
            "kvship": ({"kvship": True},
                       "kvship= is refused for KimiLinear"),
            "spec": ({"spec": SpecConfig(enabled=True, k=2)},
                     "spec= is refused for KimiLinear")}[what]
        with pytest.raises(ValueError, match=match):
            Server(module, buckets=(16,), max_batch_slots=2,
                   max_seq_len=POSITIONS, platform="cpu", **kw)
        return
    if what == "engine":
        with pytest.raises(ValueError, match="own kind of cache rows"):
            ServeEngine(module, DataParallelStrategy(), buckets=(16,),
                        slots=2, max_seq_len=POSITIONS, paged=paged).setup()
        return
    k, v = _spec(2).state(jnp.zeros, jnp.float32)
    z = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="suffix program"):
        KimiLinear(CFG).apply({"params": params}, z[:1], z[:1], k, v,
                              method="decode", slots=z[:1])


# -- (g) the spec, read off a capture ------------------------------------------------

def _captured_spec(module, slots=3, positions=64):
    module.setup_model()
    net = module.configure_decode_model()
    dummy = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    made = jax.eval_shape(net.init, jax.random.PRNGKey(0), dummy)["params"]
    _, cap = jax.eval_shape(
        lambda p, t: net.apply({"params": p}, t, True, mutable=["kv_cache"]),
        made, dummy)
    return KVCacheSpec.from_capture(
        steps.kv_layer_pairs(cap["kv_cache"]), slots, positions,
        counters=len(getattr(module, "serve_counters", ())))


@pytest.mark.limit(60)
def test_a_spec_with_layers_that_keep_a_state_and_no_rows():
    spec = _captured_spec(KimiLinearLightningModule(CFG), SLOTS, POSITIONS)
    assert spec == _spec()
    assert spec.own_state and not spec.paired and spec.tail == ()
    assert spec.shapes == ((1, SLOTS, POSITIONS, ROW),) == (spec.shape,)
    per_slot = 4 * (4 * P * K + 4 * RING * 3 * P + 4)
    assert spec.state_bytes_per_slot == per_slot
    assert spec.nbytes() == 2 * SLOTS * POSITIONS * ROW + SLOTS * per_slot
    k, v = spec.state(jax.ShapeDtypeStruct, jnp.bfloat16)
    assert [(a.shape, a.dtype.name) for a in k] == [
        ((1, SLOTS, POSITIONS, ROW), "bfloat16"),
        ((4, SLOTS, P, K), "float32"), ((4, SLOTS, RING, 3 * P), "float32"),
        ((4, SLOTS), "int32"), ((8,), "int32")] and v == ()
    # the published sizes: 2 MB of matrix a layer a slot
    row = (jax.ShapeDtypeStruct((1, 1, 5248, 640), jnp.bfloat16),)
    state = SlotState((jax.ShapeDtypeStruct((1, 1, 4096, 128), jnp.float32),
                       jax.ShapeDtypeStruct((1, 1, 4, 12288), jnp.float32),
                       jax.ShapeDtypeStruct((1, 1), jnp.int32)))
    big = KVCacheSpec.from_capture(
        [state, state, state, row, state, state, state, row, state], 192,
        5248, counters=8)
    assert big.kinds == ((2, 5248),) and big.n_layer == 9
    assert big.state_bytes_per_slot == 7 * (2097152 + 196608 + 4)
    assert big.nbytes() == 2 * 192 * 5248 * 640 * 2 \
        + 192 * big.state_bytes_per_slot
    other = SlotState((jax.ShapeDtypeStruct((1, 1, 8, 8), jnp.float32),))
    with pytest.raises(ValueError, match="differing blocks"):
        KVCacheSpec.from_capture([state, other, row], 2, 64)
    with pytest.raises(ValueError, match="no layer keeps rows"):
        KVCacheSpec.from_capture([state, state], 2, 64)


#: the five other families' specs as they were before a spec could hold
#: ``states`` (PR 43's parent, 3920fa7), tiny presets, 3 slots of 64
#: positions: a state without rows is a new case beside these
PARENTS = {
    "gpt": KVCacheSpec(n_layer=2, slots=3, max_seq_len=64, width=64),
    "evabyte": KVCacheSpec(n_layer=2, slots=3, max_seq_len=64, width=64,
                           rows=96),
    "command": KVCacheSpec(n_layer=4, slots=3, max_seq_len=64, width=32,
                           kinds=((3, 8), (1, 64)), counters=8),
    "xing": KVCacheSpec(n_layer=3, slots=3, max_seq_len=64, width=128,
                        rows=64, counters=8, paired=False),
    "zaya": KVCacheSpec(n_layer=3, slots=3, max_seq_len=64, width=32,
                        counters=8, tail=(2, 208)),
}


@pytest.mark.limit(120)
@pytest.mark.parametrize("family", sorted(PARENTS))
def test_the_five_other_families_specs_are_the_parents(family):
    from ray_lightning_tpu import models
    module = {"gpt": models.GPTLightningModule,
              "evabyte": models.EvaByteLightningModule,
              "command": models.CommandLightningModule,
              "xing": models.XingLightningModule,
              "zaya": models.ZayaLightningModule}[family]("tiny")
    spec = _captured_spec(module)
    assert spec == PARENTS[family] and spec.states == () \
        and spec.state_bytes_per_slot == 0
    k, v = spec.state(lambda shape, dtype: (shape, np.dtype(dtype).name),
                      jnp.bfloat16)
    if family in ("gpt", "evabyte"):
        assert k == v and len(k) == 2 and len(k[0]) == 4   # the bare arrays
    else:
        assert k[-1] == ((8,), "int32")
        assert len(k) == len(spec.shapes) + 1 + bool(spec.tail)


# -- the weights, the engine and the server -----------------------------------------

@pytest.mark.limit(120)
def test_weights_by_leaf_are_the_references_and_float32_where_they_say():
    tree = jax.jit(lambda k: adapter.program_tree(MODEL, k))(KEY)
    init = jax.eval_shape(KimiLinear(CFG).init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    assert jax.tree_util.tree_structure(tree) \
        == jax.tree_util.tree_structure(init)
    resident = kimi_linear.resident(jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.float32), init))
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(tree)[0],
            jax.tree_util.tree_leaves(resident)):
        assert a.dtype == b.dtype and a.shape == b.shape, path
    kda_, mla = tree["h1"], tree["h3"]
    assert kda_["attn"]["a2"].dtype == jnp.float32 \
        and kda_["attn"]["dt_bias"].dtype == jnp.float32 \
        and kda_["attn"]["b"].dtype == jnp.float32 \
        and kda_["moe"]["router"].dtype == jnp.float32 \
        and kda_["attn"]["conv_k"].dtype == jnp.bfloat16 \
        and kda_["attn"]["g_bias"].dtype == jnp.bfloat16 \
        and kda_["moe"]["down"].shape == (4, 32, 64) \
        and mla["attn"]["uk"].shape == (32, 4, 16) \
        and "mlp" in tree["h0"] and "moe" not in tree["h0"]
    # bfloat16 holds the seeded values: the resident cast loses nothing
    for name, layer, got in (("kda_q_w", 1, kda_["attn"]["q"]["kernel"]),
                             ("conv_v_w", 1, kda_["attn"]["conv_v"]),
                             ("o_norm_g", 1, kda_["attn"]["o_norm"]),
                             ("mla_q_w", 3, mla["attn"]["q"]["kernel"]),
                             ("wte", -1, tree["wte"]["embedding"])):
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(ref.leaf(MODEL, KEY, name, layer)))
    np.testing.assert_array_equal(
        np.asarray(kda_["moe"]["up"][3].astype(jnp.float32)),
        np.asarray(ref.leaf(MODEL, KEY, "up_w", 1, 3)))
    # a channel forgets over 0.6 to 1,000 positions
    rate = np.exp(np.asarray(kda_["attn"]["A_log"]))[:, None] \
        * np.log1p(np.exp(np.asarray(kda_["attn"]["dt_bias"]))).reshape(4, 8)
    assert 0.001 <= rate.min() and rate.max() <= 1.6
    # the other share's experts are other experts
    other = adapter.program_tree(ref.share_of(MODEL, 1, 2), KEY)
    np.testing.assert_array_equal(
        np.asarray(other["h1"]["moe"]["gate"][0].astype(jnp.float32)),
        np.asarray(ref.leaf(MODEL, KEY, "gate_w", 1, 4)))


@pytest.mark.limit(120)
@pytest.mark.parametrize("precision", ["float32", "fp8"])
def test_the_reference_on_held_weights_is_the_reference_on_the_key(
        held, precision):
    tokens = _tokens(9, 48).reshape(2, 24)
    a = ref.forward(KEY, tokens, MODEL, precision)
    b = ref.forward(held, tokens, MODEL, precision)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)
    for name, layer, e in (("router_w", 2, None), ("ln_mlp_g", 1, None),
                           ("conv_q_w", 4, None), ("ukv_w", 3, None),
                           ("down_w", 1, 3)):
        np.testing.assert_array_equal(
            np.asarray(ref.leaf(MODEL, KEY, name, layer, e)),
            np.asarray(ref.leaf(MODEL, held, name, layer, e)))
    assert held["gate_w"].dtype == jnp.bfloat16 \
        and held["gate_w"].shape == (4, 4, 64, 32) \
        and held["kda_q_w"].shape[0] == 4 and held["ukv_w"].shape[0] == 1 \
        and held["a1_w"].dtype == jnp.float32


@pytest.mark.limit(240)
def test_engine_serves_the_reference_tokens_through_state_and_cache(engine):
    spec = engine.kv_spec
    assert spec == _spec(3)
    assert len(engine._k) == 5 and engine._v == () \
        and engine._k[1].dtype == jnp.float32 \
        and engine._k[3].dtype == jnp.int32 \
        and engine._k[-1].shape == (8,)
    before = engine.stats()["counters"]
    seq = _tokens(11, 80)
    want = _full(seq).argmax(-1)
    got = [engine.prefill(1, pad_to_bucket(seq[:19], 32), 19, 32)]
    toks, at = np.zeros(3, np.int32), np.zeros(3, np.int32)
    for t in range(19, 75):
        toks[1], at[1] = seq[t], t
        got.append(int(engine.decode(toks, at)[1]))
    assert got == [int(x) for x in want[18:75]]
    after = engine.stats()["counters"]
    assert after["prefill_runs"] - before["prefill_runs"] == 1
    assert after["decode_runs"] - before["decode_runs"] == 56
    assert after["decode_moe_rows"] - before["decode_moe_rows"] \
        == 56 * 3 * 2 * 4
    assert sum(engine.stats()["retraces"].values()) == 0


def _ring_rows(pos):
    """The ring's rows a decode at ``pos`` reads: the three positions
    before it."""
    return np.asarray([p % RING for p in range(max(pos - 3, 0), pos)], int)


@pytest.mark.limit(240)
@pytest.mark.parametrize("name", ["freed_slot", "no_decode", "idle_gap"])
def test_decode_ahead_serves_what_the_blocking_order_serves(engine, name):
    """serve/worker.py ``_run_ahead`` over latent rows, matrices, rings
    and stamps: equal tokens and, at every step, equal latent rows and
    ring rows where a live slot can read.  (The matrix itself stands one
    position further in the order that runs ahead, by design: the tokens
    it yields are what is compared.)"""
    prompts = [_tokens(20 + i, n) for i, n in
               enumerate((5, 13, 21, 9, 27, 16))]
    none = np.zeros(0, int)
    got = serve_ahead.check_equal_and_counted(
        engine, prompts, name,
        lambda pos: [np.arange(pos), none, _ring_rows(pos)])
    assert engine.stats()["counters"]["decode_runs"] > sum(got["decoded"])
    assert sum(engine.stats()["retraces"].values()) == 0


@pytest.mark.limit(60)
def test_live_rows_count_the_layers_that_keep_rows_and_the_state_is_told():
    module = KimiLinearLightningModule(CFG)
    assert module.live_cache_rows(0) == 1 / 5 \
        and module.live_cache_rows(99) == 20.0
    full = KimiLinearLightningModule("kimi-linear-48b-a3b")
    assert full.live_cache_rows(26) == 7.0
    # the published sizes: 2 MB of matrix, a ring and a stamp a layer
    assert _captured_spec(full, 2, 64).state_bytes_per_slot \
        == 20 * (2097152 + 196608 + 4)


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
    sched = stats["scheduler"]
    assert stats["workers"][0]["state_bytes_per_slot"] \
        == _spec().state_bytes_per_slot
    assert 0 < sched["live_rows"] == pytest.approx(
        sched["live_positions"] / 5)
    assert sum(stats["workers"][0]["retraces"].values()) == 0
    ahead = sched["pump"]
    assert ahead is not None
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
