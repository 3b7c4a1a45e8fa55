"""EvaByte (models/evabyte.py, ops/eva_attention.py) against its plain
reference (chipbench/evabyte_reference.py), at a tiny size on the CPU:
width 64, 2 heads of 32, window 32, chunk 4, 2 layers, 2 output heads,
256 positions, everything in float32.

Tolerance: the two sides are the same mathematics written twice in
float32 (the reference window by window with its own masks and pooling,
the program over padded chunks on packed rows and, served, through the
cache), so they differ by summation order only.  Logits here have a
spread of about 0.8; float32 rounding through 2 layers reaches a few
1e-6 of that, and ``ATOL = 1e-5`` leaves it room while a wrong row, mask,
position or pooled member moves a logit by 1e-2 or more.
"""

from __future__ import annotations

import dataclasses
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import evabyte_reference as ref
from chipbench.adapters import evabyte as adapter
from ray_lightning_tpu.models.evabyte import (
    EvaByte, EvaByteConfig, EvaByteLightningModule)
from ray_lightning_tpu.ops import eva_attention as eva
from ray_lightning_tpu.parallel.strategy import DataParallelStrategy
from ray_lightning_tpu.serve.buckets import pad_to_bucket
from ray_lightning_tpu.serve.engine import ServeEngine
from ray_lightning_tpu.serve.kvcache import KVCacheSpec
from tests import serve_ahead

ATOL = 1e-5
MODEL = dict(vocab_size=320, hidden_size=64, num_hidden_layers=2,
             num_attention_heads=2, intermediate_size=176, num_pred_heads=2,
             window_size=32, chunk_size=4, max_position_embeddings=256,
             rope_theta=100000.0, rms_norm_eps=1e-5, init_std=0.05)
CFG = dataclasses.replace(adapter.config_of(MODEL), dtype=jnp.float32)
SLOTS = 3


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def _time_limit(request):
    """``@pytest.mark.limit(seconds)``: each test's own time limit (an
    alarm in the worker's main thread; no plugin to install)."""
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
def weights():
    w = dict(adapter.make_weights(MODEL, jax.random.PRNGKey(3)))
    # the published initialisation makes the pooling nearly uniform; a
    # larger phi and mu make a wrong pooling weight or member show
    w["phi"], w["mu"] = 8.0 * w["phi"], 4.0 * w["mu"]
    return w


class _Module(EvaByteLightningModule):
    """The module a user would hand to ``Server``, in float32 and with
    the test's weights."""

    param_dtype = None

    def __init__(self, w):
        super().__init__(CFG)
        self._w = w

    def init_params(self, rng, batch):
        return {"params": adapter.to_program_tree(self._w)}


@pytest.fixture(scope="module")
def engine(weights):
    return ServeEngine(_Module(weights), DataParallelStrategy(),
                       buckets=(16, 48, 112), slots=SLOTS, max_seq_len=256,
                       seed=0).setup()


@pytest.fixture
def programs():
    """The model's own serve methods, jitted to return LOGITS (the
    engine's programs return the sampled token).  Made anew for each
    test: which decode kernel lowers is read when a program traces."""
    net = EvaByte(CFG)
    return (jax.jit(lambda p, k, v, t, n, s: net.apply(
                {"params": p}, t, n, s, k, v, method="prefill")),
            jax.jit(lambda p, k, v, t, at: net.apply(
                {"params": p}, t, at, k, v, method="decode")))


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 320, (n,)).astype(np.int32)


def _full(weights, tokens):
    return np.asarray(ref.forward(weights, jnp.asarray(tokens)[None],
                                  MODEL))[0]


@pytest.mark.limit(120)
@pytest.mark.parametrize("T", [20, 32, 77, 131])
def test_forward_matches_reference_on_every_head(weights, T):
    toks = jnp.asarray(np.stack([_tokens(T, T), _tokens(T + 1, T)]))
    got = EvaByte(CFG).apply({"params": adapter.to_program_tree(weights)},
                             toks)
    want = ref.forward_heads(weights, toks, MODEL)
    assert got.shape == (2, T, 2, 320)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


@pytest.mark.limit(120)
def test_one_window_is_plain_causal_attention(weights):
    """A context within one window: EVA is softmax(q k^T / sqrt(d)) v
    under the causal mask, and phi, mu and the summaries play no part."""
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 32, 2, 32)), jnp.float32)
               for _ in range(3))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(32)
    scores = jnp.where(jnp.tril(jnp.ones((32, 32), bool)), scores, -jnp.inf)
    plain = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    phi, mu = (jnp.asarray(rng.normal(size=(2, 32)), jnp.float32)
               for _ in range(2))
    np.testing.assert_allclose(
        ref.eva_attention(q, k, v, phi, mu, 32, 4, "float32"), plain,
        atol=ATOL, rtol=0)
    k_sum, v_sum = eva.chunk_summaries(
        k.reshape(2, 8, 4, 64), v.reshape(2, 8, 4, 64), phi, mu,
        jnp.ones((2, 8, 4), bool))
    got = eva.eva_attention(
        *(a.reshape(2, 32, 64) for a in (q, k, v)), k_sum, v_sum, n_head=2,
        window=32, chunk=4, dtype=jnp.float32)
    np.testing.assert_allclose(got, plain.reshape(2, 32, 64), atol=ATOL,
                               rtol=0)


# -- a prompt's attention on packed rows ---------------------------------------

_PACKED = {"f32": (jnp.float32, 2e-5), "bf16": (jnp.bfloat16, 2e-2)}
_W, _CH, _H, _D = 32, 4, 2, 128       # heads of the published size


def _prompt_on_the_view(q, k, v, phi, mu, length):
    """The ``[T, H, D]`` formulation, as plain as it gets: one softmax a
    query over the exact rows of its own window up to itself and the
    pooled rows of every chunk of every earlier window; float32."""
    q, k, v = (np.asarray(a, np.float32) for a in (q, k, v))
    phi, mu = np.asarray(phi, np.float32), np.asarray(mu, np.float32)
    B, T, H, D = q.shape
    out = np.zeros_like(q)
    for b in range(B):
        for h in range(H):
            ks, vs = [], []
            for c in range(0, T - T % _W, _CH):   # chunks of whole windows
                rows = slice(c, min(c + _CH, length))
                if c >= length:                 # a pad chunk: seen by none
                    continue
                a = k[b, rows, h] @ phi[h] / np.sqrt(D)
                a = np.exp(a - a.max())
                a /= a.sum()
                ks.append(a @ k[b, rows, h] + mu[h])
                vs.append(a @ v[b, rows, h])
            for t in range(min(T, length)):
                w = t // _W
                far = w * (_W // _CH)
                keys = np.concatenate(
                    [np.reshape(ks[:far], (far, D)), k[b, w * _W:t + 1, h]])
                vals = np.concatenate(
                    [np.reshape(vs[:far], (far, D)), v[b, w * _W:t + 1, h]])
                s = keys @ q[b, t, h] / np.sqrt(D)
                p = np.exp(s - s.max())
                out[b, t, h] = p / p.sum() @ vals
    return out


@pytest.mark.limit(120)
@pytest.mark.parametrize("dtype", list(_PACKED))
@pytest.mark.parametrize("path", ["numpy", "kernels"])
@pytest.mark.parametrize("T,length", [(32, 32), (64, 64), (96, 96),
                                      (128, 128), (128, 101), (576, 576)],
                         ids=["one_window", "edge", "three", "four",
                              "padded", "eighteen"])
def test_packed_prompt_attention_is_the_views(monkeypatch, T, length, path,
                                              dtype):
    """Packed ``[B, T, H*D]`` rows in, packed rows out, against the plain
    mathematics on ``[T, H, D]``: within one window, at a window's edge
    (row 32 sees the first window's summaries and itself), over three
    and four windows, and in a padded bucket whose ``length`` lies
    inside the last window (rows below it are what the unpadded prompt
    gives); over eighteen, whose last sees 136 summary rows: two blocks
    of the kernel's online softmax, the second partly seen.  ``numpy``
    is the path the CPU takes; ``kernels`` the chip's (``flash_fwd``,
    ``eva_far``) under the interpreter."""
    if path == "kernels":
        monkeypatch.setattr(eva, "_one_tpu_chip", lambda: True)
    dt, bar = _PACKED[dtype]
    rng = np.random.default_rng(T + length)
    q, k, v = (jnp.asarray(rng.normal(size=(2, T, _H, _D)), dt)
               for _ in range(3))
    phi, mu = (jnp.asarray(rng.normal(size=(_H, _D)), dt) for _ in range(2))
    C = _H * _D
    member = jnp.broadcast_to(
        (jnp.arange(T) < length).reshape(1, T // _CH, _CH), (2, T // _CH, _CH))
    k_sum, v_sum = eva.chunk_summaries(
        k.reshape(2, T // _CH, _CH, C), v.reshape(2, T // _CH, _CH, C),
        phi, mu, member)
    got = eva.eva_attention(
        *(a.reshape(2, T, C) for a in (q, k, v)), k_sum, v_sum, n_head=_H,
        window=_W, chunk=_CH, dtype=dt)
    assert got.shape == (2, T, C) and got.dtype == dt
    want = _prompt_on_the_view(q, k, v, phi, mu, length)
    np.testing.assert_allclose(
        np.asarray(got, np.float32).reshape(2, T, _H, _D)[:, :length],
        want[:, :length], atol=bar, rtol=bar)


@pytest.mark.limit(120)
@pytest.mark.parametrize("dtype", list(_PACKED))
@pytest.mark.parametrize("path", ["view", "kernel"])
def test_rotary_on_packed_rows_is_rotary_on_the_view_to_the_bit(
        monkeypatch, path, dtype):
    """``rotary_rows`` (a roll of a head's 128 lanes by 64 under the
    sine's sign: ``eva_rotary`` under the interpreter) against ``rotary``
    (the signed-permutation product) on the ``[T, H, D]`` view, both
    compiled: the same two products and one sum an element, so the same
    bits.  (This CPU's compiler may contract ``a*b + c*d`` to one fused
    multiply-add, and does so alike in float32; from bfloat16 operands
    it leaves the view's alone, and one element in 1e5 then rounds to
    the neighbouring bfloat16: allowed there, and nowhere else.)"""
    if path == "kernel":
        monkeypatch.setattr(eva, "_one_tpu_chip", lambda: True)
    dt = _PACKED[dtype][0]
    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, 256, 3, _D)), dt)
    at = jnp.arange(256)
    got, = jax.jit(lambda x: eva.rotary_rows((x,), at, 100000.0, 3))(
        x.reshape(2, 256, 3 * _D))
    want = jax.jit(lambda x: eva.rotary(x, at, 100000.0))(x)
    assert got.dtype == dt and got.shape == (2, 256, 3 * _D)
    got, want = (np.asarray(a, np.float32).reshape(x.shape)
                 for a in (got, want))
    if (path, dtype) == ("kernel", "bf16"):
        off = got != want
        assert off.mean() < 1e-4
        np.testing.assert_allclose(got[off], want[off], rtol=2.0 ** -7)
    else:
        np.testing.assert_array_equal(got, want)


@pytest.mark.limit(120)
@pytest.mark.parametrize("dtype", list(_PACKED))
@pytest.mark.parametrize("length", [512, 300], ids=["whole", "padded"])
def test_pooling_packed_rows_is_pooling_the_view(monkeypatch, length, dtype):
    """``chunk_summaries_rows`` (``eva_pool`` under the interpreter: a
    head a block of lanes, 16 chunks of 16 rows a step) against
    ``chunk_summaries`` on the ``[.., H, D]`` view; rows beyond
    ``length`` enter no summary, and a chunk of none pools to zeros and
    ``mu``."""
    dt, bar = _PACKED[dtype]
    rng = np.random.default_rng(length)
    k, v = (jnp.asarray(rng.normal(size=(2, 512, 3 * _D)), dt)
            for _ in range(2))
    phi, mu = (jnp.asarray(rng.normal(size=(3, _D)), dt) for _ in range(2))
    member = jnp.broadcast_to(jnp.arange(512) < length, (2, 512))
    want = eva.chunk_summaries_rows(k, v, phi, mu, member, 16)
    monkeypatch.setattr(eva, "_one_tpu_chip", lambda: True)
    got = eva.chunk_summaries_rows(k, v, phi, mu, member, 16)
    for g, w in zip(got, want):
        assert g.shape == (2, 32, 3 * _D) and g.dtype == dt
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   atol=bar, rtol=bar)


def _serve(engine, programs, weights, lengths, buckets, steps, k=None,
           v=None, seeds=(0, 1, 2)):
    """Prefill ``len(lengths)`` slots, then decode ``steps`` steps with
    the slots at their own positions, teacher-forced; the widest gap of a
    logit to the reference's full forward at every position served."""
    prefill, decode = programs
    k = engine._k if k is None else k
    v = engine._v if v is None else v
    seqs = [_tokens(s, 256) for s in seeds]
    full = [_full(weights, s) for s in seqs]
    worst = 0.0
    for s, (n, b) in enumerate(zip(lengths, buckets)):
        logits, k, v = prefill(engine.params, k, v,
                               pad_to_bucket(seqs[s][:n], b), np.int32(n),
                               np.int32(s))
        worst = max(worst, np.abs(np.asarray(logits) - full[s][n - 1]).max())
    at = np.array(lengths, np.int32)
    for _ in range(steps):
        toks = np.array([seqs[s][at[s]] for s in range(len(at))], np.int32)
        logits, k, v = decode(engine.params, k, v, toks, at)
        worst = max(worst, max(
            np.abs(np.asarray(logits)[s] - full[s][at[s]]).max()
            for s in range(len(at))))
        at = at + 1
    return worst, k, v


@pytest.mark.limit(240)
@pytest.mark.parametrize("impl", ["dense", "flash_decode"])
def test_prefill_then_decode_through_the_cache(engine, programs, weights,
                                               monkeypatch, impl):
    """Three slots at different positions: lengths 13, 45 and 97 are no
    multiples of the chunk (4) and sit right-padded in buckets 16, 48 and
    112; 100 decode steps take the slots across window edges (32) and
    the third past its sixth window.  Every logit, prefill's and each
    decoded position's, against the reference's full forward; under the
    dense path and under the Pallas kernel (interpreted)."""
    monkeypatch.setenv("RLT_DECODE_IMPL", impl)
    worst, _, _ = _serve(engine, programs, weights, (13, 45, 97),
                         (16, 48, 112), 100)
    assert worst < ATOL


@pytest.mark.limit(120)
def test_pad_rows_never_enter_a_summary(engine, programs, weights):
    """Length 13 in bucket 48: chunk 3 holds position 12 and three pad
    rows.  The summary row the prefill wrote pools position 12 alone
    (its key plus mu, its value), whatever the pad tokens are, and the
    decode step at position 13 pools 12 and 13 and nothing else."""
    prefill, decode = programs
    seq = _tokens(5, 64)
    rows = []
    for pad_id in (0, 7):
        padded = pad_to_bucket(seq[:13], 48, pad_id=pad_id)
        _, k, v = prefill(engine.params, engine._k, engine._v, padded,
                          np.int32(13), np.int32(1))
        rows.append((np.asarray(k[:, 1, 32 + 3]), np.asarray(v[:, 1, 32 + 3]),
                     k, v))
    np.testing.assert_array_equal(rows[0][0], rows[1][0])
    np.testing.assert_array_equal(rows[0][1], rows[1][1])
    k, v = rows[1][2], rows[1][3]
    mu = np.stack([np.asarray(weights["mu"][i]).reshape(-1)
                   for i in range(2)])
    np.testing.assert_allclose(rows[1][0], np.asarray(k[:, 1, 12]) + mu,
                               atol=1e-6)
    np.testing.assert_allclose(rows[1][1], np.asarray(v[:, 1, 12]),
                               atol=1e-6)
    at = np.zeros(SLOTS, np.int32)
    toks = np.zeros(SLOTS, np.int32)
    at[1], toks[1] = 13, seq[13]
    logits, k, v = decode(engine.params, k, v, toks, at)
    pooled = np.asarray(v[0, 1, 32 + 3])
    pair = np.asarray(v[0, 1, 12:14])
    # a convex combination of exactly rows 12 and 13, per head
    for h in range(2):
        sl = slice(32 * h, 32 * h + 32)
        a = np.linalg.lstsq(pair[:, sl].T, pooled[sl], rcond=None)[0]
        assert abs(a.sum() - 1) < 1e-5 and (a > 0).all()
    np.testing.assert_allclose(np.asarray(logits)[1], _full(weights, seq)[13],
                               atol=ATOL)


@pytest.mark.limit(240)
def test_a_reused_slot_reads_nothing_of_its_old_rows(engine, programs,
                                                     weights):
    """Slot 2 serves a long request (to position 197: every row of its
    window part and 49 summary rows written), is freed, and serves a
    short one (length 13): the new tenant's logits are the reference's,
    so none of the former rows is seen."""
    _, k, v = _serve(engine, programs, weights, (13, 45, 97),
                     (16, 48, 112), 100)
    assert float(jnp.abs(k[:, 2, 32 + 40]).max()) > 0   # old summaries lie there
    worst, _, _ = _serve(engine, programs, weights, (97, 45, 13),
                         (112, 48, 16), 60, k=k, v=v, seeds=(7, 8, 9))
    assert worst < ATOL


@pytest.mark.limit(120)
def test_engine_serves_the_reference_tokens(engine, weights):
    """The engine's own programs (``jit_serve_prefill_48``,
    ``jit_serve_decode``) sample head 0: greedy tokens equal the
    reference's argmax, the cache holds ``window + positions / chunk``
    rows a slot, and nothing retraces."""
    assert engine.kv_spec == KVCacheSpec(n_layer=2, slots=SLOTS,
                                         max_seq_len=256, width=64, rows=96)
    assert engine.stats()["decode_kernel"] == "dense"
    seq = _tokens(11, 256)
    want = _full(weights, seq).argmax(-1)
    got = [engine.prefill(1, pad_to_bucket(seq[:45], 48), 45, 48)]
    toks, at = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    for t in range(45, 75):
        toks[1], at[1] = seq[t], t
        got.append(int(engine.decode(toks, at)[1]))
    assert got == [int(x) for x in want[44:75]]
    assert sum(engine.stats()["retraces"].values()) == 0


AHEAD_LENGTHS = (13, 45, 30, 97, 27, 64)


@pytest.mark.limit(240)
@pytest.mark.parametrize("name", sorted(serve_ahead.SCENARIOS))
def test_decode_ahead_serves_what_the_blocking_order_serves(engine, name):
    """serve/worker.py ``_run_ahead`` on a state that is no row per
    position: the same traffic through the order that waits for every
    program and through the one that queues the next decode first.
    Equal tokens, and at every step equal rows where a live slot can
    read: the exact rows of its window so far and the summaries of its
    finished chunks (the row and the summary that the decode ahead has
    already written lie one past them).  Prompts of 27 and 30 cross
    into their second window while they decode, 64 starts on a window's
    edge, and a slot freed by a long request is taken by a short one."""
    window, chunk = CFG.window_size, CFG.chunk_size

    def live_rows(pos):
        return np.concatenate([np.arange(pos % window),
                               window + np.arange(pos // chunk)])

    prompts = [_tokens(20 + i, n) for i, n in enumerate(AHEAD_LENGTHS)]
    serve_ahead.check_equal_and_counted(engine, prompts, name, live_rows)
    assert sum(engine.stats()["retraces"].values()) == 0


@pytest.mark.limit(60)
def test_gpt2_cache_shape_is_untouched():
    """A row per position: ``[B, T, C]`` captures give ``max_seq_len``
    rows a slot, whatever T; only a model's own ``[B, 1, R, C]`` block
    sets the rows."""
    k = jax.ShapeDtypeStruct((1, 8, 64), jnp.bfloat16)
    spec = KVCacheSpec.from_capture([k, k], slots=4, max_seq_len=64)
    assert spec.shape == (2, 4, 64, 64) and spec.rows is None
    own = jax.ShapeDtypeStruct((1, 1, 96, 64), jnp.bfloat16)
    assert KVCacheSpec.from_capture([own], 4, 256).shape == (1, 4, 96, 64)


@pytest.mark.limit(60)
@pytest.mark.parametrize("what", ["paged", "kvship", "spec", "paged_kernel",
                                  "suffix", "engine"])
def test_refusals_name_the_reason(weights, monkeypatch, what):
    from ray_lightning_tpu.core import steps
    from ray_lightning_tpu.serve import Server
    from ray_lightning_tpu.serve.fleet.pages import PageConfig
    from ray_lightning_tpu.serve.spec import SpecConfig
    module = _Module(weights)
    if what == "paged":
        with pytest.raises(ValueError, match="not a prefix of this state"):
            Server(module, paged=PageConfig(enabled=True, page_size=8))
    elif what == "kvship":
        with pytest.raises(ValueError, match="not a prefix of this state"):
            Server(module, kvship=True)
    elif what == "spec":
        with pytest.raises(ValueError, match="no draft model"):
            Server(module, spec=SpecConfig(enabled=True, k=2))
    elif what == "engine":
        with pytest.raises(ValueError, match="own kind of cache rows"):
            ServeEngine(module, DataParallelStrategy(), buckets=(16,),
                        slots=2, max_seq_len=256,
                        paged=PageConfig(enabled=True, page_size=8)).setup()
    else:
        net = EvaByte(CFG)
        params = adapter.to_program_tree(weights)
        cache = jnp.zeros((2, 2, 96, 64), jnp.float32)
        ints = jnp.zeros((2,), jnp.int32)
        if what == "paged_kernel":
            monkeypatch.setenv("RLT_DECODE_IMPL", "paged")
            with pytest.raises(ValueError, match="page table maps positions"):
                net.apply({"params": params}, ints, ints, cache, cache,
                          method="decode")
        else:
            step = steps.build_suffix_step(module)
            with pytest.raises(ValueError, match="no one-slot suffix"):
                step(params, cache, cache, jnp.int32(0), jnp.int32(0),
                     jnp.int32(0))


@pytest.mark.limit(60)
def test_scheduler_counts_live_rows():
    """``Scheduler.stats()``: positions live and the rows their slots
    read, means over decode steps.  A row per position by default; the
    window-and-summary count when the module gives one."""
    from ray_lightning_tpu.serve.scheduler import Scheduler
    module = EvaByteLightningModule(CFG)
    assert module.live_cache_rows(0) == 1
    assert module.live_cache_rows(31) == 32
    assert module.live_cache_rows(32) == 1 + 8
    assert module.live_cache_rows(100) == 5 + 24
    for live_rows, want in ((None, 41.0), (module.live_cache_rows, 17.0)):
        sched = Scheduler((48,), 2, 256, live_rows=live_rows)
        sched.submit(_tokens(0, 40), max_new_tokens=4)
        plan = sched.plan()
        sched.apply(plan, {"prefill": {plan["prefills"][0]["slot"]: 5},
                           "decode": {}})
        assert sched.plan()["decode"]["positions"].max() == 40
        stats = sched.stats()
        assert stats["live_positions"] == 41.0
        assert stats["live_rows"] == want       # 40 % 32 + 1 + 8


# -- what the chip's compiler makes of it (no chip: a described v5e) -----------

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


def _computations(text):
    """A compiled program's text as ``{computation: [(name, result,
    operation, operands, called, line)]}``."""
    import re
    comps, at = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if m:
            at = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            at = None
        elif at is not None:
            m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) "
                         r"([\w\-]+)\((.*?)\)(?:, |$)", line)
            if m:
                at.append((m.group(1), m.group(2), m.group(3),
                           re.findall(r"%([\w.\-]+)", m.group(4)),
                           re.findall(r"(?:calls|to_apply)=%([\w.\-]+)",
                                      line), line))
    return comps


def _holds(comps, comp, kinds):
    """The operations of ``kinds`` in ``comp`` and in what it calls."""
    found = []
    for name, _, op, _, called, line in comps.get(comp, ()):
        if op in kinds:
            found.append(line.strip()[:200])
        for c in called:
            found += _holds(comps, c, kinds)
    return found


def _packed_rows_all_the_way(text, T):
    """ISSUE 39's two structural criteria on a compiled prefill.
    (1) No fused computation that holds an ``attn/o/dot_general``
    convolution holds a ``copy`` or a ``transpose``: the attention's
    output reaches the product as the packed rows it was written in.
    (2) From each operand of a flash call back to the q / k / v product
    it came from, nothing copies or transposes anything of a prompt's
    size.  (3) Nor does anything else of the program, the embedding's
    gather apart (the pooling of a chunk's rows reads packed rows too).
    Returns what offends."""
    import re
    comps = _computations(text)
    entry = next(c for c in comps if c.startswith("main"))
    movers = ("copy", "transpose")
    offenders, o_products = [], 0
    for comp, ops in comps.items():
        if any(op == "convolution" and "attn/o/dot_general" in line
               for _, _, op, _, _, line in ops):
            o_products += 1
            offenders += _holds(comps, comp, movers)
    assert o_products == 2, o_products            # one a layer
    by_name = {name: row for row in comps[entry] for name in [row[0]]}

    def size(result):
        return max((int(np.prod([int(d) for d in dims.split(",")]))
                    for dims in re.findall(r"\[([0-9,]+)\]", result)),
                   default=0)

    def back(name, seen):
        row = by_name.get(name)
        if row is None or name in seen or size(row[1]) < T * 512:
            return
        seen.add(name)
        _, _, op, operands, called, line = row
        if op in movers:
            offenders.append(line.strip()[:200])
        held = [h for c in called for h in _holds(comps, c, movers)]
        offenders.extend(held)
        if any(_holds(comps, c, ("convolution",)) for c in called):
            return                                # the product: the end
        for o in operands:
            back(o, seen)

    flash = [row for row in comps[entry] if row[2] == "custom-call"
             and "flash_fwd" in row[5]]
    assert len(flash) == 2, len(flash)
    for row in flash:
        for o in row[3]:
            back(o, set())
    # (3) and nowhere else either, the embedding's gather apart
    for ops in comps.values():
        for _, result, op, _, _, line in ops:
            if op in movers and size(result) >= T * 512 \
                    and "/embed/" not in line:
                offenders.append(line.strip()[:200])
    return offenders


@pytest.mark.limit(240)
@pytest.mark.parametrize("program", ["decode", "prefill_1024",
                                     "prefill_1024_one_chip"])
def test_serve_programs_compile_for_v5e_and_leave_the_cache_where_it_lies(
        monkeypatch, v5e, program):
    """Heads of the published size (128) at a width of 512, windows of
    256, 32 slots, bf16: Mosaic accepts the ``eva_decode`` kernel (and,
    where this process has one device as a serve worker has, the flash
    kernel under the prefill's windows), the decode program needs
    no scratch worth the name beside the donated cache, and no program
    copies, slices or transposes anything of a layer's size.
    ``prefill_1024_one_chip`` is the prefill as a serve worker compiles
    it (this process told it has one device): the prompt's kernels
    (``eva_rotary``, ``eva_pool``, ``flash_fwd``, ``eva_far``) lower, and a prompt's
    attention is packed ``[T, H*128]`` rows from the q / k / v products
    to the ``o`` product (:func:`_packed_rows_all_the_way`)."""
    import re
    from ray_lightning_tpu.core import steps
    from ray_lightning_tpu.ops import flash_decode
    cfg = EvaByteConfig(hidden_size=512, num_hidden_layers=2,
                        num_attention_heads=4, intermediate_size=1408,
                        window_size=256, chunk_size=16,
                        max_position_embeddings=4096)
    monkeypatch.setenv("RLT_DECODE_IMPL", "flash_decode")
    monkeypatch.setattr(flash_decode, "_use_interpret", lambda: False)
    if program.endswith("one_chip"):
        monkeypatch.setattr(jax, "device_count", lambda backend=None: 1)
    module = EvaByteLightningModule(cfg)
    module.setup_model()
    net = module.configure_decode_model()
    slots = 32

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    dummy = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0), dummy)["params"]
    _, cap = jax.eval_shape(
        lambda p, t: net.apply({"params": p}, t, True, mutable=["kv_cache"]),
        params, dummy)
    spec = KVCacheSpec.from_capture(
        [k for k, _ in steps.kv_layer_pairs(cap["kv_cache"])], slots, 4096)
    assert spec.shape == (2, slots, 256 + 4096 // 16, 512)
    params = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, jnp.bfloat16), params)
    cache = on_chip(spec.shape, jnp.bfloat16)
    if program == "decode":
        fn = steps.build_decode_step(module)
        args = (on_chip((slots,), jnp.int32),) * 2
    else:
        fn = steps.build_prefill_step(module, 1024)
        args = (on_chip((1, 1024), jnp.int32), on_chip((), jnp.int32),
                on_chip((), jnp.int32))
    # (the module's float32 tests run at "highest"; a bf16 kernel is
    # compiled as the chip runs it)
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
            params, cache, cache, *args).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (
        program == "decode" or jax.device_count() == 1)
    layer = slots * spec.shape[2] * 512
    if program == "decode":
        # less scratch than half of ONE layer of one cache array
        assert compiled.memory_analysis().temp_size_in_bytes < layer
    movers = [m.group(0) for m in re.finditer(
        r"= \(?(?:bf16|f32)\[([0-9,]+)\][^ ]* (copy|copy-start|slice|"
        r"dynamic-slice|concatenate|transpose)\(", text)
        if np.prod([int(d) for d in m.group(1).split(",")]) >= layer]
    assert not movers, movers[:5]
    if program.endswith("one_chip"):
        for kernel in ("eva_rotary", "eva_pool", "flash_fwd", "eva_far"):
            assert kernel in text, kernel
        offenders = _packed_rows_all_the_way(text, 1024)
        assert not offenders, offenders[:5]


@pytest.mark.limit(120)
@pytest.mark.parametrize("entry", ["view", "rows"])
@pytest.mark.parametrize("T,H,D", [(64, 2, 32), (64, 2, 128), (1024, 2, 128)],
                         ids=["folded", "packed", "tiled"])
def test_flash_attention_lse_in_both_of_the_kernels_layouts(T, H, D, entry):
    """The prefill merges the flash kernel's causal attention over a
    window with a dense one over the summaries by their log-sum-exps:
    ``flash_attention_lse`` returns ``[B, T, H]`` whichever layout the
    forward kernel keeps it in (the kernel under the interpreter), and to
    packed ``[B, T, H*D]`` rows it answers with packed rows and a column
    a head, ``[B, H, T, 1]``."""
    from ray_lightning_tpu.ops.flash_attention import flash_attention_lse
    rng = np.random.default_rng(T + D)
    q, k, v = (jnp.asarray(rng.normal(size=(2, T, H, D)), jnp.float32)
               for _ in range(3))
    if entry == "rows":
        o, lse = flash_attention_lse(
            *(a.reshape(2, T, H * D) for a in (q, k, v)), n_head=H,
            interpret=True)
        assert o.shape == (2, T, H * D) and lse.shape == (2, H, T, 1)
        o, lse = o.reshape(2, T, H, D), lse[..., 0].transpose(0, 2, 1)
    else:
        o, lse = flash_attention_lse(q, k, v, interpret=True)
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k) / np.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, :, None, :], s,
                  -jnp.inf)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(s, -1), atol=ATOL)
    np.testing.assert_allclose(
        o, jnp.einsum("bqhk,bkhd->bqhd", jax.nn.softmax(s, -1), v),
        atol=ATOL)


@pytest.mark.limit(120)
def test_the_reference_computes_in_every_precision_it_lists(weights):
    """``PRECISIONS`` has the control's fp8: the same mathematics on
    lower-precision operands moves the logits, more the lower it is, and
    nowhere by the size of a mistake."""
    toks = jnp.asarray(_tokens(3, 100))[None]
    exact = ref.forward(weights, toks, MODEL)
    gaps = {p: float(jnp.abs(ref.forward(weights, toks, MODEL, p)
                             - exact).max()) for p in ref.PRECISIONS}
    assert gaps["float32"] == 0.0
    assert 0 < gaps["bfloat16"] < gaps["fp8"] < 0.5
    assert np.isfinite(float(ref.loss(weights, toks, toks, MODEL, "fp8")))
