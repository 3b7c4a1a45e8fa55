"""MPMD plane (ray_lightning_tpu/mpmd/): per-stage programs over DCN.

The load-bearing assertions mirror the SPMD pipeline's discipline —
scheduling is an optimization, never semantics: a 2-stage MPMD run
must land on the same final params as the SPMD pipeline AND plain ddp
(documented 2e-2 bar), while each stage verifiably compiles ONLY its
own layers (program-argument and HLO-size checks — a chunk's program
cannot compute layers whose params it never receives).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax

from ray_lightning_tpu.mpmd import MpmdConfig, MpmdPipelineStrategy
from ray_lightning_tpu.mpmd import channel as chan
from ray_lightning_tpu.mpmd import partition as part
from ray_lightning_tpu.mpmd import schedule as sched

TOL = 2e-2   # the repo-wide documented parity bar (README)


# -- schedules --------------------------------------------------------------


@pytest.mark.parametrize("kind", ["gpipe", "1f1b"])
@pytest.mark.parametrize("stages,micro,virtual",
                         [(2, 4, 1), (4, 8, 1), (3, 6, 1), (2, 8, 2)])
def test_schedule_invariants(kind, stages, micro, virtual):
    s = sched.build_schedule(kind, stages, micro, virtual)
    sched.validate(s)   # F-before-B, dep order, 1f1b depth bound
    assert len(s.ranks) == stages
    assert sum(len(ops) for ops in s.ranks) == 2 * stages * virtual * micro


def test_plain_1f1b_bubble_ties_gpipe():
    """The analytic fact the schedule module documents: at one chunk
    per rank, 1F1B's fill/drain bubble EQUALS GPipe's — what v=1 1F1B
    buys is the bounded stash, not the bubble."""
    g = sched.build_schedule("gpipe", 2, 4, 1)
    f = sched.build_schedule("1f1b", 2, 4, 1)
    assert f.bubble_fraction == pytest.approx(g.bubble_fraction)
    assert f.makespan == pytest.approx(g.makespan)


def test_interleaved_1f1b_beats_gpipe_bubble():
    """The bubble win comes from interleaving: >= 4 microbatches with
    v=2 chunks per rank must sit strictly below GPipe."""
    for stages, micro in ((2, 4), (2, 8), (4, 8)):
        g = sched.build_schedule("gpipe", stages, micro, 1)
        f = sched.build_schedule("1f1b", stages, micro, 2)
        assert f.bubble_fraction < g.bubble_fraction, (stages, micro)


def test_1f1b_stash_depth_bounded():
    """GPipe legitimately stashes all M in-flight; 1F1B must never
    exceed stages x virtual (the memory property it exists for)."""
    s = sched.build_schedule("1f1b", 2, 16, 1)
    for ops in s.ranks:
        depth = peak = 0
        for op in ops:
            depth += 1 if op.kind == "F" else -1
            peak = max(peak, depth)
        assert peak <= 2


def test_simulate_replays_measured_times():
    s = sched.build_schedule("gpipe", 2, 4, 1)
    fast = sched.simulate(s, {(0, "F"): 0.1, (0, "B"): 0.2,
                              (1, "F"): 0.1, (1, "B"): 0.2})
    assert fast.makespan == pytest.approx(1.5)
    assert fast.bubble_fraction == pytest.approx(s.bubble_fraction)


def test_resolve_virtual_auto():
    assert sched.resolve_virtual("1f1b", 0, 2, 4) == 2
    assert sched.resolve_virtual("1f1b", 0, 1, 4) == 1   # tiny: 1 layer
    assert sched.resolve_virtual("gpipe", 0, 2, 4) == 1
    assert sched.resolve_virtual("1f1b", 3, 2, 4) == 3   # explicit wins


# -- channel ----------------------------------------------------------------
# (mailbox out-of-order + dead-peer-timeout live in
# tests/test_cluster_peer.py with the backend routing test — the peer
# channel is cluster-plane surface; here: the codec layer on top)


@pytest.mark.parametrize("mode,tol", [("none", 0.0), ("fp8", 0.08),
                                      ("int4", 0.16)])
def test_codec_round_trip(mode, tol):
    """fp32 passthrough exact; fp8/int4 within their codec error
    bounds on a [-1, 1] payload (comm plane bounds, activation path)."""
    rng = np.random.default_rng(0)
    x = rng.uniform(-1, 1, (4, 128)).astype(np.float32)
    codec = chan.ChannelCodec(mode, block_size=64)
    out = np.asarray(chan.ChannelCodec.decode(
        codec.encode(chan.ef_slot("fwd", 0), x)), np.float32)
    assert out.shape == x.shape
    assert float(np.max(np.abs(out - x))) <= tol


@pytest.mark.parametrize("mode", ["fp8", "int4"])
def test_codec_error_feedback_residual(mode):
    """EF contract on the activation path: the residual equals the
    signal-minus-decode error and is re-injected next encode — a
    repeated constant payload's RUNNING MEAN decode converges tighter
    than any single decode (the EQuARX accumulation property)."""
    rng = np.random.default_rng(1)
    x = rng.uniform(-1, 1, (2, 128)).astype(np.float32)
    codec = chan.ChannelCodec(mode, block_size=64)
    slot = chan.ef_slot("fwd", 0)
    outs = []
    for _ in range(8):
        outs.append(np.asarray(chan.ChannelCodec.decode(
            codec.encode(slot, x)), np.float32))
    single = float(np.max(np.abs(outs[0] - x)))
    mean_err = float(np.max(np.abs(np.mean(outs, axis=0) - x)))
    assert mean_err < 0.5 * single or mean_err < 1e-3
    # residual is real state and round-trips (the engine carries it in
    # the stage's optimizer state)
    state = codec.state_dict()
    assert state, "EF residual missing"
    codec2 = chan.ChannelCodec(mode, block_size=64)
    codec2.load_state_dict(state)
    np.testing.assert_array_equal(
        codec2.residuals[slot], codec.residuals[slot])


def test_codec_block_divisibility_raises():
    codec = chan.ChannelCodec("fp8", block_size=64)
    with pytest.raises(ValueError, match="block"):
        codec.encode(chan.ef_slot("fwd", 0),
                     np.zeros((2, 100), np.float32))


# -- partition --------------------------------------------------------------


def test_resolve_cuts_even_split_is_planner_choice():
    assert part.resolve_cuts(8, 2, None) == (4,)
    assert part.resolve_cuts(8, 4, None) == (2, 4, 6)


def test_resolve_cuts_validates():
    with pytest.raises(ValueError, match="cuts"):
        part.resolve_cuts(4, 2, (0,))
    with pytest.raises(ValueError, match="cuts"):
        part.resolve_cuts(4, 3, (2,))
    with pytest.raises(ValueError, match="stages"):
        part.enumerate_stage_cuts(2, 3)


def test_score_cuts_prefers_balance_and_fewer_codec_bytes():
    """Uniform layers: the balance term picks the even split; the DCN
    term is codec-aware (int4 wire < fp32 wire for the same cut)."""
    kw = dict(layer_bytes=1000, boundary_bytes=4096, n_micro=4)
    even = part.score_cuts((2,), 4, **kw)
    skew = part.score_cuts((1,), 4, **kw)
    assert even < skew
    fp32 = part.score_cuts((2,), 4, **kw)
    int4 = part.score_cuts((2,), 4, codec="int4", **kw)
    assert int4[0] < fp32[0]


def test_chunk_params_split_merge_round_trip(seed):
    from ray_lightning_tpu.models.pipeline_gpt import PipelinedGPT

    module = PipelinedGPT("tiny", dataset_size=8, batch_size=4)
    spec = module.configure_mpmd()
    x = np.zeros((4, 16), np.int32)
    full = module.init_params(jax.random.PRNGKey(0), (x, x))["params"]
    p = part.build_partition(spec, (1,))
    chunks = [p.chunk_params(full, c) for c in range(2)]
    # the head mirror of the tied wte exists on the last chunk
    assert "wte" in chunks[1] and "ln_f" in chunks[1]
    assert "wpe" in chunks[0] and "ln_f" not in chunks[0]
    merged = p.merge_params(chunks)
    for a, b in zip(jax.tree_util.tree_leaves(full),
                    jax.tree_util.tree_leaves(merged)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_interleaved_partition_requires_even_layout():
    from ray_lightning_tpu.models.pipeline_gpt import PipelinedGPT
    spec = PipelinedGPT("tiny", dataset_size=8,
                        batch_size=4).configure_mpmd()   # 2 layers
    with pytest.raises(ValueError, match="interleaved"):
        part.build_partition(spec, (1,), virtual=2)   # 2 layers / 4 chunks


# -- config / strategy wiring ----------------------------------------------


def test_config_env_round_trip(monkeypatch):
    src = MpmdConfig(stages=2, cuts=(1,), schedule="gpipe",
                     microbatches=8, codec="int4", block_size=32,
                     error_feedback=False, timeout_s=9.0)
    for k, v in src.worker_env().items():
        monkeypatch.setenv(k, v)
    assert MpmdConfig.resolve(None) == src


def test_strategy_string_resolution(monkeypatch):
    from ray_lightning_tpu.parallel.strategy import (resolve_strategy,
                                                     strategy_names)
    monkeypatch.setenv("RLT_MPMD_STAGES", "3")
    monkeypatch.setenv("RLT_MPMD_CUTS", "1,3")
    strat = resolve_strategy("mpmd")
    assert isinstance(strat, MpmdPipelineStrategy)
    assert strat.config.stages == 3 and strat.config.cuts == (1, 3)
    assert "mpmd" in strategy_names()
    # the declared activation exchange rides the _dcn suffix so the
    # planner/metrics planes score it at the DCN link
    assert "activation_exchange_dcn" in strat.step_collective_bytes(
        None, None)


def test_unsupported_trainer_knobs_raise(seed):
    from ray_lightning_tpu import Trainer
    from ray_lightning_tpu.models.pipeline_gpt import PipelinedGPT

    module = PipelinedGPT("tiny", dataset_size=16, batch_size=8)
    trainer = Trainer(max_steps=1, strategy="mpmd",
                      gradient_clip_val=1.0, enable_checkpointing=False,
                      num_sanity_val_steps=0, limit_val_batches=0, seed=0)
    with pytest.raises(ValueError, match="gradient_clip_val"):
        trainer.fit(module)
    trainer = Trainer(max_steps=1, strategy="mpmd",
                      enable_checkpointing=False, num_sanity_val_steps=0,
                      limit_val_batches=0, seed=0)
    with pytest.raises(ValueError, match="fit only"):
        trainer.validate(module)


# -- parity (the acceptance bar) -------------------------------------------


def _fit(strategy, max_steps=4, micro=None):
    from ray_lightning_tpu import Trainer
    from ray_lightning_tpu.models.pipeline_gpt import PipelinedGPT

    module = PipelinedGPT("tiny", n_microbatches=2, dataset_size=16,
                          batch_size=8)
    trainer = Trainer(max_epochs=2, max_steps=max_steps,
                      strategy=strategy, enable_checkpointing=False,
                      num_sanity_val_steps=0, limit_val_batches=0,
                      log_every_n_steps=1, seed=0)
    trainer.fit(module)
    return trainer


def _worst_diff(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return max(float(np.max(np.abs(np.asarray(x, np.float32)
                                   - np.asarray(y, np.float32))))
               for x, y in zip(la, lb))


@pytest.fixture(scope="module")
def parity_runs():
    """One fit per flavor, shared across the parity assertions (each
    fit pays tiny-GPT compiles).  ``jax_threefry_partitionable`` makes
    rng lowering sharding-invariant for the comparison window: without
    it the SPMD pipeline's stage-sharded INIT draws different (equally
    random) kernels than a single-device init — this jax build
    defaults it off — and no schedule could reconcile two different
    initializations (measured: 0.55 max kernel diff at step 0)."""
    from ray_lightning_tpu.parallel.pipeline import PipelineStrategy

    prev = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        ddp = _fit("ddp")
        spmd_pipe = _fit(PipelineStrategy(stages=2))
        mpmd = _fit(MpmdPipelineStrategy(MpmdConfig(
            stages=2, schedule="1f1b", microbatches=4)))
        yield {"ddp": ddp, "pipeline": spmd_pipe, "mpmd": mpmd}
    finally:
        jax.config.update("jax_threefry_partitionable", prev)


def test_mpmd_matches_spmd_pipeline_and_ddp(parity_runs):
    """THE acceptance bar: 2-stage MPMD tiny-GPT final params within
    the documented 2e-2 of the SPMD pipeline AND plain ddp."""
    pm = parity_runs["mpmd"].state.params
    for ref in ("pipeline", "ddp"):
        diff = _worst_diff(parity_runs[ref].state.params, pm)
        assert diff < TOL, f"mpmd vs {ref}: worst param diff {diff}"
    assert parity_runs["mpmd"].callback_metrics["loss"] == pytest.approx(
        parity_runs["ddp"].callback_metrics["loss"], rel=2e-2)


def test_each_stage_compiled_only_its_own_layers(parity_runs):
    """Per-stage-program evidence: every chunk's program arguments
    carry ONLY its layer slice (it cannot compute the others), the
    slices cover the model exactly once (+ the tied mirror), and each
    stage's compiled fwd+bwd HLO is smaller than the monolithic train
    step the SPMD pipeline compiles on every host."""
    trainer = parity_runs["mpmd"]
    report = trainer._mpmd_report
    module = trainer.lightning_module
    spec = module.configure_mpmd()

    full = module.init_params(
        jax.random.PRNGKey(0),
        (np.zeros((4, 64), np.int32),) * 2)["params"]
    n_full = sum(int(np.prod(v.shape)) for v in
                 jax.tree_util.tree_leaves(full))
    tied = sum(int(np.prod(np.asarray(full[k]).shape))
               for k in spec.tied_keys)
    per_stage = report["per_stage_param_elements"]
    assert len(per_stage) == 2
    assert all(n < n_full for n in per_stage), \
        "a stage program received the whole model"
    assert sum(per_stage) == n_full + tied   # exact cover + mirror

    # monolith: the full train step every SPMD-pipeline host compiles
    from ray_lightning_tpu.core.steps import (build_init_fn,
                                              build_train_step)
    tx = module.configure_optimizers()
    batch = jax.tree_util.tree_map(
        np.asarray, next(iter(module.train_dataloader())))
    abstract = jax.eval_shape(build_init_fn(module, tx),
                              jax.random.PRNGKey(0), batch)
    mono = jax.jit(build_train_step(module, tx)).lower(
        abstract, batch).compile()
    mono_bytes = len(mono.as_text())
    for stage_hlo in report["per_stage_hlo_bytes"]:
        assert sum(stage_hlo.values()) < mono_bytes, (
            f"stage programs {stage_hlo} not smaller than the "
            f"{mono_bytes}-byte monolith")


def test_mpmd_report_shape(parity_runs):
    report = parity_runs["mpmd"]._mpmd_report
    assert report["cuts"] == [1]
    assert report["schedule"] == "1f1b"
    assert len(report["per_stage_compile_seconds"]) == 2
    assert report["activation_bytes_per_step"] > 0
    assert set(report["bubble"]) == {"gpipe", "1f1b"}
    # EF/channel state rides the stage opt state in trainer.state
    assert set(parity_runs["mpmd"].state.opt_state) == {"chunk0",
                                                        "chunk1"}
    assert "channel_ef" in parity_runs["mpmd"].state.opt_state["chunk0"]


def test_mpmd_codec_on_activation_path_stays_close(seed):
    """fp8 codec + EF on the stage boundary: training stays within the
    documented parity bar of the codec-off run over a few steps, and
    the EF residual lands in the stage optimizer state."""
    base = _fit(MpmdPipelineStrategy(MpmdConfig(
        stages=2, schedule="gpipe", microbatches=4)))
    fp8 = _fit(MpmdPipelineStrategy(MpmdConfig(
        stages=2, schedule="gpipe", microbatches=4, codec="fp8")))
    diff = _worst_diff(base.state.params, fp8.state.params)
    assert diff < TOL, f"fp8 activation codec drift {diff}"
    ef = fp8.state.opt_state["chunk0"]["channel_ef"]
    assert ef, "error-feedback residual not carried in optimizer state"


def test_mpmd_actor_mode_matches_in_process(seed, monkeypatch):
    """The true MPMD shape: per-stage cluster actors exchanging
    activations over the worker↔worker peer channel must land on
    BIT-IDENTICAL params to the in-process engine (same programs, same
    schedule, same channel — only the transport differs)."""
    monkeypatch.setenv("RLT_BACKEND", "local")
    from ray_lightning_tpu.cluster.backend import set_backend
    set_backend(None)   # fresh backend under the env override
    try:
        t_in = _fit(MpmdPipelineStrategy(MpmdConfig(
            stages=2, schedule="gpipe", microbatches=4)), max_steps=2)
        t_act = _fit(MpmdPipelineStrategy(MpmdConfig(
            stages=2, schedule="gpipe", microbatches=4, actors=True,
            timeout_s=120)), max_steps=2)
        assert _worst_diff(t_in.state.params, t_act.state.params) == 0.0
        assert t_act._mpmd_report["mode"] == "actors"
        ranks = [s["rank"] for s in t_act._mpmd_report["setup"]]
        assert ranks == [0, 1]
    finally:
        set_backend(None)
