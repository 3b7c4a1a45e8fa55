"""Comm plane: blockwise quantization, compressed collectives, policy
resolution, error-feedback convergence, and the env-knob A/B — all on
the 8-virtual-device CPU mesh.

The HLO-level guarantees (compressed programs carry the low-precision
dtype and ~4x fewer reduction bytes; policy-off is byte-identical) live
in tests/test_collective_audit.py; this file covers numerics and
plumbing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_lightning_tpu.comm import (
    CommPolicy,
    CommState,
    blockwise_dequantize,
    blockwise_quantize,
    build_grad_sync,
    compressed_psum,
)
from ray_lightning_tpu.comm.quant import payload_bytes
from ray_lightning_tpu.models import BoringModel
from ray_lightning_tpu.parallel.strategy import resolve_strategy

from tests.utils import get_trainer

WORLD = 8


# ---------------------------------------------------------------------------
# quantizer
# ---------------------------------------------------------------------------


def test_quantize_roundtrip_error_bound(seed):
    """Per-element error of the blockwise int8 round trip is bounded by
    half a quantization step: max|block| / (2 * 127)."""
    rng = np.random.default_rng(0)
    x = (rng.standard_normal((16, 256)) *
         10.0 ** rng.integers(-3, 3, size=(16, 1))).astype(np.float32)
    for bs in (32, 64, 128):
        q, s = blockwise_quantize(jnp.asarray(x), bs)
        dq = np.asarray(blockwise_dequantize(q, s, bs))
        err = np.abs(dq - x).reshape(16, 256 // bs, bs)
        bound = np.abs(x).reshape(16, 256 // bs, bs).max(-1) / (2 * 127)
        assert (err <= bound[..., None] + 1e-7).all(), bs


def test_quantize_zero_blocks_exact():
    q, s = blockwise_quantize(jnp.zeros((4, 64)), 64)
    assert np.asarray(s).max() == 0
    assert np.asarray(blockwise_dequantize(q, s, 64)).max() == 0


def test_stochastic_rounding_unbiased():
    """floor(x/s + u) averages to x/s over draws (the deterministic
    round pins every draw to the same nearest level)."""
    x = np.full((1, 64), 0.3, np.float32)
    x[0, -1] = 1.0                    # block max -> scale 1/127; the
    x = jnp.asarray(x)                # 0.3s land between levels
    vals = []
    for i in range(300):
        qi, si = blockwise_quantize(x, 64, stochastic=True,
                                    rng=jax.random.PRNGKey(i))
        vals.append(float(np.asarray(
            blockwise_dequantize(qi, si, 64))[0, :-1].mean()))
    assert np.std(vals) > 0          # actually stochastic
    assert abs(np.mean(vals) - 0.3) < 0.002   # and unbiased


def test_payload_bytes_model():
    assert payload_bytes(1024, "int8", 64) == 1024 + 4 * 16
    assert payload_bytes(1024, "bf16") == 2048
    assert payload_bytes(1000, "int8", 64) == 1000 + 4 * 16  # ceil blocks
    assert payload_bytes(1024, "fp8", 64) == 1024 + 4 * 16   # 1 byte/elem
    assert payload_bytes(1024, "int4", 64) == 512 + 4 * 16   # 2 elem/byte
    assert payload_bytes(1001, "int4", 64) == 501 + 4 * 16   # ceil pack


# -- fp8 / int4 codecs ------------------------------------------------------


def test_fp8_roundtrip_error_bound(seed):
    """e4m3's per-element error is RELATIVE: half an ulp at 3 mantissa
    bits, <= max|block| / 16 after the block scaling maps the max to
    448."""
    from ray_lightning_tpu.comm.quant import compress_cast, decompress_cast
    rng = np.random.default_rng(3)
    x = (rng.standard_normal((16, 256)) *
         10.0 ** rng.integers(-3, 3, size=(16, 1))).astype(np.float32)
    q, s = compress_cast(jnp.asarray(x), "fp8")
    assert np.asarray(q).dtype == np.uint8      # 1-byte wire everywhere
    dq = np.asarray(decompress_cast(q, s, "fp8"))
    err = np.abs(dq - x).reshape(16, 4, 64)
    bound = np.abs(x).reshape(16, 4, 64).max(-1) / 16
    assert (err <= bound[..., None] + 1e-7).all()


def test_int4_roundtrip_error_bound_and_packing(seed):
    """int4: payload is HALF the element count (two nibbles per byte),
    error bounded by half a step: max|block| / (2 * 7)."""
    from ray_lightning_tpu.comm.quant import compress_cast, decompress_cast
    rng = np.random.default_rng(4)
    x = rng.standard_normal((16, 256)).astype(np.float32)
    q, s = compress_cast(jnp.asarray(x), "int4")
    assert np.asarray(q).shape == (16, 128)
    assert np.asarray(q).dtype == np.uint8
    dq = np.asarray(decompress_cast(q, s, "int4"))
    err = np.abs(dq - x).reshape(16, 4, 64)
    bound = np.abs(x).reshape(16, 4, 64).max(-1) / 14
    assert (err <= bound[..., None] + 1e-7).all()


@pytest.mark.parametrize("mode,tol", [("fp8", 0.002), ("int4", 0.004)])
def test_stochastic_rounding_unbiased_new_codecs(mode, tol):
    """The new codecs' SR averages to the true value over draws: int4
    via the same floor(x/s + u) as int8; fp8 via exact two-neighbor
    grid rounding (E[q] == x by construction)."""
    from ray_lightning_tpu.comm.quant import compress_cast, decompress_cast
    x = np.full((1, 64), 0.3, np.float32)
    x[0, -1] = 1.0
    x = jnp.asarray(x)
    vals = []
    for i in range(300):
        qi, si = compress_cast(x, mode, stochastic=True,
                               rng=jax.random.PRNGKey(i))
        vals.append(float(np.asarray(
            decompress_cast(qi, si, mode))[0, :-1].mean()))
    assert np.std(vals) > 0
    assert abs(np.mean(vals) - 0.3) < tol, np.mean(vals)


# ---------------------------------------------------------------------------
# compressed collectives (numerics under shard_map)
# ---------------------------------------------------------------------------


def _mesh():
    return resolve_strategy("ddp").build_mesh()


PSUM_TOL = {"int8": 0.02, "bf16": 0.01, "fp8": 0.1, "int4": 0.12}


@pytest.mark.parametrize("mode", ["int8", "bf16", "fp8", "int4"])
def test_compressed_psum_matches_mean(mode, seed):
    mesh = _mesh()
    rng = np.random.default_rng(1)
    x = rng.standard_normal((WORLD, 501)).astype(np.float32)

    def body(xl):
        return compressed_psum(xl[0], "data", WORLD, mode=mode,
                               mean=True)[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"), check_vma=False)
    xg = jax.device_put(x, NamedSharding(mesh, P("data")))
    out = np.asarray(jax.jit(fn)(xg))
    ref = x.mean(0)
    # every rank must hold the SAME reduced value (replicated result)
    assert np.allclose(out, out[0][None], atol=0)
    assert np.abs(out[0] - ref).max() <= PSUM_TOL[mode] * np.abs(x).max()


@pytest.mark.parametrize("mode", ["int8", "fp8", "int4"])
def test_hierarchical_psum_matches_mean(mode, seed):
    """Two-level (ici4 x dcn2) mean over the 8-way axis: replicated
    result within the flat path's tolerance (only one quantization —
    of the ICI-summed shard — happens at all), and the level-2 error
    term is per-rank chunk-local (each rank's residual support is its
    own 1/ici slice)."""
    from ray_lightning_tpu.comm import hierarchical_psum

    mesh = _mesh()
    rng = np.random.default_rng(5)
    x = rng.standard_normal((WORLD, 501)).astype(np.float32)

    def body(xl):
        res, err = hierarchical_psum(xl[0], "data", 4, 2, mode=mode,
                                     mean=True, with_error=True)
        return res[None], err[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                       out_specs=(P("data"), P("data")),
                       check_vma=False)
    xg = jax.device_put(x, NamedSharding(mesh, P("data")))
    out, err = jax.jit(fn)(xg)
    out, err = np.asarray(out), np.asarray(err)
    ref = x.mean(0)
    assert np.allclose(out, out[0][None], atol=0)
    assert np.abs(out[0] - ref).max() <= PSUM_TOL[mode] * np.abs(x).max()
    # error support: every rank carries SOME error, only on its chunk
    # (ranks sharing a host quantize disjoint slices of the host sum)
    assert (np.abs(err).max(axis=1) > 0).all()
    chunk = 128     # ceil(501 / 4) rounded up to the 64-elem block
    for r in range(WORLD):
        local = r % 4
        outside = np.concatenate(
            [err[r, :local * chunk], err[r, (local + 1) * chunk:]])
        assert outside.size and np.abs(outside).max() == 0, r


def test_compressed_psum_error_feedback_term(seed):
    """with_error returns exactly x − dq(q(x)) — the residual error
    feedback re-injects next step."""
    mesh = _mesh()
    rng = np.random.default_rng(2)
    x = rng.standard_normal((WORLD, 130)).astype(np.float32)

    def body(xl):
        res, err = compressed_psum(xl[0], "data", WORLD, mode="int8",
                                   mean=True, with_error=True)
        return res[None], err[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                       out_specs=(P("data"), P("data")),
                       check_vma=False)
    xg = jax.device_put(x, NamedSharding(mesh, P("data")))
    _, err = jax.jit(fn)(xg)
    err = np.asarray(err)
    # the error is per-rank local and bounded by half a quant step
    step = np.abs(x).max() / 127
    assert np.abs(err).max() <= step / 2 + 1e-6
    assert np.abs(err).max() > 0      # int8 on gaussians is never exact


# ---------------------------------------------------------------------------
# policy resolution
# ---------------------------------------------------------------------------


def test_policy_resolution_per_strategy():
    """The per-strategy decision table: replicated-param data-parallel
    strategies compress, param-sharded ones decline, off is inert."""
    pol = CommPolicy(compress="int8", axes=("data",))
    for name, expect in (("ddp", True), ("zero1", True),
                         ("fsdp", False), ("spmd", False)):
        strat = resolve_strategy(name)
        mesh = strat.build_mesh()
        sync = build_grad_sync(strat, mesh, pol)
        assert (sync is not None) == expect, name
        assert build_grad_sync(strat, mesh, CommPolicy()) is None, name
    from ray_lightning_tpu.parallel.pipeline import PipelineStrategy
    ps = PipelineStrategy(stages=2)
    assert build_grad_sync(ps, ps.build_mesh(), pol) is None


def test_policy_axis_resolution():
    strat = resolve_strategy("ddp")
    mesh = strat.build_mesh()
    # explicit axes: compressed regardless of process count
    pol = CommPolicy(compress="int8", axes=("data",))
    assert pol.resolved_axes(mesh, strat.data_axis_names) == ("data",)
    # unknown axes fall away
    pol = CommPolicy(compress="int8", axes=("dcn",))
    assert pol.resolved_axes(mesh, strat.data_axis_names) == ()
    # auto on a single process: all-ICI, nothing compresses (DCN default)
    pol = CommPolicy(compress="int8")
    assert pol.resolved_axes(mesh, strat.data_axis_names) == ()
    assert build_grad_sync(strat, mesh, pol) is None
    # single-device data axis cannot compress
    one = strat.build_mesh(devices=jax.devices()[:1])
    pol = CommPolicy(compress="int8", axes=("data",))
    assert build_grad_sync(strat, one, pol) is None


def test_policy_validation_and_resolve():
    with pytest.raises(ValueError):
        CommPolicy(compress="fp4")          # fp8/int4 ARE valid now
    with pytest.raises(ValueError):
        CommPolicy(param_gather="f64")
    with pytest.raises(ValueError):
        CommPolicy(compress="int4", block_size=33)   # odd: can't pack
    with pytest.raises(ValueError):
        CommPolicy(hierarchy=1)             # 0 / -1 / >= 2 only
    with pytest.raises(ValueError):
        CommPolicy(bucket_bytes=-1)
    assert CommPolicy.resolve("int8").compress == "int8"
    assert CommPolicy.resolve("fp8").compress == "fp8"
    assert CommPolicy.resolve({"compress": "int4"}).compress == "int4"
    assert not CommPolicy.resolve(None).enabled   # env-less default: off


def test_env_knobs_roundtrip(monkeypatch):
    from ray_lightning_tpu.comm.policy import HIER_AUTO
    src = CommPolicy(compress="fp8", axes=("data",), block_size=32,
                     stochastic_rounding=True, error_feedback=False,
                     param_gather="int8", hierarchy=4,
                     bucket_bytes=1 << 20, barrier_sync=True)
    for k, v in src.worker_env().items():
        monkeypatch.setenv(k, v)
    assert CommPolicy.resolve(None) == src
    monkeypatch.setenv("RLT_COMM_HIER", "auto")
    assert CommPolicy.resolve(None).hierarchy == HIER_AUTO


def test_hierarchy_resolution():
    """(ici, dcn) resolution: explicit sizes split when they divide,
    degenerate/invalid splits fall back to flat, auto follows the local
    device count (== world on the single-process CPU mesh: flat)."""
    from ray_lightning_tpu.comm.policy import HIER_AUTO
    pol = CommPolicy(compress="int8", hierarchy=4)
    assert pol.resolved_hierarchy(8) == (4, 2)
    assert pol.resolved_hierarchy(4) == (1, 4)    # 4 >= world: flat
    assert pol.resolved_hierarchy(6) == (1, 6)    # 6 % 4: flat
    flat = CommPolicy(compress="int8")
    assert flat.resolved_hierarchy(8) == (1, 8)
    auto = CommPolicy(compress="int8", hierarchy=HIER_AUTO)
    assert auto.resolved_hierarchy(WORLD) == (1, WORLD)


# ---------------------------------------------------------------------------
# end-to-end training (the documented parity tolerances)
# ---------------------------------------------------------------------------


def _fit_boring(tmp_path, tag, steps=20, comm_policy=None, **kw):
    trainer = get_trainer(str(tmp_path / tag), checkpoint=False,
                          max_epochs=100, limit_train_batches=10**6,
                          limit_val_batches=0, max_steps=steps, seed=0,
                          comm_policy=comm_policy, **kw)
    trainer.fit(BoringModel(lr=0.05, batch_size=16))
    return trainer, float(trainer.callback_metrics["loss"])


def test_error_feedback_convergence(tmp_path, seed):
    """Quantized DDP with error feedback matches the fp32 final loss on
    the boring model within the documented 5% tolerance after 20 steps
    (README "Compressed collectives")."""
    t_fp, loss_fp = _fit_boring(tmp_path, "fp32")
    assert t_fp._grad_sync is None
    pol = CommPolicy(compress="int8", axes=("data",))
    t_q, loss_q = _fit_boring(tmp_path, "int8", comm_policy=pol)
    assert t_q._grad_sync is not None
    assert isinstance(t_q.state.opt_state, CommState)
    # residual: [world, *param] leaves, sharded on data (dim 0)
    for leaf in jax.tree_util.tree_leaves(t_q.state.opt_state.residual):
        assert leaf.shape[0] == WORLD
        assert leaf.sharding.spec[0] == "data"
        assert np.abs(np.asarray(jax.device_get(leaf))).max() > 0
    assert abs(loss_q - loss_fp) <= 0.05 * max(loss_fp, 1e-6), (
        loss_q, loss_fp)


@pytest.mark.parametrize("mode", ["fp8", "int4"])
def test_new_codec_error_feedback_convergence(tmp_path, seed, mode):
    """fp8/int4 with error feedback land within the same documented 5%
    of the fp32 final loss as int8 (coarser grids, same EF guarantee:
    quantization error is a one-step delay, not a bias)."""
    _, loss_fp = _fit_boring(tmp_path, f"fp32_{mode}")
    pol = CommPolicy(compress=mode, axes=("data",))
    t_q, loss_q = _fit_boring(tmp_path, mode, comm_policy=pol)
    assert t_q._grad_sync is not None
    assert isinstance(t_q.state.opt_state, CommState)
    assert abs(loss_q - loss_fp) <= 0.05 * max(loss_fp, 1e-6), (
        loss_q, loss_fp)


def test_hierarchical_error_feedback_convergence(tmp_path, seed):
    """Two-level int8 (ici4 x dcn2 on the virtual mesh) trains within
    the 5% envelope; the residual keeps its [world, ...] layout (each
    rank's slice now supports only its 1/ici chunk of the DCN-stage
    error)."""
    _, loss_fp = _fit_boring(tmp_path, "fp32h")
    pol = CommPolicy(compress="int8", axes=("data",), hierarchy=4)
    t_q, loss_q = _fit_boring(tmp_path, "hier", comm_policy=pol)
    assert t_q._grad_sync is not None and t_q._grad_sync.hierarchical
    assert t_q._grad_sync.describe().endswith("/hier4x2")
    for leaf in jax.tree_util.tree_leaves(t_q.state.opt_state.residual):
        assert leaf.shape[0] == WORLD
    assert abs(loss_q - loss_fp) <= 0.05 * max(loss_fp, 1e-6), (
        loss_q, loss_fp)


def test_bucketed_sync_convergence_and_partition(tmp_path, seed):
    """Bucketed overlap scheduling: the greedy partition covers every
    leaf exactly once in order, and a bucketed fit (tiny target so the
    boring model actually splits) matches fp32 within the envelope —
    including the barrier_sync A/B variant, whose program differs only
    by the optimization_barrier."""
    from ray_lightning_tpu.comm import partition_buckets

    assert partition_buckets([100, 200, 4000, 50, 50], 300) \
        == [[0, 1], [2], [3, 4]]
    assert partition_buckets([10, 10], 0) == [[0], [1]]
    assert partition_buckets([1 << 30], 1024) == [[0]]

    _, loss_fp = _fit_boring(tmp_path, "fp32bkt")
    pol = CommPolicy(compress="int8", axes=("data",), bucket_bytes=2048)
    t_q, loss_q = _fit_boring(tmp_path, "bkt", comm_policy=pol)
    assert t_q._grad_sync is not None
    assert abs(loss_q - loss_fp) <= 0.05 * max(loss_fp, 1e-6)
    polb = CommPolicy(compress="int8", axes=("data",), bucket_bytes=2048,
                      barrier_sync=True)
    _, loss_b = _fit_boring(tmp_path, "bkt_barrier", comm_policy=polb)
    assert abs(loss_b - loss_fp) <= 0.05 * max(loss_fp, 1e-6)


def test_hierarchical_step_collective_bytes_split_by_link():
    """ddp/zero1 declare the hierarchical sync per link tier: the DCN
    ops carry the compressed 1/ici shard twice (rs + ag), the ICI ops
    the fp32 levels; declared_dcn_bytes extracts the slow-tier share
    for rlt_comm_dcn_bytes_total."""
    from ray_lightning_tpu.comm.audit import declared_dcn_bytes

    mesh = _mesh()
    pol = CommPolicy(compress="int8", axes=("data",), hierarchy=4)

    class _Leaf:
        shape = (1024,)
        dtype = np.dtype(np.float32)

    class _State:
        params = {"w": _Leaf()}

    ddp = resolve_strategy("ddp")
    sync = build_grad_sync(ddp, mesh, pol)
    d = ddp.step_collective_bytes(mesh, _State(), comm=sync)
    shard = 1024 // 4
    assert d["grad_all_reduce_dcn"] == 2 * payload_bytes(shard, "int8", 64)
    assert d["grad_all_reduce_ici"] == 4 * 1024 + 4 * 1024
    assert declared_dcn_bytes(d, multi_process=True) \
        == d["grad_all_reduce_dcn"]
    # flat declarations on a multi-process run: everything crosses DCN
    flat = ddp.step_collective_bytes(
        mesh, _State(),
        comm=build_grad_sync(ddp, mesh,
                             CommPolicy(compress="int8", axes=("data",))))
    assert declared_dcn_bytes(flat, True) == sum(flat.values())
    assert declared_dcn_bytes(flat, False) == 0
    z1 = resolve_strategy("zero1")
    z = z1.step_collective_bytes(mesh, _State(),
                                 comm=build_grad_sync(z1, mesh, pol))
    assert z["grad_sync_dcn"] == d["grad_all_reduce_dcn"]
    assert z["param_all_gather"] == 4096
    # the hierarchy's DCN declaration undercuts the flat one >= 2x
    assert 2 * d["grad_all_reduce_dcn"] <= sum(flat.values())


def test_bf16_mode_tracks_fp32_tighter(tmp_path, seed):
    _, loss_fp = _fit_boring(tmp_path, "fp32b")
    _, loss_bf = _fit_boring(
        tmp_path, "bf16", comm_policy=CommPolicy(compress="bf16",
                                                 axes=("data",)))
    assert abs(loss_bf - loss_fp) <= 0.01 * max(loss_fp, 1e-6)


def test_zero1_compressed_with_param_gather(tmp_path, seed):
    _, loss_fp = _fit_boring(tmp_path, "z1fp", strategy="zero1")
    pol = CommPolicy(compress="int8", axes=("data",), param_gather="bf16")
    _, loss_q = _fit_boring(tmp_path, "z1q", strategy="zero1",
                            comm_policy=pol)
    assert abs(loss_q - loss_fp) <= 0.05 * max(loss_fp, 1e-6)


def test_env_knob_ab(tmp_path, seed, monkeypatch):
    """RLT_COMM=int8 + RLT_COMM_AXES=data activates compression with no
    Trainer argument; unsetting it restores the fp32 path — same seed,
    both finite, within the documented tolerance of each other."""
    monkeypatch.setenv("RLT_COMM", "int8")
    monkeypatch.setenv("RLT_COMM_AXES", "data")
    t_on, loss_on = _fit_boring(tmp_path, "env_on", steps=8)
    assert t_on._grad_sync is not None
    assert t_on.comm_policy.compress == "int8"
    monkeypatch.delenv("RLT_COMM")
    monkeypatch.delenv("RLT_COMM_AXES")
    t_off, loss_off = _fit_boring(tmp_path, "env_off", steps=8)
    assert t_off._grad_sync is None
    assert np.isfinite(loss_on) and np.isfinite(loss_off)
    assert abs(loss_on - loss_off) <= 0.05 * max(loss_off, 1e-6)


def test_comm_metrics_report_compressed_bytes(tmp_path, seed):
    """step_collective_bytes shrinks to the compressed wire payload
    under an active policy — the series the metrics plane charges."""
    strat = resolve_strategy("zero1")
    mesh = strat.build_mesh()
    pol = CommPolicy(compress="int8", axes=("data",))
    sync = build_grad_sync(strat, mesh, pol)

    class _Leaf:
        shape = (1024,)
        dtype = np.dtype(np.float32)

    class _State:
        params = {"w": _Leaf()}

    fp = strat.step_collective_bytes(mesh, _State())
    q = strat.step_collective_bytes(mesh, _State(), comm=sync)
    assert fp["grad_reduce_scatter"] == 4096
    assert q["grad_reduce_scatter"] == payload_bytes(1024, "int8", 64)
    assert q["grad_all_gather"] == payload_bytes(1024, "int8", 64)
    assert q["param_all_gather"] == 4096       # param_gather="none"
    pol2 = CommPolicy(compress="int8", axes=("data",),
                      param_gather="bf16")
    sync2 = build_grad_sync(strat, mesh, pol2)
    q2 = strat.step_collective_bytes(mesh, _State(), comm=sync2)
    assert q2["param_all_gather"] == 2048
    # ddp: one all-reduce key at the rs+ag compressed payload
    ddp = resolve_strategy("ddp")
    qd = ddp.step_collective_bytes(mesh, _State(), comm=sync)
    assert qd["grad_all_reduce"] == 2 * payload_bytes(1024, "int8", 64)


def test_accumulation_composes_with_comm(tmp_path, seed):
    """k-microbatch accumulation inside the mapped region: one sync per
    optimizer step, same convergence envelope."""
    _, loss_fp = _fit_boring(tmp_path, "acc_fp", steps=8,
                             accumulate_grad_batches=2)
    _, loss_q = _fit_boring(
        tmp_path, "acc_q", steps=8, accumulate_grad_batches=2,
        comm_policy=CommPolicy(compress="int8", axes=("data",)))
    assert abs(loss_q - loss_fp) <= 0.05 * max(loss_fp, 1e-6)


def test_checkpoint_roundtrip_carries_residual(tmp_path, seed):
    """The CommState residual rides the msgpack checkpoint and restores
    into the sharded layout (resume continues, not restarts)."""
    pol = CommPolicy(compress="int8", axes=("data",))
    trainer = get_trainer(str(tmp_path / "save"), max_epochs=1,
                          limit_train_batches=4, limit_val_batches=0,
                          seed=0, comm_policy=pol)
    trainer.fit(BoringModel(lr=0.05, batch_size=16))
    ck = trainer.checkpoint_callback.best_model_path or \
        trainer.checkpoint_callback.last_model_path
    assert ck
    res_before = jax.device_get(trainer.state.opt_state.residual)
    t2 = get_trainer(str(tmp_path / "resume"), checkpoint=False,
                     max_epochs=2, limit_train_batches=4,
                     limit_val_batches=0, seed=0, comm_policy=pol,
                     resume_from_checkpoint=ck)
    t2.fit(BoringModel(lr=0.05, batch_size=16))
    assert t2.global_step > trainer.global_step
    res_after = jax.device_get(t2.state.opt_state.residual)
    for a, b in zip(jax.tree_util.tree_leaves(res_before),
                    jax.tree_util.tree_leaves(res_after)):
        assert np.asarray(a).shape == np.asarray(b).shape


def test_checkpoint_roundtrip_across_codec_change(tmp_path, seed):
    """A codec change between save and resume BRIDGES: every codec
    keeps the residual's [world, *param] layout, and an EF residual is
    codec-agnostic pending correction (x − dq(q(x)) in gradient units),
    so an int8 save resumes under fp8 — or under a hierarchical policy
    — carrying the saved residual forward (mirroring the PR-7
    comm-on↔off bridge rules: same-shape keeps, structure change drops
    with a warning, anything else raises naming the leaf)."""
    pol8 = CommPolicy(compress="int8", axes=("data",))
    trainer = get_trainer(str(tmp_path / "save"), max_epochs=1,
                          limit_train_batches=4, limit_val_batches=0,
                          seed=0, comm_policy=pol8)
    trainer.fit(BoringModel(lr=0.05, batch_size=16))
    ck = trainer.checkpoint_callback.best_model_path or \
        trainer.checkpoint_callback.last_model_path
    assert ck
    res_saved = jax.device_get(trainer.state.opt_state.residual)
    for tag, pol in (
            ("fp8", CommPolicy(compress="fp8", axes=("data",))),
            ("hier", CommPolicy(compress="int8", axes=("data",),
                                hierarchy=4))):
        t2 = get_trainer(str(tmp_path / f"resume_{tag}"),
                         checkpoint=False, max_epochs=2,
                         limit_train_batches=4, limit_val_batches=0,
                         seed=0, comm_policy=pol,
                         resume_from_checkpoint=ck)
        t2.fit(BoringModel(lr=0.05, batch_size=16))
        assert t2.global_step > trainer.global_step
        assert isinstance(t2.state.opt_state, CommState)
        for a, b in zip(
                jax.tree_util.tree_leaves(res_saved),
                jax.tree_util.tree_leaves(
                    jax.device_get(t2.state.opt_state.residual))):
            assert np.asarray(a).shape == np.asarray(b).shape
        assert np.isfinite(float(t2.callback_metrics["loss"]))


def test_stochastic_rounding_trains(tmp_path, seed):
    pol = CommPolicy(compress="int8", axes=("data",),
                     stochastic_rounding=True)
    _, loss = _fit_boring(tmp_path, "sr", steps=8, comm_policy=pol)
    assert np.isfinite(loss)
