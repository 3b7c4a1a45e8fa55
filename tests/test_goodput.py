"""Goodput plane (telemetry/goodput.py): the full-run wall-clock
partition, measured MFU, and the ledger gates over them.

Three tiers:

- host-only units: the :class:`GoodputLedger` partition identity
  (``sum(buckets) == run_wall`` exact), overshoot scaling, the anatomy
  sub-split, replay re-attribution, fleet aggregation and the env-knob
  round-trip;
- local-fit integration: the default ``flops_per_step`` jaxpr pricing
  against a hand-computed GPT matmul count (within 5%);
- distributed: the identity on a REAL 2-worker fit's per-rank and
  fleet docs, and the recovery-badput difference the elastic plane
  exists for — parity recovery shows ~0 ``replay`` seconds where the
  same fault with redundancy off shows a measured replay cost.
"""

import sys
import time

import cloudpickle
import pytest

from ray_lightning_tpu import Callback, Trainer
from ray_lightning_tpu.models import BoringModel
from ray_lightning_tpu.telemetry.goodput import (
    FIT_BUCKETS,
    SERVE_BUCKETS,
    GoodputLedger,
    aggregate,
    check_identity,
    measured_mfu,
    reattribute_replay,
)

from tests.utils import cpu_plugin

# chaos fixtures run inside worker subprocesses which cannot import
# this test module by name; ship the classes by value (the
# test_failure.py seam)
cloudpickle.register_pickle_by_value(sys.modules[__name__])


# -- ledger units --------------------------------------------------------

def test_ledger_partition_identity_and_mfu():
    """Every fed second lands in exactly one bucket, the residual in
    ``other``, and the identity closes exactly against the wall."""
    t = [0.0]
    led = GoodputLedger("fit", device_tflops=1e-3, devices=2,
                        clock=lambda: t[0]).start()
    led.add("compile", 2.0)
    led.add("init", 0.5)
    for _ in range(10):
        led.note_step(0.3)
    led.add("data_wait", 0.2)
    led.set_flops_per_step(6e7)
    t[0] = 8.0
    doc = led.finalize()
    assert check_identity(doc)
    assert set(doc["buckets"]) == set(FIT_BUCKETS)
    assert doc["buckets"]["step"] == pytest.approx(3.0)
    assert doc["buckets"]["other"] == pytest.approx(2.3)
    assert doc["steps"] == 10
    assert doc["step_wall_mean_s"] == pytest.approx(0.3)
    assert doc["goodput_fraction"] == pytest.approx(3.0 / 8.0)
    # 6e7 FLOP / 0.3 s / (2 devices x 1e-3 TFLOPs peak) = 0.1
    assert doc["mfu"] == pytest.approx(0.1)
    assert measured_mfu(None, 0.3, 1e-3) is None   # never fabricated


def test_ledger_overshoot_scales_partition_closed():
    """Instrumented seconds exceeding the measured wall (overlapping
    accumulators) scale down proportionally — the identity still
    closes, nothing goes negative."""
    led = GoodputLedger("serve")
    led.note_step(4.0)          # decode
    led.add("prefill", 2.0)
    doc = led.finalize(3.0)
    assert check_identity(doc)
    assert doc["buckets"]["decode"] == pytest.approx(2.0)
    assert doc["buckets"]["prefill"] == pytest.approx(1.0)
    assert doc["goodput_fraction"] == pytest.approx(2.0 / 3.0)


def test_ledger_rejects_foreign_buckets_and_kinds():
    with pytest.raises(ValueError):
        GoodputLedger("train")
    led = GoodputLedger("fit")
    with pytest.raises(KeyError):
        led.add("decode", 1.0)          # serve bucket on a fit ledger
    assert "replay" not in SERVE_BUCKETS and "decode" not in FIT_BUCKETS


def test_useful_split_rides_anatomy_outside_identity():
    """An anatomy window sub-splits the useful bucket (compute /
    exposed / host / bubble) without entering the top-level identity."""
    led = GoodputLedger("fit")
    for _ in range(4):
        led.note_step(0.5)
    led.set_anatomy({"wall_s": 1.0, "compute_s": 0.6, "exposed_s": 0.3,
                     "host_s": 0.1, "bubble_fraction": 0.25})
    doc = led.finalize(4.0)
    assert check_identity(doc)
    split = doc["useful_split"]
    assert split["source"] == "anatomy"
    useful = doc["buckets"]["step"]
    assert split["bubble_s"] == pytest.approx(useful * 0.25)
    assert split["exposed_comm_s"] == pytest.approx(useful * 0.3)
    # bubble is carved out of compute, and the sub-split re-describes
    # ONE bucket: its parts never count toward the wall identity
    assert split["compute_s"] == pytest.approx(useful * 0.6 - useful * 0.25)
    assert sum(doc["buckets"].values()) == pytest.approx(4.0)


def test_reattribute_replay_is_identity_preserving():
    led = GoodputLedger("fit")
    for _ in range(10):
        led.note_step(0.5)
    doc = led.finalize(6.0)
    out = reattribute_replay(doc, 4)
    assert check_identity(out)
    assert out["run_wall_s"] == doc["run_wall_s"]
    assert out["buckets"]["replay"] == pytest.approx(2.0)
    assert out["buckets"]["step"] == pytest.approx(3.0)
    assert out["replayed_steps"] == 4
    assert out["goodput_fraction"] < doc["goodput_fraction"]
    # clamp: cannot move more than the step bucket holds
    clamped = reattribute_replay(doc, 100)
    assert check_identity(clamped)
    assert clamped["buckets"]["step"] >= 0
    # no-op path
    assert reattribute_replay(doc, 0)["buckets"].get("replay", 0.0) == 0.0


def test_aggregate_sums_ranks_and_extra_buckets_extend_wall():
    docs = []
    for _ in range(2):
        led = GoodputLedger("fit", device_tflops=1.0, devices=1)
        led.add("compile", 1.0)
        for _ in range(5):
            led.note_step(0.4)
        led.set_flops_per_step(1e9)
        docs.append(led.finalize(4.0))
    fleet = aggregate(docs, extra_buckets={"recovery": 1.5})
    assert check_identity(fleet)
    assert fleet["ranks"] == 2 and fleet["steps"] == 10
    # extra buckets extend BOTH the wall and their bucket
    assert fleet["run_wall_s"] == pytest.approx(9.5)
    assert fleet["buckets"]["recovery"] == pytest.approx(1.5)
    assert fleet["buckets"]["step"] == pytest.approx(4.0)
    assert fleet["mfu"] == pytest.approx(1e9 / 0.4 / 1e12, rel=1e-6)
    assert aggregate([]) == {}


def test_goodput_env_knobs_roundtrip_worker_env(monkeypatch):
    """RLT_GOODPUT* resolved on the driver ship through worker_env()
    and resolve identically on a worker (satellite: env round-trip)."""
    from ray_lightning_tpu.telemetry import TelemetryConfig
    from ray_lightning_tpu.telemetry import goodput as goodput_mod
    monkeypatch.delenv(goodput_mod.GOODPUT_ENV, raising=False)
    monkeypatch.delenv(goodput_mod.GOODPUT_TFLOPS_ENV, raising=False)
    # defaults: armed, no tflops -> nothing shipped (workers inherit
    # the same defaults)
    assert TelemetryConfig().worker_env() == {}
    assert TelemetryConfig().resolved_goodput() is True
    env = TelemetryConfig(goodput=False, goodput_tflops=275.0).worker_env()
    assert env[goodput_mod.GOODPUT_ENV] == "0"
    assert env[goodput_mod.GOODPUT_TFLOPS_ENV] == "275.0"
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    # the worker side sees only the env, no explicit config
    cfg = TelemetryConfig()
    assert cfg.resolved_goodput() is False
    assert cfg.resolved_goodput_tflops() == 275.0


# -- default flops_per_step pricing vs hand count ------------------------

@pytest.mark.slow
def test_default_flops_pricing_matches_hand_computed_gpt(tmp_path, seed):
    """The trainer's default MFU numerator — dot-counting the built
    train-step jaxpr — must land within 5% of the hand-computed matmul
    FLOPs of the GPT step (fwd + exact 2x backward, elementwise
    optimizer): the default pricing is exact for matmul-dominated
    models, not an estimate."""
    from ray_lightning_tpu.models.gpt import GPTConfig, GPTLightningModule

    B, T, C, V, L = 4, 32, 32, 512, 2
    cfg = GPTConfig(vocab_size=V, block_size=T, n_layer=L, n_head=2,
                    n_embd=C, remat=False, attention_impl="dot")

    class NoHook(GPTLightningModule):
        def flops_per_step(self):      # GPT answers itself since PR 24
            return None

    module = NoHook(cfg, batch_size=B, dataset_size=8 * B)
    trainer = Trainer(max_epochs=1, limit_train_batches=2,
                      limit_val_batches=0, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0,
                      log_every_n_steps=1, default_root_dir=str(tmp_path),
                      telemetry=True)
    trainer.fit(module)
    doc = trainer._goodput_local
    assert doc is not None and check_identity(doc)
    flops = doc.get("flops_per_step")
    assert flops, "default jaxpr pricing produced no flops_per_step"
    # forward matmuls (2*M*N*K convention): per layer qkv 6BTC^2 +
    # scores/AV 2BT^2C each + proj 2BTC^2 + MLP 16BTC^2, plus the tied
    # vocab head 2BTCV; backward doubles every dot (dgrad + wgrad)
    fwd = L * (24 * B * T * C * C + 4 * B * T * T * C) + 2 * B * T * C * V
    expected = 3 * fwd
    assert abs(flops - expected) / expected < 0.05, (flops, expected)


def test_gpt_answers_the_flops_hook_with_chipbench_count(tmp_path, seed):
    """``GPTLightningModule.flops_per_step`` is the count the benchmark
    uses (chipbench/flops.py: 6 per matmul parameter and token plus
    causal attention), over the global batch the trainer saw, so
    goodput's MFU and ``train_mfu_pct`` share a numerator."""
    from chipbench import flops as bench_flops

    from ray_lightning_tpu.models.gpt import GPTConfig, GPTLightningModule
    B, T, C, V, L = 4, 32, 32, 512, 2
    module = GPTLightningModule(
        GPTConfig(vocab_size=V, block_size=T, n_layer=L, n_head=2,
                  n_embd=C, remat=False), batch_size=B,
        dataset_size=8 * B)
    assert module.flops_per_step() is None      # no trainer, no batch
    trainer = Trainer(max_epochs=1, limit_train_batches=6,
                      limit_val_batches=0, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0,
                      default_root_dir=str(tmp_path), telemetry=True)
    trainer.fit(module)
    doc = trainer._goodput_local
    per_token = bench_flops.train_flops_per_token(
        {"n_embd": C, "n_layer": L, "vocab_size": V}, T)
    assert doc["flops_per_step"] == pytest.approx(B * T * per_token)
    # the first step (where the program loads or compiles) is compile
    # time and no sample of the step wall
    assert doc["steps"] == 5 and check_identity(doc)
    assert doc["buckets"]["compile"] > doc["step_wall_mean_s"]


def test_first_step_is_compile_time_not_a_step():
    """A 24-step fit whose first dispatch compiles for 20 s: the mean
    step wall and the MFU are the steady steps' (PERF.md, PR 21: 0.035
    was printed where the steady step gives 0.65)."""
    clock = [0.0]
    ledger = GoodputLedger("fit", device_tflops=197.0, devices=1,
                           clock=lambda: clock[0]).start()
    ledger.set_flops_per_step(16 * 1024 * 798e6)
    ledger.note_step(20.0, first=True)
    for _ in range(23):
        ledger.note_step(0.1005)
    clock[0] = 20.0 + 23 * 0.1005
    doc = ledger.finalize()
    assert doc["steps"] == 23
    assert doc["step_wall_mean_s"] == pytest.approx(0.1005)
    assert doc["buckets"]["compile"] == pytest.approx(20.0)
    assert doc["mfu"] == pytest.approx(0.66, abs=0.005)
    assert check_identity(doc)


# -- real 2-worker fit: the identity, fleetwide --------------------------

@pytest.mark.slow
def test_two_worker_fit_goodput_identity_fleetwide(tmp_path, seed):
    """The acceptance identity on a real distributed fit: every rank's
    doc closes exactly, the fleet aggregate closes exactly, and the
    export summary / trainer report carry the same partition."""
    trainer = Trainer(max_epochs=1, limit_train_batches=6,
                      limit_val_batches=0, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0,
                      log_every_n_steps=1, default_root_dir=str(tmp_path),
                      plugins=[cpu_plugin(2)],
                      telemetry={"heartbeat_interval": 0.5})
    trainer.fit(BoringModel())
    summary = trainer._telemetry_paths["summary"]
    assert "goodput" in summary, "no goodput section in export summary"
    gp = summary["goodput"]
    assert set(gp["per_rank"]) == {"0", "1"}
    for rank, doc in gp["per_rank"].items():
        assert doc["kind"] == "fit"
        assert check_identity(doc), (rank, doc)
        assert doc["steps"] == 6
        assert doc["buckets"]["step"] > 0
        assert doc["buckets"]["compile"] > 0
    fleet = gp["fleet"]
    assert check_identity(fleet), fleet
    assert fleet["ranks"] == 2 and fleet["steps"] == 12
    assert 0 < fleet["goodput_fraction"] <= 1
    # the driver-side report the bench harness exports is the fleet doc
    rep = trainer._goodput_report
    assert rep is not None and check_identity(rep)
    assert rep["goodput_fraction"] == fleet["goodput_fraction"]


# -- recovery badput: parity ~0 vs replay > 0 ----------------------------

class AdamBoring(BoringModel):
    """Adam moments make the ZeRO-1 shard a dead rank takes with it
    non-trivial (the test_failure.py fixture, shipped by value)."""

    def configure_optimizers(self):
        import optax
        return optax.adam(0.05)


class SlowStep(Callback):
    """Pace the steps so heartbeat-carried metrics briefs track the
    fleet's progress (the crash-step evidence the replayed-step
    attribution reads) and async snapshots commit between steps."""

    needs_batch = False

    def on_train_batch_end(self, trainer, module, outputs, batch, idx):
        time.sleep(0.05)


def _badput_trainer(tmp_path, snap, *, fault, elastic, max_steps=8):
    return Trainer(
        max_epochs=20, max_steps=max_steps, limit_val_batches=0,
        num_sanity_val_steps=0, enable_checkpointing=False, seed=0,
        log_every_n_steps=1, default_root_dir=str(tmp_path),
        callbacks=[SlowStep()],
        plugins=[cpu_plugin(2, strategy="zero1",
                            worker_env={"RLT_FAULT": fault})],
        telemetry={"heartbeat_interval": 0.2, "flush_every": 1,
                   "metrics_interval": 0.2},
        elastic=elastic)


@pytest.mark.slow
def test_parity_recovery_reports_zero_replay_badput(tmp_path, seed):
    """Parity recovery resumes AT the crash step — the goodput ledger
    must show zero ``replay`` seconds (the measured claim PR 13's
    zero-replay story reduces to)."""
    snap = str(tmp_path / "elastic")
    trainer = _badput_trainer(
        tmp_path, snap, fault="kill:rank=1,step=5",
        elastic={"snapshot_every_n_steps": 2, "snapshot_dir": snap,
                 "max_restarts": 2, "redundancy": 1})
    trainer.fit(AdamBoring(dataset_length=64, batch_size=2))
    rep = trainer._elastic_report
    assert rep["recovery"] == "parity" and rep["resumed_step"] == 5
    assert rep["replayed_steps"] == 0
    gp = trainer._goodput_report
    assert gp is not None and check_identity(gp)
    assert gp["buckets"]["replay"] == 0.0
    # the recovery decision itself is attributed, not hidden
    assert gp["buckets"]["recovery"] > 0


@pytest.mark.slow
def test_replay_recovery_measures_replayed_step_badput(tmp_path, seed):
    """The same fleet with redundancy off resumes from the last durable
    snapshot and re-executes steps — measured ``replay`` seconds > 0:
    parity vs replay is now a goodput difference, not a narrative."""
    snap = str(tmp_path / "elastic")
    trainer = _badput_trainer(
        tmp_path, snap, fault="kill:rank=1,step=9", max_steps=10,
        elastic={"snapshot_every_n_steps": 5, "snapshot_dir": snap,
                 "max_restarts": 2})
    trainer.fit(AdamBoring(dataset_length=64, batch_size=2))
    rep = trainer._elastic_report
    assert rep["recovery"] == "replay" and rep["resumed_step"] == 5
    # the fleet progressed well past step 5 before the kill at 9; the
    # last metrics brief pins the crash step several steps past the
    # resume point
    assert rep["replayed_steps"] >= 1
    gp = trainer._goodput_report
    assert gp is not None and check_identity(gp)
    assert gp["buckets"]["replay"] > 0
    assert gp["replayed_steps"] == rep["replayed_steps"]


# -- wire item / metrics mirror ------------------------------------------

def test_goodput_item_and_metrics_mirror():
    from ray_lightning_tpu.telemetry import goodput as goodput_mod
    from ray_lightning_tpu.telemetry.metrics import MetricsRegistry

    led = GoodputLedger("serve")
    led.note_step(1.0)
    doc = led.finalize(2.0)
    item = goodput_mod.goodput_item(3, doc)
    assert item["kind"] == "goodput" and item["rank"] == 3
    assert item["goodput"] is doc
    reg = MetricsRegistry()
    goodput_mod.publish_metrics(doc, registry=reg)
    assert reg.gauge("rlt_goodput_seconds").value(
        bucket="decode", kind="serve") == pytest.approx(1.0)
    assert reg.gauge("rlt_goodput_fraction").value(
        kind="serve") == pytest.approx(0.5)
