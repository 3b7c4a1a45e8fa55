"""Observability callbacks: throughput metrics land in callback_metrics;
profiler traces are written and never break training (SURVEY.md §5
tracing/profiling parity)."""

import os

from ray_lightning_tpu import (
    JaxProfilerCallback,
    ThroughputMonitor,
    Trainer,
)
from ray_lightning_tpu.models import BoringModel
from ray_lightning_tpu.models.gpt import GPTLightningModule


def test_throughput_monitor_logs_metrics(tmp_path, seed):
    trainer = Trainer(max_epochs=1, limit_train_batches=8,
                      limit_val_batches=0, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0,
                      default_root_dir=str(tmp_path),
                      callbacks=[ThroughputMonitor(window=4)])
    trainer.fit(BoringModel(dataset_length=64, batch_size=4))
    cbm = trainer.callback_metrics
    assert cbm["steps_per_sec"] > 0
    assert cbm["samples_per_sec"] > 0
    assert cbm["epoch_time_s"] > 0


def test_throughput_monitor_tokens_for_sequences(tmp_path, seed):
    trainer = Trainer(max_epochs=1, limit_train_batches=8,
                      limit_val_batches=0, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0,
                      default_root_dir=str(tmp_path),
                      callbacks=[ThroughputMonitor(window=4)])
    module = GPTLightningModule("tiny", dataset_size=64, batch_size=4)
    trainer.fit(module)
    cbm = trainer.callback_metrics
    # token batches are [B, T]: tokens/sec = samples/sec * T
    assert cbm["tokens_per_sec"] > cbm["samples_per_sec"]


def test_throughput_monitor_with_chunked_dispatch(tmp_path, seed):
    """steps_per_execution>1 advances global_step k at a time and fires
    batch_end once per chunk: the monitor must still measure (delta
    tracking — a modulo window check would never trigger when k does
    not divide the window) and count samples for EVERY step of the
    chunk, not just the callback's batch."""
    ratios = []

    class Capture(ThroughputMonitor):
        def on_train_batch_end(self, trainer, module, outputs, batch,
                               idx):
            super().on_train_batch_end(trainer, module, outputs, batch,
                                       idx)
            cbm = trainer.callback_metrics
            if "samples_per_sec" in cbm:
                ratios.append(cbm["samples_per_sec"]
                              / cbm["steps_per_sec"])

    trainer = Trainer(max_epochs=1, limit_train_batches=15,
                      limit_val_batches=0, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0,
                      default_root_dir=str(tmp_path),
                      steps_per_execution=5,
                      callbacks=[Capture(window=4)])
    trainer.fit(BoringModel(dataset_length=64, batch_size=4))
    assert trainer.callback_metrics["steps_per_sec"] > 0
    # samples/sec must equal batch_size x steps/sec — i.e. every step of
    # each 5-step chunk was counted, not just the last one
    assert ratios and all(abs(r - 4.0) < 1e-6 for r in ratios)


def test_profiler_callback_writes_trace(tmp_path, seed):
    prof_dir = str(tmp_path / "prof")
    trainer = Trainer(max_epochs=1, limit_train_batches=6,
                      limit_val_batches=0, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0,
                      default_root_dir=str(tmp_path),
                      callbacks=[JaxProfilerCallback(
                          start_step=2, num_steps=2, log_dir=prof_dir)])
    trainer.fit(BoringModel(dataset_length=64, batch_size=4))
    # jax writes plugins/profile/<run>/ under the log dir
    found = []
    for root, _dirs, files in os.walk(prof_dir):
        found.extend(files)
    assert found, "no profiler trace files written"


def test_profiler_stops_cleanly_when_window_spans_train_end(tmp_path, seed):
    """Window past the end of training: on_train_end must stop the trace
    without raising."""
    trainer = Trainer(max_epochs=1, limit_train_batches=3,
                      limit_val_batches=0, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0,
                      default_root_dir=str(tmp_path),
                      callbacks=[JaxProfilerCallback(
                          start_step=2, num_steps=100)])
    trainer.fit(BoringModel(dataset_length=64, batch_size=4))


def test_profiler_window_holds_the_loops_spans_and_the_clock_anchor(
        tmp_path, seed):
    """Telemetry off: the callback's window still holds the program's
    ``rlt/`` spans in its host plane, with the anchor that maps the
    trace's clock to the wall clock."""
    import glob
    import time

    from jax.profiler import ProfileData
    prof_dir = str(tmp_path / "prof")
    trainer = Trainer(max_epochs=1, limit_train_batches=6,
                      limit_val_batches=0, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0,
                      default_root_dir=str(tmp_path), telemetry=False,
                      callbacks=[JaxProfilerCallback(
                          start_step=2, num_steps=2, log_dir=prof_dir)])
    trainer.fit(BoringModel(dataset_length=64, batch_size=4))
    path = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("rlt/"):
                        found.setdefault(e.name, []).append(dict(e.stats))
    assert {"rlt/clock", "rlt/step", "rlt/data_wait",
            "rlt/callbacks"} <= set(found)
    assert sorted(s["step"] for s in found["rlt/step"]) == [2, 3]
    anchor = found["rlt/clock"][-1]
    assert abs(anchor["wall_ns"] * 1e-9 - time.time()) < 300
