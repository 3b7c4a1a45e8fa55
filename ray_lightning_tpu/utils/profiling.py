"""Observability callbacks: throughput measurement + profiler traces.

SURVEY.md §5 (tracing/profiling): the reference's only perf-measurement
code is the sharded example's ``CUDACallback`` (epoch wall time + peak
CUDA memory, examples/ray_ddp_sharded_example.py:16-45), deferring deeper
profiling to external tools.  The TPU-native equivalents here:

- :class:`ThroughputMonitor` — steps/sec, tokens or samples/sec, epoch
  wall time and peak device memory (PJRT ``memory_stats`` replacing
  ``torch.cuda.max_memory_allocated``), logged into
  ``trainer.callback_metrics`` so rank-0's numbers ride the normal
  distributed result relay.
- :class:`JaxProfilerCallback` — captures an XLA/TPU trace for a window
  of training steps via ``jax.profiler`` (view in TensorBoard /
  Perfetto), the analog of the torch profiler the reference defers to.

Both are pure host-side hooks: they never appear inside compiled steps,
and the throughput clock is careful to measure async dispatch correctly
(a step's wall time is only meaningful after forcing a device sync, which
the monitor does once per window, not per step).
"""

from __future__ import annotations

import logging
import os
import time
from typing import Optional

import numpy as np

from ray_lightning_tpu.core.callbacks import Callback
from ray_lightning_tpu import telemetry

_log = logging.getLogger(__name__)


def peak_device_memory_bytes() -> Optional[int]:
    """Peak HBM bytes in use on the first local device, if the PJRT
    backend reports it (TPU does; CPU typically returns nothing)."""
    import jax
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    return stats.get("peak_bytes_in_use")


class ThroughputMonitor(Callback):
    """Log steps/sec, samples/sec (and tokens/sec for sequence batches),
    per-epoch wall time and peak device memory.

    ``window`` controls how often the device is synced to take a
    measurement — syncing per step would serialize async dispatch and
    slow training, so the monitor forces one sync every ``window`` steps
    and averages over the window.
    """

    @staticmethod
    def _emit(trainer, name: str, value: float) -> None:
        """One emission path: ``callback_metrics`` (rank-0's copy rides
        the distributed result relay) AND a telemetry counter (every
        rank's value lands on the merged driver timeline)."""
        trainer.log_metric(name, value)
        telemetry.counter(name, value)

    def __init__(self, window: int = 50, log_tokens: bool = True):
        self.window = max(1, int(window))
        self.log_tokens = log_tokens
        self._t0: Optional[float] = None
        self._epoch_t0: Optional[float] = None
        self._units = 0
        self._samples = 0
        self._steps = 0           # optimizer steps in the current window
        self._prev_step = 0       # last observed trainer.global_step

    @staticmethod
    def _sync(outputs) -> None:
        """Force completion of the async-dispatched window."""
        import jax
        leaves = [x for x in jax.tree_util.tree_leaves(outputs)
                  if isinstance(x, jax.Array)]
        if leaves:
            jax.block_until_ready(leaves[-1])

    def _reset_window(self, trainer) -> None:
        self._t0 = None
        self._units = 0
        self._samples = 0
        self._steps = 0
        self._prev_step = trainer.global_step

    def on_train_epoch_start(self, trainer, module):
        self._epoch_t0 = time.monotonic()
        self._prev_step = trainer.global_step

    def on_validation_start(self, trainer, module):
        # mid-epoch eval does host+device work outside training; drop the
        # current window so it cannot deflate steps/sec
        self._reset_window(trainer)

    def on_train_batch_end(self, trainer, module, outputs, batch, batch_idx):
        import jax
        # under steps_per_execution>1 this hook fires once per CHUNK with
        # its last batch: count real optimizer steps by global_step delta
        # and scale the sample/token tally by it (uniform batch shapes —
        # the compiled multi-step requires them anyway)
        delta = max(1, trainer.global_step - self._prev_step)
        self._prev_step = trainer.global_step
        self._steps += delta
        leaves = [x for x in jax.tree_util.tree_leaves(batch)
                  if hasattr(x, "shape") and getattr(x, "ndim", 0) >= 1]
        if leaves:
            lead = leaves[0]
            self._samples += int(lead.shape[0]) * delta
            # tokens/sec only for [B, T] integer batches (token ids);
            # float [B, features...] batches are not sequences
            is_tokens = (self.log_tokens and lead.ndim == 2
                         and np.issubdtype(np.asarray(lead).dtype,
                                           np.integer))
            self._units += int(lead.shape[0]) * delta * (
                int(lead.shape[1]) if is_tokens else 1)
        if self._steps < self.window:
            return
        self._sync(outputs)
        now = time.monotonic()
        if self._t0 is not None:
            dt = now - self._t0
            self._emit(trainer, "steps_per_sec", self._steps / dt)
            self._emit(trainer, "samples_per_sec", self._samples / dt)
            if self.log_tokens and self._units != self._samples:
                self._emit(trainer, "tokens_per_sec", self._units / dt)
            # peak HBM per window (not just per epoch): regressions show
            # up at window granularity on the telemetry timeline
            peak = peak_device_memory_bytes()
            if peak:
                self._emit(trainer, "peak_memory_mb", peak / 1e6)
        self._t0 = now
        self._units = 0
        self._samples = 0
        self._steps = 0

    def on_train_epoch_end(self, trainer, module):
        if self._epoch_t0 is not None:
            self._emit(trainer, "epoch_time_s",
                       time.monotonic() - self._epoch_t0)
        peak = peak_device_memory_bytes()
        if peak:
            self._emit(trainer, "peak_memory_mb", peak / 1e6)
        # new window per epoch: the epoch boundary does host work
        self._reset_window(trainer)


class JaxProfilerCallback(Callback):
    """Capture a jax.profiler trace for steps [start_step, start_step +
    num_steps) of training; written under ``log_dir`` (default
    ``<default_root_dir>/profile``) for TensorBoard/Perfetto."""

    needs_batch = False   # windows on global_step; never reads the batch

    def __init__(self, start_step: int = 5, num_steps: int = 5,
                 log_dir: Optional[str] = None):
        self.start_step = int(start_step)
        self.num_steps = max(1, int(num_steps))
        self.log_dir = log_dir
        self._active = False
        self._done = False

    def _dir(self, trainer) -> str:
        return self.log_dir or os.path.join(trainer.default_root_dir,
                                            "profile")

    def on_train_batch_start(self, trainer, module, batch, batch_idx):
        # >= so a resumed run already past start_step still captures its
        # window (global_step restores from the checkpoint)
        if self._active or self._done \
                or trainer.global_step < self.start_step:
            return
        import jax
        path = self._path = self._dir(trainer)
        os.makedirs(path, exist_ok=True)
        try:
            jax.profiler.start_trace(path)
            telemetry.spans.clock_anchor()
            self._active = True
            self._started_at = trainer.global_step
        except Exception as e:  # profiling must never kill training
            _log.warning("profiler trace failed to start: %s", e)

    def on_train_batch_end(self, trainer, module, outputs, batch, batch_idx):
        if self._active and trainer.global_step >= \
                self._started_at + self.num_steps:
            self._stop(outputs)

    def on_train_end(self, trainer, module):
        if self._active:
            self._stop(None)

    def _stop(self, outputs) -> None:
        import jax
        if outputs is not None:
            leaves = [x for x in jax.tree_util.tree_leaves(outputs)
                      if isinstance(x, jax.Array)]
            if leaves:  # make the traced window include real device work
                jax.block_until_ready(leaves[-1])
        try:
            jax.profiler.stop_trace()
            telemetry.scopes.write_tables(self._path)
        except Exception as e:
            _log.warning("profiler trace failed to stop: %s", e)
        self._active = False
        self._done = True
