"""Async per-step snapshotting off the critical path.

The loop engine calls :meth:`Snapshotter.maybe_snapshot` after every
optimizer step (``Trainer._engine_one`` / ``_engine_chunk``); on the
configured cadence it triggers ``Trainer.save_sharded_checkpoint`` with
orbax async enabled, so the only blocking cost on the training thread
is the device→host copy — the disk write proceeds behind subsequent
steps.

Backpressure is bounded by construction — at most ONE save is ever
outstanding, never an unbounded queue:

- single-process runs SKIP a cadence hit while the previous save is
  still writing (counted in ``rlt_snapshot_skipped_total``);
- multi-process runs must make the same save/skip decision on every
  rank (orbax per-shard saves are collective — a rank that skips while
  another saves deadlocks the fleet), and "is the previous save still
  writing" is a local, timing-dependent question.  So multi-process
  runs WAIT for the previous save instead of skipping — deterministic,
  still bounded at one outstanding save — and the wait is measured
  into ``rlt_snapshot_stall_seconds_total`` (the number the bench
  reports; near zero when the cadence out-paces the write).

**Failure hardening**: a failed async save must not kill training — a
flaky snapshot target (full disk, GCS blip) costs durability headroom,
not the run.  A save that raises is caught, counted
(``rlt_snapshot_failed_total``), and retried at the next cadence tick;
only ``ElasticConfig.max_snapshot_failures`` CONSECUTIVE failures
re-raise (a permanently broken target must not fail silently — the
elastic driver would otherwise keep "recovering" onto snapshots that
stopped landing).  The ``snapkill`` chaos fault (elastic/faults.py)
fires here, mid-async-write, so the uncommitted-step resume contract
is testable.

Instruments (metrics plane, PR 2): ``rlt_snapshot_total``,
``rlt_snapshot_skipped_total``, ``rlt_snapshot_failed_total``,
``rlt_snapshot_seconds_total`` (blocking host time of the save call),
and ``rlt_snapshot_stall_seconds_total``.  The same numbers accumulate
in :attr:`Snapshotter.stats` so benches and tests read them without
the metrics plane; the ``checkpoint`` span (utils/checkpoint.py)
already covers each save's blocking section in the trace.
"""

from __future__ import annotations

import logging
import time

from ray_lightning_tpu.telemetry import metrics as _metrics

_log = logging.getLogger(__name__)


class Snapshotter:
    """Cadence-driven async sharded snapshots for one fit stage."""

    def __init__(self, trainer, cfg):
        self.trainer = trainer
        self.cfg = cfg
        self.directory = cfg.resolve_dir(trainer.default_root_dir)
        #: cumulative counters mirrored into the metrics registry; read
        #: directly by the chaos tests
        self.stats = {
            "snapshots": 0,
            "skipped": 0,
            "failed": 0,
            "save_seconds": 0.0,
            "stall_seconds": 0.0,
        }
        self._consecutive_failures = 0
        import jax
        self._multiprocess = jax.process_count() > 1

    def _count(self, name: str, value: float = 1.0) -> None:
        reg = _metrics.get_registry()
        if reg is not None:
            reg.counter(name).inc(value)

    def maybe_snapshot(self) -> bool:
        """One cadence check; returns True when a snapshot was taken.
        Collective in multi-process runs (every rank reaches the same
        decision from ``global_step`` alone)."""
        t = self.trainer
        n = self.cfg.snapshot_every_n_steps
        if n <= 0 or t.global_step <= 0 or t.global_step % n:
            return False
        ckpt = t._sharded_checkpointer(self.directory,
                                       max_to_keep=self.cfg.max_to_keep)
        if ckpt.saving_in_progress():
            if not self._multiprocess:
                # bounded backpressure: drop this cadence hit rather
                # than stacking saves behind a slow disk
                self.stats["skipped"] += 1
                self._count("rlt_snapshot_skipped_total")
                _log.debug("elastic snapshot at step %d skipped: "
                           "previous save still writing", t.global_step)
                return False
            # multi-process: the skip decision cannot be agreed without
            # a collective, so wait (still at most one outstanding save)
            # and make the cost visible
            t0 = time.monotonic()
            ckpt.wait()
            stall = time.monotonic() - t0
            self.stats["stall_seconds"] += stall
            self._count("rlt_snapshot_stall_seconds_total", stall)
            _log.info("elastic snapshot at step %d stalled %.3fs behind "
                      "the previous save", t.global_step, stall)
        t0 = time.monotonic()
        try:
            t.save_sharded_checkpoint(self.directory,
                                      max_to_keep=self.cfg.max_to_keep)
        except Exception:   # noqa: BLE001 - hardened: counted + retried
            self._consecutive_failures += 1
            self.stats["failed"] += 1
            self._count("rlt_snapshot_failed_total")
            limit = self.cfg.max_snapshot_failures
            if self._consecutive_failures >= limit:
                _log.error(
                    "elastic snapshot at step %d failed %d consecutive "
                    "times (limit %d); raising — the snapshot target is "
                    "broken, not flaky", t.global_step,
                    self._consecutive_failures, limit)
                raise
            _log.warning(
                "elastic snapshot at step %d failed (%d consecutive, "
                "limit %d); training continues, retrying next cadence "
                "tick", t.global_step, self._consecutive_failures,
                limit, exc_info=True)
            return False
        dt = time.monotonic() - t0
        self._consecutive_failures = 0
        self.stats["snapshots"] += 1
        self.stats["save_seconds"] += dt
        self._count("rlt_snapshot_total")
        self._count("rlt_snapshot_seconds_total", dt)
        # chaos hook: an armed snapkill fires HERE, while the async
        # orbax write is still in flight — the step dir never commits
        from ray_lightning_tpu.elastic.faults import (_elastic_restarts,
                                                      maybe_snapkill)
        maybe_snapkill(t.global_rank, t.global_step,
                       _elastic_restarts(t))
        return True
