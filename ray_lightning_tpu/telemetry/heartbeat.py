"""Worker heartbeats: periodic liveness beats over the worker→driver
queue channel, consumed by the driver watchdog
(telemetry/aggregator.py).

Two start sites share this one sender:

- ``worker_main`` (built-in backend) starts a process-level sender the
  moment the actor connects — before jax ever imports — so a worker
  that wedges during backend (libtpu) init is already visible to the
  watchdog.  Gated by ``RLT_TELEMETRY=1`` in the worker env.
- ``plugins/xla._worker_run`` starts one under backends with no
  process-level sender (real Ray actors), after the queue proxy exists.

Each beat carries rank (re-read from the environment every beat — the
built-in backend assigns ranks after spawn), pid, host, actor id, the
most recently entered span and the span ring's drop count, so the
watchdog can report "rank 2, last span 'step', heartbeat 34s old"
instead of a silent hang.

Beats also FLUSH the span recorder first: span batches otherwise wait
for ``flush_every`` records, and a rank that dies mid-batch takes its
most recent spans with it — the exact evidence the driver's crash
flight recorder (telemetry/flight.py) exists to keep.  Flushing at
heartbeat cadence bounds that loss window to ``heartbeat_interval``
seconds instead of up to ``flush_every`` records.
"""

from __future__ import annotations

import os
import socket
import threading
import time
from typing import Callable, Optional

from ray_lightning_tpu.telemetry import spans
from ray_lightning_tpu.telemetry.aggregator import TELEMETRY_KEY

_process_sender: "Optional[HeartbeatSender]" = None


def make_heartbeat(rank: int, actor_id: Optional[str] = None) -> dict:
    beat = {
        TELEMETRY_KEY: 1,
        "kind": "heartbeat",
        "rank": rank,
        "pid": os.getpid(),
        "host": socket.gethostname(),
        "actor_id": actor_id,
        "wall": time.time(),
        "last_span": spans.last_span(),
        "dropped": spans.dropped(),
    }
    # latest metrics brief (step, HBM, last collective) so a wedged
    # rank's watchdog diagnosis says WHAT it was doing when it went
    # silent, not just that it did (telemetry/metrics.py)
    from ray_lightning_tpu.telemetry.metrics import (metrics_brief,
                                                     sample_tail)
    brief = metrics_brief()
    if brief is not None:
        beat["metrics"] = brief
    # rolling sample tail (step wall / cadence / data wait): the
    # incident detectors dedupe by timestamp watermark, so carrying the
    # tail on every beat keeps them ticking even when span batches are
    # dropped under backpressure (incident-plane satellite)
    tail = sample_tail()
    if tail:
        beat["samples"] = tail
    return beat


def _env_rank() -> int:
    try:
        return int(os.environ.get("RLT_PROCESS_ID", "-1"))
    except ValueError:
        return -1


class HeartbeatSender:
    """Daemon thread beating every ``interval`` seconds via ``send``
    (a callable taking the beat dict).  A send failure (driver gone)
    ends the thread quietly — heartbeats must never crash a worker."""

    def __init__(self, send: Callable[[dict], None],
                 rank: Optional[int] = None, interval: float = 5.0,
                 actor_id: Optional[str] = None):
        self._send = send
        self._rank = rank
        self._interval = max(0.05, float(interval))
        self._actor_id = actor_id
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="rlt-heartbeat")

    def start(self) -> "HeartbeatSender":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    def _run(self) -> None:
        while not self._stop.is_set():
            rank = self._rank if self._rank is not None else _env_rank()
            try:
                # span batches first: bound the crash-loss window to one
                # heartbeat interval (module docstring).  The recorder's
                # sink is the same thread-safe queue this beat rides.
                spans.flush()
                self._send(make_heartbeat(rank, self._actor_id))
            except Exception:
                return
            self._stop.wait(self._interval)


def start_process_heartbeat(send: Callable[[dict], None],
                            interval: float = 5.0,
                            actor_id: Optional[str] = None
                            ) -> HeartbeatSender:
    """Start (once) the per-process sender used by worker_main; rank is
    re-read from ``RLT_PROCESS_ID`` each beat."""
    global _process_sender
    if _process_sender is None:
        _process_sender = HeartbeatSender(
            send, rank=None, interval=interval, actor_id=actor_id).start()
    return _process_sender


def process_heartbeat_active() -> bool:
    """True when the per-process (worker_main) sender is running — the
    plugin-level start site then skips starting a duplicate."""
    return _process_sender is not None
