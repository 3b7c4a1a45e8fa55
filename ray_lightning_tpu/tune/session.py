"""Per-trial session: the process/thread-local context that makes
``report`` / ``checkpoint_dir`` work inside a running trial.

Reference behavior being reproduced: ``tune.report`` and
``tune.checkpoint_dir`` only work in the process Tune launched
(reference: tune.py:130-134, :161-178 route them through the queue so
they execute on the trial driver).  Here the session is thread-local —
the local runner executes each trial in its own thread — and the
framework's distributed plugins relay worker-side calls to the trial
thread through the worker→driver queue exactly like the reference.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Optional

_local = threading.local()


class TrialSession:
    """Live context of one running trial.

    ``devices`` is the trial's leased device subset, acquired LAZILY the
    first time in-process training asks for devices (tune/runner.py
    ``_DeviceLeaser``) — trials whose compute lives in actor
    subprocesses never acquire, so the tune driver never initializes a
    JAX backend for them.  None = no lease, the trial may span every
    visible device.
    """

    def __init__(self, trial, on_report, device_leaser=None):
        self.trial = trial
        self._on_report = on_report
        self._step = 0
        self._leaser = device_leaser
        self.devices = None

    def acquire_devices(self):
        if self._leaser is not None and self.devices is None:
            self.devices = self._leaser.acquire()
            # record the lease on the trial for post-hoc debugging via
            # ExperimentAnalysis (which chips ran which trial — the
            # inspectability the reference gets from placement groups)
            self.trial.leased_devices = [str(d) for d in self.devices]
        return self.devices

    def release_devices(self) -> None:
        if self._leaser is not None and self.devices is not None:
            self._leaser.release(self.devices)
            self.devices = None

    def report(self, **metrics) -> None:
        self._step += 1
        metrics = dict(metrics)
        metrics.setdefault("training_iteration", self._step)
        self._on_report(self.trial, metrics)

    @contextlib.contextmanager
    def checkpoint_dir(self, step: int):
        """Directory for this trial's checkpoint at ``step`` (parity with
        ``tune.checkpoint_dir``, which the reference writes into via
        fsspec, tune.py:161-167)."""
        path = os.path.join(self.trial.logdir, f"checkpoint_{step:06d}")
        os.makedirs(path, exist_ok=True)
        yield path
        self.trial.latest_checkpoint = path


def _get() -> Optional[TrialSession]:
    return getattr(_local, "session", None)


def set_session(session: Optional[TrialSession]) -> None:
    _local.session = session


def in_session() -> bool:
    return _get() is not None


def note_plan_report(report: dict) -> None:
    """Record the planner's PlanReport dict on the live trial (no-op
    outside a trial) — the post-hoc "which plan did this trial train
    under" analog of ``leased_devices`` / ``metrics_url``.  Called by
    plan/planner.py after every (including memo-reused) plan."""
    s = _get()
    if s is not None:
        s.trial.plan_report = report


def report(_metrics: Optional[dict] = None, **metrics) -> None:
    """Report metrics for the current trial (``tune.report`` analog).

    Resolves against the builtin runner's session when one is live,
    falling back to a *real* Ray Tune/Train session (tune/ray_bridge.py)
    — so a train_fn written against this API runs unchanged under
    genuine ``ray.tune.run``.
    """
    merged = dict(_metrics or {})
    merged.update(metrics)
    s = _get()
    if s is not None:
        s.report(**merged)
        return
    from ray_lightning_tpu.tune import ray_bridge
    if ray_bridge.report(merged):
        return
    raise RuntimeError(
        "tune.report() called outside a tune trial; run this function "
        "via ray_lightning_tpu.tune.run() or a real Ray Tune trial.")


def deliver_checkpoint(blob: bytes, step: int, filename: str) -> None:
    """Write checkpoint bytes where the live trial session keeps
    checkpoints — builtin runner's trial dir, classic Ray Tune's
    ``checkpoint_dir``, or staged for the modern Train API's next
    report (reference analog: tune.py:161-167)."""
    s = _get()
    if s is not None:
        with s.checkpoint_dir(step) as d:
            with open(os.path.join(d, filename), "wb") as f:
                f.write(blob)
        return
    from ray_lightning_tpu.tune import ray_bridge
    if ray_bridge.stage_checkpoint(blob, step, filename):
        return
    raise RuntimeError(
        "Tune checkpoint relay outside a tune trial; run via "
        "ray_lightning_tpu.tune.run() or a real Ray Tune trial.")


@contextlib.contextmanager
def checkpoint_dir(step: int):
    s = _get()
    if s is None:
        from ray_lightning_tpu.tune import ray_bridge
        if ray_bridge.in_session():
            with ray_bridge.checkpoint_dir(step) as path:
                yield path
            return
        raise RuntimeError("tune.checkpoint_dir() outside a tune trial.")
    with s.checkpoint_dir(step) as path:
        yield path


def get_trial_devices():
    """Devices leased to the current trial, or None (no trial / no
    lease declared).  LocalPlugin consults this so an in-process
    trial's mesh spans only its own partition of the host's chips; the
    lease is acquired on first call (may block until a chunk frees)."""
    s = _get()
    return s.acquire_devices() if s is not None else None


def get_trial_id() -> str:
    s = _get()
    return s.trial.trial_id if s else "default"


def get_trial_dir() -> Optional[str]:
    s = _get()
    return s.trial.logdir if s else None


def get_trial():
    """The live Trial object, or None outside a builtin tune trial.
    The metrics exporter (telemetry/exporter.py) uses it to give each
    concurrent trial its own ephemeral /metrics port and to record the
    bound URL on the trial for ExperimentAnalysis."""
    s = _get()
    return s.trial if s else None
