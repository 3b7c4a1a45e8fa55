"""Distributed run telemetry: per-worker spans, driver aggregation,
heartbeats, Perfetto trace export — and the trace plane on top.

One coherent observability layer replacing three disconnected ones
(rank-0-only ThroughputMonitor numbers, the CSVLogger, and external
profilers): every rank records spans/counters (``spans.py``), batches
stream to the driver over the existing worker→driver queue channel,
and the driver merges them into a Chrome/Perfetto ``trace.json`` +
``telemetry.jsonl`` with per-rank step percentiles and straggler skew
(``aggregator.py``).  Worker heartbeats (``heartbeat.py``) feed a
driver watchdog that names a dead or wedged rank instead of hanging
silently.  ``tracing.py`` ties spans to *requests* (per-request trace
ids through the serve plan broadcast, per-tenant latency attribution)
and arms on-demand ``jax.profiler`` windows; ``flight.py`` is the
crash black box dumped at death-classification time.

Enable with ``Trainer(telemetry=True)`` (or a config dict /
``TelemetryConfig``), or process-wide with ``RLT_TELEMETRY=1``.
Artifacts land under ``<default_root_dir>/telemetry/`` — or, inside a
builtin tune trial, under the trial's own logdir so concurrent trials
never interleave.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Optional

from ray_lightning_tpu.telemetry.spans import (  # noqa: F401
    counter,
    disable,
    drain,
    dropped,
    enable,
    enabled,
    flush,
    last_span,
    span,
)
from ray_lightning_tpu.telemetry.aggregator import (  # noqa: F401
    TELEMETRY_KEY,
    TelemetryAggregator,
    WorkerHeartbeatTimeout,
    get_active,
    set_active,
    spans_item,
)
from ray_lightning_tpu.telemetry.flight import (  # noqa: F401
    FlightRecorder,
    flight_path,
)
from ray_lightning_tpu.telemetry.tracing import (  # noqa: F401
    mint_trace_id,
    profile_tick,
    record_request_span,
)
from ray_lightning_tpu.telemetry.anatomy import (  # noqa: F401
    AnatomyController,
    StepAnatomy,
    anatomy_item,
    anatomy_tick,
    disable_anatomy,
    enable_anatomy,
    get_anatomy_controller,
    parse_trace_anatomy,
)
from ray_lightning_tpu.telemetry.goodput import (  # noqa: F401
    GoodputLedger,
    disable_goodput,
    enable_goodput,
    finish_run,
    goodput_item,
    measured_mfu,
    start_run,
)
from ray_lightning_tpu.telemetry.incident import (  # noqa: F401
    Detector,
    DetectorConfig,
    Incident,
    IncidentConfig,
    IncidentManager,
    TimelineStore,
)
from ray_lightning_tpu.telemetry.metrics import (  # noqa: F401
    MetricsRegistry,
    disable_metrics,
    enable_metrics,
    flush_metrics,
    get_registry,
    metrics_enabled,
    metrics_item,
    note_step_collectives,
    note_traced_collective,
    on_compile,
    on_step,
    record_collective,
)

__all__ = [
    "TelemetryConfig",
    "TelemetryAggregator",
    "WorkerHeartbeatTimeout",
    "TELEMETRY_KEY",
    "span",
    "counter",
    "enable",
    "disable",
    "enabled",
    "flush",
    "drain",
    "dropped",
    "last_span",
    "get_active",
    "set_active",
    "spans_item",
    "FlightRecorder",
    "flight_path",
    "mint_trace_id",
    "record_request_span",
    "profile_tick",
    "MetricsRegistry",
    "enable_metrics",
    "disable_metrics",
    "metrics_enabled",
    "flush_metrics",
    "get_registry",
    "metrics_item",
    "record_collective",
    "note_traced_collective",
    "note_step_collectives",
    "on_step",
    "on_compile",
    "GoodputLedger",
    "enable_goodput",
    "disable_goodput",
    "start_run",
    "finish_run",
    "goodput_item",
    "measured_mfu",
    "StepAnatomy",
    "AnatomyController",
    "anatomy_item",
    "anatomy_tick",
    "enable_anatomy",
    "disable_anatomy",
    "get_anatomy_controller",
    "parse_trace_anatomy",
    "Detector",
    "DetectorConfig",
    "Incident",
    "IncidentConfig",
    "IncidentManager",
    "TimelineStore",
]


@dataclass
class TelemetryConfig:
    """Picklable telemetry settings carried on the Trainer (the trainer
    ships to workers, so the config rides along for free)."""

    enabled: bool = False
    #: explicit output dir; None = <default_root_dir>/telemetry (or the
    #: tune trial's logdir when running inside a builtin tune trial)
    dir: Optional[str] = None
    heartbeat_interval: float = 5.0
    heartbeat_timeout: float = 60.0
    #: raise WorkerHeartbeatTimeout past this silence (None = log only)
    hard_timeout: Optional[float] = None
    flush_every: int = 256
    capacity: int = 65536
    #: crash flight recorder (telemetry/flight.py): per-rank ring of the
    #: most recent driver-ingested records, dumped as flight_<rank>.json
    #: on a wedge verdict / death classification.  Bounded by this many
    #: records per rank; 0 still keeps heartbeats (min ring is 1).
    flight_capacity: int = 256
    #: metrics plane (telemetry/metrics.py): per-rank typed instruments
    #: (HBM gauges, step-time histogram, collective byte counters)
    #: riding the same worker→driver channel as spans
    metrics: bool = True
    #: seconds between device-state samples / window flushes
    metrics_interval: float = 2.0
    #: driver HTTP endpoint (/metrics Prometheus exposition + /status
    #: JSON).  None = no server unless RLT_METRICS_PORT is set; 0 = an
    #: ephemeral port (read it back from the returned metrics_url)
    metrics_port: Optional[int] = None
    #: anatomy plane (telemetry/anatomy.py): every N dispatches each
    #: rank arms a short jax.profiler window, parses its own capture
    #: locally into a StepAnatomy (measured compute/collective/exposed/
    #: host split) and ships only the compact dict to the driver.
    #: None = disarmed unless RLT_ANATOMY / RLT_ANATOMY_EVERY_N_STEPS
    #: arm it (resolved_anatomy below)
    anatomy_every_n_steps: Optional[int] = None
    #: dispatches traced per anatomy window
    anatomy_steps: int = 4
    #: incident plane (telemetry/incident.py): driver-side timelines +
    #: rolling anomaly detectors + auto-RCA incident reports.  On by
    #: default whenever telemetry is enabled; RLT_INCIDENT=0 disarms
    incident: bool = True
    #: baseline samples per detector before it may trip
    incident_warmup: int = 16
    #: consecutive breached (healthy) samples to open (close)
    incident_patience: int = 3
    #: seconds after close before the same detector may re-trip
    incident_cooldown_s: float = 30.0
    #: per-(series, rank) timeline ring capacity
    incident_capacity: int = 512
    #: goodput plane (telemetry/goodput.py): the per-run wall-clock
    #: partition + measured MFU.  None = armed whenever telemetry is
    #: enabled unless RLT_GOODPUT=0 disarms; an explicit bool wins
    goodput: Optional[bool] = None
    #: per-device peak TFLOPs for the MFU denominator; None defers to
    #: RLT_GOODPUT_TFLOPS, then the device kind's published peak
    #: (telemetry/goodput.py DEVICE_PEAKS; unknown kind: no MFU)
    goodput_tflops: Optional[float] = None

    @classmethod
    def resolve(cls, value: Any) -> "TelemetryConfig":
        """Trainer's ``telemetry=`` argument → a config.  None defers to
        the ``RLT_TELEMETRY`` env var; True/False force; a dict supplies
        field overrides (enabled unless it says otherwise)."""
        if isinstance(value, cls):
            return value
        if value is None:
            return cls(enabled=os.environ.get("RLT_TELEMETRY", "")
                       in ("1", "true"))
        if isinstance(value, bool):
            return cls(enabled=value)
        if isinstance(value, dict):
            cfg = dict(value)
            cfg.setdefault("enabled", True)
            return cls(**cfg)
        raise TypeError(
            f"telemetry must be None/bool/dict/TelemetryConfig; got "
            f"{type(value).__name__}")

    def resolved_metrics_port(self) -> Optional[int]:
        """Port for the driver's /metrics endpoint: the explicit config
        field, else the ``RLT_METRICS_PORT`` env var, else None (no
        server)."""
        if self.metrics_port is not None:
            return int(self.metrics_port)
        env = os.environ.get("RLT_METRICS_PORT", "").strip()
        if env:
            try:
                return int(env)
            except ValueError:
                import logging
                logging.getLogger(__name__).warning(
                    "RLT_METRICS_PORT=%r is not an integer; metrics "
                    "endpoint disabled", env)
        return None

    def resolved_anatomy(self) -> "tuple[Optional[int], int]":
        """(every_n_dispatches, window_dispatches) with the RLT_ANATOMY*
        env merged in: the explicit config field wins, else
        ``RLT_ANATOMY_EVERY_N_STEPS``, else bare ``RLT_ANATOMY=1`` arms
        the default cadence.  (None, window) = disarmed."""
        from ray_lightning_tpu.telemetry import anatomy as _anatomy
        every = self.anatomy_every_n_steps
        if every is None:
            env = os.environ.get(_anatomy.ANATOMY_EVERY_ENV, "").strip()
            if env:
                try:
                    every = int(env)
                except ValueError:
                    import logging
                    logging.getLogger(__name__).warning(
                        "%s=%r is not an integer; anatomy disarmed",
                        _anatomy.ANATOMY_EVERY_ENV, env)
            elif os.environ.get(_anatomy.ANATOMY_ENV, "") in ("1", "true"):
                every = _anatomy.DEFAULT_EVERY_N
        steps = self.anatomy_steps
        env = os.environ.get(_anatomy.ANATOMY_STEPS_ENV, "").strip()
        if env:
            try:
                steps = int(env)
            except ValueError:
                pass
        if every is not None and every <= 0:
            every = None
        return every, max(1, int(steps))

    def resolved_incident(self) -> "IncidentConfig":
        """Driver-side incident-plane config: these TelemetryConfig
        fields as the base, with the ``RLT_INCIDENT*`` env merged on
        top (env wins — the same precedence as every other knob)."""
        from ray_lightning_tpu.telemetry.incident import IncidentConfig
        base = IncidentConfig(
            enabled=bool(self.incident),
            capacity=int(self.incident_capacity),
            warmup=int(self.incident_warmup),
            patience=int(self.incident_patience),
            cooldown_s=float(self.incident_cooldown_s))
        return IncidentConfig.from_env(base=base)

    def resolved_goodput(self) -> bool:
        """Is the goodput ledger armed?  The explicit config bool wins;
        None defers to ``RLT_GOODPUT`` (unset = armed — goodput rides
        telemetry by default, so arming telemetry is opting in)."""
        if self.goodput is not None:
            return bool(self.goodput)
        from ray_lightning_tpu.telemetry import goodput as _goodput
        return _goodput.goodput_armed()

    def resolved_goodput_tflops(self) -> Optional[float]:
        """Per-device peak TFLOPs for MFU: the explicit config field,
        else ``RLT_GOODPUT_TFLOPS``, else None (the trainer then looks
        the device kind up in ``goodput.DEVICE_PEAKS``; an unknown kind
        prices no MFU)."""
        if self.goodput_tflops is not None:
            return float(self.goodput_tflops)
        from ray_lightning_tpu.telemetry import goodput as _goodput
        env = os.environ.get(_goodput.GOODPUT_TFLOPS_ENV, "").strip()
        if env:
            try:
                return float(env)
            except ValueError:
                import logging
                logging.getLogger(__name__).warning(
                    "%s=%r is not a number; ignored",
                    _goodput.GOODPUT_TFLOPS_ENV, env)
        return None

    def worker_env(self) -> dict:
        """Env knobs actor fleets must inherit so every rank arms the
        same anatomy cadence and goodput plane the driver resolved
        (ships in the plugin's base worker env like the
        RLT_COMM*/RLT_PLAN* knobs)."""
        from ray_lightning_tpu.telemetry import anatomy as _anatomy
        from ray_lightning_tpu.telemetry import goodput as _goodput
        out = {}
        every, steps = self.resolved_anatomy()
        if every is not None:
            out[_anatomy.ANATOMY_EVERY_ENV] = str(every)
            out[_anatomy.ANATOMY_STEPS_ENV] = str(steps)
        if not self.resolved_goodput():
            out[_goodput.GOODPUT_ENV] = "0"
        if not self.resolved_incident().enabled:
            # detectors live on the driver, but workers gate their
            # heartbeat sample tail + arm-file polling on the same knob
            from ray_lightning_tpu.telemetry import incident as _incident
            out[_incident.INCIDENT_ENV] = "0"
        tflops = self.resolved_goodput_tflops()
        if tflops is not None:
            out[_goodput.GOODPUT_TFLOPS_ENV] = str(tflops)
        return out

    def resolve_dir(self, default_root_dir: str) -> str:
        if self.dir:
            return self.dir
        try:
            from ray_lightning_tpu.tune.session import get_trial_dir
            trial_dir = get_trial_dir()
        except Exception:
            trial_dir = None
        if trial_dir:
            return os.path.join(trial_dir, "telemetry")
        return os.path.join(default_root_dir, "telemetry")
