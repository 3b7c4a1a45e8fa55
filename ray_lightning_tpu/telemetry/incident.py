"""Incident plane: live timelines, rolling anomaly detectors, auto-RCA.

Every prior observability plane is either instantaneous (``/metrics``,
``/status`` serve the *current* value) or post-hoc (flight rings dump at
death, the driver's ledger compares at merge time).  This module makes
the run watch itself:

- :class:`TimelineStore` — bounded per-(series, rank) ring buffers of
  time-stamped samples for the load-bearing series (step wall, step
  interval, data wait, exposed comm, TTFT/TPOT p99, queue depth,
  goodput fraction, HBM peak), fed from the existing span / heartbeat /
  anatomy / goodput ingest paths and served as ``GET /timeline``
  (telemetry/exporter.py).  Memory is invariant by construction
  (``deque(maxlen=...)`` — the flight.py discipline), including a cap
  on the number of distinct (series, rank) keys.
- :class:`Detector` — rolling-baseline anomaly detection per series:
  median + MAD band over a warmup window, *consecutive*-breach patience
  and post-clear cooldown — the same debounce vocabulary as the serve
  autoscaler (serve/fleet/autoscale.py), because both answer "is this
  signal really moving or just noisy".  Breached samples never enter
  the baseline, so a spike cannot normalize itself.
- :class:`IncidentManager` — a tripped detector opens an
  :class:`Incident` that *arms its own evidence*: it writes the
  incident arm file (workers poll it inside ``anatomy_tick`` and force
  an off-cadence anatomy window — evidence captured AFTER detection,
  not luckily-before), snapshots the goodput ledger, dumps the tripping
  rank's flight ring, pulls the correlated event log (compile,
  snapshot/snapshot_stall, recovery/replay, autoscale, plan), ranks
  probable causes with a named rule per verdict (straggler-rank,
  data-starvation, exposed-comm-growth, compile-storm,
  autoscale-thrash, snapshot-stall, replan-recommended) and dumps
  ``incident_<id>.json``.  Open/closed incidents surface on ``/status``
  and in the export summary; ``rlt_incident_total{series,verdict}`` /
  ``rlt_incident_active`` ride the driver-side metric series.

The detectors run DRIVER-side (ticked from the same poll loops that
call ``watchdog_check``); the arm file is the driver→worker channel —
the same shared-filesystem control-file idiom as the on-demand profile
window (telemetry/tracing.py ``RLT_PROFILE_CONTROL``).

No numpy/jax at module import: this package must stay importable in
worker bootstrap before heavy deps load.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

_log = logging.getLogger(__name__)

#: master switch ("0"/"false" disables the whole plane)
INCIDENT_ENV = "RLT_INCIDENT"
#: per-(series, rank) timeline ring capacity
INCIDENT_CAPACITY_ENV = "RLT_INCIDENT_CAPACITY"
#: baseline samples required before a detector may trip
INCIDENT_WARMUP_ENV = "RLT_INCIDENT_WARMUP"
#: consecutive breached samples required to open (and clear) an incident
INCIDENT_PATIENCE_ENV = "RLT_INCIDENT_PATIENCE"
#: seconds after an incident closes before the same detector may re-trip
INCIDENT_COOLDOWN_ENV = "RLT_INCIDENT_COOLDOWN"
#: MAD band multiplier (bigger = less sensitive)
INCIDENT_MAD_K_ENV = "RLT_INCIDENT_MAD_K"
#: path of the incident arm file workers poll (set by the plugin, like
#: RLT_PROFILE_CONTROL — shared-filesystem backends only)
INCIDENT_CONTROL_ENV = "RLT_INCIDENT_CONTROL"

#: the incident_<id>.json top-level schema (pinned by
#: telemetry/selfcheck.py so the report format cannot drift silently)
INCIDENT_SCHEMA_KEYS = (
    "id", "run_kind", "series", "rank", "state", "verdict",
    "opened_ts", "closed_ts", "trigger", "causes", "evidence",
)

#: detector direction + per-series overrides, armed per run kind.
#: exposed_comm_s and goodput_fraction sample at anatomy/ledger cadence
#: (orders of magnitude sparser than steps), so their warmup/patience
#: are proportionally shorter.
FIT_SERIES: dict[str, tuple[str, dict]] = {
    "step_wall_s": ("high", {}),
    "step_interval_s": ("high", {}),
    "data_wait_s": ("high", {"abs_floor": 0.05}),
    "exposed_comm_s": ("high", {"warmup": 3, "patience": 1}),
    "goodput_fraction": ("low", {"warmup": 4, "patience": 2}),
    "hbm_peak_bytes": ("high", {"rel_floor": 0.10}),
}
SERVE_SERIES: dict[str, tuple[str, dict]] = {
    "ttft_p99_s": ("high", {}),
    "tpot_p99_s": ("high", {}),
    "queue_depth": ("high", {"abs_floor": 4.0}),
    "goodput_fraction": ("low", {"warmup": 4, "patience": 2}),
    "hbm_peak_bytes": ("high", {"rel_floor": 0.10}),
}

#: how far back (seconds) the event log correlates with a fresh incident
EVENT_WINDOW_S = 120.0


def _median(vals: list[float]) -> float:
    s = sorted(vals)
    n = len(s)
    if not n:
        return 0.0
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


# -- timelines -----------------------------------------------------------

class TimelineStore:
    """Bounded per-(series, rank) rings of ``(ts, value)`` samples plus
    one bounded event ring.  ``ts`` is wall-clock (``time.time()``,
    matching span timestamps) so worker- and driver-fed series land on
    one timeline.  Memory is invariant: each ring is a
    ``deque(maxlen=capacity)`` and the number of distinct rings is
    capped (a metric-label-cardinality explosion cannot grow the
    driver)."""

    def __init__(self, capacity: int = 512, max_keys: int = 256,
                 event_capacity: int = 256):
        self.capacity = max(8, int(capacity))
        self.max_keys = max(1, int(max_keys))
        self._lock = threading.Lock()
        self._rings: dict[tuple[str, int], deque] = {}
        self._events: deque = deque(maxlen=max(16, int(event_capacity)))
        self.dropped_keys = 0

    def note(self, series: str, rank: int, value: float,
             ts: Optional[float] = None) -> None:
        key = (str(series), int(rank))
        with self._lock:
            ring = self._rings.get(key)
            if ring is None:
                if len(self._rings) >= self.max_keys:
                    self.dropped_keys += 1
                    return
                ring = self._rings[key] = deque(maxlen=self.capacity)
            ring.append((float(ts if ts is not None else time.time()),
                         float(value)))

    def note_event(self, name: str, ts: Optional[float] = None,
                   **detail: Any) -> None:
        ev = {"ts": float(ts if ts is not None else time.time()),
              "event": str(name)}
        clean = {k: v for k, v in detail.items() if v is not None}
        if clean:
            ev["detail"] = clean
        with self._lock:
            self._events.append(ev)

    def series_names(self) -> list[str]:
        with self._lock:
            return sorted({s for s, _ in self._rings})

    def latest(self, series: str, rank: int) -> Optional[tuple]:
        with self._lock:
            ring = self._rings.get((series, int(rank)))
            return ring[-1] if ring else None

    def samples(self, series: str, rank: int,
                since: Optional[float] = None) -> list[tuple]:
        with self._lock:
            ring = self._rings.get((series, int(rank)))
            out = list(ring) if ring else []
        if since is not None:
            out = [p for p in out if p[0] >= since]
        return out

    def events(self, since: Optional[float] = None) -> list[dict]:
        with self._lock:
            out = list(self._events)
        if since is not None:
            out = [e for e in out if e["ts"] >= since]
        return out

    @staticmethod
    def _downsample(points: list[tuple], limit: int) -> list[list]:
        """At most ``limit`` points, stride-sampled, always keeping the
        newest sample (the one a live dashboard cares about most)."""
        if limit <= 0 or len(points) <= limit:
            return [[round(t, 6), v] for t, v in points]
        stride = -(-len(points) // limit)          # ceil division
        kept = points[::stride]
        if kept[-1] is not points[-1]:
            kept.append(points[-1])
        return [[round(t, 6), v] for t, v in kept]

    def window(self, series: Optional[str] = None,
               rank: Optional[int] = None,
               window_s: Optional[float] = None,
               downsample: int = 0) -> dict:
        """The ``GET /timeline`` document: per-series per-rank sample
        arrays (``[[ts, value], ...]``) plus the event log, optionally
        restricted to one series/rank, the trailing ``window_s``
        seconds, and at most ``downsample`` points per ring."""
        since = time.time() - float(window_s) if window_s else None
        with self._lock:
            keys = sorted(self._rings)
        doc: dict[str, Any] = {"series": {}, "events": []}
        for s, r in keys:
            if series is not None and s != series:
                continue
            if rank is not None and r != int(rank):
                continue
            pts = self.samples(s, r, since=since)
            if not pts:
                continue
            doc["series"].setdefault(s, {})[str(r)] = \
                self._downsample(pts, int(downsample))
        doc["events"] = self.events(since=since)
        doc["dropped_keys"] = self.dropped_keys
        return doc

    def stats(self) -> dict:
        with self._lock:
            return {"keys": len(self._rings), "capacity": self.capacity,
                    "max_keys": self.max_keys,
                    "events": len(self._events),
                    "dropped_keys": self.dropped_keys}


# -- detectors -----------------------------------------------------------

@dataclass
class DetectorConfig:
    """One series' anomaly policy (autoscale.py vocabulary: a breach
    must hold ``patience`` CONSECUTIVE samples to open, a clear must
    hold ``patience`` samples to close, and after closing the detector
    is quiet for ``cooldown_s``)."""

    direction: str = "high"          # "high": spikes are bad; "low": dips
    warmup: int = 16                 # baseline samples before arming
    baseline: int = 64               # rolling baseline window size
    patience: int = 3
    cooldown_s: float = 30.0
    mad_k: float = 6.0               # band = mad_k * 1.4826 * MAD
    rel_floor: float = 0.25          # band >= rel_floor * |median|
    abs_floor: float = 0.0           # band >= abs_floor

    def __post_init__(self):
        if self.direction not in ("high", "low"):
            raise ValueError(f"detector direction {self.direction!r}")
        if self.warmup < 1 or self.patience < 1 or self.baseline < 2:
            raise ValueError("detector warmup/patience/baseline too small")


class Detector:
    """Rolling median+MAD anomaly detector over one (series, rank).

    The breach predicate is monotone by construction (selfcheck pins
    it): for a fixed baseline, if ``v`` breaches a "high" detector then
    every ``v' > v`` breaches too — the band is a threshold, not a
    window, so a worse regression can never be judged healthier."""

    def __init__(self, series: str, rank: int, cfg: DetectorConfig,
                 clock=time.monotonic):
        self.series = series
        self.rank = int(rank)
        self.cfg = cfg
        self._clock = clock
        self._baseline: deque = deque(maxlen=cfg.baseline)
        self._streak = 0
        self._clear_streak = 0
        self._cooldown_until = 0.0
        self.tripped = False
        self.trips = 0

    def band(self) -> Optional[tuple[float, float, float]]:
        """(median, lo, hi) of the current healthy band, or None while
        warming up."""
        vals = list(self._baseline)
        if len(vals) < self.cfg.warmup:
            return None
        med = _median(vals)
        mad = _median([abs(v - med) for v in vals])
        half = max(self.cfg.mad_k * 1.4826 * mad,
                   self.cfg.rel_floor * abs(med), self.cfg.abs_floor)
        return med, med - half, med + half

    def breaches(self, value: float) -> bool:
        b = self.band()
        if b is None:
            return False
        med, lo, hi = b
        return value > hi if self.cfg.direction == "high" else value < lo

    def observe(self, value: float,
                ts: Optional[float] = None) -> Optional[dict]:
        """Feed one sample.  Returns ``{"transition": "opened", ...}``
        when the patience streak fills, ``{"transition": "closed", ...}``
        when a tripped detector sees ``patience`` healthy samples, else
        None.  Breached samples never enter the baseline — an anomaly
        must not normalize itself into the definition of healthy."""
        value = float(value)
        now = self._clock()
        breach = self.breaches(value)
        b = self.band()
        if not breach:
            self._baseline.append(value)
        if not self.tripped:
            if breach and now >= self._cooldown_until:
                self._streak += 1
                if self._streak >= self.cfg.patience:
                    self.tripped = True
                    self.trips += 1
                    self._streak = 0
                    self._clear_streak = 0
                    med, lo, hi = b
                    return {"transition": "opened", "value": value,
                            "ts": ts, "median": med,
                            "band": [lo, hi],
                            "direction": self.cfg.direction,
                            "patience": self.cfg.patience}
            else:
                self._streak = 0
            return None
        # tripped: wait for the signal to actually recover
        if breach:
            self._clear_streak = 0
            return None
        self._clear_streak += 1
        if self._clear_streak < self.cfg.patience:
            return None
        self.tripped = False
        self._clear_streak = 0
        self._cooldown_until = now + self.cfg.cooldown_s
        out = {"transition": "closed", "value": value, "ts": ts}
        if b is not None:
            out["median"] = b[0]
            out["band"] = [b[1], b[2]]
        return out

    @property
    def in_cooldown(self) -> bool:
        return self._clock() < self._cooldown_until

    def stats(self) -> dict:
        return {"series": self.series, "rank": self.rank,
                "tripped": self.tripped, "trips": self.trips,
                "samples": len(self._baseline),
                "streak": self._streak,
                "in_cooldown": self.in_cooldown}


# -- incidents -----------------------------------------------------------

@dataclass
class IncidentConfig:
    """Driver-side incident-plane knobs (TelemetryConfig fields merged
    with the ``RLT_INCIDENT*`` env — env wins, the TelemetryConfig
    precedence rule)."""

    enabled: bool = True
    capacity: int = 512
    warmup: int = 16
    patience: int = 3
    cooldown_s: float = 30.0
    mad_k: float = 6.0
    #: steps of the evidence anatomy window an open incident arms
    arm_steps: int = 4
    #: retained incident objects (oldest closed evicted past this)
    max_incidents: int = 64

    @classmethod
    def from_env(cls, base: "Optional[IncidentConfig]" = None) \
            -> "IncidentConfig":
        cfg = base if base is not None else cls()
        env = os.environ
        if env.get(INCIDENT_ENV, "").strip().lower() in ("0", "false"):
            cfg = IncidentConfig(**{**cfg.__dict__, "enabled": False})
            return cfg
        kw = dict(cfg.__dict__)
        for env_name, key, cast in (
                (INCIDENT_CAPACITY_ENV, "capacity", int),
                (INCIDENT_WARMUP_ENV, "warmup", int),
                (INCIDENT_PATIENCE_ENV, "patience", int),
                (INCIDENT_COOLDOWN_ENV, "cooldown_s", float),
                (INCIDENT_MAD_K_ENV, "mad_k", float)):
            raw = env.get(env_name, "").strip()
            if not raw:
                continue
            try:
                kw[key] = cast(raw)
            except ValueError:
                _log.warning("%s=%r is not a %s; ignored",
                             env_name, raw, cast.__name__)
        return IncidentConfig(**kw)


@dataclass
class Incident:
    """One detected anomaly with its armed evidence and cause ranking."""

    id: str
    run_kind: str
    series: str
    rank: int
    opened_ts: float
    trigger: dict
    state: str = "open"
    closed_ts: Optional[float] = None
    verdict: str = "unattributed"
    causes: list = field(default_factory=list)
    evidence: dict = field(default_factory=dict)
    path: Optional[str] = None

    def to_dict(self) -> dict:
        return {
            "id": self.id, "run_kind": self.run_kind,
            "series": self.series, "rank": self.rank,
            "state": self.state, "verdict": self.verdict,
            "opened_ts": round(self.opened_ts, 6),
            "closed_ts": (round(self.closed_ts, 6)
                          if self.closed_ts is not None else None),
            "trigger": self.trigger, "causes": self.causes,
            "evidence": self.evidence,
        }

    def brief(self) -> dict:
        return {"id": self.id, "series": self.series, "rank": self.rank,
                "state": self.state, "verdict": self.verdict,
                "opened_ts": round(self.opened_ts, 3),
                "closed_ts": (round(self.closed_ts, 3)
                              if self.closed_ts is not None else None),
                "path": self.path}


# -- arm file: the driver→worker "capture evidence NOW" channel ----------

def write_arm_file(path: str, incident_id: str, steps: int) -> bool:
    """Atomically write the incident arm file (driver side).  Workers
    polling it (:class:`ArmWatcher` inside ``anatomy_tick``) force an
    off-cadence anatomy window.  Never raises."""
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"id": incident_id, "steps": int(steps),
                       "ts": time.time()}, f)
        os.replace(tmp, path)
        return True
    except OSError:
        _log.debug("incident arm file write failed", exc_info=True)
        return False


class ArmWatcher:
    """Worker-side throttled poll of the arm file: yields each arm
    request exactly once per id (the tracing.py _FilePoller idiom)."""

    def __init__(self, path: str, min_poll: float = 0.25,
                 clock=time.monotonic):
        self.path = path
        self.min_poll = min_poll
        self._clock = clock
        self._next_poll = 0.0
        self._seen: set[str] = set()

    def poll(self) -> Optional[dict]:
        now = self._clock()
        if now < self._next_poll:
            return None
        self._next_poll = now + self.min_poll
        try:
            with open(self.path) as f:
                ctl = json.load(f)
        except (OSError, ValueError):
            return None
        iid = str(ctl.get("id", ""))
        if not iid or iid in self._seen:
            return None
        self._seen.add(iid)
        return ctl


# -- cause rules ---------------------------------------------------------

def _recent_vs_prior(samples: list[tuple], split_ts: float) \
        -> Optional[tuple[float, float]]:
    """(prior median, recent median) of a series around ``split_ts``."""
    prior = [v for t, v in samples if t < split_ts]
    recent = [v for t, v in samples if t >= split_ts]
    if len(prior) < 2 or not recent:
        return None
    return _median(prior), _median(recent)


def rule_straggler_rank(incident: Incident, timeline: TimelineStore,
                        events: list[dict]) -> Optional[dict]:
    """Measured (anatomy-backed) straggler attribution: when the armed
    window shows one rank with markedly LESS exposed-comm share than
    its peers, that rank is the one everyone else waits for — a slow
    rank never waits in the collective, its peers do.  High host share
    on the named rank corroborates (the stall is host-side)."""
    per_rank = (incident.evidence.get("anatomy") or {})
    if len(per_rank) < 2:
        return None
    shares = {}
    hosts = {}
    for r, a in per_rank.items():
        wall = float(a.get("wall_s") or 0.0)
        if wall <= 0:
            continue
        shares[int(r)] = float(a.get("exposed_s") or 0.0) / wall
        hosts[int(r)] = float(a.get("host_s") or 0.0) / wall
    if len(shares) < 2:
        return None
    straggler = min(shares, key=shares.get)
    skew = max(shares.values()) - shares[straggler]
    if skew < 0.05:
        return None
    return {"rule": "straggler-rank", "score": round(2.0 + skew, 4),
            "detail": {"rank": straggler,
                       "exposed_share": {str(r): round(v, 4)
                                         for r, v in shares.items()},
                       "host_share": {str(r): round(v, 4)
                                      for r, v in hosts.items()}}}


def rule_data_starvation(incident: Incident, timeline: TimelineStore,
                         events: list[dict]) -> Optional[dict]:
    """data_wait grew vs its pre-incident level on some rank: the input
    pipeline, not the device, is the bottleneck."""
    best = None
    for series, rank in [("data_wait_s", r) for r in range(-1, 64)]:
        samples = timeline.samples(series, rank)
        if not samples:
            continue
        split = _recent_vs_prior(samples, incident.opened_ts - 1.0)
        if split is None:
            continue
        prior, recent = split
        if recent > max(2.0 * prior, prior + 0.05):
            score = 1.0 + min(4.0, recent / max(prior, 1e-6)) / 4.0
            if best is None or score > best["score"]:
                best = {"rule": "data-starvation",
                        "score": round(score, 4),
                        "detail": {"rank": rank,
                                   "prior_median_s": round(prior, 6),
                                   "recent_median_s": round(recent, 6)}}
    return best


def rule_exposed_comm_growth(incident: Incident, timeline: TimelineStore,
                             events: list[dict]) -> Optional[dict]:
    """Measured exposed-comm grew vs its pre-incident level — the
    collectives stopped hiding behind compute."""
    best = None
    for rank in range(-1, 64):
        samples = timeline.samples("exposed_comm_s", rank)
        if not samples:
            continue
        split = _recent_vs_prior(samples, incident.opened_ts - 1.0)
        if split is None:
            continue
        prior, recent = split
        if recent > max(1.5 * prior, prior + 1e-4):
            score = 0.9 + min(4.0, recent / max(prior, 1e-9)) / 5.0
            if best is None or score > best["score"]:
                best = {"rule": "exposed-comm-growth",
                        "score": round(score, 4),
                        "detail": {"rank": rank,
                                   "prior_median_s": round(prior, 6),
                                   "recent_median_s": round(recent, 6)}}
    return best


def rule_compile_storm(incident: Incident, timeline: TimelineStore,
                       events: list[dict]) -> Optional[dict]:
    """Repeated recompiles inside the correlation window: shape churn /
    cache misses are eating the step budget."""
    compiles = [e for e in events if e["event"] == "compile"]
    if len(compiles) < 3:
        return None
    return {"rule": "compile-storm",
            "score": round(1.2 + 0.1 * len(compiles), 4),
            "detail": {"compiles": len(compiles),
                       "window_s": EVENT_WINDOW_S}}


def rule_autoscale_thrash(incident: Incident, timeline: TimelineStore,
                          events: list[dict]) -> Optional[dict]:
    """Opposing autoscale actuations inside the window: the fleet is
    oscillating, and every actuation pays a spawn/drain tax."""
    acts = [((e.get("detail") or {}).get("action") or "")
            for e in events if e["event"] == "autoscale"]
    if len(acts) < 2 or len({a for a in acts if a}) < 2:
        return None
    return {"rule": "autoscale-thrash",
            "score": round(1.1 + 0.1 * len(acts), 4),
            "detail": {"actuations": len(acts), "actions": acts[-6:]}}


def rule_snapshot_stall(incident: Incident, timeline: TimelineStore,
                        events: list[dict]) -> Optional[dict]:
    """A snapshot write stalled the step loop inside the window."""
    stalls = [e for e in events if e["event"] == "snapshot_stall"]
    if not stalls:
        return None
    seconds = sum(float((e.get("detail") or {}).get("seconds") or 0.0)
                  for e in stalls)
    return {"rule": "snapshot-stall",
            "score": round(1.3 + min(1.0, seconds), 4),
            "detail": {"stalls": len(stalls),
                       "stall_seconds": round(seconds, 6)}}


CAUSE_RULES = (
    rule_straggler_rank,
    rule_data_starvation,
    rule_exposed_comm_growth,
    rule_compile_storm,
    rule_autoscale_thrash,
    rule_snapshot_stall,
)


# -- the manager ---------------------------------------------------------

class IncidentManager:
    """Driver-resident incident lifecycle: detectors over the timeline
    feed, evidence arming on open, cause ranking, ``incident_<id>.json``
    dumps, and the /status + /metrics surfaces.  Owned by the
    :class:`~ray_lightning_tpu.telemetry.aggregator.TelemetryAggregator`
    and ticked from the driver poll loops (never from a hot step)."""

    def __init__(self, out_dir: str, cfg: Optional[IncidentConfig] = None,
                 run_kind: str = "fit", clock=time.monotonic,
                 timeline: Optional[TimelineStore] = None,
                 flight_hook: Optional[Callable[[int, str],
                                               Optional[str]]] = None):
        self.cfg = cfg if cfg is not None else IncidentConfig.from_env()
        self.out_dir = out_dir
        self.run_kind = run_kind
        self._clock = clock
        self.timeline = timeline if timeline is not None else \
            TimelineStore(capacity=self.cfg.capacity)
        #: called with (rank, cause) to dump that rank's flight ring
        self.flight_hook = flight_hook
        #: arm-file path (plugins set this; None = in-process arm only)
        self.arm_path: Optional[str] = None
        self._lock = threading.Lock()
        self._detectors: dict[tuple[str, int], Detector] = {}
        self._incidents: list[Incident] = []
        self._counts: dict[tuple[str, str], int] = {}   # (series, verdict)
        self._last_sample_ts: dict[tuple[str, int], float] = {}
        self._goodput_latest: Optional[dict] = None
        self._series = FIT_SERIES if run_kind == "fit" else SERVE_SERIES

    # -- feeds ----------------------------------------------------------

    def _detector(self, series: str, rank: int) -> Optional[Detector]:
        spec = self._series.get(series)
        if spec is None:
            return None
        key = (series, int(rank))
        det = self._detectors.get(key)
        if det is None:
            direction, over = spec
            det = Detector(series, rank, DetectorConfig(
                direction=direction,
                warmup=over.get("warmup", self.cfg.warmup),
                patience=over.get("patience", self.cfg.patience),
                cooldown_s=over.get("cooldown_s", self.cfg.cooldown_s),
                mad_k=over.get("mad_k", self.cfg.mad_k),
                rel_floor=over.get("rel_floor", 0.25),
                abs_floor=over.get("abs_floor", 0.0),
            ), clock=self._clock)
            self._detectors[key] = det
        return det

    def note_sample(self, series: str, rank: int, value: float,
                    ts: Optional[float] = None) -> None:
        """One timeline sample: record it and tick that series' detector
        (opening/closing incidents on transitions).  The single entry
        point every aggregator ingest path calls."""
        if not self.cfg.enabled:
            return
        ts = float(ts if ts is not None else time.time())
        self.timeline.note(series, rank, value, ts=ts)
        with self._lock:
            self._last_sample_ts[(series, int(rank))] = ts
            det = self._detector(series, rank)
            if det is None:
                return
            transition = det.observe(value, ts=ts)
        if transition is None:
            return
        if transition.pop("transition") == "opened":
            self._open(series, int(rank), transition)
        else:
            self._close(series, int(rank), transition)

    def note_tail(self, rank: int, samples: Any) -> None:
        """Heartbeat-carried rolling sample tail (telemetry/heartbeat.py)
        — the backstop feed that keeps detectors ticking when span
        batches are dropped under backpressure.  Entries already seen
        via the span path are skipped by timestamp watermark (the span
        feed and the tail describe the same underlying steps)."""
        if not isinstance(samples, (list, tuple)):
            return
        for s in samples:
            try:
                series = str(s["s"])
                ts = float(s["ts"])
                value = float(s["v"])
            except (KeyError, TypeError, ValueError):
                continue
            with self._lock:
                seen = self._last_sample_ts.get((series, int(rank)), 0.0)
            # 50ms slack: a span's end timestamp and the worker-side
            # hook's own clock read for the same step differ by the
            # code between them, not by a real new sample
            if ts <= seen + 0.05:
                continue
            self.note_sample(series, rank, value, ts=ts)

    def note_event(self, name: str, ts: Optional[float] = None,
                   **detail: Any) -> None:
        if not self.cfg.enabled:
            return
        self.timeline.note_event(name, ts=ts, **detail)

    def note_anatomy(self, rank: int, anatomy: dict,
                     capture_dir: Optional[str] = None) -> None:
        """Anatomy window evidence: feed the exposed-comm series and
        attach the per-rank breakdown to every open incident (windows
        arriving after open are exactly the evidence the incident
        armed)."""
        if not self.cfg.enabled or not anatomy:
            return
        exposed = anatomy.get("exposed_s")
        if exposed is not None:
            self.note_sample("exposed_comm_s", rank, float(exposed))
        with self._lock:
            open_incidents = [i for i in self._incidents
                              if i.state == "open"]
        for inc in open_incidents:
            ev = inc.evidence
            ev.setdefault("anatomy", {})[str(rank)] = dict(anatomy)
            if capture_dir:
                ev["anatomy_dir"] = capture_dir
            self._rank_causes(inc)
            self._dump(inc)

    def note_goodput(self, doc: dict) -> None:
        if not self.cfg.enabled or not isinstance(doc, dict):
            return
        with self._lock:
            self._goodput_latest = dict(doc)
        frac = doc.get("goodput_fraction")
        if frac is not None:
            self.note_sample("goodput_fraction", -1, float(frac))

    def note_divergence(self, observed: dict,
                        band: float = 0.5) -> Optional[Incident]:
        """ROADMAP 5(a) leg: the plan's modeled comm diverged from the
        anatomy-measured exposed comm past ``band`` (relative) — open a
        ``replan-recommended`` incident so the operator (or a future
        re-planning loop) knows the placement decision is stale."""
        if not self.cfg.enabled:
            return None
        ratio = observed.get("ratio")
        if ratio is None:
            return None
        if abs(float(ratio) - 1.0) <= band:
            return None
        inc = self._open("plan_divergence", -1, {
            "value": float(ratio), "median": 1.0,
            "band": [1.0 - band, 1.0 + band], "direction": "high",
            "patience": 1},
            verdict="replan-recommended",
            causes=[{"rule": "replan-recommended",
                     "score": round(abs(float(ratio) - 1.0), 4),
                     "detail": dict(observed)}])
        return inc

    # -- lifecycle ------------------------------------------------------

    def _open(self, series: str, rank: int, trigger: dict,
              verdict: Optional[str] = None,
              causes: Optional[list] = None) -> Incident:
        now_wall = time.time()
        inc = Incident(
            id=uuid.uuid4().hex[:8], run_kind=self.run_kind,
            series=series, rank=rank, opened_ts=now_wall,
            trigger={k: v for k, v in trigger.items() if v is not None})
        with self._lock:
            self._incidents.append(inc)
            # bounded retention: evict oldest CLOSED incidents first
            while len(self._incidents) > self.cfg.max_incidents:
                closed = next((i for i in self._incidents
                               if i.state == "closed"), None)
                self._incidents.remove(closed or self._incidents[0])
        # evidence arming, in order of perishability:
        # 1. flight ring of the tripping rank (it is overwriting itself)
        if self.flight_hook is not None and rank >= 0:
            try:
                path = self.flight_hook(
                    rank, f"incident {inc.id}: {series} anomaly")
                if path:
                    inc.evidence["flight_dumps"] = {str(rank): path}
            except Exception:
                _log.debug("incident flight dump failed", exc_info=True)
        # 2. an anatomy window (captured AFTER detection — the arm file
        #    forces the workers' next anatomy_tick off-cadence; an
        #    in-process controller is armed directly)
        if verdict is None:
            inc.evidence["anatomy_armed"] = self._arm_anatomy(inc.id)
        # 3. goodput ledger snapshot (closed incidents report the delta)
        with self._lock:
            if self._goodput_latest is not None:
                inc.evidence["goodput_open"] = dict(self._goodput_latest)
        # 4. the correlated event log
        inc.evidence["events"] = self.timeline.events(
            since=now_wall - EVENT_WINDOW_S)
        if causes is not None:
            inc.causes = causes
            inc.verdict = verdict or "unattributed"
            # explicit verdict (note_divergence): the cause IS the
            # trigger — rule re-ranking must never clobber it
            inc.pinned = True
            self._count(inc)
        else:
            # count first under the provisional verdict; _rank_causes
            # moves the count when a rule names a better one
            self._count(inc)
            self._rank_causes(inc)
        self.note_event("incident_open", id=inc.id, series=series,
                        rank=rank)
        self._dump(inc)
        _log.warning(
            "incident %s OPEN: %s anomaly on rank %d (value %.6g vs "
            "healthy median %.6g) -> %s", inc.id, series, rank,
            trigger.get("value", float("nan")),
            trigger.get("median", float("nan")), inc.path)
        return inc

    def _close(self, series: str, rank: int, transition: dict) -> None:
        with self._lock:
            inc = next((i for i in reversed(self._incidents)
                        if i.state == "open" and i.series == series
                        and i.rank == rank), None)
        if inc is None:
            return
        self._finalize(inc, transition)

    def _finalize(self, inc: Incident, transition: dict) -> None:
        inc.state = "closed"
        inc.closed_ts = time.time()
        inc.trigger["cleared"] = {k: v for k, v in transition.items()
                                 if v is not None}
        with self._lock:
            gp = self._goodput_latest
        opened_gp = inc.evidence.get("goodput_open")
        if gp and opened_gp:
            delta = {}
            for bucket, v in (gp.get("buckets") or {}).items():
                before = (opened_gp.get("buckets") or {}).get(bucket, 0.0)
                d = float(v) - float(before)
                if abs(d) > 1e-9:
                    delta[bucket] = round(d, 6)
            inc.evidence["goodput_delta"] = delta
        self._rank_causes(inc)
        self.note_event("incident_close", id=inc.id, series=inc.series,
                        rank=inc.rank)
        self._dump(inc)
        _log.warning("incident %s CLOSED after %.1fs (verdict %s)",
                     inc.id, inc.closed_ts - inc.opened_ts, inc.verdict)

    def _arm_anatomy(self, incident_id: str) -> bool:
        armed = False
        if self.arm_path:
            armed = write_arm_file(self.arm_path, incident_id,
                                   self.cfg.arm_steps)
        try:
            from ray_lightning_tpu.telemetry.anatomy import (
                get_anatomy_controller)
            ctl = get_anatomy_controller()
            if ctl is not None:
                ctl.arm_now(tag=f"incident-{incident_id}")
                armed = True
        except Exception:
            _log.debug("in-process anatomy arm failed", exc_info=True)
        return armed

    def _rank_causes(self, inc: Incident) -> None:
        if getattr(inc, "pinned", False):
            return
        events = inc.evidence.get("events", []) + self.timeline.events(
            since=inc.opened_ts)
        ranked = []
        for rule in CAUSE_RULES:
            try:
                hit = rule(inc, self.timeline, events)
            except Exception:
                _log.debug("cause rule %s failed", rule.__name__,
                           exc_info=True)
                hit = None
            if hit is not None:
                ranked.append(hit)
        ranked.sort(key=lambda c: -c["score"])
        inc.causes = ranked
        new_verdict = ranked[0]["rule"] if ranked else "unattributed"
        if new_verdict != inc.verdict:
            with self._lock:
                key = (inc.series, inc.verdict)
                if self._counts.get(key):
                    self._counts[key] -= 1
                self._counts[(inc.series, new_verdict)] = \
                    self._counts.get((inc.series, new_verdict), 0) + 1
            inc.verdict = new_verdict

    def _count(self, inc: Incident) -> None:
        with self._lock:
            key = (inc.series, inc.verdict)
            self._counts[key] = self._counts.get(key, 0) + 1

    def _dump(self, inc: Incident) -> None:
        try:
            os.makedirs(self.out_dir, exist_ok=True)
            path = os.path.join(self.out_dir, f"incident_{inc.id}.json")
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(inc.to_dict(), f, indent=1)
            os.replace(tmp, path)
            inc.path = path
        except OSError:
            _log.debug("incident dump failed", exc_info=True)

    def close_all(self, reason: str = "run_end") -> None:
        """Export-time sweep: an incident whose series simply stopped
        arriving (the run ended) closes with the reason on record."""
        with self._lock:
            open_incidents = [i for i in self._incidents
                              if i.state == "open"]
        for inc in open_incidents:
            self._finalize(inc, {"reason": reason})

    # -- surfaces -------------------------------------------------------

    @property
    def open_incidents(self) -> list[Incident]:
        with self._lock:
            return [i for i in self._incidents if i.state == "open"]

    @property
    def incidents(self) -> list[Incident]:
        with self._lock:
            return list(self._incidents)

    def stats(self) -> dict:
        """The ``incidents`` section of /status and the export summary."""
        with self._lock:
            incidents = list(self._incidents)
            counts = dict(self._counts)
        if not self.cfg.enabled:
            return {"enabled": False}
        return {
            "enabled": True,
            "open": [i.brief() for i in incidents if i.state == "open"],
            "recent": [i.brief() for i in incidents[-8:]],
            "total": len(incidents),
            "by_verdict": {f"{s}/{v}": n
                           for (s, v), n in sorted(counts.items()) if n},
            "detectors": [d.stats() for d in self._detectors.values()
                          if d.stats()["samples"] or d.tripped],
            "timeline": self.timeline.stats(),
        }

    def metric_samples(self) -> list[dict]:
        """Driver-side metric series merged into the aggregator's rank
        ``-1`` window: ``rlt_incident_total{series,verdict}`` and
        ``rlt_incident_active``."""
        if not self.cfg.enabled:
            return []
        with self._lock:
            counts = dict(self._counts)
            active = sum(1 for i in self._incidents if i.state == "open")
        out = [{"name": "rlt_incident_total", "type": "counter",
                "labels": {"series": s, "verdict": v}, "value": n}
               for (s, v), n in sorted(counts.items()) if n]
        out.append({"name": "rlt_incident_active", "type": "gauge",
                    "labels": {}, "value": active})
        return out


__all__ = [
    "INCIDENT_ENV",
    "INCIDENT_CAPACITY_ENV",
    "INCIDENT_WARMUP_ENV",
    "INCIDENT_PATIENCE_ENV",
    "INCIDENT_COOLDOWN_ENV",
    "INCIDENT_MAD_K_ENV",
    "INCIDENT_CONTROL_ENV",
    "INCIDENT_SCHEMA_KEYS",
    "FIT_SERIES",
    "SERVE_SERIES",
    "TimelineStore",
    "DetectorConfig",
    "Detector",
    "IncidentConfig",
    "Incident",
    "IncidentManager",
    "ArmWatcher",
    "write_arm_file",
]
