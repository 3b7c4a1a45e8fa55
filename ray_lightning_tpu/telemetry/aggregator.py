"""Driver-side telemetry aggregation, watchdog and trace export.

Workers stream batched span/counter records and heartbeats through the
existing worker→driver queue (``{type: queue}`` frames under the
built-in backend, ``ray.util.queue`` under Ray — cluster/protocol.py);
``process_results`` routes every telemetry-marked item here.  The
aggregator:

- merges all ranks into one timeline and exports a Chrome/Perfetto
  ``trace.json`` (one Perfetto "process" per rank) plus a
  ``telemetry.jsonl`` record stream next to the CSVLogger output;
- computes per-rank step-time percentiles and straggler skew
  (max/min of per-rank mean step time);
- ingests per-rank cumulative metrics windows (telemetry/metrics.py),
  keeps the window stream for ``metrics.jsonl``, and derives per-rank /
  per-op collective achieved bandwidth (GiB/s) and HBM peaks into the
  summary — the numbers the live ``/metrics`` exposition
  (telemetry/exporter.py) serves while the run is still going;
- runs the heartbeat watchdog: a rank that was beating and stopped for
  longer than ``heartbeat_timeout`` gets a driver log line naming the
  rank, its last span and heartbeat age — the "which worker wedged"
  diagnosis the reference never had (a straggling host was invisible
  until the whole job stalled, SURVEY.md §5);
- mirrors every ingested batch into the crash flight recorder
  (telemetry/flight.py): bounded per-rank rings dumped as
  ``flight_<rank>.json`` on a wedge verdict, at elastic
  death-classification time, or when the failure diagnosis finds a
  dead process — the black box the normal export path cannot be;
- reassembles per-request span trees from the trace ids the serve
  plane's plan broadcast propagates (telemetry/tracing.py):
  ``request_trees`` groups driver + worker spans by trace id, and
  ``tenant_breakdown`` summarizes per-tenant TTFT/TPOT with queue vs
  prefill vs decode attribution for ``/status``.

The active aggregator is THREAD-local (``set_active``): the builtin
tune runner executes trials on threads, and each trial's
``process_results`` loop must feed its own aggregator.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Any, Optional

_log = logging.getLogger(__name__)

#: marker key on queue items that belong to telemetry, not user relays
TELEMETRY_KEY = "__rlt_telemetry__"


def spans_item(rank: int, records: list[dict], host: Optional[str] = None,
               pid: Optional[int] = None) -> dict:
    """Wire item carrying a batch of span/counter records."""
    return {TELEMETRY_KEY: 1, "kind": "spans", "rank": rank,
            "host": host, "pid": pid or os.getpid(), "records": records}


_local = threading.local()


def set_active(agg: "Optional[TelemetryAggregator]") -> None:
    _local.agg = agg


def get_active() -> "Optional[TelemetryAggregator]":
    return getattr(_local, "agg", None)


class WorkerHeartbeatTimeout(RuntimeError):
    """Raised by the watchdog when ``hard_timeout`` is configured and a
    rank's heartbeats have been silent that long."""


def _percentile(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile over an already-sorted list (numpy-free:
    this package must stay importable before heavy deps load)."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


class TelemetryAggregator:
    """Merge per-rank telemetry; diagnose dead/wedged workers."""

    def __init__(self, out_dir: str, heartbeat_timeout: float = 60.0,
                 hard_timeout: Optional[float] = None,
                 clock=time.monotonic, flight_capacity: int = 256,
                 incident_cfg=None, run_kind: str = "fit"):
        from ray_lightning_tpu.telemetry.flight import FlightRecorder
        from ray_lightning_tpu.telemetry.incident import IncidentManager
        self.out_dir = out_dir
        self.heartbeat_timeout = heartbeat_timeout
        self.hard_timeout = hard_timeout
        self._clock = clock
        #: crash black box: bounded per-rank rings of the most recent
        #: ingested spans/heartbeats, dumpable independently of export
        self.flight = FlightRecorder(out_dir,
                                     span_capacity=flight_capacity)
        #: incident plane (telemetry/incident.py): live timelines +
        #: rolling anomaly detectors + auto-RCA reports, fed from every
        #: ingest path below and ticked at sample arrival (driver-side
        #: poll loops, never a worker hot path)
        self.incidents = IncidentManager(
            out_dir, cfg=incident_cfg, run_kind=run_kind, clock=clock,
            flight_hook=lambda rank, cause: self.flight.dump(
                rank, cause, handle=self._workers.get(rank)))
        #: per-rank (start_ts, k) of the previous step span — the
        #: step_interval_s series (start-to-start cadence) catches a
        #: straggler whose sleep lands BETWEEN its own step spans
        self._prev_step_span: dict[int, tuple] = {}
        self._lock = threading.Lock()
        #: /status memoization: sections recompute only when the ingest
        #: epoch moved (every mutation bumps it); scrapes between
        #: ingests are dictionary lookups
        self._epoch = 0
        self._memo: dict[str, tuple] = {}
        self.memo_recomputes: dict[str, int] = {}
        self._records: list[dict] = []
        #: pid -> {"at": driver clock, "beat": latest beat dict}; keyed
        #: by pid because the backend-level sender may beat before the
        #: worker learns its rank (the beat itself carries the rank)
        self._hb: dict[int, dict] = {}
        self._workers: dict[int, Any] = {}   # rank -> ActorHandle
        self._warned: set[int] = set()
        self._diagnosed = False
        #: metrics plane (telemetry/metrics.py): full window stream for
        #: metrics.jsonl (bounded) + latest cumulative window per rank
        self._metric_windows: list[dict] = []
        self._metric_windows_cap = 20000
        self._metric_windows_dropped = 0
        self._metrics_latest: dict[int, dict] = {}
        self._metrics_first_ts: dict[int, float] = {}
        #: anatomy plane (telemetry/anatomy.py): latest measured
        #: per-step breakdown per rank + total windows ingested
        self._anatomy_latest: dict[int, dict] = {}
        self._anatomy_windows = 0
        #: elastic plane: per-rank liveness verdicts + the cumulative
        #: shrink-to-continue restart count, exported as driver-side
        #: (rank -1) series so /metrics shows FLEET health, not just
        #: driver-log text (rlt_worker_alive / rlt_restarts_total)
        self._fleet_alive: dict[int, int] = {}
        self._restarts = 0
        #: recovery route + driver-side decision seconds of the current
        #: elastic attempt (parity | replay | scratch — elastic/driver)
        self._recovery_mode: Optional[str] = None
        self._recovery_seconds: Optional[float] = None
        #: goodput plane (telemetry/goodput.py): latest finalized run
        #: ledger per rank + the driver-side recovery attribution that
        #: folds into the fleet aggregate (replayed steps become the
        #: ``replay`` badput bucket, decision seconds the ``recovery``
        #: bucket)
        self._goodput_latest: dict[int, dict] = {}
        self._replayed_steps = 0

    # -- memoized section assembly ---------------------------------------

    def _memoized(self, key: str, fn):
        """Recompute ``fn`` only when the ingest epoch moved since its
        last computation — /status scrapes of an idle aggregator cost
        one dict lookup per section, not a full re-aggregation."""
        with self._lock:
            epoch = self._epoch
            hit = self._memo.get(key)
        if hit is not None and hit[0] == epoch:
            return hit[1]
        val = fn()
        with self._lock:
            self._memo[key] = (epoch, val)
            self.memo_recomputes[key] = \
                self.memo_recomputes.get(key, 0) + 1
        return val

    # -- ingestion -------------------------------------------------------

    def register_worker(self, rank: int, handle: Any = None) -> None:
        self._workers[rank] = handle

    def maybe_ingest(self, item: Any) -> bool:
        """Consume a queue payload if it is telemetry; False otherwise
        (the caller then treats it as a normal relay item)."""
        if not (isinstance(item, dict) and item.get(TELEMETRY_KEY)):
            return False
        kind = item.get("kind")
        if kind == "spans":
            self.ingest_records(item.get("rank", -1), item["records"])
        elif kind == "heartbeat":
            self._note_heartbeat(item)
        elif kind == "metrics":
            self.ingest_metrics(item)
        elif kind == "anatomy":
            self.ingest_anatomy(item)
        elif kind == "goodput":
            self.ingest_goodput(item)
        return True

    def ingest_goodput(self, item: dict) -> None:
        """One rank's finalized run-ledger doc (telemetry/goodput.py):
        keep the latest per rank for /status + the export summary, and
        mirror a brief into the flight recorder so a crash's black box
        says where THAT rank's run wall was going."""
        rank = item.get("rank", -1)
        doc = item.get("goodput") or {}
        with self._lock:
            self._goodput_latest[rank] = dict(doc)
            self._epoch += 1
        self.flight.note_goodput(rank, doc)
        self.incidents.note_goodput(doc)

    def set_replayed_steps(self, n: int) -> None:
        """Steps the resumed attempt re-executed after a snapshot-replay
        recovery (elastic/driver.py) — re-attributed from the fleet
        aggregate's ``step`` bucket into ``replay`` badput."""
        with self._lock:
            self._replayed_steps = max(0, int(n))
            self._epoch += 1
        if n:
            self.incidents.note_event("replay", steps=int(n))

    def goodput_stats(self) -> dict:
        """Per-rank run-ledger docs + the fleet aggregate (identity
        ``sum(buckets) == run_wall`` holds on both levels) — the
        ``goodput`` section of /status and the export summary.
        Memoized per ingest epoch."""
        return self._memoized("goodput_stats", self._compute_goodput_stats)

    def _compute_goodput_stats(self) -> dict:
        from ray_lightning_tpu.telemetry import goodput as _goodput
        with self._lock:
            latest = {r: dict(d)
                      for r, d in sorted(self._goodput_latest.items())}
            replayed = self._replayed_steps
            rec_s = self._recovery_seconds
        if not latest:
            return {}
        docs = list(latest.values())
        extra = {}
        if rec_s and docs[0].get("kind") == "fit":
            extra["recovery"] = float(rec_s)
        fleet = _goodput.aggregate(docs, extra_buckets=extra)
        if replayed and fleet:
            fleet = _goodput.reattribute_replay(fleet, replayed)
        return {"per_rank": {str(r): d for r, d in latest.items()},
                "fleet": fleet}

    def ingest_anatomy(self, item: dict) -> None:
        """One rank's compact step anatomy (telemetry/anatomy.py): keep
        the latest per rank for /status + the export summary, and
        mirror it into the flight recorder so a crash's black box
        carries where THAT rank's device time was going."""
        rank = item.get("rank", -1)
        anatomy = item.get("anatomy") or {}
        with self._lock:
            self._anatomy_latest[rank] = dict(anatomy)
            self._anatomy_windows += 1
            self._epoch += 1
        self.flight.note_anatomy(rank, anatomy)
        # incident evidence: a window arriving while an incident is
        # open is exactly the capture that incident armed; the carried
        # dir (incident-armed windows keep theirs) becomes the link
        self.incidents.note_anatomy(rank, anatomy,
                                    capture_dir=item.get("dir"))
        self.incidents.note_event("anatomy", rank=rank,
                                  dir=item.get("dir"))

    def anatomy_stats(self) -> dict:
        """Per-rank measured step anatomy + straggler skew (slowest
        rank's measured step wall / fastest's) — the ``anatomy``
        section of /status and the export summary.  Memoized per
        ingest epoch."""
        return self._memoized("anatomy_stats", self._compute_anatomy_stats)

    def _compute_anatomy_stats(self) -> dict:
        with self._lock:
            latest = {str(r): dict(a)
                      for r, a in sorted(self._anatomy_latest.items())}
            windows = self._anatomy_windows
        if not latest:
            return {}
        out: dict[str, Any] = {"per_rank": latest, "windows": windows}
        walls = [a.get("wall_s", 0.0) for a in latest.values()]
        if len(walls) >= 2 and min(walls) > 0:
            out["straggler_skew"] = round(max(walls) / min(walls), 3)
        return out

    def ingest_metrics(self, item: dict) -> None:
        """One cumulative metrics window from a rank: keep the stream
        (for metrics.jsonl) and the latest state (for /metrics)."""
        rank = item.get("rank", -1)
        with self._lock:
            if len(self._metric_windows) >= self._metric_windows_cap:
                self._metric_windows.pop(0)
                self._metric_windows_dropped += 1
            self._metric_windows.append(item)
            self._metrics_latest[rank] = item
            self._metrics_first_ts.setdefault(
                rank, item.get("ts", time.time()))
            self._epoch += 1
        if self.incidents.cfg.enabled:
            peaks = [float(m.get("value", 0.0))
                     for m in item.get("metrics", ())
                     if m.get("name") == "rlt_hbm_peak_bytes"]
            if self.incidents.run_kind == "fit" and not any(
                    m.get("name") == "rlt_steps_total"
                    and m.get("value", 0) > 0
                    for m in item.get("metrics", ())):
                # before the first step the high-water mark is set-up's
                # (a few hundred MB): a baseline made of those samples
                # calls the train state itself an anomaly (seen on the
                # v5e, PR 21; PERF.md)
                peaks = []
            if peaks:
                self.incidents.note_sample(
                    "hbm_peak_bytes", rank, max(peaks),
                    ts=item.get("ts"))

    def latest_metrics(self) -> dict[int, dict]:
        """rank -> latest cumulative metrics window (exporter surface).
        A synthetic rank ``-1`` window carries the driver's own series
        (fleet liveness, restart count) when any exist — merged with an
        ingested rank ``-1`` window (the serve plane's driver registry)
        rather than clobbering it."""
        with self._lock:
            out = dict(self._metrics_latest)
        drv = self._driver_metrics()
        if drv:
            base = out.get(-1)
            out[-1] = {
                TELEMETRY_KEY: 1, "kind": "metrics", "rank": -1,
                "ts": time.time(),
                "metrics": (list(base.get("metrics", ()))
                            if base else []) + drv,
            }
        return out

    # -- fleet health (elastic plane) ------------------------------------

    def set_restarts(self, n: int) -> None:
        """Cumulative shrink-to-continue restart count — set by the
        plugin on every attempt so the counter survives the per-attempt
        aggregator rebuild (elastic/driver.py)."""
        with self._lock:
            self._restarts = int(n)
            self._epoch += 1

    def set_recovery(self, mode: Optional[str],
                     seconds: Optional[float] = None) -> None:
        """The recovery route the elastic driver chose for this attempt
        (``parity``/``replay``/``scratch``) plus its classification+
        reconstruction seconds — exported as ``rlt_recovery_mode`` /
        ``rlt_recovery_seconds`` driver-side series so the zero-replay
        path is visible on ``/metrics``, not just in the report."""
        with self._lock:
            self._recovery_mode = mode
            self._recovery_seconds = seconds
            self._epoch += 1
        if mode is not None:
            self.incidents.note_event("recovery", mode=mode,
                                      seconds=seconds)

    def note_event(self, name: str, **detail: Any) -> None:
        """One correlated run event (compile, snapshot, snapshot_stall,
        autoscale, plan, …) onto the incident timeline — the log a
        fresh incident pulls as evidence."""
        self.incidents.note_event(name, **detail)
        with self._lock:
            self._epoch += 1

    def note_serve_signals(self, queue_depth: Optional[float] = None,
                           ttft_p99_s: Optional[float] = None,
                           tpot_p99_s: Optional[float] = None) -> None:
        """Serve-plane driver signals (pump peek / fleet autoscaler
        tick): the fleetwide TTFT/TPOT/queue-depth detector feed."""
        if not self.incidents.cfg.enabled:
            return
        if queue_depth is not None:
            self.incidents.note_sample("queue_depth", -1,
                                       float(queue_depth))
        if ttft_p99_s is not None:
            self.incidents.note_sample("ttft_p99_s", -1,
                                       float(ttft_p99_s))
        if tpot_p99_s is not None:
            self.incidents.note_sample("tpot_p99_s", -1,
                                       float(tpot_p99_s))

    def incident_stats(self) -> dict:
        """The ``incidents`` section of /status and the export summary."""
        return self.incidents.stats()

    def timeline_window(self, series: Optional[str] = None,
                        rank: Optional[int] = None,
                        window_s: Optional[float] = None,
                        downsample: int = 0) -> dict:
        """The ``GET /timeline`` document (telemetry/exporter.py)."""
        return self.incidents.timeline.window(
            series=series, rank=rank, window_s=window_s,
            downsample=downsample)

    def note_worker_alive(self, rank: int, alive: bool) -> None:
        v = 1 if alive else 0
        with self._lock:
            # epoch-bump only on a real change: the watchdog re-probes
            # liveness every poll iteration, and an unchanged verdict
            # must not invalidate the memoized /status sections
            if self._fleet_alive.get(rank) != v:
                self._fleet_alive[rank] = v
                self._epoch += 1

    def _update_fleet_health(self, now: float) -> None:
        """Refresh the per-rank liveness gauges: the backend's process
        probe when it can answer, heartbeat age otherwise."""
        with self._lock:
            handles = dict(self._workers)
            beats = {b["beat"].get("rank", -1): now - b["at"]
                     for b in self._hb.values()}
        for rank, handle in handles.items():
            alive = getattr(handle, "alive", lambda: None)() \
                if handle is not None else None
            if alive is None:
                age = beats.get(rank)
                if age is None:
                    continue   # never beat, nothing to say yet
                alive = age <= self.heartbeat_timeout
            self.note_worker_alive(rank, bool(alive))

    def _driver_metrics(self) -> list[dict]:
        goodput = self.goodput_stats()
        incident_samples = self.incidents.metric_samples()
        # a lone all-zero incident gauge is not worth synthesizing a
        # driver window for — only count the plane once it has news
        if len(incident_samples) == 1 \
                and not incident_samples[0]["value"]:
            incident_samples = []
        with self._lock:
            fleet = dict(self._fleet_alive)
            restarts = self._restarts
            rec_mode = self._recovery_mode
            rec_s = self._recovery_seconds
        if not fleet and not restarts and rec_mode is None \
                and not goodput and not incident_samples:
            return []
        out = [{"name": "rlt_worker_alive", "type": "gauge",
                "labels": {"worker": str(rank)}, "value": v}
               for rank, v in sorted(fleet.items())]
        out.append({"name": "rlt_restarts_total", "type": "counter",
                    "labels": {}, "value": restarts})
        if rec_mode is not None:
            out.append({"name": "rlt_recovery_mode", "type": "gauge",
                        "labels": {"mode": rec_mode}, "value": 1})
            if rec_s is not None:
                out.append({"name": "rlt_recovery_seconds",
                            "type": "gauge", "labels": {},
                            "value": rec_s})
        fleet_gp = (goodput or {}).get("fleet") or {}
        if fleet_gp:
            kind = fleet_gp.get("kind", "fit")
            for bucket, seconds in (fleet_gp.get("buckets") or {}).items():
                out.append({"name": "rlt_goodput_seconds",
                            "type": "gauge",
                            "labels": {"bucket": bucket, "kind": kind,
                                       "scope": "fleet"},
                            "value": seconds})
            out.append({"name": "rlt_goodput_fraction", "type": "gauge",
                        "labels": {"kind": kind, "scope": "fleet"},
                        "value": fleet_gp.get("goodput_fraction", 0.0)})
            if fleet_gp.get("mfu") is not None:
                out.append({"name": "rlt_mfu", "type": "gauge",
                            "labels": {"scope": "fleet"},
                            "value": fleet_gp["mfu"]})
        # incident plane: rlt_incident_total{series,verdict} +
        # rlt_incident_active ride the same driver-side rank -1 window
        out.extend(incident_samples)
        return out

    def fleet_health(self) -> dict[int, int]:
        """rank -> 1/0 liveness verdict (tests/status surface)."""
        with self._lock:
            return dict(self._fleet_alive)

    def ingest_records(self, rank: int, records: list[dict]) -> None:
        for r in records:
            r.setdefault("rank", rank)
        with self._lock:
            self._records.extend(records)
            self._epoch += 1
        self.flight.note_records(rank, records)
        if self.incidents.cfg.enabled:
            self._feed_timeline(records)

    def _feed_timeline(self, records: list[dict]) -> None:
        """Span-path timeline feed: per-step wall and data-wait samples
        plus the step-cadence (start-to-start interval) series — the
        interval catches a straggler whose stall lands BETWEEN its own
        step spans (a sleep in a callback inflates no span, but the
        whole fleet's cadence)."""
        inc = self.incidents
        for r in records:
            if r.get("t") != "span":
                continue
            name = r.get("name")
            rk = r.get("rank", -1)
            ts = float(r.get("ts", 0.0))
            dur = float(r.get("dur", 0.0))
            if name == "step":
                k = max(1, int((r.get("attrs") or {}).get("k", 1)))
                inc.note_sample("step_wall_s", rk, dur / k, ts=ts + dur)
                prev = self._prev_step_span.get(rk)
                self._prev_step_span[rk] = (ts, k)
                if prev is not None and ts > prev[0]:
                    inc.note_sample("step_interval_s", rk,
                                    (ts - prev[0]) / prev[1], ts=ts)
            elif name == "data_wait":
                inc.note_sample("data_wait_s", rk, dur, ts=ts + dur)
            elif name == "compile":
                inc.note_event("compile", ts=ts, rank=rk,
                               seconds=round(dur, 6))

    def _note_heartbeat(self, beat: dict) -> None:
        key = beat.get("pid") or beat.get("rank", -1)
        with self._lock:
            self._hb[key] = {"at": self._clock(), "beat": beat}
            # a recovered worker (e.g. un-wedged) re-arms its warning
            self._warned.discard(key)
        self.flight.note_heartbeat(beat)
        self.flight.note_metrics_brief(beat.get("rank", -1),
                                       beat.get("metrics"))
        # detector backstop: the beat's rolling sample tail keeps the
        # timelines ticking when span batches are dropped under
        # backpressure (entries the span path already fed are skipped
        # by timestamp watermark inside note_tail)
        if self.incidents.cfg.enabled and beat.get("samples"):
            self.incidents.note_tail(beat.get("rank", -1),
                                     beat.get("samples"))

    def heartbeats(self) -> dict:
        """Latest beat per worker process, with its current age on the
        driver clock (tests/diagnostics/status endpoint)."""
        now = self._clock()
        with self._lock:
            return {k: {**v, "age": now - v["at"]}
                    for k, v in self._hb.items()}

    def metrics_briefs(self) -> dict[int, dict]:
        """rank -> {step, hbm_bytes, last_collective}: the latest
        heartbeat-carried brief, falling back to values derivable from
        the rank's latest metrics window (in-process runs have metrics
        but no heartbeats)."""
        out: dict[int, dict] = {}
        for rank, item in self.latest_metrics().items():
            brief: dict = {}
            for m in item.get("metrics", ()):
                if m["name"] == "rlt_steps_total":
                    brief["step"] = int(m.get("value", 0))
                elif m["name"] == "rlt_hbm_bytes" and \
                        (m.get("labels") or {}).get("device") == "0":
                    brief["hbm_bytes"] = int(m.get("value", 0))
            if brief:
                out[rank] = brief
        with self._lock:
            beats = [v["beat"] for v in self._hb.values()]
        for beat in beats:
            brief = beat.get("metrics")
            rank = beat.get("rank", -1)
            if brief:
                out.setdefault(rank, {}).update(
                    {k: v for k, v in brief.items() if v is not None})
        return out

    # -- watchdog --------------------------------------------------------

    @staticmethod
    def _describe(beat: dict, age: float) -> str:
        rank = beat.get("rank", -1)
        who = f"rank {rank}" if rank >= 0 else \
            f"unranked worker (actor {beat.get('actor_id')!r})"
        # the heartbeat-carried metrics brief turns "went silent" into
        # "went silent at step N during a reduce_scatter with X GiB HBM
        # in use" — what the rank was doing, not just that it stopped
        extra = ""
        brief = beat.get("metrics") or {}
        if brief.get("step") is not None:
            extra += f", step {brief['step']}"
        if brief.get("hbm_bytes"):
            extra += f", hbm {brief['hbm_bytes'] / 2**30:.2f} GiB"
        if brief.get("last_collective"):
            extra += f", last collective {brief['last_collective']!r}"
        return (f"{who}: last heartbeat {age:.1f}s ago, last span "
                f"{beat.get('last_span')!r}{extra}, "
                f"pid {beat.get('pid')}, host {beat.get('host')}")

    def _alive_note(self, rank: int) -> str:
        handle = self._workers.get(rank)
        alive = getattr(handle, "alive", lambda: None)() \
            if handle is not None else None
        if alive is None:
            return ""
        return ", process alive" if alive else ", process DEAD"

    def watchdog_check(self) -> None:
        """Called from the driver's poll loop: log a diagnosis line the
        first time a rank's heartbeats go silent past the timeout (and
        raise once past ``hard_timeout`` when configured, so a wedged
        collective cannot hang the driver forever)."""
        now = self._clock()
        self._update_fleet_health(now)
        with self._lock:
            snapshot = [(k, v["at"], v["beat"]) for k, v in self._hb.items()]
        for key, at, beat in snapshot:
            age = now - at
            if age <= self.heartbeat_timeout:
                continue
            if key not in self._warned:
                self._warned.add(key)
                _log.warning(
                    "telemetry watchdog: %s%s — worker is dead or wedged "
                    "(heartbeat timeout %.1fs)",
                    self._describe(beat, age),
                    self._alive_note(beat.get("rank", -1)),
                    self.heartbeat_timeout)
                # wedge verdict: dump the rank's black box NOW — a
                # wedged worker will never flush again, so the ring is
                # the only record of what it was doing
                rank = beat.get("rank", -1)
                self.flight.dump(
                    rank,
                    f"watchdog wedge verdict: heartbeat silent "
                    f"{age:.1f}s (timeout {self.heartbeat_timeout:.1f}s)"
                    f"{self._alive_note(rank)}",
                    handle=self._workers.get(rank))
            if self.hard_timeout is not None and age > self.hard_timeout:
                raise WorkerHeartbeatTimeout(
                    f"telemetry watchdog: {self._describe(beat, age)} "
                    f"exceeded hard timeout {self.hard_timeout:.1f}s")

    def log_failure_diagnosis(self) -> None:
        """On a worker failure, log every worker's last-known state once
        — turns 'a future errored' into 'rank 2 died mid-step'."""
        if self._diagnosed:
            return
        self._diagnosed = True
        now = self._clock()
        with self._lock:
            snapshot = [(v["at"], v["beat"]) for v in self._hb.values()]
        if not snapshot:
            return
        lines = [self._describe(beat, now - at) for at, beat in snapshot]
        _log.warning("telemetry: worker state at failure:\n  %s",
                     "\n  ".join(lines))
        # black-box dumps for every rank whose process probe reads dead:
        # the failure that just surfaced on the driver is about to tear
        # the fleet down, and these rings are the last evidence
        for rank, handle in sorted(self._workers.items()):
            alive = getattr(handle, "process_alive", lambda: None)() \
                if handle is not None else None
            if alive is False:
                self.flight.dump(rank, "worker failure: process dead "
                                 "at failure diagnosis", handle=handle)

    def dump_flights(self, ranks, cause: str) -> list:
        """Dump ``flight_<rank>.json`` for each given rank (the elastic
        driver's death-classification hook).  Returns the paths."""
        out = []
        for rank in ranks:
            path = self.flight.dump(rank, cause,
                                    handle=self._workers.get(rank))
            if path:
                out.append(path)
        return out

    # -- analysis --------------------------------------------------------

    def step_stats(self) -> dict:
        """Per-rank step-time percentiles + straggler skew.  Chunked
        dispatch (k steps per span) is normalized to per-step time.
        Memoized per ingest epoch."""
        return self._memoized("step_stats", self._compute_step_stats)

    def _compute_step_stats(self) -> dict:
        per_rank: dict[int, list[float]] = {}
        with self._lock:
            records = list(self._records)
        for r in records:
            if r.get("t") == "span" and r.get("name") == "step":
                k = max(1, int((r.get("attrs") or {}).get("k", 1)))
                per_rank.setdefault(r.get("rank", -1), []).append(
                    r["dur"] * 1000.0 / k)
        out: dict[str, Any] = {"per_rank": {}}
        means = []
        for rank in sorted(per_rank):
            ds = sorted(per_rank[rank])
            mean = sum(ds) / len(ds)
            means.append(mean)
            out["per_rank"][str(rank)] = {
                "steps": len(ds),
                "mean_ms": round(mean, 3),
                "p50_ms": round(_percentile(ds, 50), 3),
                "p90_ms": round(_percentile(ds, 90), 3),
                "p95_ms": round(_percentile(ds, 95), 3),
                "max_ms": round(ds[-1], 3),
            }
        if len(means) >= 2 and min(means) > 0:
            # straggler skew: how much slower the slowest rank's mean
            # step is than the fastest rank's (1.0 = perfectly even)
            out["straggler_skew"] = round(max(means) / min(means), 3)
        return out

    # -- per-request tracing (telemetry/tracing.py) ----------------------

    @staticmethod
    def _span_trace_ids(record: dict) -> list:
        """Trace ids a span belongs to: its own ``trace`` attr plus
        every id in a shared span's ``traces`` map (the serve decode
        advances many requests in one program — the span fans out to
        each of their trees)."""
        attrs = record.get("attrs") or {}
        ids = []
        tid = attrs.get("trace")
        if tid:
            ids.append(str(tid))
        shared = attrs.get("traces")
        if isinstance(shared, dict):
            ids.extend(str(t) for t in shared.values() if t)
        elif isinstance(shared, (list, tuple)):
            ids.extend(str(t) for t in shared if t)
        return ids

    def request_trees(self) -> dict[str, list[dict]]:
        """trace id -> that request's spans (driver + every rank),
        time-ordered: the reassembled queue→prefill→decode→complete
        tree of each request's life."""
        with self._lock:
            records = list(self._records)
        trees: dict[str, list[dict]] = {}
        for r in records:
            if r.get("t") != "span":
                continue
            for tid in self._span_trace_ids(r):
                trees.setdefault(tid, []).append(r)
        for spans_ in trees.values():
            spans_.sort(key=lambda r: (r.get("ts", 0.0),
                                       r.get("depth", 0)))
        return trees

    def tenant_breakdown(self) -> dict[str, dict]:
        """Per-tenant request-latency attribution from the driver-side
        ``request`` summary spans (+ worker ``prefill`` spans joined by
        trace id): TTFT split into queue wait vs prefill, decode time
        and TPOT — the "which phase is slow for WHICH tenant" surface
        on ``/status`` and in the exported summary.  Memoized per
        ingest epoch."""
        return self._memoized("tenant_breakdown",
                              self._compute_tenant_breakdown)

    def _compute_tenant_breakdown(self) -> dict[str, dict]:
        with self._lock:
            records = list(self._records)
        prefill_by_trace: dict[str, float] = {}
        requests: list[tuple[dict, dict]] = []
        for r in records:
            if r.get("t") != "span":
                continue
            attrs = r.get("attrs") or {}
            if r.get("name") == "prefill" and attrs.get("trace") \
                    and r.get("rank", -1) >= 0:
                prefill_by_trace[str(attrs["trace"])] = float(
                    r.get("dur", 0.0))
            elif r.get("name") == "request":
                requests.append((r, attrs))
        out: dict[str, dict] = {}
        acc: dict[str, dict[str, list]] = {}
        for r, attrs in requests:
            tenant = str(attrs.get("tenant", "default"))
            entry = out.setdefault(tenant, {"requests": 0, "failed": 0,
                                            "tokens": 0})
            a = acc.setdefault(tenant, {"queue_wait": [], "ttft": [],
                                        "prefill": [], "decode": [],
                                        "tpot": []})
            entry["requests"] += 1
            entry["tokens"] += int(attrs.get("tokens", 0) or 0)
            if attrs.get("status") == "failed":
                entry["failed"] += 1
            ttft = attrs.get("ttft_s")
            queue = attrs.get("queue_s")
            tpot = attrs.get("tpot_s")
            if queue is not None:
                a["queue_wait"].append(float(queue))
            if ttft is not None:
                a["ttft"].append(float(ttft))
                # decode attribution: everything after the first token
                a["decode"].append(
                    max(0.0, float(r.get("dur", 0.0)) - float(ttft)))
            if tpot is not None:
                a["tpot"].append(float(tpot))
            pf = prefill_by_trace.get(str(attrs.get("trace")))
            if pf is not None:
                a["prefill"].append(pf)
        for tenant, phases in acc.items():
            entry = out[tenant]
            for phase, vals in phases.items():
                vals.sort()
                if not vals:
                    continue
                entry[f"{phase}_p50_ms"] = round(
                    _percentile(vals, 50) * 1e3, 3)
                entry[f"{phase}_p99_ms"] = round(
                    _percentile(vals, 99) * 1e3, 3)
        return out

    # -- metrics derivations ---------------------------------------------

    def _rank_step_seconds(self) -> dict[int, float]:
        """Total recorded step-span time per rank — the bandwidth
        denominator for collectives compiled into the step program."""
        out: dict[int, float] = {}
        with self._lock:
            records = list(self._records)
        for r in records:
            if r.get("t") == "span" and r.get("name") == "step":
                rank = r.get("rank", -1)
                out[rank] = out.get(rank, 0.0) + float(r.get("dur", 0.0))
        return out

    @staticmethod
    def _window_values(item: dict, name: str) -> list[tuple[dict, float]]:
        return [((m.get("labels") or {}), float(m.get("value", 0.0)))
                for m in item.get("metrics", ()) if m["name"] == name]

    def collective_stats(self) -> dict:
        """Per-op byte totals and achieved GiB/s, per rank and summed.

        Denominator preference per (rank, op): measured op seconds
        (host-dispatched collectives record them) → the rank's total
        step-span time (traced in-step collectives overlap with the
        step) → elapsed wall time between the rank's first and latest
        metrics window.  The step/wall denominators make the figure a
        lower bound on fabric bandwidth — the transfer shares the
        denominator with compute — which is exactly the "achieved"
        number a comms optimization must move."""
        step_secs = self._rank_step_seconds()
        latest = self.latest_metrics()
        with self._lock:
            first_ts = dict(self._metrics_first_ts)
        per_op: dict[str, dict] = {}
        for rank, item in latest.items():
            secs_by_op = {labels.get("op"): v for labels, v in
                          self._window_values(
                              item, "rlt_collective_seconds_total")}
            elapsed = max(0.0, item.get("ts", 0.0)
                          - first_ts.get(rank, item.get("ts", 0.0)))
            for labels, nbytes in self._window_values(
                    item, "rlt_collective_bytes_total"):
                op = labels.get("op", "?")
                if nbytes <= 0:
                    continue
                denom = secs_by_op.get(op) or step_secs.get(rank) \
                    or elapsed
                gibs = round(nbytes / denom / 2**30, 6) if denom else None
                entry = per_op.setdefault(
                    op, {"bytes": 0, "gibs": 0.0, "per_rank": {}})
                entry["bytes"] += int(nbytes)
                entry["per_rank"][str(rank)] = {
                    "bytes": int(nbytes), "gibs": gibs}
                if gibs:
                    # ranks move their shares concurrently: job-level
                    # achieved bandwidth is the sum of per-rank rates
                    entry["gibs"] = round(entry["gibs"] + gibs, 6)
        return per_op

    def hbm_stats(self) -> dict[str, int]:
        """Per-rank peak HBM bytes (device 0) from the latest windows."""
        out: dict[str, int] = {}
        for rank, item in self.latest_metrics().items():
            peaks = [v for labels, v in self._window_values(
                item, "rlt_hbm_peak_bytes")]
            if peaks:
                out[str(rank)] = int(max(peaks))
        return out

    def dropped_stats(self) -> dict[str, int]:
        """Per-rank telemetry ring-buffer drop counts — silent data loss
        the summary must surface (a trace with holes must say so)."""
        out: dict[str, int] = {}
        for rank, item in self.latest_metrics().items():
            for _labels, v in self._window_values(
                    item, "rlt_telemetry_dropped_total"):
                if v > 0:
                    out[str(rank)] = int(v)
        if self._metric_windows_dropped:
            out["driver_windows"] = self._metric_windows_dropped
        return out

    # -- export ----------------------------------------------------------

    def _trace_events(self, records: list[dict]) -> list[dict]:
        spans = [r for r in records if r.get("t") in ("span", "counter")]
        if not spans:
            return []
        t0 = min(r["ts"] for r in spans)
        events: list[dict] = []
        for rank in sorted({r.get("rank", -1) for r in spans}):
            events.append({"ph": "M", "name": "process_name", "pid": rank,
                           "args": {"name": f"rank {rank}"}})
        for r in spans:
            base = {"pid": r.get("rank", -1), "tid": 0,
                    "ts": round((r["ts"] - t0) * 1e6, 1)}
            if r["t"] == "span":
                events.append({**base, "ph": "X", "cat": "rlt",
                               "name": r["name"],
                               "dur": round(r["dur"] * 1e6, 1),
                               "args": r.get("attrs") or {}})
            else:
                events.append({**base, "ph": "C", "name": r["name"],
                               "args": {r["name"]: r["value"]}})
        return events

    def export(self) -> dict:
        """Write ``trace.json`` (Chrome/Perfetto), ``telemetry.jsonl``
        and — when any metrics windows arrived — ``metrics.jsonl``
        under ``out_dir``; returns their paths plus the summary dict."""
        os.makedirs(self.out_dir, exist_ok=True)
        trace_path = os.path.join(self.out_dir, "trace.json")
        jsonl_path = os.path.join(self.out_dir, "telemetry.jsonl")
        # an incident whose series simply stopped (the run ended) closes
        # with the reason on record before the summary freezes
        self.incidents.close_all(reason="run_end")
        with self._lock:
            records = list(self._records)
            windows = list(self._metric_windows)
        stats = self.step_stats()
        summary = {
            "t": "summary",
            "records": len(records),
            "ranks": sorted({r.get("rank", -1) for r in records}),
            "step_stats": stats,
        }
        trees = self.request_trees()
        if trees:
            # per-request trace plane: every traced request's span count
            # (the full trees are in trace.json via their trace attrs)
            # plus the per-tenant latency attribution
            summary["requests"] = {
                "traced": len(trees),
                "tenants": self.tenant_breakdown(),
            }
        if self.flight.dumped:
            summary["flight_dumps"] = dict(self.flight.dumped)
        anatomy = self.anatomy_stats()
        if anatomy:
            # measured step-time truth (telemetry/anatomy.py): where
            # device time went per rank, from real profiler captures
            summary["anatomy"] = anatomy
        goodput = self.goodput_stats()
        if goodput:
            # run-time truth (telemetry/goodput.py): the full-run
            # wall-clock partition + measured MFU, per rank and fleet
            summary["goodput"] = goodput
            fleet_gp = goodput.get("fleet") or {}
            # scalar conveniences for bench JSON lines / quick greps
            if "goodput_fraction" in fleet_gp:
                summary["goodput_fraction"] = fleet_gp["goodput_fraction"]
            if fleet_gp.get("mfu") is not None:
                summary["mfu"] = fleet_gp["mfu"]
        incidents = self.incident_stats()
        if incidents.get("total"):
            # incident plane (telemetry/incident.py): detected
            # anomalies with their cause rankings + evidence links
            summary["incidents"] = incidents
        collectives = self.collective_stats()
        hbm = self.hbm_stats()
        dropped = self.dropped_stats()
        if windows:
            summary["metrics"] = {
                "windows": len(windows),
                "collectives": collectives,
                "hbm_peak_bytes": hbm,
                "dropped_records": dropped,
            }
            # scalar conveniences for bench JSON lines / quick greps
            summary["hbm_peak_bytes"] = max(hbm.values()) if hbm else 0
            summary["collective_gibs"] = round(
                sum(v.get("gibs") or 0.0 for v in collectives.values()),
                6)
        if dropped:
            # data loss must be loud: a trace/metrics stream with holes
            # silently reads as "nothing happened there"
            _log.warning(
                "telemetry: ring buffers dropped records (per rank: %s) "
                "— raise TelemetryConfig.capacity or lower flush_every "
                "to capture the full stream", dropped)
        tmp = trace_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"traceEvents": self._trace_events(records),
                       "displayTimeUnit": "ms"}, f)
        os.replace(tmp, trace_path)
        tmp = jsonl_path + ".tmp"
        with open(tmp, "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
            f.write(json.dumps(summary) + "\n")
        os.replace(tmp, jsonl_path)
        out = {"trace": trace_path, "jsonl": jsonl_path,
               "summary": summary}
        if windows:
            metrics_path = os.path.join(self.out_dir, "metrics.jsonl")
            tmp = metrics_path + ".tmp"
            with open(tmp, "w") as f:
                for w in windows:
                    f.write(json.dumps(w) + "\n")
                f.write(json.dumps(summary) + "\n")
            os.replace(tmp, metrics_path)
            out["metrics"] = metrics_path
        skew = stats.get("straggler_skew")
        _log.info(
            "telemetry: %d records from ranks %s -> %s%s", len(records),
            summary["ranks"], trace_path,
            f" (straggler skew {skew})" if skew else "")
        return out
