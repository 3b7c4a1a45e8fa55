"""Incident plane (telemetry/incident.py): bounded timelines, rolling
median+MAD anomaly detectors, and auto-RCA incident reports that arm
their own evidence.

The e2e case mirrors the plane's reason to exist: a 2-worker fit with
an injected bounded straggler (``RLT_FAULT=slow:...,count=N``) must
open an incident AT RUNTIME that names the slow rank with measured
(anatomy-backed) attribution, link its evidence files, and close the
incident once the fault clears — no post-hoc rerun with a profiler.
"""

import json
import os

import pytest

from ray_lightning_tpu import Trainer, telemetry
from ray_lightning_tpu.models import BoringModel
from ray_lightning_tpu.telemetry import TelemetryConfig
from ray_lightning_tpu.telemetry.aggregator import TelemetryAggregator
from ray_lightning_tpu.telemetry.incident import (
    INCIDENT_SCHEMA_KEYS,
    ArmWatcher,
    Detector,
    DetectorConfig,
    IncidentConfig,
    IncidentManager,
    TimelineStore,
    write_arm_file,
)

from tests.utils import cpu_plugin


@pytest.fixture(autouse=True)
def _clean_telemetry():
    yield
    telemetry.disable()
    telemetry.disable_anatomy()
    telemetry.disable_metrics()
    telemetry.set_active(None)


# -- timeline store ------------------------------------------------------

def test_timeline_ring_bounded_memory():
    """The memory invariant: any run length, fixed ring size."""
    tl = TimelineStore(capacity=16)
    for i in range(10_000):
        tl.note("step_wall_s", 0, float(i), ts=float(i))
    pts = tl.samples("step_wall_s", 0)
    assert len(pts) == 16
    # newest samples win (it's a ring, not a head-keep)
    assert pts[-1] == (9999.0, 9999.0)
    assert pts[0] == (9984.0, 9984.0)
    st = tl.stats()
    assert st["keys"] == 1 and st["capacity"] == 16


def test_timeline_key_cardinality_capped():
    """A label-cardinality explosion cannot grow the driver: distinct
    (series, rank) rings are capped, overflow is counted not stored."""
    tl = TimelineStore(capacity=16, max_keys=4)
    for rank in range(10):
        tl.note("ttft_p99_s", rank, 0.5)
    st = tl.stats()
    assert st["keys"] == 4
    assert st["dropped_keys"] == 6
    assert tl.window()["dropped_keys"] == 6


def test_timeline_window_filters_and_downsample():
    tl = TimelineStore(capacity=512)
    for i in range(100):
        tl.note("step_wall_s", 0, float(i), ts=1000.0 + i)
        tl.note("data_wait_s", 1, 0.01, ts=1000.0 + i)
    tl.note_event("compile", ts=1050.0, rank=0, seconds=1.5)
    doc = tl.window(series="step_wall_s", rank=0, downsample=10)
    assert set(doc["series"]) == {"step_wall_s"}
    pts = doc["series"]["step_wall_s"]["0"]
    assert len(pts) <= 11           # stride keep-newest may add one
    assert pts[-1] == [1099.0, 99.0]   # newest sample always kept
    assert doc["events"] and doc["events"][0]["event"] == "compile"
    # unfiltered doc carries both series
    assert set(tl.window()["series"]) == {"step_wall_s", "data_wait_s"}


# -- detectors -----------------------------------------------------------

def _fed(det, values, t):
    out = []
    for v in values:
        t[0] += 1.0
        out.append(det.observe(v, ts=t[0]))
    return out


def test_detector_no_false_trip_flat_and_noisy():
    t = [0.0]
    cfg = DetectorConfig(warmup=8, patience=2, cooldown_s=1.0)
    flat = Detector("step_wall_s", 0, cfg, clock=lambda: t[0])
    assert all(r is None for r in _fed(flat, [0.05] * 50, t))
    assert not flat.tripped
    noisy = Detector("step_wall_s", 1, cfg, clock=lambda: t[0])
    vals = [0.05 + 0.004 * ((i * 13) % 7) / 7 for i in range(50)]
    assert all(r is None for r in _fed(noisy, vals, t))
    assert not noisy.tripped and noisy.trips == 0


def test_detector_trips_on_spike_after_patience():
    t = [0.0]
    det = Detector("step_wall_s", 1,
                   DetectorConfig(warmup=8, patience=3, cooldown_s=1.0),
                   clock=lambda: t[0])
    _fed(det, [0.05] * 12, t)
    # patience 3: two breached samples are noise
    assert _fed(det, [0.5, 0.5], t) == [None, None]
    assert not det.tripped and det._streak == 2
    (ev,) = _fed(det, [0.5], t)
    assert ev["transition"] == "opened"
    assert ev["value"] == 0.5 and ev["direction"] == "high"
    assert ev["band"][0] < 0.05 < ev["band"][1] < 0.5
    assert det.tripped and det.trips == 1
    # a healthy sample mid-streak resets patience (consecutive, not
    # cumulative): pin on a fresh detector
    det2 = Detector("step_wall_s", 2,
                    DetectorConfig(warmup=8, patience=3, cooldown_s=1.0),
                    clock=lambda: t[0])
    _fed(det2, [0.05] * 12, t)
    assert _fed(det2, [0.5, 0.5, 0.05, 0.5, 0.5], t) == [None] * 5
    assert not det2.tripped


def test_detector_close_then_cooldown_state_machine():
    t = [0.0]
    cfg = DetectorConfig(warmup=8, patience=2, cooldown_s=10.0)
    det = Detector("step_wall_s", 0, cfg, clock=lambda: t[0])
    _fed(det, [0.05] * 12, t)
    opened = _fed(det, [0.5, 0.5], t)
    assert opened[-1]["transition"] == "opened"
    # while tripped, breaches keep it open and healthy samples must be
    # consecutive to close
    assert _fed(det, [0.5, 0.05, 0.5], t) == [None] * 3
    assert det.tripped
    closed = _fed(det, [0.05, 0.05], t)
    assert closed[-1]["transition"] == "closed"
    assert not det.tripped and det.in_cooldown
    # inside the cooldown window the same breach cannot re-trip
    assert _fed(det, [0.5, 0.5, 0.5], t) == [None] * 3
    assert det.trips == 1
    # past the cooldown it trips again
    t[0] += cfg.cooldown_s
    reopened = _fed(det, [0.5, 0.5], t)
    assert reopened[-1]["transition"] == "opened"
    assert det.trips == 2


def test_detector_low_direction_dips():
    t = [0.0]
    det = Detector("goodput_fraction", -1,
                   DetectorConfig(direction="low", warmup=4, patience=1),
                   clock=lambda: t[0])
    _fed(det, [0.8] * 6, t)
    assert not det.breaches(2.0)     # high is fine for a "low" detector
    (ev,) = _fed(det, [0.05], t)
    assert ev["transition"] == "opened"


# -- incident manager ----------------------------------------------------

def _manager(tmp_path, t, **cfg_kw):
    kw = dict(warmup=4, patience=2, cooldown_s=0.0)
    kw.update(cfg_kw)
    return IncidentManager(str(tmp_path), cfg=IncidentConfig(**kw),
                           run_kind="fit", clock=lambda: t[0])


def _feed_steps(mgr, t, values, rank=1, t0=100.0):
    for v in values:
        t[0] += 1.0
        mgr.note_sample("step_wall_s", rank, v, ts=t0 + t[0])


def test_manager_open_close_dump_schema(tmp_path):
    t = [0.0]
    mgr = _manager(tmp_path, t)
    _feed_steps(mgr, t, [0.05] * 10)
    assert not mgr.open_incidents
    _feed_steps(mgr, t, [0.5, 0.5])
    (inc,) = mgr.open_incidents
    assert inc.series == "step_wall_s" and inc.rank == 1
    assert inc.path and os.path.exists(inc.path)
    assert os.path.basename(inc.path) == f"incident_{inc.id}.json"
    with open(inc.path) as f:
        doc = json.load(f)
    assert set(doc) == set(INCIDENT_SCHEMA_KEYS)
    assert doc["state"] == "open" and doc["trigger"]["value"] == 0.5
    # recovery closes it and the dump is refreshed in place
    _feed_steps(mgr, t, [0.05, 0.05])
    assert not mgr.open_incidents
    with open(inc.path) as f:
        doc = json.load(f)
    assert doc["state"] == "closed"
    assert doc["closed_ts"] >= doc["opened_ts"]
    assert doc["trigger"]["cleared"]["value"] == 0.05
    # metric surface: one counter row per (series, verdict) + the gauge
    samples = mgr.metric_samples()
    by_name = {m["name"] for m in samples}
    assert by_name == {"rlt_incident_total", "rlt_incident_active"}
    active = [m for m in samples if m["name"] == "rlt_incident_active"]
    assert active[0]["value"] == 0
    total = [m for m in samples if m["name"] == "rlt_incident_total"]
    assert sum(m["value"] for m in total) == 1
    assert total[0]["labels"]["series"] == "step_wall_s"


def test_manager_goodput_delta_and_events_evidence(tmp_path):
    t = [0.0]
    mgr = _manager(tmp_path, t)
    mgr.note_goodput({"goodput_fraction": 0.8,
                      "buckets": {"step": 10.0, "data_wait": 1.0}})
    mgr.note_event("snapshot_stall", seconds=0.25)
    _feed_steps(mgr, t, [0.05] * 10 + [0.5, 0.5])
    (inc,) = mgr.open_incidents
    assert inc.evidence["goodput_open"]["goodput_fraction"] == 0.8
    assert [e["event"] for e in inc.evidence["events"]] == \
        ["snapshot_stall"]
    # the stall inside the window is a ranked cause
    assert inc.verdict == "snapshot-stall", inc.causes
    mgr.note_goodput({"goodput_fraction": 0.5,
                      "buckets": {"step": 12.0, "data_wait": 4.0}})
    _feed_steps(mgr, t, [0.05, 0.05])
    assert inc.state == "closed"
    assert inc.evidence["goodput_delta"] == {"step": 2.0,
                                             "data_wait": 3.0}


def test_manager_anatomy_attribution_names_straggler(tmp_path):
    """The armed window's measured exposed-comm shares attribute the
    incident: the rank that never waits in the collective is the one
    everyone waits FOR."""
    t = [0.0]
    mgr = _manager(tmp_path, t)
    _feed_steps(mgr, t, [0.05] * 10 + [0.5, 0.5])
    (inc,) = mgr.open_incidents
    mgr.note_anatomy(0, {"wall_s": 0.5, "exposed_s": 0.4,
                         "compute_s": 0.05, "host_s": 0.05},
                     capture_dir="/tmp/anat0")
    mgr.note_anatomy(1, {"wall_s": 0.5, "exposed_s": 0.01,
                         "compute_s": 0.05, "host_s": 0.44})
    assert inc.verdict == "straggler-rank", inc.causes
    assert inc.causes[0]["detail"]["rank"] == 1
    assert set(inc.evidence["anatomy"]) == {"0", "1"}
    assert inc.evidence["anatomy_dir"] == "/tmp/anat0"


def test_manager_divergence_and_bounded_retention(tmp_path):
    t = [0.0]
    mgr = _manager(tmp_path, t, max_incidents=3)
    inc = mgr.note_divergence({"ratio": 1.8, "modeled_comm_s": 1.0})
    assert inc is not None and inc.verdict == "replan-recommended"
    assert inc.series == "plan_divergence"
    assert mgr.note_divergence({"ratio": 1.2}) is None   # inside band
    for _ in range(6):
        mgr.note_divergence({"ratio": 3.0})
    assert len(mgr.incidents) == 3      # retention bound holds
    # export-time sweep closes whatever is still open
    mgr.close_all(reason="run_end")
    assert not mgr.open_incidents
    assert all(i.trigger["cleared"]["reason"] == "run_end"
               for i in mgr.incidents)


def test_manager_disabled_is_inert(tmp_path):
    t = [0.0]
    mgr = IncidentManager(str(tmp_path),
                          cfg=IncidentConfig(enabled=False),
                          clock=lambda: t[0])
    _feed_steps(mgr, t, [0.05] * 10 + [9.0] * 5)
    assert not mgr.incidents
    assert mgr.stats() == {"enabled": False}
    assert mgr.metric_samples() == []


def test_heartbeat_tail_deduped_by_watermark(tmp_path):
    """Tail entries the span path already fed (same step, timestamps
    within the 50ms slack) must not double-count; genuinely newer
    entries must land."""
    t = [0.0]
    mgr = _manager(tmp_path, t)
    mgr.note_sample("step_wall_s", 0, 0.05, ts=1000.0)
    mgr.note_tail(0, [
        {"s": "step_wall_s", "ts": 999.5, "v": 0.05},    # older
        {"s": "step_wall_s", "ts": 1000.04, "v": 0.05},  # within slack
        {"s": "step_wall_s", "ts": 1001.0, "v": 0.06},   # new
        {"s": "step_wall_s", "v": 0.07},                 # malformed
    ])
    pts = mgr.timeline.samples("step_wall_s", 0)
    assert [p[0] for p in pts] == [1000.0, 1001.0]
    # and the watermark advanced: replaying the same tail adds nothing
    mgr.note_tail(0, [{"s": "step_wall_s", "ts": 1001.0, "v": 0.06}])
    assert len(mgr.timeline.samples("step_wall_s", 0)) == 2


def test_arm_file_roundtrip_once_per_id(tmp_path):
    path = str(tmp_path / "incident" / "arm.json")
    t = [0.0]
    w = ArmWatcher(path, min_poll=0.25, clock=lambda: t[0])
    assert w.poll() is None                  # no file yet
    assert write_arm_file(path, "abc123", steps=4)
    t[0] += 0.3
    ctl = w.poll()
    assert ctl["id"] == "abc123" and ctl["steps"] == 4
    t[0] += 0.3
    assert w.poll() is None                  # same id: seen
    assert write_arm_file(path, "def456", steps=2)
    t[0] += 0.1
    assert w.poll() is None                  # throttled (min_poll)
    t[0] += 0.25
    assert w.poll()["id"] == "def456"


# -- aggregator integration ---------------------------------------------

def _span(name, rank, ts, dur, **attrs):
    r = {"t": "span", "name": name, "rank": rank, "ts": ts, "dur": dur,
         "depth": 0}
    if attrs:
        r["attrs"] = attrs
    return r


def test_hbm_baseline_starts_with_the_first_step(tmp_path):
    """The high-water mark before the first step is set-up's: a
    baseline made of those samples called the train state itself an
    anomaly (v5e, PR 21).  Windows before a step are not fed."""
    def window(ts, peak, steps):
        metrics = [{"name": "rlt_hbm_peak_bytes", "value": peak,
                    "labels": {"device": "0"}}]
        if steps is not None:
            metrics.append({"name": "rlt_steps_total", "value": steps})
        return {"kind": "metrics", "rank": 0, "ts": ts, "metrics": metrics}

    agg = TelemetryAggregator(str(tmp_path), heartbeat_timeout=60)
    for i in range(20):                      # set-up: 0.4 GB, no step yet
        agg.ingest_metrics(window(1000.0 + i, 4e8, 0 if i % 2 else None))
    assert agg.incidents.timeline.samples("hbm_peak_bytes", 0) == []
    for i in range(40):                      # training: 11.7 GB, flat
        agg.ingest_metrics(window(1020.0 + i, 11.7e9, 10 * (i + 1)))
    assert len(agg.incidents.timeline.samples("hbm_peak_bytes", 0)) == 40
    assert agg.incident_stats()["total"] == 0
    # a real jump over the steady mark still opens one
    for i in range(5):
        agg.ingest_metrics(window(1060.0 + i, 15.5e9, 500 + i))
    assert agg.incident_stats()["total"] == 1
    # a serve fleet's mark is set at start-up and fed from the start
    serve = TelemetryAggregator(str(tmp_path / "serve"),
                                heartbeat_timeout=60, run_kind="serve")
    serve.ingest_metrics(window(1000.0, 6.3e9, None))
    assert len(serve.incidents.timeline.samples("hbm_peak_bytes", 0)) == 1


def test_aggregator_feeds_timeline_from_spans(tmp_path):
    agg = TelemetryAggregator(str(tmp_path), heartbeat_timeout=60)
    for i in range(5):
        agg.ingest_records(0, [
            _span("step", 0, 1000.0 + i, 0.08, k=2),
            _span("data_wait", 0, 1000.5 + i, 0.01),
        ])
    # step wall normalized per-step by the chunk size k
    walls = agg.incidents.timeline.samples("step_wall_s", 0)
    assert len(walls) == 5 and abs(walls[0][1] - 0.04) < 1e-9
    # cadence series: start-to-start deltas, normalized by the PREVIOUS
    # span's k (4 intervals from 5 steps)
    ivals = agg.incidents.timeline.samples("step_interval_s", 0)
    assert len(ivals) == 4 and abs(ivals[0][1] - 0.5) < 1e-9
    assert len(agg.incidents.timeline.samples("data_wait_s", 0)) == 5
    doc = agg.timeline_window(series="step_wall_s", rank=0)
    assert set(doc["series"]) == {"step_wall_s"}
    assert agg.incident_stats()["enabled"] is True


def test_aggregator_status_sections_memoized_per_epoch(tmp_path):
    """Satellite: /status section assembly recomputes only when the
    ingest epoch moved — idle scrapes are dict lookups."""
    agg = TelemetryAggregator(str(tmp_path), heartbeat_timeout=60)
    agg.ingest_records(0, [_span("step", 0, 1000.0, 0.05)])
    first = agg.step_stats()
    assert agg.step_stats() is first            # cached object, no work
    assert agg.memo_recomputes["step_stats"] == 1
    agg.ingest_records(0, [_span("step", 0, 1001.0, 0.05)])
    second = agg.step_stats()
    assert second["per_rank"]["0"]["steps"] == 2
    assert agg.memo_recomputes["step_stats"] == 2
    # the first liveness verdict is a real change (bumps the epoch);
    # the watchdog's re-probes of the SAME verdict must not
    agg.note_worker_alive(0, True)
    third = agg.step_stats()
    recomputes = agg.memo_recomputes["step_stats"]
    epoch_before = agg._epoch
    agg.note_worker_alive(0, True)
    agg.note_worker_alive(0, True)
    assert agg._epoch == epoch_before
    assert agg.step_stats() is third
    assert agg.memo_recomputes["step_stats"] == recomputes


def test_aggregator_serve_signals_and_export_summary(tmp_path):
    agg = TelemetryAggregator(str(tmp_path), heartbeat_timeout=60,
                              run_kind="serve")
    for _ in range(8):
        agg.note_serve_signals(queue_depth=2, ttft_p99_s=0.1,
                               tpot_p99_s=0.02)
    for s in ("queue_depth", "ttft_p99_s", "tpot_p99_s"):
        assert len(agg.incidents.timeline.samples(s, -1)) == 8, s
    # an explicit-verdict incident lands in the export summary and
    # keeps its verdict through the run-end close
    agg.incidents.note_divergence({"ratio": 2.5})
    summary = agg.export()["summary"]
    assert summary["incidents"]["total"] == 1
    assert "plan_divergence/replan-recommended" in \
        summary["incidents"]["by_verdict"]
    assert not agg.incidents.open_incidents    # export closes the run


# -- config resolution ---------------------------------------------------

def test_resolved_incident_env_precedence(monkeypatch):
    from ray_lightning_tpu.telemetry import incident as inc_mod

    for k in (inc_mod.INCIDENT_ENV, inc_mod.INCIDENT_WARMUP_ENV,
              inc_mod.INCIDENT_PATIENCE_ENV):
        monkeypatch.delenv(k, raising=False)
    cfg = TelemetryConfig(incident_warmup=5, incident_patience=4)
    r = cfg.resolved_incident()
    assert r.enabled and r.warmup == 5 and r.patience == 4
    # env outranks config fields (the worker/operator override channel)
    monkeypatch.setenv(inc_mod.INCIDENT_WARMUP_ENV, "9")
    assert cfg.resolved_incident().warmup == 9
    monkeypatch.setenv(inc_mod.INCIDENT_WARMUP_ENV, "bogus")
    assert cfg.resolved_incident().warmup == 5     # malformed: ignored
    monkeypatch.setenv(inc_mod.INCIDENT_ENV, "0")
    assert not cfg.resolved_incident().enabled
    monkeypatch.delenv(inc_mod.INCIDENT_ENV)
    # worker_env ships the disarm (and only the disarm: the default-on
    # case adds nothing, pinned by telemetry/selfcheck.py)
    assert inc_mod.INCIDENT_ENV not in TelemetryConfig().worker_env()
    env = TelemetryConfig(incident=False).worker_env()
    assert env[inc_mod.INCIDENT_ENV] == "0"


def test_fault_slow_count_bounds_straggler():
    from ray_lightning_tpu.elastic.faults import parse_fault

    spec = parse_fault("slow:rank=1,step=5,seconds=0.01,count=3")
    fired = [s for s in range(1, 12) if spec.should_fire(1, s)]
    assert fired == [5, 6, 7]            # bounded: [step, step+count)
    assert not spec.should_fire(0, 6)    # wrong rank
    assert spec.describe() == "slow:rank=1,step=5,seconds=0.01,count=3"
    # count=1 default keeps the legacy unbounded straggler
    legacy = parse_fault("slow:rank=1,step=5,seconds=0.01")
    assert legacy.should_fire(1, 500)


# -- end-to-end over the cluster backend --------------------------------

@pytest.mark.slow
def test_e2e_slow_rank_opens_and_closes_incident(tmp_path, seed):
    """2-worker fit with a bounded straggler on rank 1: the driver must
    open an incident at runtime, arm an anatomy window whose measured
    exposed-comm shares NAME rank 1, link the evidence files, and close
    the incident after the fault clears."""
    trainer = Trainer(
        max_epochs=1, limit_train_batches=40, limit_val_batches=0,
        num_sanity_val_steps=0, enable_checkpointing=False, seed=0,
        log_every_n_steps=10**9,
        plugins=[cpu_plugin(2, worker_env={
            "RLT_FAULT": "slow:rank=1,step=16,seconds=0.35,count=12"})],
        default_root_dir=str(tmp_path),
        telemetry={"heartbeat_interval": 0.2,
                   # cadence effectively off: the only way a window
                   # can happen is the incident arming it
                   "anatomy_every_n_steps": 10_000,
                   "anatomy_steps": 2,
                   "incident_warmup": 8,
                   "incident_patience": 2,
                   "incident_cooldown_s": 0.5})
    # 192 rows / batch 2 / 2 ranks = 48 per-rank batches >= the 40 limit
    trainer.fit(BoringModel(dataset_length=192))

    agg = trainer.plugin._telemetry_agg
    incidents = agg.incidents.incidents
    assert incidents, "no incident opened for an injected straggler"
    # the straggler's own sleep lands BETWEEN its step spans, so the
    # cadence/wall detectors trip on rank 1 (and possibly on rank 0,
    # whose collective waits for it) — at least one incident must name
    # rank 1 on a step-time series
    rank1 = [i for i in incidents
             if i.rank == 1 and i.series in ("step_interval_s",
                                             "step_wall_s",
                                             "data_wait_s")]
    assert rank1, [(i.series, i.rank) for i in incidents]
    inc = rank1[0]
    # the fault is bounded (count=12 of 40 steps): the incident closed
    assert inc.state == "closed", inc.brief()
    # evidence armed at open: flight ring dump + the arm file
    assert inc.evidence.get("anatomy_armed") is True
    flight = inc.evidence.get("flight_dumps", {}).get("1")
    assert flight and os.path.exists(flight)
    # the armed anatomy window landed DURING the fault and the measured
    # exposed-comm shares attribute the incident to rank 1 (lowest
    # share: its peers wait in the collective, it never does)
    attributed = [i for i in incidents
                  if i.verdict == "straggler-rank"]
    assert attributed, [(i.series, i.rank, i.verdict, i.causes)
                        for i in incidents]
    assert attributed[0].causes[0]["detail"]["rank"] == 1
    anatomy_ev = attributed[0].evidence["anatomy"]
    assert set(anatomy_ev) >= {"0", "1"}
    # the report is on disk with the pinned schema
    with open(inc.path) as f:
        doc = json.load(f)
    assert set(doc) == set(INCIDENT_SCHEMA_KEYS)
    # surfaced in the export summary (same doc /status serves)
    summary = trainer._telemetry_paths["summary"]
    assert summary["incidents"]["total"] >= 1
    assert summary["incidents"]["by_verdict"], summary["incidents"]
