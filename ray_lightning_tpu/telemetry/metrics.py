"""Per-rank metrics plane: typed instruments + device/collective accounting.

PR 1 gave the run a *trace* plane (spans, heartbeats, Perfetto export);
this module adds the *numeric* plane standard monitoring infra can
scrape and alert on (TorchTitan treats per-rank throughput/memory
metrics as a production requirement — PAPERS.md):

- A process-wide :class:`MetricsRegistry` of typed instruments
  (:class:`Counter` / :class:`Gauge` / :class:`Histogram` with fixed
  buckets).  Names are validated at registration: ``rlt_``-prefixed,
  Prometheus-clean (``^rlt_[a-z0-9_]+$``) and carrying a unit suffix
  (``_bytes`` / ``_seconds`` / ``_total``), so the driver's ``/metrics``
  exposition never emits an unscrapable series.
- Collective byte accounting.  Host-side collectives
  (:func:`ray_lightning_tpu.parallel.gather.fetch_tree`) record bytes +
  seconds directly (:func:`record_collective`).  Collectives *compiled
  into* the step program (ring attention's ppermute rotation, the
  pipeline's activation hops, the ZeRO reduce-scatter/all-gather the
  sharding annotations imply) can only be observed at trace time — they
  register a bytes-per-execution cost (:func:`note_traced_collective`)
  that :func:`on_step` multiplies by executed steps, so the counters
  track actual traffic, not trace count.
- Device state sampling: a window pump thread reads
  ``jax.local_devices()[i].memory_stats()`` into current/peak HBM
  gauges each window and flushes the full cumulative snapshot to the
  sink (the worker→driver queue under cluster backends, the aggregator
  directly in-process).  Backends without memory stats (virtual CPU
  devices) report 0 so the gauges still exist to scrape.

Disabled is the default: every entry point checks one module global and
returns; hot loops keep their instrumentation unconditionally.  Like
spans.py, nothing heavy imports at module load (worker_main touches this
package before jax exists); jax is imported lazily inside the sampler.
"""

from __future__ import annotations

import logging
import re
import sys
import threading
import time
from typing import Any, Callable, Optional

from ray_lightning_tpu.telemetry import spans
from ray_lightning_tpu.telemetry.aggregator import TELEMETRY_KEY

_log = logging.getLogger(__name__)

#: Prometheus-clean instrument name: rlt_ prefix, lowercase, and a unit
#: suffix so the exposition is self-describing (satellite lint contract)
NAME_RE = re.compile(r"^rlt_[a-z0-9_]+$")
UNIT_SUFFIXES = ("_bytes", "_seconds", "_total")

#: unitless boolean gauges (Prometheus "up"-style) explicitly exempt
#: from the unit-suffix rule — a 0/1 liveness verdict has no unit to
#: carry.  Keep this list short and deliberate.
UNITLESS_GAUGES = ("rlt_worker_alive", "rlt_recovery_mode",
                   "rlt_goodput_fraction", "rlt_mfu",
                   "rlt_incident_active",
                   # accepted/drafted ratio in [0, 1] — a rate carries
                   # no unit (serve/scheduler.py speculative decode)
                   "rlt_spec_acceptance_rate")

#: step-time histogram bounds (seconds): sub-ms dispatch latency up to
#: multi-second giant-model steps
STEP_TIME_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                     0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

#: every instrument the framework itself registers (kept in one place so
#: the name lint — ``python -m ray_lightning_tpu.telemetry.metrics
#: --check-names`` and tests/test_metrics.py — covers the full surface)
CORE_METRICS = (
    "rlt_steps_total",
    "rlt_compiles_total",
    "rlt_compile_seconds_total",
    "rlt_compile_cache_hits_total",
    "rlt_compile_cache_misses_total",
    "rlt_time_to_first_step_seconds",
    "rlt_step_time_seconds",
    "rlt_hbm_bytes",
    "rlt_hbm_peak_bytes",
    "rlt_collective_bytes_total",
    "rlt_collective_ops_total",
    "rlt_collective_seconds_total",
    # comm plane (comm/collectives.py hierarchical sync): bytes the
    # step's declared collectives push across the slow DCN tier, and
    # the exposed (non-overlapped) comm seconds per step.  The exposed
    # gauge carries a ``source`` label naming its provenance:
    # ``anatomy`` = measured from trace-event overlap on the device
    # timelines during instrumented runs (telemetry/anatomy.py — the
    # number of record); ``wall_minus_floor`` = a caller's
    # differential proxy (leg wall minus the same-process fp32 floor,
    # which also pays codec quantize/dequantize compute)
    "rlt_comm_dcn_bytes_total",
    "rlt_comm_exposed_seconds",
    # anatomy plane (telemetry/anatomy.py AnatomyController): measured
    # per-step device-time split from cadence-armed profiler windows,
    # each rank parsing its own capture — compute / collective
    # (overlap-inclusive) / exposed (trace-measured non-overlapped) /
    # host gap, the DCN-link share, and completed windows
    "rlt_anatomy_compute_seconds",
    "rlt_anatomy_collective_seconds",
    "rlt_anatomy_exposed_seconds",
    "rlt_anatomy_host_seconds",
    "rlt_anatomy_dcn_seconds",
    "rlt_anatomy_windows_total",
    "rlt_data_wait_seconds_total",
    "rlt_telemetry_dropped_total",
    # trace plane (telemetry/tracing.py + serve per-request tracing):
    # alertable span-ring data loss + request-phase latency instruments
    "rlt_spans_dropped_total",
    "rlt_serve_queue_wait_seconds",
    "rlt_profile_windows_total",
    # elastic plane (elastic/snapshot.py + the driver-side fleet
    # health series the aggregator synthesizes)
    "rlt_snapshot_total",
    "rlt_snapshot_skipped_total",
    "rlt_snapshot_failed_total",
    "rlt_snapshot_seconds_total",
    "rlt_snapshot_stall_seconds_total",
    "rlt_snapshot_restore_total",
    "rlt_restarts_total",
    "rlt_worker_alive",
    # zero-replay recovery (elastic/redundancy.py + driver routing):
    # parity-tick wire bytes, skipped ticks, in-memory restores, the
    # chosen route and its driver-side decision seconds
    "rlt_parity_ticks_total",
    "rlt_parity_bytes_total",
    "rlt_parity_skipped_total",
    "rlt_parity_restore_total",
    "rlt_recovery_mode",
    "rlt_recovery_seconds",
    # peer-channel retry trail (cluster/peer.py bounded backoff)
    "rlt_peer_retries_total",
    # goodput plane (telemetry/goodput.py): the run-wall partition per
    # bucket, the useful fraction, and measured MFU — per rank from the
    # worker registries, fleet-aggregated as driver (rank -1) series
    "rlt_goodput_seconds",
    "rlt_goodput_fraction",
    "rlt_mfu",
    # MPMD plane (mpmd/engine.py): simulated bubble seconds/step per
    # schedule, set once per fit from the measured per-op replay
    "rlt_mpmd_bubble_seconds",
    # planner plane (core/trainer.py _resolve_auto_strategy gauges the
    # PlanReport counts after a strategy="auto" resolution)
    "rlt_plan_candidates_total",
    "rlt_plan_pruned_total",
    "rlt_plan_rejected_total",
    "rlt_plan_compiled_total",
    "rlt_plan_seconds",
    # incident plane (telemetry/incident.py): detector trips by series
    # and ranked verdict, plus how many incidents are open right now
    "rlt_incident_total",
    "rlt_incident_active",
    # speculative decode (serve/scheduler.py): draft/accept accounting
    # per tenant plus the rolling acceptance-rate gauge
    "rlt_spec_drafted_total",
    "rlt_spec_accepted_total",
    "rlt_spec_fallbacks_total",
    "rlt_spec_acceptance_rate",
    # disaggregated decode (serve/fleet/router.py): KV-page shipping
    # over the peer channel — wire bytes by codec, chaos retries, and
    # per-request pooled-mode failovers
    "rlt_kvship_ships_total",
    "rlt_kvship_bytes_total",
    "rlt_kvship_retries_total",
    "rlt_kvship_failovers_total",
)


def validate_metric_name(name: str) -> str:
    """Raise ValueError unless ``name`` is Prometheus-clean and carries
    a unit suffix; returns the name for chaining."""
    if not NAME_RE.match(name):
        raise ValueError(
            f"metric name {name!r} must match {NAME_RE.pattern}")
    if not name.endswith(UNIT_SUFFIXES) and name not in UNITLESS_GAUGES:
        raise ValueError(
            f"metric name {name!r} must end with a unit suffix "
            f"{UNIT_SUFFIXES} (or be a declared unitless boolean "
            f"gauge: {UNITLESS_GAUGES})")
    return name


def _label_key(labels: dict) -> tuple:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class Counter:
    """Monotonic cumulative value per label set."""

    __slots__ = ("name", "_values", "_lock")

    kind = "counter"

    def __init__(self, name: str):
        self.name = validate_metric_name(name)
        self._values: dict[tuple, float] = {}
        self._lock = threading.Lock()

    def inc(self, value: float = 1.0, **labels: Any) -> None:
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + float(value)

    def value(self, **labels: Any) -> float:
        return self._values.get(_label_key(labels), 0.0)

    def snapshot(self) -> list[dict]:
        with self._lock:
            items = list(self._values.items())
        return [{"name": self.name, "type": self.kind,
                 "labels": dict(k), "value": v} for k, v in items]


class Gauge(Counter):
    """Point-in-time value per label set (same storage, set not add)."""

    kind = "gauge"

    def set(self, value: float, **labels: Any) -> None:
        with self._lock:
            self._values[_label_key(labels)] = float(value)


class Histogram:
    """Fixed-bucket cumulative histogram (Prometheus semantics: each
    bucket counts observations <= its upper bound).  One independent
    bucket array per label set — the serve plane's TTFT/TPOT series
    split by ``status=ok|failed`` so failed requests stop reading as
    missing observations (trace-plane satellite)."""

    __slots__ = ("name", "buckets", "_series", "_lock")

    kind = "histogram"

    def __init__(self, name: str, buckets: tuple = STEP_TIME_BUCKETS):
        self.name = validate_metric_name(name)
        self.buckets = tuple(sorted(float(b) for b in buckets))
        #: label key -> [counts, sum, count]
        self._series: dict[tuple, list] = {}
        self._lock = threading.Lock()

    def observe(self, value: float, **labels: Any) -> None:
        value = float(value)
        i = 0
        for i, b in enumerate(self.buckets):
            if value <= b:
                break
        else:
            i = len(self.buckets)
        key = _label_key(labels)
        with self._lock:
            series = self._series.get(key)
            if series is None:
                series = self._series[key] = [
                    [0] * (len(self.buckets) + 1), 0.0, 0]   # +1: +Inf
            series[0][i] += 1
            series[1] += value
            series[2] += 1

    def snapshot(self) -> list[dict]:
        with self._lock:
            items = [(dict(k), list(s[0]), s[1], s[2])
                     for k, s in self._series.items()]
        return [{"name": self.name, "type": self.kind, "labels": labels,
                 "buckets": list(self.buckets), "counts": counts,
                 "sum": total, "count": n}
                for labels, counts, total, n in items]


class MetricsRegistry:
    """Per-process instrument registry + the window pump's data source.

    ``snapshot()`` returns the full cumulative state (Prometheus-style:
    the driver derives rates/bandwidth from deltas or elapsed time, the
    worker never resets)."""

    def __init__(self, rank: int = 0,
                 sink: Optional[Callable[[dict], None]] = None):
        self.rank = rank
        self.sink = sink
        self._instruments: dict[str, Any] = {}
        self._lock = threading.Lock()
        #: op -> bytes one execution of the compiled step moves (filled
        #: at trace time; multiplied by executed steps in on_step)
        self.traced_bytes: dict[str, int] = {}
        #: the subset of traced bytes that crosses the DCN tier
        #: (comm/audit.py declared_dcn_bytes) — charged per step into
        #: rlt_comm_dcn_bytes_total
        self.traced_dcn_bytes: int = 0
        self.last_collective: Optional[str] = None
        self.current_step = 0
        self.last_hbm_bytes = 0
        self._sink_failed = False

    # -- instruments -----------------------------------------------------

    def _get(self, cls, name: str, **kw):
        inst = self._instruments.get(name)
        if inst is None:
            with self._lock:
                inst = self._instruments.get(name)
                if inst is None:
                    inst = self._instruments[name] = cls(name, **kw)
        if not isinstance(inst, cls):
            raise TypeError(
                f"metric {name!r} already registered as "
                f"{type(inst).__name__}, requested {cls.__name__}")
        return inst

    def counter(self, name: str) -> Counter:
        return self._get(Counter, name)

    def gauge(self, name: str) -> Gauge:
        return self._get(Gauge, name)

    def histogram(self, name: str,
                  buckets: tuple = STEP_TIME_BUCKETS) -> Histogram:
        return self._get(Histogram, name, buckets=buckets)

    # -- device sampling -------------------------------------------------

    def sample_device_state(self) -> None:
        """Current/peak HBM per local device.  Backends whose
        ``memory_stats()`` is None (virtual CPU devices) report 0 — the
        gauges still exist, so dashboards don't break per platform."""
        cur = self.gauge("rlt_hbm_bytes")
        peak = self.gauge("rlt_hbm_peak_bytes")
        try:
            import jax
            devices = jax.local_devices()
        except Exception:
            devices = []
        if not devices:
            cur.set(0, device="0")
            peak.set(0, device="0")
            return
        for i, dev in enumerate(devices):
            try:
                stats = dev.memory_stats() or {}
            except Exception:
                stats = {}
            in_use = int(stats.get("bytes_in_use", 0) or 0)
            cur.set(in_use, device=str(i))
            peak.set(int(stats.get("peak_bytes_in_use", 0) or 0),
                     device=str(i))
            if i == 0:
                self.last_hbm_bytes = in_use

    # -- snapshot / flush ------------------------------------------------

    def snapshot(self) -> list[dict]:
        # span/metric records lost to the ring buffer are data loss the
        # driver must surface (satellite: silent-drop visibility)
        dropped = spans.dropped()
        self.gauge("rlt_telemetry_dropped_total").set(dropped)
        # the same loss as a true Prometheus COUNTER so it is alertable
        # (rate() > 0 == silent trace loss), not just a summary field +
        # a driver log line (trace-plane satellite).  spans.dropped() is
        # monotonic per recorder; the max() guards a recorder restart.
        c = self.counter("rlt_spans_dropped_total")
        delta = dropped - c.value()
        if delta > 0:
            c.inc(delta)
        # compile-plane counters (persistent-cache hits/misses + real
        # backend-compile seconds) mirror in when that module is live;
        # sys.modules-gated so an unused compile plane costs nothing
        cc = sys.modules.get("ray_lightning_tpu.compile.cache")
        if cc is not None:
            cc.publish_metrics(self)
        with self._lock:
            instruments = list(self._instruments.values())
        out: list[dict] = []
        for inst in instruments:
            out.extend(inst.snapshot())
        return out

    def flush(self) -> None:
        if self.sink is None:
            return
        try:
            self.sink(metrics_item(self.rank, self.snapshot()))
        except Exception:
            if not self._sink_failed:
                self._sink_failed = True
                _log.warning("metrics sink failed; further windows will "
                             "be dropped silently", exc_info=True)

    def brief(self) -> dict:
        """Tiny state summary carried on heartbeats so the watchdog can
        say what a wedged rank was *doing* (step, HBM, last collective),
        not just that it went silent."""
        return {"step": self.current_step,
                "hbm_bytes": self.last_hbm_bytes,
                "last_collective": self.last_collective}


class _MetricsPump:
    """Daemon thread sampling device state + flushing the snapshot every
    ``interval`` seconds (and once at stop, so short runs still export
    at least one window)."""

    def __init__(self, registry: MetricsRegistry, interval: float = 2.0):
        self._registry = registry
        self._interval = max(0.05, float(interval))
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="rlt-metrics-pump")

    def start(self) -> "_MetricsPump":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    def _run(self) -> None:
        while not self._stop.wait(self._interval):
            self._window()
        self._window()   # final flush on stop

    def _window(self) -> None:
        try:
            self._registry.sample_device_state()
        except Exception:   # sampling must never kill the pump
            pass
        self._registry.flush()


def metrics_item(rank: int, snapshot: list[dict]) -> dict:
    """Wire item carrying one cumulative metrics window (rides the same
    worker→driver queue as span batches)."""
    return {TELEMETRY_KEY: 1, "kind": "metrics", "rank": rank,
            "ts": time.time(), "metrics": snapshot}


_registry: Optional[MetricsRegistry] = None
_pump: Optional[_MetricsPump] = None

# -- rolling sample tail (incident-plane satellite) ----------------------
# A tiny fixed-size deque of the rank's most recent raw samples, attached
# to every heartbeat (heartbeat.py make_heartbeat).  The driver's
# incident detectors dedupe by timestamp watermark, so the tail keeps
# them ticking when span batches are dropped under backpressure (the
# blind spot behind the PR 9 `dropped` counter) — heartbeats are tiny
# and never ride the span ring.
from collections import deque as _deque

SAMPLE_TAIL_LEN = 32
_sample_tail: "_deque[dict]" = _deque(maxlen=SAMPLE_TAIL_LEN)
_last_step_t: Optional[float] = None


def note_tail_sample(series: str, value: float,
                     ts: Optional[float] = None) -> None:
    """Append one raw sample to the heartbeat tail (deque append is
    atomic; no lock on the hot path)."""
    _sample_tail.append({"s": series, "ts": ts if ts is not None
                         else time.time(), "v": float(value)})


def sample_tail() -> list[dict]:
    """Snapshot of the rolling tail, oldest first (heartbeat payload)."""
    return list(_sample_tail)


def reset_sample_tail() -> None:
    global _last_step_t
    _sample_tail.clear()
    _last_step_t = None


def enable_metrics(rank: int = 0,
                   sink: Optional[Callable[[dict], None]] = None,
                   interval: float = 2.0,
                   pump: bool = True) -> MetricsRegistry:
    """Install the process-wide registry (and its window pump when a
    sink will consume the flushes)."""
    global _registry, _pump
    disable_metrics()
    reset_sample_tail()
    _registry = MetricsRegistry(rank=rank, sink=sink)
    if pump and sink is not None:
        _pump = _MetricsPump(_registry, interval=interval).start()
    return _registry


def disable_metrics() -> None:
    global _registry, _pump
    if _pump is not None:
        _pump.stop()
        _pump = None
    _registry = None


def metrics_enabled() -> bool:
    return _registry is not None


def get_registry() -> Optional[MetricsRegistry]:
    return _registry


def flush_metrics() -> None:
    """Final window: sample + push the cumulative snapshot to the sink
    (teardown paths call this before disable so the driver always sees
    the run's last state)."""
    reg = _registry
    if reg is None:
        return
    try:
        reg.sample_device_state()
    except Exception:
        pass
    reg.flush()


# -- hot-path entry points (all one-global-check no-ops when disabled) --

def record_collective(op: str, nbytes: int,
                      seconds: Optional[float] = None) -> None:
    """Account one host-dispatched collective: ``nbytes`` of logical
    payload moved by ``op`` (and how long it took, when measured —
    seconds make the per-op achieved GiB/s exact instead of inferred)."""
    reg = _registry
    if reg is None:
        return
    reg.last_collective = op
    reg.counter("rlt_collective_bytes_total").inc(nbytes, op=op)
    reg.counter("rlt_collective_ops_total").inc(1, op=op)
    if seconds is not None:
        reg.counter("rlt_collective_seconds_total").inc(seconds, op=op)


def note_traced_collective(op: str, nbytes_per_step: int) -> None:
    """Register the byte cost of a collective compiled INTO the step
    program (observed once at trace time, executed every step): each
    :func:`on_step` then adds ``nbytes_per_step × k`` to the counters.
    Re-tracing the same op overwrites (last trace wins) so recompiles
    never double-count."""
    reg = _registry
    if reg is None:
        return
    reg.traced_bytes[op] = int(nbytes_per_step)
    reg.last_collective = op


def note_step_collectives(op_bytes: dict,
                          dcn_bytes: Optional[int] = None) -> None:
    """Bulk :func:`note_traced_collective` (the trainer registers the
    strategy's implied gradient/param collectives in one call).
    ``dcn_bytes`` (comm/audit.py ``declared_dcn_bytes``) is the
    DCN-crossing share, charged per executed step into
    ``rlt_comm_dcn_bytes_total`` so the hierarchical sync's inter-host
    savings are a scrapeable series."""
    reg = _registry
    if reg is None:
        return
    for op, nbytes in (op_bytes or {}).items():
        if nbytes > 0:
            reg.traced_bytes[op] = int(nbytes)
    if dcn_bytes is not None:
        reg.traced_dcn_bytes = int(dcn_bytes)


def on_step(duration_s: float, k: int = 1,
            step: Optional[int] = None) -> None:
    """Account one train dispatch: ``k`` optimizer steps in
    ``duration_s`` host seconds.  Observes the per-step-normalized time
    into the histogram, bumps the step counter, and charges every
    traced-collective cost ``k`` times."""
    global _last_step_t
    reg = _registry
    if reg is None:
        return
    k = max(1, int(k))
    reg.histogram("rlt_step_time_seconds").observe(duration_s / k)
    reg.counter("rlt_steps_total").inc(k)
    # heartbeat tail: per-step wall plus dispatch-to-dispatch cadence.
    # The interval covers this dispatch AND the host time between
    # dispatches (callbacks, snapshot stalls, a straggler's sleep) —
    # inflation the in-span step wall cannot see, which is exactly what
    # the driver's step_interval_s detector trips on.
    now = time.time()
    note_tail_sample("step_wall_s", duration_s / k, ts=now)
    if _last_step_t is not None and now > _last_step_t:
        note_tail_sample("step_interval_s", (now - _last_step_t) / k,
                         ts=now)
    _last_step_t = now
    if step is not None:
        reg.current_step = int(step)
    if reg.traced_bytes:
        bytes_c = reg.counter("rlt_collective_bytes_total")
        ops_c = reg.counter("rlt_collective_ops_total")
        for op, nbytes in reg.traced_bytes.items():
            bytes_c.inc(nbytes * k, op=op)
            ops_c.inc(k, op=op)
    if reg.traced_dcn_bytes:
        reg.counter("rlt_comm_dcn_bytes_total").inc(
            reg.traced_dcn_bytes * k)


def note_exposed_comm(seconds: float,
                      source: str = "wall_minus_floor") -> None:
    """Record the EXPOSED (non-overlapped) comm seconds per step, with
    its provenance as a ``source`` label:

    - ``"anatomy"`` — MEASURED from collective/compute event-interval
      overlap on the device timelines of a real profiler capture
      (telemetry/anatomy.py publishes it during instrumented runs;
      this is the number of record);
    - ``"wall_minus_floor"`` — a caller's differential proxy: a leg's
      wall seconds/step minus the comm-off fp32 floor measured in the
      same process (includes codec quantize/dequantize compute, so it
      upper-bounds the measured figure).  Nothing in the package feeds
      it; tests/test_metrics.py pins the label.
    """
    reg = _registry
    if reg is None:
        return
    reg.gauge("rlt_comm_exposed_seconds").set(float(seconds),
                                              source=source)


def on_compile() -> None:
    reg = _registry
    if reg is None:
        return
    reg.counter("rlt_compiles_total").inc(1)


def on_data_wait(seconds: float) -> None:
    """Cumulative host-side input-pipeline stall (the data_wait span's
    numeric twin: scrape its rate against rlt_step_time_seconds to see
    when the loader, not the device, is the bottleneck)."""
    reg = _registry
    if reg is None:
        return
    reg.counter("rlt_data_wait_seconds_total").inc(seconds)
    note_tail_sample("data_wait_s", seconds)


def metrics_brief() -> Optional[dict]:
    """Heartbeat payload hook (None when the metrics plane is off)."""
    reg = _registry
    return reg.brief() if reg is not None else None


# -- name lint (format.sh --check / tests/test_metrics.py) ---------------

_REGISTRATION_RE = re.compile(
    r"\b(?:counter|gauge|histogram)\(\s*['\"]([^'\"]+)['\"]")


def lint_metric_names(package_root: Optional[str] = None) -> list[str]:
    """Validate CORE_METRICS plus every name literal passed to a
    counter()/gauge()/histogram() registration in the source tree.
    Returns the list of violations (empty = clean)."""
    import os
    problems: list[str] = []
    names = set(CORE_METRICS)
    if package_root is None:
        package_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
    for dirpath, _dirs, files in os.walk(package_root):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            try:
                with open(path, encoding="utf-8") as f:
                    src = f.read()
            except OSError:
                continue
            names.update(_REGISTRATION_RE.findall(src))
    for name in sorted(names):
        try:
            validate_metric_name(name)
        except ValueError as e:
            problems.append(str(e))
    return problems


def _main(argv: list[str]) -> int:
    if "--check-names" in argv:
        problems = lint_metric_names()
        for p in problems:
            print(f"metrics lint: {p}")
        if not problems:
            print(f"metrics lint: {len(CORE_METRICS)}+ instrument names "
                  f"Prometheus-clean")
        return 1 if problems else 0
    print("usage: python -m ray_lightning_tpu.telemetry.metrics "
          "--check-names")
    return 2


if __name__ == "__main__":   # pragma: no cover - exercised via format.sh
    import sys
    sys.exit(_main(sys.argv[1:]))
