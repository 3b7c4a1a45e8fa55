"""Trace→anatomy: measured step-time truth from XLA profiler captures.

Every perf claim since PR 10 has been modeled (declared bytes × modeled
bandwidth) or proxied (wall minus a floor measured in the same
process).  This module is the measurement side: parse a captured
``jax.profiler`` Chrome-trace into a per-rank, per-step
:class:`StepAnatomy` — where the device time actually went:

- ``compute_s``   — device seconds under non-collective ops (union of
  their intervals, so concurrent fusions don't double-count);
- ``collective_s`` — collective device seconds (overlap-INCLUSIVE sum,
  split ``by_op`` and ``by_link`` ici/dcn via comm/audit.py's
  collective-name / replica-group classification);
- ``exposed_s``   — the MEASURED exposed comm: collective interval
  time not covered by any compute interval on the same device
  timeline.  This is the number a wall-minus-floor proxy
  approximates (the proxy includes quantize/dequantize compute, the
  measured number is pure serialization);
- ``host_s``      — host-gap/dispatch time: window wall not covered by
  ANY device op (the host→device link, the python loop, a pipeline
  bubble).

The decomposition is an interval-algebra identity, not an estimate:

    wall_s == compute_s + exposed_s + host_s        (exactly)

because ``exposed = |collective ∖ compute|`` and ``host = wall −
|collective ∪ compute|``.  Tests and the selfcheck pin it.

ONE parser for every trace layout (`chipbench/reduce.py` keeps its own
copy of the interval algebra: the benchmark imports nothing it judges):

- TPU/device traces: processes named ``/device:TPU:k`` with nested
  "XLA Ops" (per-instruction) and "XLA Modules" (per-execution)
  tracks;
- CPU proxy traces: one ``/host:CPU`` process whose
  ``tf_XLATfrtCpuClient/<id>`` threads are the per-(virtual-)device
  timelines — HLO op events carry ``hlo_module``/``hlo_op`` args and
  collectives appear by name (``all-reduce`` …), so the same anatomy
  math runs on the 8-virtual-device CPU mesh the test suite audits.
  One honest caveat: the CPU thunk executor serializes ops per device
  thread, so measured exposed ≈ collective there — real overlap needs
  a real fabric (ROADMAP item 5).

The second half is auto-capture: :class:`AnatomyController` arms a
short profiler window on a step cadence through the same
``WorkerProfiler`` machinery the on-demand ``POST /debug/profile``
controllers drive (telemetry/tracing.py), parses the capture LOCALLY
on the rank that wrote it, and ships only the compact anatomy dict
over the worker→driver queue — never the multi-MB trace.  Arm with
``TelemetryConfig(anatomy_every_n_steps=…)`` or ``RLT_ANATOMY=1`` /
``RLT_ANATOMY_EVERY_N_STEPS=N`` / ``RLT_ANATOMY_STEPS=W``.

No jax at module import (worker_main touches this package before jax
exists); the profiler is reached only through tracing.WorkerProfiler
inside the capture window.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import logging
import os
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from ray_lightning_tpu.telemetry.aggregator import TELEMETRY_KEY

_log = logging.getLogger(__name__)

#: env knobs (TelemetryConfig.resolved_anatomy merges them): RLT_ANATOMY=1
#: arms the default cadence; the other two override cadence / window
ANATOMY_ENV = "RLT_ANATOMY"
ANATOMY_EVERY_ENV = "RLT_ANATOMY_EVERY_N_STEPS"
ANATOMY_STEPS_ENV = "RLT_ANATOMY_STEPS"

#: incident-plane arm channel (incident.py INCIDENT_CONTROL_ENV): when
#: set in the worker env, every AnatomyController polls the arm file and
#: forces an off-cadence evidence window on detector trip
INCIDENT_CONTROL_ENV = "RLT_INCIDENT_CONTROL"

#: default cadence when armed via bare RLT_ANATOMY=1 (dispatches between
#: windows) and default window length (dispatches traced per window)
DEFAULT_EVERY_N = 50
DEFAULT_WINDOW = 4


# -- trace file location + low-level parsing -------------------------------

def locate_trace_json(trace_dir: str) -> str:
    """Newest ``*.trace.json.gz`` under a profiler capture dir (the ONE
    locator)."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.trace.json.gz")))
    if not paths:
        raise FileNotFoundError(f"no trace.json.gz under {trace_dir}")
    return paths[-1]


def read_trace(path: str) -> dict:
    """Load one Chrome-trace JSON (gzipped or plain)."""
    if path.endswith(".gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    with open(path) as f:
        return json.load(f)


def _meta_maps(events: list) -> tuple[dict, dict]:
    """(pid → process name, (pid, tid) → thread name) metadata maps."""
    procs: dict = {}
    threads: dict = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e.get("pid")] = e.get("args", {}).get("name", "")
        elif e.get("name") == "thread_name":
            threads[(e.get("pid"), e.get("tid"))] = \
                e.get("args", {}).get("name", "")
    return procs, threads


def device_track_events(trace_path: str, track: str = "XLA Ops") -> list:
    """Complete ('X') events on one device-side track (TPU layout).

    Device processes are named ``/device:TPU:0`` etc. and carry nested
    tracks — "Steps" ⊃ "XLA Modules" ⊃ "XLA Ops" — so callers must pick
    ONE track or they double-count: per-op analysis wants "XLA Ops",
    per-step wall time wants "XLA Modules".
    """
    data = read_trace(trace_path)
    events = data.get("traceEvents", [])
    procs, threads = _meta_maps(events)

    def on_track(e) -> bool:
        pname = procs.get(e.get("pid"), "")
        tname = threads.get((e.get("pid"), e.get("tid")), "")
        return "/device:" in pname and tname == track

    return [e for e in events
            if e.get("ph") == "X" and e.get("dur") and on_track(e)]


def bucket_of(name: str) -> str:
    """Coarse op-category for a device event name (HLO-ish).  The ONE
    category-bucketing table."""
    n = name.lower()
    if "pallas" in n or "custom-call" in n or "flash" in n:
        return "pallas/custom"
    if "convert" in n:
        return "convert-fusion"
    if "all-reduce" in n or "all-gather" in n or "reduce-scatter" in n \
            or "collective" in n or "permute" in n:
        return "collective"
    if "multiply" in n and ("reduce" in n or "subtract" in n):
        return "multiply-reduce-fusion"
    if n.startswith("fusion") or ".fusion" in n:
        return "generic-fusion"
    if "dot" in n or "dense" in n or "conv" in n:
        return "dot/conv"
    if "copy" in n or "bitcast" in n or "transpose" in n:
        return "copy/layout"
    if "dynamic" in n or "gather" in n or "scatter" in n or "slice" in n:
        return "gather/scatter"
    if "reduce" in n or "add" in n:
        return "reduce/add"
    return "other"


#: CPU-layout wrapper/bookkeeping events that are NOT device work
_CPU_NOISE = ("ThreadpoolListener", "ThunkExecutor", "ParseArguments")

#: CPU-layout per-execution dispatch wrapper (the "module event" analog)
_CPU_EXEC = "TfrtCpuExecutable::ExecuteHelper"


def device_timelines(trace_path: str) -> list[dict]:
    """Per-device op/module timelines from either trace layout.

    Returns ``[{"device": label, "ops": [events], "modules": [events]}]``
    — TPU: one entry per ``/device:`` process ("XLA Ops" / "XLA
    Modules" tracks).  CPU: the thunk executor runs HLO ops on one
    ``tf_XLATfrtCpuClient`` thread per virtual device — OR inline on
    the dispatching python thread for a lone device — so the op test
    is the ``hlo_op``/``hlo_module`` event args (only real HLO
    executions carry them), grouped by thread; the ExecuteHelper
    dispatch wrappers on the same thread stand in for module events.
    Timelines without any op event are dropped.
    """
    data = read_trace(trace_path)
    events = data.get("traceEvents", [])
    procs, threads = _meta_maps(events)
    device_pids = {pid for pid, name in procs.items() if "/device:" in name}
    out: dict[Any, dict] = {}
    for e in events:
        if e.get("ph") != "X" or not e.get("dur"):
            continue
        pid, tid = e.get("pid"), e.get("tid")
        if pid in device_pids:
            track = threads.get((pid, tid), "")
            tl = out.setdefault(pid, {
                "device": procs.get(pid, str(pid)),
                "ops": [], "modules": []})
            if track == "XLA Ops":
                tl["ops"].append(e)
            elif track == "XLA Modules":
                tl["modules"].append(e)
            continue
        name = e.get("name", "")
        args = e.get("args") or {}
        is_op = ("hlo_op" in args or "hlo_module" in args) \
            and not any(w in name for w in _CPU_NOISE)
        if is_op or name == _CPU_EXEC:
            tl = out.setdefault((pid, tid), {
                "device": threads.get((pid, tid), f"{pid}/{tid}"),
                "ops": [], "modules": []})
            (tl["ops"] if is_op else tl["modules"]).append(e)
    return [tl for tl in out.values() if tl["ops"]]


# -- interval algebra ------------------------------------------------------

def _union(intervals: list[tuple[float, float]]) -> list:
    """Merge overlapping [start, end) intervals (sorted, disjoint)."""
    if not intervals:
        return []
    intervals = sorted(intervals)
    out = [list(intervals[0])]
    for s, e in intervals[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def _measure(merged: list) -> float:
    return sum(e - s for s, e in merged)


def _subtract(a_merged: list, b_merged: list) -> list:
    """Interval difference a ∖ b over already-merged interval lists."""
    out = []
    bi = 0
    for s, e in a_merged:
        cur = s
        while bi < len(b_merged) and b_merged[bi][1] <= cur:
            bi += 1
        j = bi
        while j < len(b_merged) and b_merged[j][0] < e:
            bs, be = b_merged[j]
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
            j += 1
        if cur < e:
            out.append((cur, e))
    return out


# -- the anatomy -----------------------------------------------------------

@dataclass
class StepAnatomy:
    """Per-step device-time breakdown of one rank's capture window.

    All ``*_s`` figures are seconds PER STEP PER DEVICE: timeline sums
    divided by ``devices`` × ``steps`` (SPMD lockstep), with ``wall_s``
    the window's global extent per step.  Identity (pinned by tests +
    selfcheck): ``wall_s == compute_s + exposed_s + host_s`` (up to the
    clamp of ``host_s`` at 0); ``collective_s`` is the
    overlap-inclusive total, so it can exceed ``exposed_s``.
    """

    steps: int = 0
    devices: int = 0
    wall_s: float = 0.0
    compute_s: float = 0.0
    collective_s: float = 0.0
    exposed_s: float = 0.0
    host_s: float = 0.0
    #: collective device seconds per step, split by op kind and by link
    collective_by_op: dict = field(default_factory=dict)
    collective_by_link: dict = field(default_factory=dict)
    #: host-gap share of the window — the measured (per-stage, for MPMD
    #: ranks) bubble fraction
    bubble_fraction: float = 0.0
    #: per-module device seconds per step (top modules; MPMD stage
    #: programs land here one entry per stage program)
    modules: dict = field(default_factory=dict)
    #: "xla-device" (TPU module/op tracks) | "cpu-host" (client threads)
    source: str = ""

    def as_dict(self) -> dict:
        """Compact JSON-safe dict (the wire/bench form)."""
        rd = lambda v: round(float(v), 9)   # noqa: E731
        return {
            "steps": int(self.steps),
            "devices": int(self.devices),
            "wall_s": rd(self.wall_s),
            "compute_s": rd(self.compute_s),
            "collective_s": rd(self.collective_s),
            "exposed_s": rd(self.exposed_s),
            "host_s": rd(self.host_s),
            "collective_by_op": {k: rd(v) for k, v
                                 in sorted(self.collective_by_op.items())},
            "collective_by_link": {k: rd(v) for k, v
                                   in sorted(self.collective_by_link.items())},
            "bubble_fraction": round(float(self.bubble_fraction), 6),
            "modules": {k: rd(v) for k, v in self.modules.items()},
            "source": self.source,
        }


def _infer_steps(tl: dict) -> int:
    """Executions of the dominant program in one timeline.

    TPU: count of the dominant "XLA Modules" event.  CPU: the
    ExecuteHelper wrappers dispatch EVERY module, so count per-op-name
    occurrences within the dominant ``hlo_module`` and take the median
    (each instruction runs once per execution; the median is robust to
    an op name repeated by unrelated modules).
    """
    mods = tl["modules"]
    ops = tl["ops"]
    by_mod_dur: dict[str, float] = collections.defaultdict(float)
    for e in ops:
        m = (e.get("args") or {}).get("hlo_module")
        if m:
            by_mod_dur[m] += e["dur"]
    if by_mod_dur:
        dom = max(by_mod_dur, key=by_mod_dur.get)
        counts = collections.Counter(
            e["name"] for e in ops
            if (e.get("args") or {}).get("hlo_module") == dom)
        ks = sorted(counts.values())
        if ks:
            return max(1, ks[len(ks) // 2])
    if mods:
        by_name: dict[str, list] = collections.defaultdict(list)
        for e in mods:
            by_name[e["name"]].append(e["dur"])
        dom_durs = max(by_name.values(), key=sum)
        return max(1, len(dom_durs))
    return 1


def _timeline_anatomy(tl: dict, ici_size: int,
                      multi_process: bool) -> dict:
    """One device timeline's window totals (µs) + inferred steps.

    Totals are NOT normalized here: the CPU thunk executor rotates its
    worker threads across dispatches, so one device's window can span
    several thread timelines — the caller sums timelines and divides
    by the real device count, never averages per thread.
    """
    from ray_lightning_tpu.comm import audit
    ops = tl["ops"]
    coll_iv, comp_iv = [], []
    by_op: dict[str, float] = collections.defaultdict(float)
    by_link: dict[str, float] = collections.defaultdict(float)
    for e in ops:
        iv = (e["ts"], e["ts"] + e["dur"])
        kind = audit.collective_kind(e.get("name", ""))
        if kind is not None:
            coll_iv.append(iv)
            by_op[kind] += e["dur"]
            by_link[audit.event_link(e.get("args"), ici_size,
                                     multi_process)] += e["dur"]
        else:
            comp_iv.append(iv)
    coll_u = _union(coll_iv)
    comp_u = _union(comp_iv)
    all_events = ops + tl["modules"]
    return {
        "steps": _infer_steps(tl),
        "t0": min(e["ts"] for e in all_events),
        "t1": max(e["ts"] + e["dur"] for e in all_events),
        "compute": _measure(comp_u),
        "collective": sum(by_op.values()),
        "exposed": _measure(_subtract(coll_u, comp_u)),
        "busy": _measure(_union(coll_u + comp_u)),
        "by_op": dict(by_op),
        "by_link": dict(by_link),
        "modules": _timeline_modules(tl),
    }


def _timeline_modules(tl: dict) -> dict:
    by_module: dict[str, float] = collections.defaultdict(float)
    for e in tl["ops"]:
        m = (e.get("args") or {}).get("hlo_module")
        if m:
            by_module[m] += e["dur"]
    if not by_module:
        for e in tl["modules"]:
            by_module[e["name"]] += e["dur"]
    return dict(by_module)


def parse_trace_anatomy(trace_dir: str, *, steps: Optional[int] = None,
                        ici_size: Optional[int] = None,
                        multi_process: Optional[bool] = None,
                        devices: Optional[int] = None) -> StepAnatomy:
    """Parse one rank's capture dir into a :class:`StepAnatomy`.

    ``steps``: dispatches the window covered (None = infer from the
    dominant program's execution count).  ``ici_size``: ranks per host
    block for the ici/dcn split (None = this process's local device
    count, the contiguous-block layout comm/audit.py assumes).
    ``multi_process``: group-less collectives cross DCN when True
    (None = ask jax, False when jax is unavailable).  ``devices``: the
    per-rank normalization denominator — TPU traces have one timeline
    per device process so it's the timeline count, but the CPU thunk
    executor rotates threads across dispatches, so there the local
    device count (asked of jax when None) is the truth and the
    timeline sums are divided by it.

    Raises ``FileNotFoundError`` (no trace file) / ``ValueError`` (no
    device events — e.g. a window that closed before any dispatch).
    """
    path = locate_trace_json(trace_dir) if os.path.isdir(trace_dir) \
        else trace_dir
    timelines = device_timelines(path)
    if not timelines:
        raise ValueError(f"no device op events in {path}")
    local_devices = None
    if ici_size is None or multi_process is None or devices is None:
        try:
            import jax
            local_devices = max(1, jax.local_device_count())
            if ici_size is None:
                ici_size = local_devices
            if multi_process is None:
                multi_process = jax.process_count() > 1
        except Exception:
            ici_size = ici_size or 1
            multi_process = bool(multi_process)
    source = "xla-device" if any("/device:" in tl["device"]
                                 for tl in timelines) else "cpu-host"
    rows = [_timeline_anatomy(tl, ici_size, multi_process)
            for tl in timelines]
    if devices is None:
        if source == "xla-device" or local_devices is None:
            devices = len(rows)
        else:
            devices = min(local_devices, len(rows))
    n_dev = max(1, int(devices))
    n_steps = steps or max(r["steps"] for r in rows)
    # per-device, per-step normalization: SUM over timelines (one
    # device's work may span several executor threads), divide by the
    # device count and the window's steps
    norm = 1e-6 / (n_dev * max(1, n_steps))

    def total(key: str) -> float:
        return sum(r[key] for r in rows)

    a = StepAnatomy(steps=n_steps, devices=n_dev, source=source)
    # wall: the window's global extent — SPMD devices run in lockstep,
    # so the extent per step IS the per-device step wall
    extent = max(r["t1"] for r in rows) - min(r["t0"] for r in rows)
    a.wall_s = extent * 1e-6 / max(1, n_steps)
    a.compute_s = total("compute") * norm
    a.collective_s = total("collective") * norm
    a.exposed_s = total("exposed") * norm
    a.host_s = max(0.0, a.wall_s - total("busy") * norm)
    a.bubble_fraction = (a.host_s / a.wall_s) if a.wall_s > 0 else 0.0
    for r in rows:
        for k, v in r["by_op"].items():
            a.collective_by_op[k] = a.collective_by_op.get(k, 0.0) \
                + v * norm
        for k, v in r["by_link"].items():
            a.collective_by_link[k] = a.collective_by_link.get(k, 0.0) \
                + v * norm
    mod_tot: dict[str, float] = collections.defaultdict(float)
    for r in rows:
        for k, v in r["modules"].items():
            mod_tot[k] += v * norm
    a.modules = dict(sorted(mod_tot.items(),
                            key=lambda kv: -kv[1])[:8])
    return a


def profile_dir_anatomy(last_dir: "str | None") -> Optional[dict]:
    """Parsed anatomy for a completed ``POST /debug/profile`` window:
    ``{rank_label: anatomy_dict}`` over the window's ``rank<k>/``
    subdirs).  None when there is no window directory; a capture that
    is there and cannot be parsed raises (a failed parse used to become
    a missing field)."""
    if not last_dir or not os.path.isdir(last_dir):
        return None
    subs = sorted(d for d in os.listdir(last_dir)
                  if d.startswith("rank")
                  and os.path.isdir(os.path.join(last_dir, d)))
    if subs:
        return {d[len("rank"):]: parse_trace_anatomy(
            os.path.join(last_dir, d)).as_dict() for d in subs}
    return {"0": parse_trace_anatomy(last_dir).as_dict()}


# -- synthetic-trace fixture (tests + selfcheck golden) --------------------

def write_synthetic_trace(trace_dir: str, ops: list[dict],
                          modules: Optional[list[dict]] = None,
                          device: str = "/device:TPU:0") -> str:
    """Write a minimal TPU-layout ``*.trace.json.gz`` capture under
    ``trace_dir``: one device process with "XLA Ops"/"XLA Modules"
    tracks.  ``ops``/``modules``: dicts with ``name``, ``ts``, ``dur``
    (µs) and optional ``args``.  Returns the trace path.  This is the
    golden fixture that pins the exposed-comm overlap math without a
    profiler in the loop."""
    pid, ops_tid, mod_tid = 1, 1, 2
    events = [
        {"ph": "M", "name": "process_name", "pid": pid,
         "args": {"name": device}},
        {"ph": "M", "name": "thread_name", "pid": pid, "tid": ops_tid,
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "name": "thread_name", "pid": pid, "tid": mod_tid,
         "args": {"name": "XLA Modules"}},
    ]
    for e in ops:
        events.append({"ph": "X", "pid": pid, "tid": ops_tid,
                       "name": e["name"], "ts": float(e["ts"]),
                       "dur": float(e["dur"]),
                       "args": e.get("args") or {}})
    for e in modules or ():
        events.append({"ph": "X", "pid": pid, "tid": mod_tid,
                       "name": e["name"], "ts": float(e["ts"]),
                       "dur": float(e["dur"]),
                       "args": e.get("args") or {}})
    out_dir = os.path.join(trace_dir, "plugins", "profile", "synthetic")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "synthetic.trace.json.gz")
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return path


# -- auto-capture: cadence-armed windows, parsed locally -------------------

def anatomy_item(rank: int, anatomy: dict,
                 capture_dir: Optional[str] = None) -> dict:
    """Wire item carrying one rank's compact anatomy dict (rides the
    same worker→driver queue as span batches and metrics windows).
    ``capture_dir`` (incident-armed windows only) links the preserved
    raw capture so the incident report can reference its evidence."""
    item = {TELEMETRY_KEY: 1, "kind": "anatomy", "rank": rank,
            "ts": time.time(), "anatomy": anatomy}
    if capture_dir:
        item["dir"] = capture_dir
    return item


class AnatomyController:
    """Worker-side cadence capture: every ``every_n`` dispatches, arm a
    ``window``-dispatch ``jax.profiler`` trace through the same
    :class:`~ray_lightning_tpu.telemetry.tracing.WorkerProfiler` the
    on-demand profile controllers use, parse THIS rank's capture
    locally, publish ``rlt_anatomy_*`` gauges + the measured exposed
    comm into the local metrics registry, ship the compact dict via
    ``sink``, and delete the capture dir.  Failures disarm the window
    and never raise into the train loop."""

    def __init__(self, rank: int, every_n: int, window: int,
                 sink: Optional[Callable[[dict], None]] = None):
        from ray_lightning_tpu.telemetry.tracing import WorkerProfiler
        self.rank = int(rank)
        self.every_n = max(1, int(every_n))
        self.window = max(1, int(window))
        self.sink = sink
        self.last: Optional[dict] = None
        self.windows = 0
        self._dispatches = 0
        self._window_id = 0
        self._dir: Optional[str] = None
        self._profiler = WorkerProfiler(rank=self.rank)
        #: pending off-cadence arm ({"tag", "steps"}) — incident plane
        self._forced: Optional[dict] = None
        #: tag of the window currently capturing (None = cadence window)
        self._active_tag: Optional[str] = None
        # driver→worker arm channel: incident manager writes the arm
        # file (incident.py write_arm_file), every rank polls it here —
        # same shared-filesystem idiom as RLT_PROFILE_CONTROL
        self._arm_watcher = None
        ctl_path = os.environ.get(INCIDENT_CONTROL_ENV)
        if ctl_path:
            from ray_lightning_tpu.telemetry.incident import ArmWatcher
            self._arm_watcher = ArmWatcher(ctl_path)

    def arm_now(self, tag: Optional[str] = None,
                steps: Optional[int] = None) -> None:
        """Force the NEXT tick to open a window regardless of cadence —
        the incident plane's "capture evidence after detection" hook.
        The window's capture dir is preserved and linked on the wire
        item instead of deleted."""
        self._forced = {"tag": tag or "incident",
                        "steps": int(steps) if steps else None}

    def tick(self) -> None:
        """Once per dispatch (loop-engine hook, next to profile_tick)."""
        prof = self._profiler
        if prof._active:
            prof.note_step()
            if not prof._active:       # window just closed: parse + ship
                self._finish()
            return
        if self._arm_watcher is not None and self._forced is None:
            ctl = self._arm_watcher.poll()
            if ctl is not None:
                self.arm_now(tag=f"incident-{ctl.get('id')}",
                             steps=ctl.get("steps"))
        self._dispatches += 1
        forced, self._forced = self._forced, None
        if forced is None and self._dispatches % self.every_n:
            return
        self._window_id += 1
        d = tempfile.mkdtemp(prefix="rlt_anatomy_")
        self._dir = d
        steps = (forced or {}).get("steps") or self.window
        prof.maybe_start({"id": f"anatomy-{self.rank}-{self._window_id}",
                          "steps": steps, "dir": d})
        if not prof._active:
            # another window owns the profiler (e.g. an on-demand
            # POST /debug/profile capture) — skip to the next cadence;
            # a forced (incident) arm retries on the next dispatch
            shutil.rmtree(d, ignore_errors=True)
            self._dir = None
            self._forced = forced
        else:
            self._active_tag = (forced or {}).get("tag")

    def _finish(self) -> None:
        d, self._dir = self._dir, None
        tag, self._active_tag = self._active_tag, None
        try:
            anatomy = parse_trace_anatomy(
                os.path.join(d, f"rank{self.rank}")).as_dict()
            self.last = anatomy
            self.windows += 1
            self._publish_metrics(anatomy)
            if self.sink is not None:
                # incident-armed windows keep + link their raw capture
                # (the evidence dir the report references); cadence
                # windows ship the compact dict only and delete it
                self.sink(anatomy_item(
                    self.rank, anatomy,
                    capture_dir=d if tag else None))
        except Exception as e:   # never break the train loop, but say so
            _log.warning("anatomy window %s dropped: %s", d, e)
        finally:
            if d and not tag:
                shutil.rmtree(d, ignore_errors=True)

    def _publish_metrics(self, anatomy: dict) -> None:
        from ray_lightning_tpu.telemetry import metrics as _metrics
        reg = _metrics.get_registry()
        if reg is None:
            return
        reg.gauge("rlt_anatomy_compute_seconds").set(anatomy["compute_s"])
        reg.gauge("rlt_anatomy_collective_seconds").set(
            anatomy["collective_s"])
        reg.gauge("rlt_anatomy_exposed_seconds").set(anatomy["exposed_s"])
        reg.gauge("rlt_anatomy_host_seconds").set(anatomy["host_s"])
        reg.gauge("rlt_anatomy_dcn_seconds").set(
            anatomy["collective_by_link"].get("dcn", 0.0))
        reg.counter("rlt_anatomy_windows_total").inc(1)
        # the exposed-comm gauge's MEASURED source
        _metrics.note_exposed_comm(anatomy["exposed_s"], source="anatomy")

    def stop(self) -> None:
        """Teardown: abandon any mid-capture window (a partial trace is
        not an anatomy)."""
        self._profiler.stop()
        if self._dir:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None


_controller: Optional[AnatomyController] = None


def enable_anatomy(rank: int, every_n: int, window: int = DEFAULT_WINDOW,
                   sink: Optional[Callable[[dict], None]] = None
                   ) -> AnatomyController:
    """Install the process-wide auto-capture controller (plugins call
    this when TelemetryConfig/RLT_ANATOMY* arm a cadence)."""
    global _controller
    disable_anatomy()
    _controller = AnatomyController(rank, every_n, window, sink=sink)
    return _controller


def disable_anatomy() -> None:
    global _controller
    if _controller is not None:
        _controller.stop()
    _controller = None


def get_anatomy_controller() -> Optional[AnatomyController]:
    return _controller


def anatomy_tick() -> None:
    """Loop-engine hook, once per dispatch.  Free (one global check)
    when no controller is armed."""
    ctl = _controller
    if ctl is None:
        return
    try:
        ctl.tick()
    except Exception:    # capture must never break the train loop
        _log.debug("anatomy tick failed", exc_info=True)


__all__ = [
    "ANATOMY_ENV",
    "ANATOMY_EVERY_ENV",
    "ANATOMY_STEPS_ENV",
    "INCIDENT_CONTROL_ENV",
    "DEFAULT_EVERY_N",
    "DEFAULT_WINDOW",
    "StepAnatomy",
    "locate_trace_json",
    "read_trace",
    "device_track_events",
    "device_timelines",
    "bucket_of",
    "parse_trace_anatomy",
    "profile_dir_anatomy",
    "write_synthetic_trace",
    "anatomy_item",
    "AnatomyController",
    "enable_anatomy",
    "disable_anatomy",
    "get_anatomy_controller",
    "anatomy_tick",
]
