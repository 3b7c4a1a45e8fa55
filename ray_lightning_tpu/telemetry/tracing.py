"""Per-request distributed tracing + on-demand profiling control.

The span plane (spans.py) times *phases*; this module ties phases to
*requests*.  Every ``Server.submit`` mints a trace id that propagates
driver→worker inside the scheduler's plan broadcast (each prefill entry
carries ``trace=...``, the decode entry carries a slot→trace map) and
worker→driver through the ordinary span batches on the queue channel —
worker spans simply carry the id as a ``trace`` attr.  The driver-side
request phases (queue wait, admission, completion/failure) are recorded
as synthetic rank ``-1`` span records fed straight to the active
aggregator, which reassembles one span tree per request
(``TelemetryAggregator.request_trees``) and summarizes per-tenant
TTFT/TPOT breakdowns for ``/status``
(``TelemetryAggregator.tenant_breakdown``).

The second half is the on-demand ``jax.profiler`` window — replacing
"restart with JaxProfilerCallback configured":

- :class:`ServeProfileController` — driver side of the serve plane's
  ``POST /debug/profile?steps=N``: the pump attaches the armed window to
  the next plan broadcast (the same driver→worker control path the
  trace ids ride) and counts the steps; every worker runs the capture
  through a :class:`WorkerProfiler`.
- :class:`FileProfileController` / :func:`profile_tick` — the fit
  path's equivalent: the exporter POST writes a control file under the
  telemetry dir (location shipped to workers via the
  ``RLT_PROFILE_CONTROL`` env var — shared-filesystem backends only),
  and the loop engine polls it once per dispatch at a bounded rate.

No jax at module import (worker_main touches this package before jax
exists); ``jax.profiler`` is imported inside the capture calls, which
never raise into serving/training.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
import uuid
from typing import Any, Optional

from ray_lightning_tpu.telemetry import scopes, spans

_log = logging.getLogger(__name__)

#: span-record attribute carrying the request trace id (a single id on
#: request-scoped spans; ``traces`` carries a slot→id map on the shared
#: decode span, which the aggregator fans out to every live request)
TRACE_ATTR = "trace"
TRACES_ATTR = "traces"

#: env var pointing fit workers at the profile control file
PROFILE_CONTROL_ENV = "RLT_PROFILE_CONTROL"

#: ceiling on one capture window — an unbounded window would trace
#: until the run ends and write an unbounded xplane file
MAX_PROFILE_STEPS = 10_000


def mint_trace_id() -> str:
    """One request's trace id: 16 hex chars, unique per process fleet."""
    return uuid.uuid4().hex[:16]


def span_record(name: str, t0: float, t1: Optional[float] = None,
                rank: int = -1, **attrs: Any) -> dict:
    """A synthetic span record in the spans.py wire schema.  ``t0``/
    ``t1`` are wall-clock seconds (``time.time()``), matching the
    offset-corrected timestamps worker recorders emit, so driver and
    worker spans merge onto one timeline."""
    if t1 is None:
        t1 = time.time()
    rec = {"t": "span", "name": name, "ts": float(t0),
           "dur": max(0.0, float(t1) - float(t0)), "rank": rank,
           "depth": 0}
    clean = {k: v for k, v in attrs.items() if v is not None}
    if clean:
        rec["attrs"] = clean
    return rec


def record_request_span(name: str, t0: float, t1: Optional[float] = None,
                        **attrs: Any) -> None:
    """Feed one driver-side request span to the active aggregator
    (thread-local — the serve pump binds the fleet's aggregator).
    No-op without an aggregator so the scheduler stays unit-testable
    and tracing stays free when telemetry is off."""
    from ray_lightning_tpu.telemetry.aggregator import get_active
    agg = get_active()
    if agg is None:
        return
    try:
        agg.ingest_records(-1, [span_record(name, t0, t1, **attrs)])
    except Exception:   # tracing must never break the pump
        _log.debug("request span dropped", exc_info=True)


def _attach_window_anatomy(controller, out: dict) -> None:
    """Link the parsed per-rank step anatomy (telemetry/anatomy.py)
    next to a completed window's ``last_dir`` in a controller's status
    dict.  Parsed once per window dir and cached on the controller —
    /status polls must not re-read a multi-MB trace each scrape."""
    last_dir = out.get("last_dir")
    if not last_dir:
        return
    cached = getattr(controller, "_anatomy_cache", None)
    if cached is None or cached[0] != last_dir:
        from ray_lightning_tpu.telemetry.anatomy import profile_dir_anatomy
        try:
            cached = (last_dir, "anatomy", profile_dir_anatomy(last_dir))
        except Exception as e:   # noqa: BLE001 - /status must answer
            # a capture that cannot be parsed says so, under its own key
            cached = (last_dir, "anatomy_error", repr(e))
        controller._anatomy_cache = cached
    if cached[2] is not None:
        out[cached[1]] = cached[2]


# -- on-demand profiling: serve plane (plan-broadcast control) -----------

class ServeProfileController:
    """Driver-side state machine for ``POST /debug/profile?steps=N``.

    States: idle → pending (POST accepted) → active (window attached to
    a plan broadcast; the driver counts dispatched steps) → done (trace
    dir linkable from ``/status``).  One window at a time; a POST while
    one is pending/active is rejected with its current state.
    """

    def __init__(self, base_dir: str):
        self.base_dir = base_dir
        self._lock = threading.Lock()
        self._state = "idle"
        self._req: Optional[dict] = None
        self.last_dir: Optional[str] = None
        self.windows = 0

    def request(self, steps: int) -> dict:
        steps = max(1, min(int(steps), MAX_PROFILE_STEPS))
        with self._lock:
            if self._state in ("pending", "active"):
                return {"accepted": False, "state": self._state,
                        "error": "a profile window is already "
                                 f"{self._state}"}
            pid = uuid.uuid4().hex[:8]
            out_dir = os.path.join(self.base_dir, "profile", pid)
            self._req = {"id": pid, "steps": steps, "dir": out_dir,
                         "remaining": steps}
            self._state = "pending"
        _log.info("profile: window armed (%d steps) -> %s", steps, out_dir)
        return {"accepted": True, "state": "pending", "id": pid,
                "steps": steps, "dir": out_dir}

    def take_pending(self) -> Optional[dict]:
        """Pump hook: claim the armed window for the next plan broadcast
        (pending → active).  Returns the picklable control dict workers
        act on, or None."""
        with self._lock:
            if self._state != "pending":
                return None
            self._state = "active"
            req = self._req
        return {"id": req["id"], "steps": req["steps"], "dir": req["dir"]}

    def note_step(self) -> None:
        """Pump hook: one plan dispatched while a window is active."""
        with self._lock:
            if self._state != "active":
                return
            self._req["remaining"] -= 1
            if self._req["remaining"] > 0:
                return
            self._state = "done"
            self.last_dir = self._req["dir"]
            self.windows += 1
        from ray_lightning_tpu.telemetry import metrics as _metrics
        reg = _metrics.get_registry()
        if reg is not None:
            reg.counter("rlt_profile_windows_total").inc(1)
        _log.info("profile: window complete -> %s", self.last_dir)

    def status(self) -> dict:
        with self._lock:
            out = {"state": self._state}
            if self._req is not None:
                out["id"] = self._req["id"]
                out["dir"] = self._req["dir"]
                out["steps"] = self._req["steps"]
                if self._state == "active":
                    out["remaining"] = self._req["remaining"]
            if self.last_dir is not None:
                out["last_dir"] = self.last_dir
        _attach_window_anatomy(self, out)
        return out


class WorkerProfiler:
    """Worker-side capture window: start on the plan's control dict,
    count serve steps, stop after N.  Each rank writes its own subdir
    so multi-host captures never collide.  Failures log and disarm —
    profiling must never fail a serve step."""

    def __init__(self, rank: int = 0):
        self.rank = rank
        self._remaining = 0
        self._active = False
        self._dir: Optional[str] = None
        self._seen: set[str] = set()

    def maybe_start(self, ctl: Optional[dict]) -> None:
        if not ctl or ctl.get("id") in self._seen or self._active:
            return
        self._seen.add(ctl.get("id", ""))
        out_dir = os.path.join(ctl["dir"], f"rank{self.rank}")
        try:
            os.makedirs(out_dir, exist_ok=True)
            import jax
            jax.profiler.start_trace(out_dir)
        except Exception as e:
            _log.warning("profile: start_trace failed: %s", e)
            return
        # the trace counts from its own zero: anchor it to the wall
        # clock that span records use (telemetry/spans.py)
        spans.clock_anchor()
        self._active = True
        self._dir = out_dir
        self._remaining = int(ctl["steps"])
        _log.info("profile: rank %d capturing %d steps -> %s",
                  self.rank, self._remaining, out_dir)

    @property
    def on_last_step(self) -> bool:
        """The step now running is the last one the window captures:
        the next ``note_step`` stops the trace."""
        return self._active and self._remaining <= 1

    def note_step(self) -> None:
        if not self._active:
            return
        self._remaining -= 1
        if self._remaining <= 0:
            self.stop()

    def stop(self) -> None:
        if not self._active:
            return
        self._active = False
        try:
            import jax
            jax.profiler.stop_trace()
            # operation names in the trace are fusion.N: the table that
            # says which part of the model each belongs to goes beside
            # it, and stays in this process for a reader that asks after
            # the programs went (a serve engine rebuilt)
            scopes.write_tables(self._dir)
        except Exception as e:
            _log.warning("profile: stop_trace failed: %s", e)


# -- on-demand profiling: fit plane (control-file arm) -------------------

class FileProfileController:
    """Fit-path driver side: ``POST /debug/profile`` writes a control
    file the workers poll (:func:`profile_tick`).  Only meaningful when
    the backend shares a filesystem with the workers — the plugin only
    wires this controller up when it does."""

    def __init__(self, control_path: str):
        self.control_path = control_path
        self._last: Optional[dict] = None

    def request(self, steps: int) -> dict:
        steps = max(1, min(int(steps), MAX_PROFILE_STEPS))
        pid = uuid.uuid4().hex[:8]
        out_dir = os.path.join(os.path.dirname(self.control_path), pid)
        ctl = {"id": pid, "steps": steps, "dir": out_dir}
        os.makedirs(os.path.dirname(self.control_path), exist_ok=True)
        tmp = self.control_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(ctl, f)
        os.replace(tmp, self.control_path)   # workers see complete JSON
        self._last = ctl
        _log.info("profile: fit window armed (%d steps) -> %s",
                  steps, out_dir)
        return {"accepted": True, "state": "armed", **ctl}

    def status(self) -> dict:
        if self._last is None:
            return {"state": "idle"}
        out = {"state": "armed", **self._last}
        try:
            done = sorted(fn for fn in os.listdir(self._last["dir"])
                          if fn.endswith(".done"))
        except OSError:
            done = []
        if done:
            out["state"] = "done"
            out["ranks_done"] = [fn[:-len(".done")] for fn in done]
            out["last_dir"] = self._last["dir"]
            _attach_window_anatomy(self, out)
        return out


class _FilePoller:
    """Per-process fit-side poller: reads the control file at most every
    ``min_poll`` seconds (one monotonic compare per step otherwise),
    runs the capture window, and drops a ``rank<k>.done`` marker so the
    driver's ``/status`` can report completion."""

    def __init__(self, control_path: str, min_poll: float = 0.5):
        self.control_path = control_path
        self.min_poll = min_poll
        self._next_poll = 0.0
        self._profiler: Optional[WorkerProfiler] = None
        self._ctl: Optional[dict] = None

    def _rank(self) -> int:
        try:
            return int(os.environ.get("RLT_PROCESS_ID", "0"))
        except ValueError:
            return 0

    def tick(self) -> None:
        prof = self._profiler
        if prof is not None and prof._active:
            prof.note_step()
            if not prof._active:     # window just closed: drop marker
                try:
                    with open(os.path.join(
                            self._ctl["dir"],
                            f"rank{self._rank()}.done"), "w") as f:
                        f.write("1")
                except OSError:
                    pass
            return
        now = time.monotonic()
        if now < self._next_poll:
            return
        self._next_poll = now + self.min_poll
        try:
            with open(self.control_path) as f:
                ctl = json.load(f)
        except (OSError, ValueError):
            return
        if prof is None:
            prof = self._profiler = WorkerProfiler(rank=self._rank())
        self._ctl = ctl
        prof.maybe_start(ctl)


_poller: "Optional[_FilePoller]" = None
_poller_checked = False


def profile_tick() -> None:
    """Loop-engine hook, called once per dispatch.  Free (one global
    check) unless ``RLT_PROFILE_CONTROL`` is set in this process."""
    global _poller, _poller_checked
    if _poller is None:
        if _poller_checked:
            return
        _poller_checked = True
        path = os.environ.get(PROFILE_CONTROL_ENV, "").strip()
        if not path:
            return
        _poller = _FilePoller(path)
    try:
        _poller.tick()
    except Exception:    # profiling must never break the train loop
        _log.debug("profile tick failed", exc_info=True)


def reset_profile_tick() -> None:
    """Re-read the env on the next tick (tests / respawned workers)."""
    global _poller, _poller_checked
    if _poller is not None and _poller._profiler is not None:
        _poller._profiler.stop()
    _poller = None
    _poller_checked = False


__all__ = [
    "TRACE_ATTR",
    "TRACES_ATTR",
    "PROFILE_CONTROL_ENV",
    "mint_trace_id",
    "span_record",
    "record_request_span",
    "ServeProfileController",
    "FileProfileController",
    "WorkerProfiler",
    "profile_tick",
    "reset_profile_tick",
]
