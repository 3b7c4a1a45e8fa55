"""Goodput plane: full-run wall-clock attribution + measured MFU.

PR 14's anatomy plane made *step-time* truth measured (``wall ==
compute + exposed + host`` from real profiler captures); this module
answers the *run-time* question the operator actually asks: of the
whole fit or serve run's wall-clock, how much was useful work — and
what MFU did the useful part achieve.  TorchTitan reports MFU as the
headline training metric and veScale-style systems treat end-to-end
goodput as the primary dial (PAPERS.md); here both become measured,
scrapeable, and regression-gated.

The core contract is a strict partition: a :class:`GoodputLedger`
attributes **every second of run wall-clock to exactly one bucket**,

===========  ==========================================================
kind         buckets (disjoint, exhaustive)
===========  ==========================================================
``fit``      ``step`` (useful: measured train dispatch wall),
             ``compile`` (trace+jit build, PR 3 counters),
             ``init`` (state init / restore), ``data_wait`` (host
             input-pipeline stall), ``snapshot`` (blocking host time
             of async saves) + ``snapshot_stall`` (multi-process
             wait-for-previous-save, PR 7), ``recovery``
             (driver-side route decision, PR 13) + ``replay``
             (re-executed steps after a snapshot resume — the measured
             badput that parity recovery avoids), ``other`` (residual)
``serve``    ``decode`` (useful: token-producing dispatch wall),
             ``prefill``, ``queue_idle`` (pump waiting for work),
             ``autoscale`` (fleet actuation seconds, PR 15),
             ``other`` (residual)
===========  ==========================================================

with the identity ``sum(buckets) == run_wall`` EXACT by construction:
the residual lands in ``other``, and if instrumented time ever
overshoots the measured wall (clock skew between overlapping
accumulators) every bucket is scaled down proportionally so the
partition still closes.  Tests and ``telemetry/selfcheck.py`` pin the
identity; ``/status`` and ``/goodput`` serve the fractions.

The useful bucket additionally carries a *sub-split* (``useful_split``,
deliberately outside the top-level identity): the anatomy plane's
measured compute / exposed-comm / host / bubble shares when
``RLT_ANATOMY`` armed a window during the run, a wall proxy otherwise.

MFU pairs with the partition: ``flops_per_step`` (the
``LightningModule.flops_per_step()`` hook, or the default pricing of
the train-step jaxpr via the PR 12 dot-counting machinery) divided by
the measured mean step wall × ``devices`` × ``device_tflops``
(``RLT_GOODPUT_TFLOPS``, else the published peak of the device kind
in :data:`DEVICE_PEAKS`; a kind with no row prices no MFU).

Like every plane here: disabled is the default, entry points are
one-global-check no-ops, and nothing heavy imports at module load.
Arm/disarm rides ``TelemetryConfig`` (on whenever telemetry is on,
``RLT_GOODPUT=0`` disarms; knobs ship through ``worker_env()``).
"""

from __future__ import annotations

import logging
import os
import time
from typing import Any, Callable, Optional

from ray_lightning_tpu.telemetry.aggregator import TELEMETRY_KEY

_log = logging.getLogger(__name__)

#: arm/disarm: goodput is on whenever telemetry is on unless this is 0
GOODPUT_ENV = "RLT_GOODPUT"
#: per-device peak TFLOPs override for the MFU denominator (defaults
#: to the :data:`DEVICE_PEAKS` entry of the device the fit runs on)
GOODPUT_TFLOPS_ENV = "RLT_GOODPUT_TFLOPS"

#: published per-chip peaks, keyed by jax's ``device_kind``.  MFU is
#: priced from here and from nowhere else: a device that is not in the
#: table has no default.  Source: Google Cloud documentation, "TPU v5e"
#: (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip).
DEVICE_PEAKS = {
    "TPU v5 lite": {"tflops_bf16": 197.0, "hbm_gbps": 819.0,
                    "hbm_gb": 16.0},
}


def device_peak(device_kind: str) -> dict:
    """The :data:`DEVICE_PEAKS` row of ``device_kind``; an unknown kind
    is an error, never a default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak on record for device kind "
            f"{device_kind!r} (known: {sorted(DEVICE_PEAKS)}); add its "
            f"row to telemetry/goodput.py DEVICE_PEAKS with the source"
        ) from None

#: the partition, per run kind: disjoint, exhaustive (``other`` is the
#: residual), pinned by telemetry/selfcheck.py
FIT_BUCKETS = ("step", "compile", "init", "data_wait", "snapshot",
               "snapshot_stall", "recovery", "replay", "other")
SERVE_BUCKETS = ("decode", "prefill", "draft", "kv_ship", "kv_fed",
                 "queue_idle", "autoscale", "other")
BUCKETS = {"fit": FIT_BUCKETS, "serve": SERVE_BUCKETS}
#: which bucket is "useful" (the goodput-fraction numerator) per kind
USEFUL_BUCKET = {"fit": "step", "serve": "decode"}

#: identity tolerance: the partition closes to float roundoff, not to
#: a sloppy epsilon (the selfcheck asserts this exact bound)
IDENTITY_TOL = 1e-6


class GoodputLedger:
    """One run's wall-clock partition + MFU accumulator.

    Feed it seconds (:meth:`add` / :meth:`note_step`), then
    :meth:`finalize` against the measured run wall; :meth:`peek` gives
    the same doc mid-run without closing the ledger (the live /status
    surface)."""

    def __init__(self, kind: str = "fit",
                 device_tflops: Optional[float] = None,
                 devices: int = 1, clock: Callable[[], float] = None):
        if kind not in BUCKETS:
            raise ValueError(f"unknown goodput kind {kind!r}; "
                             f"expected one of {sorted(BUCKETS)}")
        self.kind = kind
        self.buckets: dict[str, float] = {b: 0.0 for b in BUCKETS[kind]}
        self.devices = max(1, int(devices))
        self.device_tflops = device_tflops
        self.steps = 0
        self.flops_per_step: Optional[float] = None
        self._anatomy: Optional[dict] = None
        self._clock = clock or time.monotonic
        self._t0: Optional[float] = None
        self.doc: Optional[dict] = None

    # -- feeding ---------------------------------------------------------

    def start(self) -> "GoodputLedger":
        self._t0 = self._clock()
        return self

    def add(self, bucket: str, seconds: float) -> None:
        if bucket not in self.buckets:
            raise KeyError(
                f"bucket {bucket!r} is not in the {self.kind!r} "
                f"partition {tuple(self.buckets)}")
        if seconds > 0:
            self.buckets[bucket] += float(seconds)

    def note_step(self, seconds: float, k: int = 1,
                  first: bool = False) -> None:
        """One train/decode dispatch: ``k`` steps in ``seconds`` wall.
        A fit's ``first`` dispatch (with the wait for its result) is
        where the step program loads from the cache or compiles: it is
        ``compile`` time and no sample of the step wall.  Counted as a
        step it made a 24-step fit's mean step 0.9 s and its MFU 0.035
        where the steady step gives 0.65 (PERF.md, PR 21)."""
        if first:
            self.add("compile", seconds)
            return
        self.add(USEFUL_BUCKET[self.kind], seconds)
        self.steps += max(1, int(k))

    def set_flops_per_step(self, flops: Optional[float]) -> None:
        self.flops_per_step = None if flops is None else float(flops)

    def set_anatomy(self, anatomy: Optional[dict]) -> None:
        """Latest measured step anatomy (telemetry/anatomy.py compact
        dict) — the useful bucket's measured sub-split source."""
        if anatomy:
            self._anatomy = dict(anatomy)

    # -- composition -----------------------------------------------------

    def _useful_split(self, useful_s: float) -> dict:
        """Sub-split of the useful bucket: anatomy-measured shares when
        a window landed, wall proxy otherwise.  Deliberately OUTSIDE
        the top-level identity (it re-describes one bucket)."""
        a = self._anatomy
        wall = float(a.get("wall_s", 0.0)) if a else 0.0
        if not a or wall <= 0:
            return {"source": "wall", "wall_s": round(useful_s, 6)}
        bubble = float(a.get("bubble_fraction") or 0.0)
        split = {"source": "anatomy"}
        for key, out in (("compute_s", "compute_s"),
                         ("exposed_s", "exposed_comm_s"),
                         ("host_s", "host_s")):
            split[out] = round(
                useful_s * float(a.get(key, 0.0)) / wall, 6)
        if bubble:
            # the bubble share is carved out of compute (the anatomy
            # identity has no separate bubble term; bubble_fraction is
            # the schedule-idle share of device time)
            split["bubble_s"] = round(useful_s * bubble, 6)
            split["compute_s"] = round(
                max(0.0, split["compute_s"] - split["bubble_s"]), 6)
        return split

    def _compose(self, wall: float) -> dict:
        wall = max(0.0, float(wall))
        buckets = dict(self.buckets)
        known = sum(buckets.values())
        if known <= wall:
            buckets["other"] += wall - known
        elif known > 0:
            # instrumented time overshot the measured wall (overlapping
            # accumulators / clock skew): scale the whole partition down
            # so the identity still closes exactly
            scale = wall / known
            buckets = {b: s * scale for b, s in buckets.items()}
        useful = buckets[USEFUL_BUCKET[self.kind]]
        doc: dict[str, Any] = {
            "kind": self.kind,
            "run_wall_s": round(wall, 6),
            "buckets": {b: round(s, 6) for b, s in buckets.items()},
            "goodput_fraction": round(useful / wall, 6) if wall else 0.0,
            "steps": self.steps,
            "devices": self.devices,
        }
        # rounding must not break the identity: re-close on the residual
        drift = doc["run_wall_s"] - sum(doc["buckets"].values())
        doc["buckets"]["other"] = round(
            max(0.0, doc["buckets"]["other"] + drift), 9)
        step_mean = useful / self.steps if self.steps else None
        if step_mean is not None:
            doc["step_wall_mean_s"] = round(step_mean, 6)
        doc["useful_split"] = self._useful_split(useful)
        if self.flops_per_step is not None:
            doc["flops_per_step"] = self.flops_per_step
        if self.device_tflops is not None:
            doc["device_tflops"] = self.device_tflops
        mfu = measured_mfu(self.flops_per_step, step_mean,
                           self.device_tflops, self.devices)
        if mfu is not None:
            doc["mfu"] = mfu
        return doc

    def peek(self) -> dict:
        """The doc as of now (ledger stays open) — live /status."""
        elapsed = (self._clock() - self._t0) if self._t0 is not None \
            else sum(self.buckets.values())
        return self._compose(elapsed)

    def finalize(self, wall: Optional[float] = None) -> dict:
        """Close the ledger against the measured run wall (default: the
        elapsed clock since :meth:`start`) and keep the doc."""
        if wall is None:
            wall = (self._clock() - self._t0) if self._t0 is not None \
                else sum(self.buckets.values())
        self.doc = self._compose(wall)
        return self.doc


def measured_mfu(flops_per_step: Optional[float],
                 step_wall_s: Optional[float],
                 device_tflops: Optional[float],
                 devices: int = 1) -> Optional[float]:
    """Model FLOPs Utilization: achieved FLOP/s of the measured step
    divided by the fleet's peak (``devices × device_tflops``).  None
    when any input is missing (MFU must never be fabricated)."""
    if not flops_per_step or not step_wall_s or not device_tflops:
        return None
    peak = float(device_tflops) * 1e12 * max(1, int(devices))
    if peak <= 0 or step_wall_s <= 0:
        return None
    return round(float(flops_per_step) / float(step_wall_s) / peak, 8)


def check_identity(doc: dict, tol: float = IDENTITY_TOL) -> bool:
    """Does ``sum(buckets) == run_wall`` hold on a composed doc?"""
    buckets = doc.get("buckets") or {}
    return abs(sum(buckets.values())
               - float(doc.get("run_wall_s", 0.0))) <= tol


def reattribute_replay(doc: dict, replayed_steps: int) -> dict:
    """Move the measured cost of ``replayed_steps`` re-executed steps
    from the ``step`` bucket to ``replay`` — the driver-side badput
    attribution of a snapshot-resume recovery (PR 13's parity route
    keeps this at ~0).  Identity-preserving: seconds move between
    buckets, the wall is untouched."""
    out = dict(doc)
    buckets = dict(out.get("buckets") or {})
    steps = int(out.get("steps") or 0)
    mean = out.get("step_wall_mean_s")
    if replayed_steps <= 0 or not mean or "replay" not in buckets:
        return out
    moved = min(buckets.get("step", 0.0),
                min(replayed_steps, steps) * float(mean))
    buckets["step"] = round(buckets["step"] - moved, 9)
    buckets["replay"] = round(buckets.get("replay", 0.0) + moved, 9)
    out["buckets"] = buckets
    out["replayed_steps"] = int(replayed_steps)
    wall = float(out.get("run_wall_s") or 0.0)
    if wall:
        out["goodput_fraction"] = round(buckets["step"] / wall, 6)
    return out


def aggregate(docs: list, extra_buckets: Optional[dict] = None) -> dict:
    """Fleet-level doc from per-rank/per-replica docs of one kind:
    walls and buckets sum; ``extra_buckets`` (e.g. the router's
    autoscale actuation seconds or the driver's recovery decision)
    extend BOTH the wall and their bucket, so the identity holds on
    the aggregate by construction."""
    docs = [d for d in docs if d]
    if not docs:
        return {}
    kind = docs[0].get("kind", "fit")
    buckets = {b: 0.0 for b in BUCKETS.get(kind, FIT_BUCKETS)}
    wall = 0.0
    steps = 0
    flops_steps = 0.0
    useful_s = 0.0
    devices = 0
    tflops = None
    for d in docs:
        wall += float(d.get("run_wall_s") or 0.0)
        steps += int(d.get("steps") or 0)
        devices += int(d.get("devices") or 0)
        if d.get("device_tflops") is not None:
            tflops = float(d["device_tflops"])
        for b, s in (d.get("buckets") or {}).items():
            buckets[b] = buckets.get(b, 0.0) + float(s)
        if d.get("flops_per_step") and d.get("steps"):
            flops_steps += float(d["flops_per_step"]) * int(d["steps"])
            useful_s += float(
                (d.get("buckets") or {}).get(USEFUL_BUCKET[kind], 0.0))
    for b, s in (extra_buckets or {}).items():
        if s and b in buckets:
            buckets[b] += float(s)
            wall += float(s)
    useful = buckets.get(USEFUL_BUCKET[kind], 0.0)
    out: dict[str, Any] = {
        "kind": kind,
        "run_wall_s": round(wall, 6),
        "buckets": {b: round(s, 6) for b, s in buckets.items()},
        "goodput_fraction": round(useful / wall, 6) if wall else 0.0,
        "steps": steps,
        "ranks": len(docs),
    }
    drift = out["run_wall_s"] - sum(out["buckets"].values())
    out["buckets"]["other"] = round(
        max(0.0, out["buckets"].get("other", 0.0) + drift), 9)
    if steps:
        # fleet seconds one GLOBAL step costs: per-rank steps are summed
        # into ``steps`` (each rank counts the step it co-executed), so
        # the per-global-step quantum is useful x ranks / steps — what
        # :func:`reattribute_replay` moves per re-executed step
        out["step_wall_mean_s"] = round(useful * len(docs) / steps, 6)
    # fleet MFU: total achieved FLOP/s over total peak — equivalently
    # the steps-weighted flops over the summed useful seconds
    if flops_steps and useful_s and tflops and devices:
        out["mfu"] = measured_mfu(flops_steps / steps,
                                  useful_s / steps, tflops,
                                  max(1, devices // len(docs)))
        if out["mfu"] is None:
            out.pop("mfu")
    return out


def goodput_item(rank: int, doc: dict) -> dict:
    """Wire item carrying one finalized (or peeked) ledger doc over the
    worker→driver queue (aggregator kind ``goodput``)."""
    return {TELEMETRY_KEY: 1, "kind": "goodput", "rank": rank,
            "ts": time.time(), "goodput": doc}


def publish_metrics(doc: dict, registry=None) -> None:
    """Mirror a doc into the metrics plane: per-bucket
    ``rlt_goodput_seconds{bucket=...}``, ``rlt_goodput_fraction`` and
    ``rlt_mfu`` — the /metrics twin of the /status section."""
    if registry is None:
        from ray_lightning_tpu.telemetry import metrics as _metrics
        registry = _metrics.get_registry()
    if registry is None or not doc:
        return
    kind = doc.get("kind", "fit")
    for bucket, seconds in (doc.get("buckets") or {}).items():
        registry.gauge("rlt_goodput_seconds").set(
            float(seconds), bucket=bucket, kind=kind)
    registry.gauge("rlt_goodput_fraction").set(
        float(doc.get("goodput_fraction", 0.0)), kind=kind)
    if doc.get("mfu") is not None:
        registry.gauge("rlt_mfu").set(float(doc["mfu"]))


# -- plane state (plugins arm it; the trainer/loop engine feed it) -------

#: (rank, sink) when the plane is armed; sink consumes wire items
_plane: Optional[tuple] = None
#: the active fit-run ledger (module-global so the loop engine's
#: data-wait site feeds it without plumbing, like metrics.on_data_wait)
_run_ledger: Optional[GoodputLedger] = None


def goodput_armed() -> bool:
    return os.environ.get(GOODPUT_ENV, "") not in ("0", "false")


def enable_goodput(rank: int = 0,
                   sink: Optional[Callable[[dict], None]] = None) -> None:
    """Arm the plane for this process (the plugin's telemetry setup)."""
    global _plane
    _plane = (rank, sink)


def disable_goodput() -> None:
    global _plane, _run_ledger
    _plane = None
    _run_ledger = None


def goodput_enabled() -> bool:
    return _plane is not None


def start_run(kind: str = "fit",
              device_tflops: Optional[float] = None,
              devices: int = 1) -> Optional[GoodputLedger]:
    """Open the run ledger if the plane is armed (trainer _run_stage)."""
    global _run_ledger
    if _plane is None:
        return None
    _run_ledger = GoodputLedger(kind, device_tflops=device_tflops,
                                devices=devices).start()
    return _run_ledger


def get_run_ledger() -> Optional[GoodputLedger]:
    return _run_ledger


def on_data_wait(seconds: float) -> None:
    """Hot-path hook next to metrics.on_data_wait (loop engine)."""
    ledger = _run_ledger
    if ledger is not None and "data_wait" in ledger.buckets:
        ledger.add("data_wait", seconds)


def finish_run(wall: Optional[float] = None) -> Optional[dict]:
    """Close the run ledger: finalize, mirror into /metrics, ship the
    doc to the driver, return it (trainer stage teardown)."""
    global _run_ledger
    ledger, _run_ledger = _run_ledger, None
    if ledger is None:
        return None
    doc = ledger.finalize(wall)
    publish_metrics(doc)
    if _plane is not None:
        rank, sink = _plane
        if sink is not None:
            try:
                sink(goodput_item(rank, doc))
            except Exception:
                _log.warning("goodput sink failed; doc dropped",
                             exc_info=True)
    return doc


__all__ = [
    "BUCKETS",
    "FIT_BUCKETS",
    "GOODPUT_ENV",
    "GOODPUT_TFLOPS_ENV",
    "GoodputLedger",
    "SERVE_BUCKETS",
    "USEFUL_BUCKET",
    "aggregate",
    "check_identity",
    "disable_goodput",
    "enable_goodput",
    "finish_run",
    "get_run_ledger",
    "goodput_armed",
    "goodput_enabled",
    "goodput_item",
    "measured_mfu",
    "on_data_wait",
    "publish_metrics",
    "reattribute_replay",
    "start_run",
]
