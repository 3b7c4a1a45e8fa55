"""Device-trace capture + bucketed breakdown for TPU benchmarking.

Wraps ``jax.profiler.trace`` and derives per-op/per-module figures from
the emitted Chrome-trace JSON to answer two questions the wall clock
cannot (it also counts whatever the host was doing between
dispatches):

- where does *device* time go per step (op-category buckets)?
- what is the pure device time per step (compute + collectives), for
  framework-vs-native ratios that hold even when the host link drifts?

The trace PARSING itself — file locator, track/thread-layout handling,
the category-bucketing table — lives in
``ray_lightning_tpu/telemetry/anatomy.py`` (ONE parser for the whole
repo; the anatomy plane, the profile controllers and these bench
helpers all read traces through it).  This module keeps the
bench-facing derivations: roofline, breakdown, top-ops, dominant
module.  Used by ``bench_native_baseline.py`` (device-time ratio legs),
``profile_headline.py`` and the ad-hoc perf work in
benchmarks/README.md.
"""

from __future__ import annotations

import collections
import tempfile
from typing import Callable

from ray_lightning_tpu.telemetry.anatomy import (  # noqa: F401  (re-export)
    bucket_of,
    device_track_events,
    locate_trace_json,
)

#: legacy aliases (pre-anatomy private names, kept for ad-hoc scripts)
_latest_trace_json = locate_trace_json
_device_events = device_track_events


def capture_trace(run: Callable[[], None], out_dir: str | None = None) -> str:
    """Run ``run()`` under the JAX profiler; return the trace directory."""
    import jax

    out_dir = out_dir or tempfile.mkdtemp(prefix="rlt_trace_")
    with jax.profiler.trace(out_dir):
        run()
    return out_dir


def roofline(trace_dir: str, steps: int, *,
             peak_tflops: float = 197.0, peak_gbps: float = 819.0,
             k: int = 30) -> list[dict]:
    """Per-op roofline table from the trace's own HLO cost metadata.

    Each "XLA Ops" event carries ``model_flops`` and ``bytes_accessed``;
    dividing by measured device time gives achieved TFLOP/s and GB/s,
    and max(flops/peak_flops, bytes/peak_bw) gives the roofline-bound
    fraction — ops far below 1.0 on *both* axes are overhead and
    therefore levers.  Defaults are TPU v5e peaks (bf16 MXU ~197
    TFLOP/s, HBM ~819 GB/s).

    Returns rows sorted by total time: {op, category, source, ms_per_step,
    count, tflops, gbps, bound_frac, bound_by}.
    """
    agg: dict[str, dict] = {}
    for e in device_track_events(locate_trace_json(trace_dir)):
        args = e.get("args", {})
        # deduplicated_name: XLA emitted one program for several
        # identical ops (e.g. the 12 per-layer attention kernels);
        # aggregate under the canonical name + category
        key = args.get("deduplicated_name") or e["name"]
        row = agg.setdefault(key, {
            "op": key,
            "category": args.get("hlo_category", "?"),
            "source": (args.get("tf_op") or args.get("source") or "")[:80],
            "ms": 0.0, "count": 0, "flops": 0.0, "bytes": 0.0})
        row["ms"] += e["dur"] / 1000.0
        row["count"] += 1
        row["flops"] += float(args.get("model_flops", 0) or 0)
        row["bytes"] += float(args.get("bytes_accessed", 0) or 0)
    rows = sorted(agg.values(), key=lambda r: -r["ms"])[:k]
    for r in rows:
        secs = r["ms"] / 1000.0
        r["ms_per_step"] = round(r["ms"] / steps, 3)
        r["tflops"] = round(r["flops"] / secs / 1e12, 1) if secs else 0.0
        r["gbps"] = round(r["bytes"] / secs / 1e9, 1) if secs else 0.0
        cf = r["tflops"] / peak_tflops
        bf = r["gbps"] / peak_gbps
        r["bound_frac"] = round(max(cf, bf), 2)
        r["bound_by"] = "compute" if cf >= bf else "bandwidth"
        del r["ms"], r["flops"], r["bytes"]
    return rows


def device_breakdown(trace_dir: str) -> dict[str, float]:
    """Total device time (ms) per bucket across the whole trace."""
    out: dict[str, float] = collections.defaultdict(float)
    for e in device_track_events(locate_trace_json(trace_dir)):
        out[bucket_of(e["name"])] += e["dur"] / 1000.0
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def top_ops(trace_dir: str, k: int = 25) -> list[tuple[str, float, int]]:
    """(name, total ms, count) for the k most expensive device ops."""
    tot: dict[str, float] = collections.defaultdict(float)
    cnt: dict[str, int] = collections.defaultdict(int)
    for e in device_track_events(locate_trace_json(trace_dir)):
        tot[e["name"]] += e["dur"] / 1000.0
        cnt[e["name"]] += 1
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [(name, ms, cnt[name]) for name, ms in ranked]


def dominant_module(trace_dir: str) -> tuple[str, float, int]:
    """(name, median_ms, count) of the XLA module with the largest total
    device time in the trace.

    In a traced training window that module is the train step; taking
    the MEDIAN event duration makes the figure robust to a first
    execution inflated by compilation and to stragglers, and using
    device-track module events makes it immune to host jitter — the
    property the framework-vs-native ratios need on transfer-bound
    workloads, where the wall clock drifts with the host
    (benchmarks/README.md).
    """
    import statistics

    evs = device_track_events(locate_trace_json(trace_dir),
                              track="XLA Modules")
    agg: dict[str, list] = collections.defaultdict(list)
    for e in evs:
        agg[e["name"]].append(e["dur"] / 1000.0)
    if not agg:
        raise ValueError(f"no XLA module events under {trace_dir}")
    name, durs = max(agg.items(), key=lambda kv: sum(kv[1]))
    return name, float(statistics.median(durs)), len(durs)


def dominant_module_ms(trace_dir: "str | None",
                       *, consume: bool = True) -> "float | None":
    """Median device ms of the dominant module.  None only when no
    capture was made (``trace_dir`` is None: a profiler-less backend,
    and the caller said so on stderr); a capture that is there and
    cannot be parsed raises.  ``consume`` removes the trace dir."""
    import shutil

    if not trace_dir:
        return None
    try:
        return dominant_module(trace_dir)[1]
    finally:
        if consume:
            shutil.rmtree(trace_dir, ignore_errors=True)


def total_device_ms(trace_dir: str, module_filter: str = "") -> float:
    """Total device time (ms) spent executing XLA modules in the trace.

    Uses the "XLA Modules" track (one event per module execution, no
    nesting) so the result is pure device busy time — immune to host
    jitter.  ``module_filter``: only count modules whose name
    contains it (e.g. "train_step" to exclude init/eval programs).
    """
    evs = device_track_events(locate_trace_json(trace_dir),
                              track="XLA Modules")
    return sum(e["dur"] / 1000.0 for e in evs
               if module_filter in e.get("name", ""))
