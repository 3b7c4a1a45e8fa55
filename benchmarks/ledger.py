"""Perf-regression ledger: turn bench JSON trajectories into a gate.

The repo accumulates one measured JSON blob per round (the driver's
``BENCH_r*.json``, any ``bench.py``-family output) but until now a
regression was something a human noticed diffing them.  This module
compares two rounds record-by-record and exits nonzero when a tracked
figure regresses past its band — the pre-merge perf gate
(``python bench.py --compare prev.json`` or
``python -m benchmarks.ledger prev.json curr.json``).

Accepted inputs, auto-detected per file:

- a driver ``BENCH_r*.json`` blob (``{"parsed": {...}, "tail": "..."}``
  — every JSON object line in ``tail`` is a record, ``parsed`` too);
- a file of JSON lines (one record per line, non-JSON lines ignored);
- one JSON object / array of objects.

Records join on their ``metric`` name.  Tracked figures and their
regression direction:

==============================  ======  ==============================
figure                          worse    band
==============================  ======  ==============================
``value`` (steps/sec legs)      lower   ``step_band`` (default 5%)
``device_ms``                   higher  ``step_band``
``exposed_comm_seconds`` /
``measured_exposed_comm_seconds``  higher  ``exposed_band`` (default
                                        10%) + ``min_exposed_s``
                                        absolute floor, so sub-ms CPU
                                        noise never trips the gate
``serve.tokens_per_sec`` /
``fleet.tokens_per_sec``        lower   ``serve_band`` (default 15% —
                                        CPU-proxy serving wall clock
                                        is noisier than steps/sec)
``serve.ttft_p99_ms`` /
``fleet.ttft_p99_ms``           higher  ``serve_band`` +
                                        ``min_ttft_ms`` floor
``serve.tpot_p50_ms`` /
``fleet.tpot_p50_ms``           higher  ``serve_band`` +
                                        ``min_tpot_ms`` floor
``goodput.fraction``            lower   ``goodput_band`` (default 10%)
                                        + ``min_goodput_delta``
                                        absolute floor
``goodput.mfu``                 lower   ``goodput_band``
``measured_bubble_fraction_*``  higher  ``goodput_band`` + the same
                                        absolute floor (bench_pipeline
                                        1f1b/gpipe audit)
``incident_ab.overhead_pct``    higher  ``incident_band`` (default 2%,
                                        ABSOLUTE: the current round's
                                        incident-plane on-vs-off
                                        steps/sec delta, gated even
                                        without a previous round —
                                        bench_incident.py A/B leg)
``serve.spec.acceptance_rate``
/ ``...tokens_per_target_
forward``                       lower   ``serve_band`` + 5-point
                                        acceptance floor (draft-quality
                                        collapse is a regression even
                                        while tokens/s holds —
                                        bench_serve.py spec leg)
``fleet.disagg.ttft_p99_ms``    higher  ``serve_band`` + ``min_ttft_ms``
                                        (the 4x-burst prefill/decode
                                        split leg, bench_fleet.py)
``fleet.disagg.fp8_
compression_ratio``             lower   ``serve_band`` (KV wire bytes
                                        vs the raw fp32 control)
``fleet.federated_reuse_
ratio``                         lower   ``serve_band`` + 2-point
                                        absolute floor (prefix pages
                                        PULLED from other replicas on
                                        the fed-on leg — a collapse
                                        means the directory stopped
                                        federating even while
                                        tokens/s holds)
==============================  ======  ==============================

Improvements are reported too (the ledger is a trajectory, not just an
alarm); metrics present on only one side are listed as uncompared so a
silently dropped leg can't read as "no regression".  A figure present
on only ONE side of a joined metric (a field added or dropped between
rounds — e.g. comparing a goodput-aware round against a pre-goodput
``BENCH_r*.json``) is skipped with a note in ``skipped``, never a
KeyError and never a regression: new instrumentation bootstraps
cleanly against old baselines.
"""

from __future__ import annotations

import json
from typing import Any, Iterable

#: default relative bands (fraction of the previous value)
STEP_BAND = 0.05
EXPOSED_BAND = 0.10
#: serve-side figures (bench_serve.py `serve`, bench_fleet.py `fleet`):
#: wall-clock tokens/s + latency tails on the CPU proxy swing more than
#: compiled-step device time, so the band is wider
SERVE_BAND = 0.15
#: absolute floor under which exposed-comm drift is noise, not signal
MIN_EXPOSED_S = 1e-4
#: absolute TTFT floor: p99 jitter below this is scheduler noise
MIN_TTFT_MS = 2.0
#: absolute TPOT floor: per-token p50 drift below half a millisecond is
#: dispatch noise on the CPU proxy, not a decode-kernel regression
MIN_TPOT_MS = 0.5
#: goodput-fraction / MFU band (telemetry/goodput.py): whole-run wall
#: attribution swings more than compiled-step time (compile/init share
#: varies with cache state), so the band is wider than step_band
GOODPUT_BAND = 0.10
#: absolute goodput-fraction / bubble-fraction floor: drift smaller
#: than 2 points of fraction is wall-clock noise, not a regression
MIN_GOODPUT_DELTA = 0.02
#: absolute spec-decode acceptance floor: under 5 points of
#: accepted/drafted drift is workload mix, not draft-model regression
MIN_ACCEPT_DELTA = 0.05
#: detector-overhead ceiling (telemetry/incident.py): the incident
#: plane runs on every fit, so its measured on-vs-off step-wall cost
#: (benchmarks/bench_incident.py) is gated ABSOLUTELY at 2%
INCIDENT_BAND = 0.02


def _iter_records(obj: Any) -> Iterable[dict]:
    """Yield every bench record (dict with a ``metric`` key) inside an
    arbitrary loaded JSON value / raw text blob."""
    if isinstance(obj, dict):
        if "metric" in obj:
            yield obj
        for key in ("parsed",):
            if isinstance(obj.get(key), dict):
                yield from _iter_records(obj[key])
        tail = obj.get("tail")
        if isinstance(tail, str):
            yield from _iter_text(tail)
    elif isinstance(obj, list):
        for item in obj:
            yield from _iter_records(item)


def _iter_text(text: str) -> Iterable[dict]:
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        yield from _iter_records(obj)


def load_records(source: Any) -> dict[str, dict]:
    """``metric name → record`` from a path, loaded JSON value, or a
    list of record dicts (later duplicates win — the newest emission
    of a re-run leg is the round's figure)."""
    if isinstance(source, str):
        with open(source) as f:
            text = f.read()
        try:
            records = list(_iter_records(json.loads(text)))
        except ValueError:
            records = list(_iter_text(text))
    else:
        records = list(_iter_records(source))
    return {r["metric"]: r for r in records}


def _exposed_of(rec: dict) -> "float | None":
    """The record's exposed-comm figure, measured preferred."""
    v = rec.get("measured_exposed_comm_seconds")
    if v is None:
        v = rec.get("exposed_comm_seconds")
    return None if v is None else float(v)


def compare(prev: Any, curr: Any, *, step_band: float = STEP_BAND,
            exposed_band: float = EXPOSED_BAND,
            serve_band: float = SERVE_BAND,
            goodput_band: float = GOODPUT_BAND,
            incident_band: float = INCIDENT_BAND,
            min_exposed_s: float = MIN_EXPOSED_S,
            min_ttft_ms: float = MIN_TTFT_MS,
            min_tpot_ms: float = MIN_TPOT_MS) -> dict:
    """Compare two rounds; the returned report's ``ok`` is the gate.

    ``prev``/``curr``: anything :func:`load_records` accepts.
    """
    prev_by = load_records(prev)
    curr_by = load_records(curr)
    regressions: list[dict] = []
    improvements: list[dict] = []
    skipped: list[dict] = []
    compared = 0

    def check(metric, figure, old, new, worse_is, band, floor=0.0):
        nonlocal compared
        if (old is None) != (new is None):
            # one-sided figure: a field this round of instrumentation
            # added (old side predates it) or dropped.  Note it —
            # silence would read as "compared, fine" — but never gate:
            # new figures must bootstrap cleanly against old rounds
            skipped.append({
                "metric": metric, "figure": figure,
                "note": ("not in previous round (bootstrapping)"
                         if old is None else
                         "missing from current round")})
            return
        if old is None or new is None or old <= 0:
            return
        compared += 1
        delta = (new - old) / old
        worse = delta if worse_is == "higher" else -delta
        row = {"metric": metric, "figure": figure,
               "prev": old, "curr": new, "delta_pct": round(delta * 100, 2)}
        if worse > band and abs(new - old) > floor:
            regressions.append(row)
        elif worse < -band:
            improvements.append(row)

    for metric in sorted(set(prev_by) & set(curr_by)):
        p, c = prev_by[metric], curr_by[metric]
        if p.get("unit") == "steps/sec" and c.get("unit") == "steps/sec":
            check(metric, "steps_per_sec", p.get("value"), c.get("value"),
                  "lower", step_band)
        if p.get("device_ms") is not None and c.get("device_ms") is not None:
            check(metric, "device_ms", p["device_ms"], c["device_ms"],
                  "higher", step_band)
        pe, ce = _exposed_of(p), _exposed_of(c)
        if pe is not None and ce is not None:
            check(metric, "exposed_comm_seconds", pe, ce, "higher",
                  exposed_band, floor=min_exposed_s)
        # serve-side fields (bench_serve.py `serve` dict, bench_fleet.py
        # `fleet` dict): throughput lower-is-worse, TTFT tail
        # higher-is-worse — the serving legs join the same gate as the
        # fit-side steps/sec instead of regressing silently
        for key in ("serve", "fleet"):
            ps, cs = p.get(key), c.get(key)
            if not (isinstance(ps, dict) and isinstance(cs, dict)):
                continue
            check(metric, f"{key}.tokens_per_sec",
                  ps.get("tokens_per_sec"), cs.get("tokens_per_sec"),
                  "lower", serve_band)
            check(metric, f"{key}.ttft_p99_ms", ps.get("ttft_p99_ms"),
                  cs.get("ttft_p99_ms"), "higher", serve_band,
                  floor=min_ttft_ms)
            # per-output-token latency: the decode-kernel tier's
            # headline — a slower hot path shows here before it moves
            # tokens/s on a queue-bound replay
            check(metric, f"{key}.tpot_p50_ms", ps.get("tpot_p50_ms"),
                  cs.get("tpot_p50_ms"), "higher", serve_band,
                  floor=min_tpot_ms)
            # speculative decode (bench_serve.py spec leg): an
            # acceptance-rate collapse or a tokens-per-target-forward
            # slide is a draft-quality regression even while wall-clock
            # tokens/s holds on the CPU proxy
            psp = ps.get("spec") if isinstance(ps.get("spec"), dict) \
                else {}
            csp = cs.get("spec") if isinstance(cs.get("spec"), dict) \
                else {}
            if psp or csp:
                check(metric, f"{key}.spec.acceptance_rate",
                      psp.get("acceptance_rate"),
                      csp.get("acceptance_rate"), "lower", serve_band,
                      floor=MIN_ACCEPT_DELTA)
                check(metric, f"{key}.spec.tokens_per_target_forward",
                      psp.get("tokens_per_target_forward"),
                      csp.get("tokens_per_target_forward"), "lower",
                      serve_band)
            # disaggregated decode (bench_fleet.py disagg legs): the
            # split-pool TTFT tail and the fp8 wire-compression ratio
            pd = ps.get("disagg") if isinstance(ps.get("disagg"), dict) \
                else {}
            cd = cs.get("disagg") if isinstance(cs.get("disagg"), dict) \
                else {}
            if pd or cd:
                check(metric, f"{key}.disagg.ttft_p99_ms",
                      pd.get("ttft_p99_ms"), cd.get("ttft_p99_ms"),
                      "higher", serve_band, floor=min_ttft_ms)
                check(metric, f"{key}.disagg.fp8_compression_ratio",
                      pd.get("fp8_compression_ratio"),
                      cd.get("fp8_compression_ratio"), "lower",
                      serve_band)
            # prefix federation (bench_fleet.py fed-on leg): fraction
            # of requested prefill tokens satisfied by pages PULLED
            # from another replica over the kvship plane — lower means
            # the directory stopped federating, a regression even
            # while tokens/s holds on the CPU proxy
            check(metric, f"{key}.federated_reuse_ratio",
                  ps.get("federated_reuse_ratio"),
                  cs.get("federated_reuse_ratio"), "lower", serve_band,
                  floor=MIN_GOODPUT_DELTA)
        # goodput plane (telemetry/goodput.py `goodput` dict): the
        # useful-fraction of run wall and measured MFU are both
        # lower-is-worse; one-sided presence (a pre-goodput baseline)
        # lands in `skipped` via check()'s bootstrap path
        pg = p.get("goodput") if isinstance(p.get("goodput"), dict) \
            else {}
        cg = c.get("goodput") if isinstance(c.get("goodput"), dict) \
            else {}
        if pg or cg:
            check(metric, "goodput.fraction", pg.get("fraction"),
                  cg.get("fraction"), "lower", goodput_band,
                  floor=MIN_GOODPUT_DELTA)
            check(metric, "goodput.mfu", pg.get("mfu"), cg.get("mfu"),
                  "lower", goodput_band)
        # measured pipeline-bubble fractions (bench_pipeline.py anatomy
        # audit): schedule-idle share of device time, higher-is-worse
        for fig in ("measured_bubble_fraction_1f1b",
                    "measured_bubble_fraction_gpipe"):
            if p.get(fig) is not None or c.get(fig) is not None:
                check(metric, fig, p.get(fig), c.get(fig), "higher",
                      goodput_band, floor=MIN_GOODPUT_DELTA)
    # incident-plane detector overhead (bench_incident.py A/B leg):
    # an ABSOLUTE gate on the CURRENT round — the measured incident
    # on-vs-off steps/sec delta must stay within incident_band even
    # when the previous round has no such leg (overhead that merely
    # holds steady at 5% is still a broken contract)
    for metric in sorted(curr_by):
        ia = curr_by[metric].get("incident_ab")
        if not isinstance(ia, dict) or ia.get("overhead_pct") is None:
            continue
        compared += 1
        pct = float(ia["overhead_pct"])
        row = {"metric": metric, "figure": "incident_ab.overhead_pct",
               "prev": ia.get("steps_per_sec_off"),
               "curr": ia.get("steps_per_sec_on"),
               "delta_pct": round(pct, 2),
               "note": "absolute gate: incident plane on vs off"}
        if pct > incident_band * 100:
            regressions.append(row)
    report = {
        "metric": "perf_ledger",
        "compared": compared,
        "regressions": regressions,
        "improvements": improvements,
        "skipped": skipped,
        "only_prev": sorted(set(prev_by) - set(curr_by)),
        "only_curr": sorted(set(curr_by) - set(prev_by)),
        "bands": {"step": step_band, "exposed": exposed_band,
                  "serve": serve_band, "goodput": goodput_band,
                  "incident": incident_band,
                  "min_exposed_s": min_exposed_s,
                  "min_ttft_ms": min_ttft_ms,
                  "min_goodput_delta": MIN_GOODPUT_DELTA},
        "ok": not regressions,
    }
    return report


def main(argv: list) -> int:
    import argparse
    parser = argparse.ArgumentParser(
        prog="python -m benchmarks.ledger",
        description="Compare two bench JSON rounds; exit 1 on regression.")
    parser.add_argument("prev", help="previous round (BENCH_r*.json or "
                        "a file of bench JSON lines)")
    parser.add_argument("curr", help="current round, same formats")
    parser.add_argument("--step-band", type=float, default=STEP_BAND,
                        help="relative band for steps/sec + device_ms "
                        f"(default {STEP_BAND})")
    parser.add_argument("--exposed-band", type=float, default=EXPOSED_BAND,
                        help="relative band for exposed-comm seconds "
                        f"(default {EXPOSED_BAND})")
    parser.add_argument("--serve-band", type=float, default=SERVE_BAND,
                        help="relative band for serve/fleet tokens-per-"
                        f"sec and TTFT p99 (default {SERVE_BAND})")
    parser.add_argument("--goodput-band", type=float,
                        default=GOODPUT_BAND,
                        help="relative band for goodput fraction, MFU "
                        "and measured bubble fractions "
                        f"(default {GOODPUT_BAND})")
    parser.add_argument("--incident-band", type=float,
                        default=INCIDENT_BAND,
                        help="ABSOLUTE ceiling on the incident plane's "
                        "measured on-vs-off steps/sec overhead "
                        f"(default {INCIDENT_BAND})")
    args = parser.parse_args(argv)
    report = compare(args.prev, args.curr, step_band=args.step_band,
                     exposed_band=args.exposed_band,
                     serve_band=args.serve_band,
                     goodput_band=args.goodput_band,
                     incident_band=args.incident_band)
    print(json.dumps(report))
    return 0 if report["ok"] else 1


if __name__ == "__main__":   # pragma: no cover - exercised via bench.py
    import sys
    sys.exit(main(sys.argv[1:]))
