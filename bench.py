"""Benchmark: GPT-2-small training steps/sec through the full framework
path (Trainer → compiled SPMD train step) on ONE TPU chip.  Without a
chip it fails: it does not fall back to a smaller model on the CPU.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": "steps/sec", "vs_baseline": N,
   "device_ms": M, "telemetry_jsonl": "<path>",
   "hbm_peak_bytes": N, "collective_gibs": N,
   "time_to_first_step_seconds": N, "compile_cache": "hit|miss|off"}

``telemetry_jsonl`` points at the run's exported span/counter stream
(telemetry/): BENCH rounds can attribute a regression to a phase
(step vs data_wait vs compile) straight from the recorded spans.
``hbm_peak_bytes`` / ``collective_gibs`` come from the metrics plane
(telemetry/metrics.py) so rounds track memory and comms regressions
alongside steps/sec.  ``time_to_first_step_seconds`` and
``compile_cache`` come from the compile plane (compile/): the
persistent cache is on by default (``JAX_COMPILATION_CACHE_DIR`` or
``<checkout>/.jax_cache``), so run twice to measure the cold→warm
startup difference.

``value`` is wall steps/sec (the BASELINE.md bar as specified);
``device_ms`` is the median device time of the compiled train step
from a warm-tail trace — the number that does not move with what else
the host is doing.

The reference publishes no numbers (BASELINE.md); ``vs_baseline`` is
measured against the stored first-round value below so rounds are
comparable to each other.  Timing/emission logic lives in
``benchmarks/harness.py``, shared with the per-config scripts under
``benchmarks/``.

The line also carries ``anatomy`` — the measured per-step device-time
split (compute/collective/exposed/host, telemetry/anatomy.py) parsed
from the same warm-tail trace as ``device_ms``.  ``--compare
prev.json`` (a file of bench JSON lines) runs the perf-regression
ledger (benchmarks/ledger.py) over this round's records and exits
nonzero when step time, device_ms or exposed-comm regresses past its
band — the pre-merge perf gate.

One process for each chip: the ``RLT_FLEET_AB`` leg starts replica
processes that need a device of their own, so it runs FIRST, before
this process has touched JAX; the headline fit then takes the chip
in-process.
"""

from __future__ import annotations

import json
import os
import sys

# First recorded value of this cell, so vs_baseline always compares
# like with like: one v5e chip, gpt2-small (seq 1024, bf16 compute,
# remat off), batch 8 — an older claim, from records since removed.
BASELINES = {
    "gpt2s_train_steps_per_sec_tpu": 10.0,
}
METRIC = "gpt2s_train_steps_per_sec_tpu"

WARMUP_STEPS = 3
TIMED_STEPS = 30


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        description="Headline bench; --compare turns it into the "
        "pre-merge perf-regression gate (benchmarks/ledger.py).")
    parser.add_argument(
        "--compare", metavar="PREV_JSON", default=None,
        help="previous round (a file of bench JSON lines); after the "
        "run the ledger compares this round's records against it and "
        "the process exits nonzero when step time, device_ms or "
        "exposed-comm regresses past its band")
    parser.add_argument(
        "--out", metavar="CURR_JSON", default=None,
        help="also write this round's records as JSON lines (the file "
        "a later --compare can read)")
    args = parser.parse_args(argv)

    metric = METRIC
    fleet_results = None
    if os.environ.get("RLT_FLEET_AB") == "1":
        # fleet-plane traffic replay (benchmarks/bench_fleet.py): record
        # a multi-tenant trace, replay at 1x/2x/4x against 1 vs 2
        # replicas plus an autoscaling 1→3 leg — one `fleet` JSON line
        # with tokens/s + TTFT per multiplier, autoscale events, the
        # prefix-reuse ratio and the greedy-parity verdict.  Joins the
        # --compare ledger via fleet.tokens_per_sec / fleet.ttft_p99_ms.
        # Runs before this process initialises a JAX backend (module
        # docstring).
        from benchmarks.bench_fleet import run_fleet_ab
        fleet_results = run_fleet_ab(metric + "_fleet")

    import jax

    from benchmarks.harness import run_steps_per_sec
    from ray_lightning_tpu.models.gpt import CONFIGS, GPTLightningModule

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench.py measures on a TPU chip; JAX found platform="
              f"{dev.platform!r} ({dev.device_kind}).  A CPU run gives no "
              f"number worth recording — nothing was benchmarked.",
              file=sys.stderr)
        return 2
    cfg, batch = CONFIGS["gpt2-small"], 8

    trace_steps = 8
    module = GPTLightningModule(
        cfg, dataset_size=batch * (WARMUP_STEPS + TIMED_STEPS + trace_steps),
        batch_size=batch)
    results = [run_steps_per_sec(
        module, metric, warmup=WARMUP_STEPS,
        timed=TIMED_STEPS, baseline=BASELINES.get(metric),
        trace_steps=trace_steps, inline_device_ms=True)]

    if os.environ.get("RLT_REMAT_AB") == "1":
        # remat-policy ladder (benchmarks/bench_remat.py): compile +
        # time every feasible policy of the headline fixture's
        # configure_remat() ladder and emit ONE `remat` JSON field —
        # per-policy device ms/step + HBM peak + measured winner vs the
        # hand-picked default (gap documented when the hand pick wins).
        from benchmarks.bench_remat import run_remat_ab
        run_remat_ab(metric + "_remat")

    if os.environ.get("RLT_COMM_AB") == "1":
        # comm-plane A/B legs (benchmarks/bench_comm.py): fp32 floor,
        # flat int8, hierarchical int8/fp8/int4, and the bucketed-vs-
        # barrier overlap pair — one JSON line per leg with
        # ``exposed_comm_seconds`` (wall minus the fp32 floor) so the
        # tentpole's overlap win is a single diff.  Runs inline on a
        # multi-device mesh; a single-device session re-runs the legs
        # on the 8-virtual-device CPU proxy in a subprocess.
        from benchmarks.bench_comm import run_comm_ab
        comm_results = run_comm_ab(metric + "_comm")
        if comm_results:
            results.extend(comm_results)

    if fleet_results:
        results.extend(fleet_results)

    if args.out:
        with open(args.out, "w") as f:
            for r in results:
                f.write(json.dumps(r) + "\n")

    if args.compare:
        # perf-regression ledger (benchmarks/ledger.py): this round's
        # records vs the given previous round — the pre-merge gate.
        # Nonzero exit when step time / device_ms / exposed-comm
        # regresses past its band.
        from benchmarks import ledger
        report = ledger.compare(args.compare, results)
        print(json.dumps(report))
        return 0 if report["ok"] else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
