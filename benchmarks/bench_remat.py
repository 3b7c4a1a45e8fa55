"""Remat-policy A/B ladder: compile and time EVERY feasible policy on
the headline fixture, emit one ``remat`` JSON field.

``bench.py`` runs this when ``RLT_REMAT_AB=1``.  Until PR 12 the
remat-policy walk was manual — hand-measured picks live as comments in
``models/gpt.py`` (e.g. ``dots`` bought +17% steps/s on gpt2-medium)
and every new claim meant a hand-driven re-run.  This ladder automates
the headroom hunt the 49.35 ms/step plateau has been waiting on: every
policy of the module's ``configure_remat()`` ladder gets

- an AOT memory probe (``memory_analysis`` of the compiled train step
  — argument + output + temp − alias, the planner's own peak account),
  which also decides *feasibility*: a policy whose modeled peak
  exceeds the device budget (when the runtime reports one) is recorded
  as infeasible instead of risking an OOM mid-ladder;
- a measured wall steps/sec leg through the shared harness, with the
  warm-tail ``device_ms`` when the platform's profiler cooperates.

One summary JSON line then carries per-policy device ms/step + HBM
peak + the measured winner NEXT TO the hand-picked default, with the
gap documented — so every future policy claim is one JSON diff, and a
ladder winner slower than the hand pick is visible, not silent.
"""

from __future__ import annotations

import json
import os
import sys

WARMUP = 3
TIMED = 15


def _compiled_peak(module) -> "tuple[int, str | None]":
    """(peak bytes of the single-device donated train step, error) —
    the same arg+out+temp−alias account the planner's verify stage
    reads (compile/aot.py ScoredCompile.peak_bytes)."""
    import jax
    import numpy as np

    from ray_lightning_tpu.core.steps import build_init_fn, build_train_step

    try:
        batch = jax.tree_util.tree_map(
            np.asarray, next(iter(module.train_dataloader())))
        tx = module.configure_optimizers()
        if isinstance(tx, dict):
            tx = tx["optimizer"]
        abstract = jax.eval_shape(build_init_fn(module, tx),
                                  jax.random.PRNGKey(0), batch)
        jitted = jax.jit(build_train_step(module, tx), donate_argnums=0)
        mem = jitted.lower(abstract, batch).compile().memory_analysis()
        peak = (int(mem.argument_size_in_bytes)
                + int(mem.output_size_in_bytes)
                + int(mem.temp_size_in_bytes)
                - int(mem.alias_size_in_bytes))
        return max(0, peak), None
    except Exception as e:   # noqa: BLE001 - per-policy soft fail
        return 0, f"{type(e).__name__}: {e}"


def _device_budget() -> "int | None":
    import jax
    dev = jax.devices()[0]
    try:
        stats = dev.memory_stats()
        if stats and "bytes_limit" in stats:
            return int(stats["bytes_limit"])
    except Exception:   # noqa: BLE001 - CPU / profiler-less backends
        pass
    if getattr(dev, "platform", None) == "tpu":
        from ray_lightning_tpu.core.trainer import Trainer
        return Trainer._HBM_BY_KIND.get(getattr(dev, "device_kind", ""))
    return None


def run_remat_ab(metric_prefix: str = "remat_ab") -> dict:
    """Emit one ladder leg per feasible policy plus the ``remat``
    summary line (module docstring)."""
    import jax

    from benchmarks.harness import run_steps_per_sec
    from ray_lightning_tpu.models.gpt import GPTLightningModule

    platform = jax.devices()[0].platform
    fixture = "tiny" if platform == "cpu" else "gpt2-small"
    batch = 8
    steps = WARMUP + TIMED + 4

    hand = GPTLightningModule(fixture).configure_remat().default
    budget = _device_budget()
    policies: dict = {}
    for policy in GPTLightningModule(fixture).configure_remat().policies:
        module = GPTLightningModule(fixture, dataset_size=batch * steps,
                                    batch_size=batch)
        module.configure_remat().apply(policy)
        peak, err = _compiled_peak(module)
        entry: dict = {"hbm_peak_bytes": peak}
        if err is not None:
            entry["error"] = f"compile: {err}"
            policies[policy] = entry
            continue
        if budget is not None and peak > budget:
            entry["error"] = (f"infeasible: compiled peak "
                              f"{peak >> 20} MiB > {budget >> 20} "
                              f"MiB device budget")
            policies[policy] = entry
            continue
        try:
            res = run_steps_per_sec(
                module, f"{metric_prefix}_{policy}", warmup=WARMUP,
                timed=TIMED, telemetry=False,
                trace_steps=4, inline_device_ms=True)
        except Exception as e:   # noqa: BLE001 - one bad leg != no ladder
            entry["error"] = f"run: {type(e).__name__}: {e}"
            policies[policy] = entry
            continue
        wall_ms = 1000.0 / res["value"]
        entry["steps_per_sec"] = res["value"]
        entry["wall_ms"] = round(wall_ms, 3)
        # device_ms is the number of record when the platform traces;
        # CPU smoke runs rank on wall ms
        entry["device_ms"] = res.get("device_ms")
        entry["rank_ms"] = round(res.get("device_ms") or wall_ms, 3)
        policies[policy] = entry

    timed_ok = {p: e for p, e in policies.items() if "rank_ms" in e}
    winner = min(timed_ok, key=lambda p: timed_ok[p]["rank_ms"]) \
        if timed_ok else None
    summary = {
        "metric": metric_prefix,
        "remat": {
            "fixture": fixture,
            "batch": batch,
            "hand_picked": hand,
            "winner": winner,
            "policies": policies,
        },
    }
    if winner is not None and hand in timed_ok:
        win_ms = timed_ok[winner]["rank_ms"]
        hand_ms = timed_ok[hand]["rank_ms"]
        summary["remat"]["winner_ms"] = win_ms
        summary["remat"]["hand_picked_ms"] = hand_ms
        summary["remat"]["winner_le_hand_picked"] = win_ms <= hand_ms
        # the acceptance contract: the ladder's winner beats (or ties)
        # the hand pick — when it doesn't, the gap is documented here
        # rather than silently dropped
        summary["remat"]["gap_pct"] = round(
            100.0 * (win_ms - hand_ms) / hand_ms, 2)
    print(json.dumps(summary))
    return summary


def main() -> None:
    run_remat_ab(os.environ.get("RLT_REMAT_AB_METRIC", "remat_ab"))


if __name__ == "__main__":
    sys.exit(main())
