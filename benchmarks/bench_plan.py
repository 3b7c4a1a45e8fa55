"""Benchmark: what does ``Trainer(strategy="auto")`` cost, and what
does it pick?

Prints exactly ONE JSON line (the ``plan`` row of the benchmark
suite):

  {"metric": "plan", "candidates": N, "pruned": N, "rejected": N,
   "compiled": N, "plan_seconds": S, "winner": "...",
   "auto_time_to_first_step_seconds": A,
   "manual_time_to_first_step_seconds": M,
   "compile_cache": "hit|miss|off", "plan": "auto"}

Two fits of the same GPT config back to back: ``strategy="auto"``
(planning + top-k AOT verify + training) vs the best hand-picked
configuration for this topology (the manual baseline the planner is
supposed to match).  ``auto − manual`` time-to-first-step is the
planner's real overhead — with the persistent compile cache active the
winner's verify compile IS the fit's first-dispatch cache hit, so the
gap shrinks to the scoring cost.  Both fits share one cache dir, so
run order matters and is fixed: auto first (cold), manual second
(warm from the planner's own artifacts — the reuse story, measured).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile


def _fit(cfg, batch: int, steps: int, root: str, **kw):
    from ray_lightning_tpu import Trainer
    from ray_lightning_tpu.models.gpt import GPTLightningModule

    module = GPTLightningModule(cfg, dataset_size=batch * steps,
                                batch_size=batch)
    trainer = Trainer(max_steps=steps, max_epochs=10**6, seed=0,
                      default_root_dir=root, enable_checkpointing=False,
                      num_sanity_val_steps=0, limit_val_batches=0,
                      log_every_n_steps=10**9, **kw)
    trainer.fit(module)
    return trainer


def main() -> None:
    import jax

    from ray_lightning_tpu.compile import cache as compile_cache

    platform = jax.devices()[0].platform
    cfg = "tiny" if platform == "cpu" else "gpt2-small"
    batch, steps = 8, 4

    with tempfile.TemporaryDirectory() as td:
        auto = _fit(cfg, batch, steps, os.path.join(td, "auto"),
                    strategy="auto")
        report = auto._plan_report or {}
        # manual baseline: the same plan hand-picked (DDP over every
        # chip is the measured-best manual config for these sizes)
        manual = _fit(cfg, batch, steps, os.path.join(td, "manual"),
                      strategy="ddp")
        result = {
            "metric": "plan",
            "candidates": report.get("enumerated", 0),
            "pruned": report.get("pruned", 0),
            "rejected": report.get("rejected", 0),
            "compiled": report.get("compiled", 0),
            "plan_seconds": report.get("plan_seconds", 0.0),
            "winner": report.get("winner"),
            "auto_time_to_first_step_seconds": round(
                auto.time_to_first_step or 0.0, 3),
            "manual_time_to_first_step_seconds": round(
                manual.time_to_first_step or 0.0, 3),
            "compile_cache": compile_cache.status_word(),
            "plan": "auto",
        }
    print(json.dumps(result))


if __name__ == "__main__":
    sys.exit(main())
