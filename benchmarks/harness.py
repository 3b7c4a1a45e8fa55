"""Shared benchmark harness: time steady-state training steps through
the full framework path (Trainer → compiled SPMD step) and print one
JSON line per metric, the same contract as the repo-root ``bench.py``.

The BASELINE configs (BASELINE.md) are each covered by a script in this
directory; ``python -m benchmarks.bench_resnet50`` etc.  The timing
method matches bench.py: warmup to steady state, then fetch a loss
scalar as the device sync point.
"""

from __future__ import annotations

import json
import time

import numpy as np


def run_steps_per_sec(module, metric: str, *, warmup: int = 3,
                      timed: int = 30, baseline: "float | None" = None,
                      strategy=None, trainer_kwargs=None,
                      trace_steps: int = 0,
                      inline_device_ms: bool = False,
                      telemetry: bool = True,
                      extra_fields: "dict | None" = None) -> dict:
    """Time steady-state steps; optionally profile a WARM tail.

    ``trace_steps > 0``: after the timed window closes (and its sync
    lands), the profiler traces that many additional steps of the SAME
    fit — the compiled program is warm, so the window holds step
    executions and no compilation.  The result dict then carries
    ``trace_dir``.  A profiler that cannot start or stop fails the run:
    a record that asked for device time and silently lacks it is worse
    than no record.

    ``inline_device_ms``: fold the dominant XLA module's median device
    ms/step (from the warm-tail trace) into the ONE printed JSON line
    as ``device_ms`` — the number of record alongside the wall
    steps/sec, which also moves with what else the host is doing.  The
    trace dir is consumed.

    ``telemetry`` (default on): run with the framework telemetry layer
    enabled and report the exported ``telemetry.jsonl`` path as
    ``telemetry_jsonl`` in the JSON line, so a BENCH regression can be
    attributed to a phase (step vs data_wait vs compile) from the span
    stream instead of re-running under a profiler.

    Any captured warm-tail trace additionally lands as an ``anatomy``
    field (telemetry/anatomy.py): the MEASURED per-step device-time
    split — compute / collective (by op + ici/dcn link) /
    trace-measured exposed comm / host gap — so every leg's claim is
    one JSON diff against the previous round
    (``bench.py --compare`` / benchmarks/ledger.py gates on it).
    """
    from ray_lightning_tpu import Trainer
    from ray_lightning_tpu.core.callbacks import Callback

    class Timer(Callback):
        """>=` comparisons + actual step counting so chunked dispatch
        (steps_per_execution>1: global_step advances k at a time) is
        timed correctly."""

        needs_batch = False   # reads metrics/step only, never the batch

        def __init__(self):
            self.t0 = None
            self.start_step = None
            self.steps = None
            self.elapsed = None
            self.trace_dir = None
            self._last_metrics = None

        @staticmethod
        def _sync(metrics):
            # fetch a loss value: waits for the step that produced it
            float(np.asarray(metrics["loss"]).ravel()[-1])

        def on_train_batch_end(self, trainer, mod, metrics, batch, idx):
            self._last_metrics = metrics
            if self.t0 is None and trainer.global_step >= warmup:
                self._sync(metrics)
                self.start_step = trainer.global_step
                self.t0 = time.monotonic()
            elif self.t0 is not None and self.elapsed is None \
                    and trainer.global_step >= self.start_step + timed:
                self._sync(metrics)
                self.elapsed = time.monotonic() - self.t0
                self.steps = trainer.global_step - self.start_step
                if trace_steps > 0 and self.trace_dir is None:
                    import tempfile

                    import jax
                    d = tempfile.mkdtemp(prefix="rlt_trace_")
                    jax.profiler.start_trace(d)
                    self.trace_dir = d

        def on_train_end(self, trainer, mod):
            if self.trace_dir is not None:
                import jax
                if self._last_metrics is not None:
                    self._sync(self._last_metrics)
                jax.profiler.stop_trace()

    timer = Timer()
    # chunked dispatch rounds the warmup boundary up to a chunk edge, so
    # leave 2 chunks of slack past warmup+timed
    slack = 2 * (trainer_kwargs or {}).get("steps_per_execution", 1)
    trainer = Trainer(
        max_steps=warmup + timed + slack + trace_steps, max_epochs=10**6,
        strategy=strategy,
        enable_checkpointing=False, num_sanity_val_steps=0,
        limit_val_batches=0, log_every_n_steps=10**9, callbacks=[timer],
        seed=0, telemetry=bool(telemetry), **(trainer_kwargs or {}))
    trainer.fit(module)
    assert timer.elapsed is not None, "did not reach timed steps"
    steps_per_sec = timer.steps / timer.elapsed
    result = {
        "metric": metric,
        "value": round(steps_per_sec, 3),
        "unit": "steps/sec",
        "vs_baseline": round(steps_per_sec / (baseline or steps_per_sec), 3),
    }
    # cold-vs-warm startup tracking (compile/): how long until the first
    # step ran, and whether the persistent compilation cache served this
    # process ("hit"), compiled everything fresh ("miss") or was off —
    # so BENCH rounds catch startup regressions steps/sec can't see
    ttfs = getattr(trainer, "time_to_first_step", None)
    if ttfs is not None:
        result["time_to_first_step_seconds"] = round(ttfs, 3)
    from ray_lightning_tpu.compile import cache as compile_cache
    result["compile_cache"] = compile_cache.status_word()
    # comm plane: which dtype the gradient collectives rode ("fp32" =
    # uncompressed).  _grad_sync is the worker-side resolution (present
    # after a LocalPlugin fit); distributed drivers fall back to the
    # policy, which only activates on multi-process meshes.
    pol = getattr(trainer, "comm_policy", None)
    sync = getattr(trainer, "_grad_sync", None)
    active = sync is not None or (
        pol is not None and pol.enabled and trainer.world_size > 1)
    result["comm"] = pol.compress if (active and pol is not None) else "fp32"
    # planner plane: whether this run's parallelism was picked by the
    # strategy="auto" cost model ("auto" — the PlanReport landed on
    # trainer._plan_report) or hand-configured ("manual")
    result["plan"] = ("auto" if getattr(trainer, "_plan_report", None)
                      else "manual")
    # MPMD plane: per-stage compile seconds, simulated bubble fractions
    # per schedule and activation wire bytes (mpmd/engine.py report) —
    # the fields bench_pipeline.py's one-diff comparison reads
    rep = getattr(trainer, "_mpmd_report", None)
    if rep:
        result["mpmd"] = {
            "schedule": rep["schedule"],
            "stages": rep["stages"],
            "virtual": rep.get("virtual", 1),
            "cuts": rep.get("cuts"),
            "codec": rep["codec"],
            "per_stage_compile_seconds":
                rep.get("per_stage_compile_seconds"),
            "bubble_fraction": {
                k: v["bubble_fraction"]
                for k, v in rep.get("bubble", {}).items()},
            "activation_bytes_per_step":
                rep.get("activation_bytes_per_step"),
        }
    if timer.trace_dir is not None:
        # measured step anatomy from the warm-tail trace
        # (telemetry/anatomy.py): where the device time of THIS leg's
        # steps actually went — compute / collective (by op and
        # ici/dcn link) / trace-measured exposed comm / host gap.
        # Parsed before the device_ms path below consumes the dir.
        from ray_lightning_tpu.telemetry.anatomy import (
            parse_trace_anatomy,
        )
        result["anatomy"] = parse_trace_anatomy(timer.trace_dir).as_dict()
    # goodput plane (telemetry/goodput.py): the run's wall-clock
    # partition + measured MFU, compacted to the fields the ledger
    # gates on (benchmarks/ledger.py goodput-fraction / MFU bands)
    gp = getattr(trainer, "_goodput_report", None)
    if gp:
        result["goodput"] = {
            "fraction": gp.get("goodput_fraction"),
            "mfu": gp.get("mfu"),
            "run_wall_s": gp.get("run_wall_s"),
            "buckets": gp.get("buckets"),
        }
    paths = getattr(trainer, "_telemetry_paths", None)
    if paths:
        result["telemetry_jsonl"] = paths["jsonl"]
        # memory + comms alongside steps/sec, so BENCH rounds catch HBM
        # and collective-traffic regressions that leave wall time alone
        summary = paths.get("summary") or {}
        if "hbm_peak_bytes" in summary:
            result["hbm_peak_bytes"] = summary["hbm_peak_bytes"]
        if "collective_gibs" in summary:
            result["collective_gibs"] = summary["collective_gibs"]
    if inline_device_ms and timer.trace_dir is not None:
        from benchmarks import trace_tools
        result["device_ms"] = round(
            trace_tools.dominant_module_ms(timer.trace_dir), 2)
        timer.trace_dir = None
    if callable(extra_fields):
        # derived fields (e.g. bench_comm's exposed_comm_seconds need
        # the measured value): compute from the assembled result
        result.update(extra_fields(result) or {})
    elif extra_fields:
        result.update(extra_fields)
    print(json.dumps(result))
    if timer.trace_dir is not None:
        result["trace_dir"] = timer.trace_dir
    return result
