"""BASELINE config #3: Tune PBT over MNIST lr, 4 trials — measured.

The full config asks for 4 × v4-8 (one pod slice per trial); on this
box the same sweep runs TIME-SLICED on one chip: ``resources_per_trial``
declares one TPU per trial, the builtin runner's device leaser
partitions the single visible chip into one lease, and the four trial
threads serialize on it (tune/runner.py _DeviceLeaser — the same
mechanism that gives concurrent trials disjoint chip halves on larger
hosts).  PBT still exploits: the population dict accumulates across the
serialized trials, so later trials clone earlier winners' checkpoints
(tune/schedulers.py PopulationBasedTraining works on recorded results,
not on wall-clock coexistence).

What the one JSON line measures, round over round:

- ``value``: sweep wall seconds for 4 trials × 6 epochs of the MNIST
  classifier with per-epoch checkpoint+report — the Tune layer's
  end-to-end overhead (scheduling, lease churn, checkpoint
  serialization, exploit restarts) on top of training compute.
- ``best_accuracy``: the sweep must still LEARN (PBT pulls the
  population toward the good lr).
- ``exploits``: exploit restarts that actually happened (0 would mean
  the PBT path went untested).
- ``compute_floor_s`` + ``tune_overhead_ratio``: the sweep wall
  DECOMPOSED.  A standalone fit of one trial's exact workload measures
  the steady per-step seconds; the floor is
  ``trials x epochs x batches x measured_step`` — pure training
  compute, no Tune.  ``wall / floor`` is then the Tune layer's overhead
  as a TRACKED RATIO, round over round, instead of an absolute wall
  number that moves with the box (benchmarks/README.md row).

    python -m benchmarks.bench_tune_pbt

Reference surface: ray_lightning/tests/test_tune.py:42-57 (per-trial
isolation) + the reference's PBT usage via ray.tune schedulers
(SURVEY.md §3.3); BASELINE.md config #3.
"""

from __future__ import annotations

import json
import os
import time

import jax


def main() -> None:
    from ray_lightning_tpu import Trainer, tune
    from ray_lightning_tpu.models import LightningMNISTClassifier

    platform = jax.devices()[0].platform
    # CPU smoke (CI): shrink the workload, keep every moving part
    epochs = 6 if platform != "cpu" else 2
    train_batches = 30 if platform != "cpu" else 4
    batch_size = 128 if platform != "cpu" else 16

    exploits: list[str] = []
    trials = 4

    def measured_step_s() -> float:
        """Steady per-step seconds of ONE trial's exact workload,
        measured by a standalone fit (no Tune): median over the
        post-compile steps — the compute-only number the floor is
        built from."""
        from ray_lightning_tpu.core.callbacks import Callback

        class StepTimer(Callback):
            needs_batch = False

            def __init__(self):
                self.marks = []

            def on_train_batch_end(self, trainer, module, outputs,
                                   batch, idx):
                self.marks.append(time.monotonic())

        timer = StepTimer()
        module = LightningMNISTClassifier(
            config={"batch_size": batch_size, "lr": 0.05},
            train_size=batch_size * train_batches)
        Trainer(max_epochs=2, limit_train_batches=train_batches,
                limit_val_batches=0, num_sanity_val_steps=0,
                enable_checkpointing=False, logger=False, seed=0,
                callbacks=[timer]).fit(module)
        import numpy as np
        deltas = np.diff(np.asarray(timer.marks))
        # skip the compile-bearing first step; the median resists
        # host-side outliers
        return float(np.median(deltas[1:])) if len(deltas) > 1 else 0.0

    step_s = measured_step_s()

    def train_fn(config, checkpoint_dir=None):
        module = LightningMNISTClassifier(
            config={"batch_size": batch_size, "lr": config["lr"]},
            train_size=batch_size * train_batches)
        trainer = Trainer(
            max_epochs=epochs,
            limit_train_batches=train_batches,
            limit_val_batches=2,
            num_sanity_val_steps=0,
            enable_checkpointing=False,
            logger=False,
            seed=0,
            callbacks=[tune.TuneReportCheckpointCallback(
                on="validation_end")],
            default_root_dir=tune.get_trial_dir(),
        )
        ckpt_path = None
        if checkpoint_dir:
            exploits.append(checkpoint_dir)
            ckpt_path = os.path.join(checkpoint_dir, "checkpoint")
        trainer.fit(module, ckpt_path=ckpt_path)

    t0 = time.monotonic()
    analysis = tune.run(
        train_fn,
        # deliberately includes two lrs too small to compete: PBT's job
        # in this sweep is to exploit them onto the winners' weights
        config={"lr": tune.grid_search([0.05, 0.01, 1e-4, 1e-5])},
        resources_per_trial=tune.get_tune_resources(
            num_workers=1, use_tpu=True, tpus_per_worker=1),
        scheduler=tune.PopulationBasedTraining(
            metric="ptl/val_accuracy", mode="max",
            perturbation_interval=2,
            hyperparam_mutations={"lr": [0.05, 0.01]}),
        local_dir=os.environ.get("RLT_TUNE_DIR", "rlt_tune"),
        name=f"pbt_bench_{int(time.time())}",
    )
    wall = time.monotonic() - t0

    best = analysis.get_best_trial("ptl/val_accuracy", "max")
    # compute-only floor: what the sweep's training steps alone cost —
    # everything above it is the Tune layer (scheduling, lease churn,
    # checkpoint serialization, exploit restarts, validation)
    floor = trials * epochs * train_batches * step_s
    line = {
        "metric": f"tune_pbt_mnist_4trials_wall_s_{platform}",
        "value": round(wall, 2),
        "unit": "s",
        "best_accuracy": round(
            float(best.last_result["ptl/val_accuracy"]), 3),
        "exploits": len(exploits),
        "trials_terminated": sum(
            t.status == "TERMINATED" for t in analysis.trials),
        "measured_step_s": round(step_s, 5),
        "compute_floor_s": round(floor, 2),
        "tune_overhead_ratio": round(wall / floor, 2) if floor else None,
    }
    print(json.dumps(line), flush=True)
    assert line["trials_terminated"] == trials, analysis.trials


if __name__ == "__main__":
    main()
