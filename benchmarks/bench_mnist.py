"""BASELINE config #1: the MNIST classifier, steps/sec.

The reference's flagship example workload (examples/ray_ddp_example.py);
tiny by design — this measures per-step framework overhead more than
compute.

    python -m benchmarks.bench_mnist
"""

import jax

from benchmarks.harness import run_steps_per_sec

# first v5e measurement, B=128 MLP (an older claim, from records since
# removed): per-step host dispatch dominates at this size (compute is
# microseconds)
BASELINES = {"tpu": 63.9}


def main():
    from ray_lightning_tpu.models import LightningMNISTClassifier

    platform = jax.devices()[0].platform
    batch = 128
    module = LightningMNISTClassifier(config={"batch_size": batch},
                                      train_size=batch * 40)
    run_steps_per_sec(module, f"mnist_b{batch}_steps_per_sec_{platform}",
                      timed=100, baseline=BASELINES.get(platform))

    # dispatch-bound workload fix: fold 32 steps into one compiled
    # program (Trainer(steps_per_execution=32)) — one host dispatch per
    # 32 optimizer steps.  train_size is a multiple of 32 batches so
    # every chunk is full.
    module = LightningMNISTClassifier(config={"batch_size": batch},
                                      train_size=batch * 64)
    run_steps_per_sec(
        module, f"mnist_b{batch}_k32_steps_per_sec_{platform}",
        timed=960, baseline=BASELINES.get(platform),
        trainer_kwargs={"steps_per_execution": 32})

    # transfer-bound workload (host→device batch transfer vs sub-ms
    # compute): device-resident train set — batches are gathered
    # on-device by index, only int32 indices cross the link.  How much
    # this and the chunk size k buy is to be re-measured on today's code.
    module = LightningMNISTClassifier(config={"batch_size": batch},
                                      train_size=batch * 128)
    run_steps_per_sec(
        module, f"mnist_b{batch}_cached_steps_per_sec_{platform}",
        timed=2560, baseline=BASELINES.get(platform),
        trainer_kwargs={"steps_per_execution": 64,
                        "cache_train_dataset": True})


if __name__ == "__main__":
    main()
