"""BASELINE config #2: ResNet-50 / CIFAR-10-shaped data, steps/sec/chip.

    python -m benchmarks.bench_resnet50
"""

import jax

from benchmarks.harness import run_steps_per_sec

# first TPU measurement of this exact config (v5e chip, B=128, 32x32,
# NHWC bf16) — later rounds compare against it
BASELINES = {"tpu": 26.4}


def main():
    from ray_lightning_tpu.models.resnet import ResNetLightningModule

    platform = jax.devices()[0].platform
    batch = 128 if platform != "cpu" else 8
    cfg = "resnet50" if platform != "cpu" else "resnet18"
    module = ResNetLightningModule(cfg, batch_size=batch,
                                   train_size=batch * 40)
    run_steps_per_sec(module, f"{cfg}_b{batch}_steps_per_sec_{platform}",
                      baseline=BASELINES.get(platform))

    # image batches are ~1.6 MB: the host→device link (not compute)
    # can bound the streamed number, so also measure with the train
    # set resident on device — the figure the link does not bound
    module = ResNetLightningModule(cfg, batch_size=batch,
                                   train_size=batch * 40)
    run_steps_per_sec(
        module, f"{cfg}_b{batch}_cached_steps_per_sec_{platform}",
        timed=120, baseline=BASELINES.get(platform),
        trainer_kwargs={"steps_per_execution": 8,
                        "cache_train_dataset": True})


if __name__ == "__main__":
    main()
