"""Throughput tuning for small models, where the host is the bottleneck.

The reference's examples stop at "attach the plugin"
(examples/ray_ddp_example.py:118-173); on TPU the next question is
always throughput, and for small models the bottleneck is the host —
per-step dispatch latency and host→device batch transfer — not the
MXU.  This example walks the three knobs that address it (the order
and the size of each effect are older claims from records since
removed — to be re-measured on today's code):

1. ``Trainer(steps_per_execution=k)`` — k optimizer steps ride ONE
   compiled dispatch (``lax.scan`` over stacked batches): k× fewer
   host round-trips.
2. ``Trainer(cache_train_dataset=True)`` — the train set uploads once
   and lives on device; each epoch a device-side repack follows the
   loader's own index order (shuffle-accurate), and steps gather their
   batch by index — the per-step transfer disappears.  Works under
   distributed plugins too (the cache shards across workers' devices).
3. ``Trainer(precision="bf16")`` — float batch leaves cast to bf16 at
   the host boundary, halving whatever transfer remains.

Also on by default (env knobs, models/gpt.py): bf16-resident params
with an fp32 master (``RLT_BF16_PARAMS``), the fused bf16-logits LM
loss (``RLT_FUSED_CE``), double-buffered streamed input
(``RLT_STREAM_PREFETCH``), and conditional state donation
(``RLT_DONATE`` — auto skips ``donate_argnums`` on small states, worth
−3.4% device time on the gpt2-small headline; see
``core/trainer.py _should_donate``).

    python -m ray_lightning_tpu.examples.ray_perf_tuning_example \
        [--smoke-test] [--num-workers N]
"""

from __future__ import annotations

import argparse
import time

from ray_lightning_tpu import RayXlaPlugin, Trainer
from ray_lightning_tpu.models import LightningMNISTClassifier


def run(steps_per_execution: int = 1, cache: bool = False,
        precision: str = "32", num_workers: int = 0,
        max_epochs: int = 2, train_size: int = 2048) -> tuple[float, int]:
    """One fit with the given knobs; returns (seconds, steps)."""
    plugins = []
    if num_workers > 0:
        plugins.append(RayXlaPlugin(num_workers=num_workers,
                                    platform="cpu"))
    model = LightningMNISTClassifier(config={"batch_size": 128},
                                     train_size=train_size)
    trainer = Trainer(
        plugins=plugins or None,
        max_epochs=max_epochs,
        steps_per_execution=steps_per_execution,
        cache_train_dataset=cache,
        precision=precision,
        enable_checkpointing=False,
        num_sanity_val_steps=0,
        limit_val_batches=0,
        log_every_n_steps=10**9,
        seed=0,
    )
    t0 = time.monotonic()
    trainer.fit(model)
    return time.monotonic() - t0, trainer.global_step


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke-test", action="store_true",
                        help="tiny sizes, single fit per config")
    parser.add_argument("--num-workers", type=int, default=0,
                        help=">0: run through RayXlaPlugin CPU actors "
                             "(cache shards across workers)")
    args = parser.parse_args()

    kw = dict(num_workers=args.num_workers)
    if args.smoke_test:
        import jax
        jax.config.update("jax_platforms", "cpu")  # CI boxes have no TPU
        kw.update(max_epochs=1, train_size=512)

    configs = [
        ("streamed (baseline)", dict()),
        ("steps_per_execution=8", dict(steps_per_execution=8)),
        ("+ cache_train_dataset", dict(steps_per_execution=8, cache=True)),
        ("+ precision=bf16", dict(steps_per_execution=8, cache=True,
                                  precision="bf16")),
    ]
    for name, knobs in configs:
        secs, steps = run(**{**kw, **knobs})
        print(f"{name:28s} {steps / secs:8.1f} steps/s "
              f"({steps} steps in {secs:.1f}s)")


if __name__ == "__main__":
    main()
