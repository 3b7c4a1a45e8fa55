"""Trace a GPT-config train step and print a device-time breakdown.

Usage:  python -m benchmarks.profile_headline [steps] [config]

``config`` is any ``models.gpt.CONFIGS`` name (default gpt2-small, the
headline).  Builds the same compiled train step the Trainer runs
(core/steps.py), warms it OUTSIDE the trace (a window that holds a
compilation says little about steady state), then traces
``steps`` warm executions.  Env toggles under test (RLT_BF16_PARAMS /
RLT_REMAT_POLICY / RLT_FLASH_*) are read by the model as usual, so A/B
runs are just env changes.
"""

from __future__ import annotations

import json
import sys

import numpy as np

from benchmarks import trace_tools


def main() -> None:
    import jax

    from ray_lightning_tpu.core.steps import build_init_fn, build_train_step
    from ray_lightning_tpu.models.gpt import CONFIGS, GPTLightningModule

    timed = int(sys.argv[1]) if len(sys.argv) > 1 else 5
    platform = jax.devices()[0].platform
    default_cfg = "gpt2-small" if platform != "cpu" else "tiny"
    cfg_name = sys.argv[2] if len(sys.argv) > 2 else default_cfg
    if cfg_name not in CONFIGS:
        raise SystemExit(
            f"unknown config {cfg_name!r}; options: {sorted(CONFIGS)}")
    cfg = CONFIGS[cfg_name]
    batch_size = 8

    module = GPTLightningModule(cfg, dataset_size=batch_size * 2,
                                batch_size=batch_size)
    module.setup_model()
    tx = module.configure_optimizers()
    batch = next(iter(module.train_dataloader()))
    batch = jax.device_put(jax.tree_util.tree_map(np.asarray, batch))

    init_fn = jax.jit(build_init_fn(module, tx))
    step_fn = jax.jit(build_train_step(module, tx), donate_argnums=0)

    state = init_fn(jax.random.PRNGKey(0), batch)
    for _ in range(3):  # warm: compile + steady-state allocator
        state, metrics = step_fn(state, batch)
    float(np.asarray(metrics["loss"]))  # waits for the device

    def run():
        nonlocal state
        for _ in range(timed):
            state, m = step_fn(state, batch)
        float(np.asarray(m["loss"]))

    trace_dir = trace_tools.capture_trace(run)

    total = trace_tools.total_device_ms(trace_dir)
    print(json.dumps({"device_ms_per_step": round(total / timed, 2),
                      "steps": timed, "trace_dir": trace_dir}))
    print("\n# bucket ms/step")
    for b, ms in trace_tools.device_breakdown(trace_dir).items():
        print(f"{b:28s} {ms / timed:8.2f}")
    print("\n# roofline (per dedup'd op): ms/step  n/step  TFLOP/s  GB/s  "
          "bound")
    for r in trace_tools.roofline(trace_dir, timed):
        print(f"{r['ms_per_step']:8.2f} {r['count'] / timed:6.1f} "
              f"{r['tflops']:8.1f} {r['gbps']:7.1f}  "
              f"{r['bound_frac']:4.2f} {r['bound_by'][:4]}  "
              f"[{r['category']}] {r['source'][:60]}")


if __name__ == "__main__":
    main()
