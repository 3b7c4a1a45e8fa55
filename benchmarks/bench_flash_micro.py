"""Micro-benchmark: flash attention fwd+bwd device time at a given
shape, isolated from the rest of the model.

Usage:  python -m benchmarks.bench_flash_micro [T] [steps]

Times ``jit(value_and_grad)`` of a scalar loss over
``flash_attention(q, k, v, causal=True)`` at the headline shape
(B=8, H=12, D=64, T=1024 by default) and prints wall ms/iter plus the
device ms/iter of the dominant XLA module (device time, not host
time).  The knobs
under test (RLT_FLASH_*) are env vars, so A/B runs are just env
changes — the same pattern as profile_headline.py.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    t = int(sys.argv[1]) if len(sys.argv) > 1 else 1024
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    b, h, d = 8, 12, 64

    from ray_lightning_tpu.ops.flash_attention import flash_attention

    key = jax.random.PRNGKey(0)
    kq, kk, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, t, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, t, h, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, t, h, d), jnp.bfloat16)
    co = jax.random.normal(kg, (b, t, h, d), jnp.bfloat16)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) * co.astype(jnp.float32))

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    val, grads = step(q, k, v)
    for _ in range(2):
        val, grads = step(q, k, v)
    float(np.asarray(val))  # fetching the value waits for the device

    t0 = time.monotonic()
    for _ in range(steps):
        val, grads = step(q, k, v)
    float(np.asarray(val))
    wall_ms = (time.monotonic() - t0) / steps * 1000

    from benchmarks import trace_tools

    def run():
        for _ in range(8):
            out = step(q, k, v)
        float(np.asarray(out[0]))

    try:
        trace_dir = trace_tools.capture_trace(run)
    except Exception as e:  # profiler-less backends still get wall time
        sys.stderr.write(f"trace skipped: {e}\n")
        trace_dir = None
    dev_ms = trace_tools.dominant_module_ms(trace_dir)

    print(json.dumps({
        "metric": f"flash_fwdbwd_T{t}",
        "wall_ms": round(wall_ms, 3),
        "device_ms": round(dev_ms, 3) if dev_ms else None,
        "unit": "ms/iter"}))


if __name__ == "__main__":
    main()
