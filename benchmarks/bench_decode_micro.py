"""Micro-benchmark: per-token decode attention A/B — dense masked
einsum vs the Pallas flash-decode kernel vs its paged variant
(ops/flash_decode.py) at serving shapes.

Usage:  python -m benchmarks.bench_decode_micro [steps] [L ...]

Times ``jit(cached_attention)`` — one new token per slot against a
[1, S, L, H*D] KV cache with RAGGED per-slot positions (the serve
plane's steady state: every slot at a different depth) — and prints
one JSON line per (impl, L) with wall ms/iter plus the device ms/iter
of the dominant XLA module (device time, not host time — same
discipline as bench_flash_micro.py).

The acceptance bar is enforced where the kernel actually compiles
(TPU): at L >= 2048 the length-aware kernel must beat the dense
einsum on device ms — the dense path reads and scores all L cache
rows per token while the kernel's clamped index map stops fetching at
``positions[s]``.  On CPU the kernel runs under the Pallas
interpreter (numerics-only; orders of magnitude slower), so the bar
is reported but not asserted.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

#: serving shape: 8 slots, 8 heads x 64 = C 512 (128-aligned for TPU)
S, H, D = 8, 8, 64
PAGE_SIZE = 128


def _ragged_positions(L: int) -> np.ndarray:
    """Per-slot depths spread over [L/8, L-1] — the steady-state mix a
    continuous-batching scheduler produces (no two slots aligned)."""
    return np.linspace(L // 8, L - 1, S).astype(np.int32)


def _bench_impl(impl: str, L: int, steps: int, platform: str) -> dict:
    from benchmarks import trace_tools
    from ray_lightning_tpu.ops.attention import cached_attention
    from ray_lightning_tpu.serve.fleet.pages import identity_page_table

    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (S, 1, H, D), jnp.bfloat16)
    kc = jax.random.normal(kk, (1, S, L, H * D), jnp.bfloat16)
    vc = jax.random.normal(kv, (1, S, L, H * D), jnp.bfloat16)
    pos = jnp.asarray(_ragged_positions(L))
    table = (jnp.asarray(identity_page_table(S, L, PAGE_SIZE))
             if impl == "paged" else None)

    @jax.jit
    def step(q, kc, vc, pos):
        return cached_attention(q, kc, vc, pos, layer=0, impl=impl,
                                page_table=table)

    out = step(q, kc, vc, pos)
    out.block_until_ready()
    for _ in range(2):
        step(q, kc, vc, pos).block_until_ready()

    t0 = time.monotonic()
    for _ in range(steps):
        out = step(q, kc, vc, pos)
    out.block_until_ready()
    wall_ms = (time.monotonic() - t0) / steps * 1000

    def run():
        for _ in range(8):
            out = step(q, kc, vc, pos)
        out.block_until_ready()

    try:
        trace_dir = trace_tools.capture_trace(run)
    except Exception as e:  # profiler-less backends still get wall time
        sys.stderr.write(f"trace skipped: {e}\n")
        trace_dir = None
    dev_ms = trace_tools.dominant_module_ms(trace_dir)

    return {
        "metric": f"decode_micro_{impl}_L{L}",
        "impl": impl,
        "L": L,
        "slots": S,
        "wall_ms": round(wall_ms, 3),
        "device_ms": round(dev_ms, 3) if dev_ms else None,
        "platform": platform,
        "unit": "ms/iter",
    }


def main() -> int:
    steps = int(sys.argv[1]) if len(sys.argv) > 1 else 30
    lengths = ([int(a) for a in sys.argv[2:]]
               if len(sys.argv) > 2 else [512, 2048])
    platform = jax.devices()[0].platform
    if platform == "cpu":
        # the interpreter is numerics-only; keep smoke runs tractable
        steps = min(steps, 5)
        lengths = [min(length, 512) for length in lengths]

    rows = []
    for L in sorted(set(lengths)):
        for impl in ("dense", "flash_decode", "paged"):
            row = _bench_impl(impl, L, steps, platform)
            rows.append(row)
            print(json.dumps(row), flush=True)

    # the acceptance bar, enforced where the kernel compiles
    if platform == "tpu":
        by = {(r["impl"], r["L"]): r for r in rows}
        for L in sorted({r["L"] for r in rows}):
            if L < 2048:
                continue
            dense = by[("dense", L)]
            flash = by[("flash_decode", L)]
            d = dense.get("device_ms") or dense["wall_ms"]
            f = flash.get("device_ms") or flash["wall_ms"]
            assert f < d, (
                f"flash-decode did not beat dense at L={L}: "
                f"{f} vs {d} ms/iter")
    return 0


if __name__ == "__main__":
    sys.exit(main())
