"""Comm-plane selfcheck (wired into ``format.sh --check``).

Runs in a fresh interpreter so it can force a small virtual CPU mesh
BEFORE jax initializes, then asserts the invariants that don't need a
full training run:

- policy resolution on every built-in strategy: DDP / ZeRO-1 resolve to
  a GradSync on a multi-device data mesh, FSDP / SPMD / pipeline
  decline (params sharded), and the off policy is inert everywhere;
- the RLT_COMM* env knobs (codec, hierarchy, buckets included)
  round-trip through ``worker_env()`` → ``resolve()`` unchanged;
- the compressed collectives LOWER without error on a CPU mesh (every
  codec: int8 / bf16 / fp8 / int4, under shard_map),
  the two-level hierarchical psum lowers with its grouped collectives,
  and the quantizer round-trips exactly-representable payloads
  bit-exactly;
- the bucket partitioner covers every leaf exactly once, in order;
- the comm metric names (rlt_comm_dcn_bytes_total,
  rlt_comm_exposed_seconds) are registered in the lint's CORE_METRICS
  surface.
"""

from __future__ import annotations

import os


def _main(argv) -> int:   # noqa: ARG001 - argv kept for parity
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from ray_lightning_tpu.comm import CommPolicy, build_grad_sync
    from ray_lightning_tpu.comm.collectives import compressed_psum
    from ray_lightning_tpu.comm.quant import (blockwise_dequantize,
                                              blockwise_quantize)
    from ray_lightning_tpu.parallel.pipeline import PipelineStrategy
    from ray_lightning_tpu.parallel.strategy import (_STRATEGIES,
                                                     resolve_strategy)

    problems: list[str] = []
    policy = CommPolicy(compress="int8", axes=("data",))
    off = CommPolicy()

    # 1. policy resolution per built-in strategy
    expect_sync = {"ddp": True, "dp": True, "zero1": True, "sharded": True,
                   "fsdp": False, "zero3": False, "spmd": False}
    for name in sorted(_STRATEGIES):
        if name == "auto":
            # planner sentinel (plan/): resolved into one of the
            # concrete strategies below before any mesh/grad_sync exists
            continue
        strat = resolve_strategy(name)
        mesh = strat.build_mesh()
        got = build_grad_sync(strat, mesh, policy) is not None
        if got != expect_sync[name]:
            problems.append(
                f"strategy {name!r}: grad_transform resolved to "
                f"{'GradSync' if got else 'None'}, expected "
                f"{'GradSync' if expect_sync[name] else 'None'}")
        if build_grad_sync(strat, mesh, off) is not None:
            problems.append(f"strategy {name!r}: off policy not inert")
    pstrat = PipelineStrategy(stages=2)
    if build_grad_sync(pstrat, pstrat.build_mesh(), policy) is not None:
        problems.append("pipeline strategy should decline compression")

    # 2. env knob round-trip (hierarchy/bucket/barrier knobs included)
    src = CommPolicy(compress="fp8", axes=("data",), block_size=128,
                     stochastic_rounding=True, error_feedback=False,
                     param_gather="bf16", hierarchy=2,
                     bucket_bytes=1 << 20, barrier_sync=True,
                     gather_bucket_bytes=1 << 14)
    saved = {k: os.environ.get(k) for k in src.worker_env()}
    os.environ.update(src.worker_env())
    try:
        if CommPolicy.resolve(None) != src:
            problems.append("RLT_COMM* env round-trip changed the policy")
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    # 3. compressed collectives lower on the CPU mesh (every codec,
    #    flat AND two-level); quantizer exact on representable payloads
    from jax.sharding import PartitionSpec as P

    from ray_lightning_tpu.comm.collectives import (hierarchical_psum,
                                                    partition_buckets)
    strat = resolve_strategy("ddp")
    mesh = strat.build_mesh()
    world = mesh.shape["data"]
    for mode in ("int8", "bf16", "fp8", "int4"):
        def body(x, mode=mode):
            return compressed_psum(x[0], "data", world, mode=mode,
                                   mean=True)[None]
        fn = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"), check_vma=False)
        try:
            jax.jit(fn).lower(
                jax.ShapeDtypeStruct((world, 300), np.float32)).compile()
        except Exception as e:   # noqa: BLE001 - report, don't crash
            problems.append(f"compressed psum ({mode}) failed to lower "
                            f"on the CPU mesh: {e!r}")

    def hier_body(x):
        return hierarchical_psum(x[0], "data", 2, world // 2,
                                 mode="int8", mean=True)[None]
    try:
        fn = jax.shard_map(hier_body, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"), check_vma=False)
        jax.jit(fn).lower(
            jax.ShapeDtypeStruct((world, 300), np.float32)).compile()
    except Exception as e:   # noqa: BLE001
        problems.append(f"hierarchical psum failed to lower on the CPU "
                        f"mesh: {e!r}")

    # 3b. bucket partitioner invariant: every index exactly once, in
    # order, and the target is respected (oversized leaves go alone)
    for sizes, target in (([100, 200, 4000, 50, 50], 300),
                          ([8] * 7, 16), ([1], 0)):
        buckets = partition_buckets(sizes, target)
        flat = [i for b in buckets for i in b]
        if flat != list(range(len(sizes))):
            problems.append(
                f"bucket partition {buckets} of {sizes} does not cover "
                f"every leaf exactly once in order")
        if target > 0 and any(sum(sizes[i] for i in b) < target
                              for b in buckets[:-1]):
            problems.append(f"bucket partition {buckets} closed a "
                            f"bucket under target {target}")

    # 3c. comm metric names are on the lint surface
    from ray_lightning_tpu.telemetry.metrics import CORE_METRICS
    for name in ("rlt_comm_dcn_bytes_total", "rlt_comm_exposed_seconds"):
        if name not in CORE_METRICS:
            problems.append(f"{name} missing from telemetry CORE_METRICS")
    # two blocks whose max-abs is exactly 127 -> scale 1.0 -> integer
    # payloads must round-trip bit-exactly
    x = np.concatenate([np.arange(-127, 1), np.arange(0, 128)]) \
        .astype(np.float32).reshape(2, 128)
    q, s = blockwise_quantize(jax.numpy.asarray(x), 128)
    if not np.array_equal(np.asarray(blockwise_dequantize(q, s, 128)), x):
        problems.append("int8 quantizer not exact on representable ints")

    for p in problems:
        print(f"comm selfcheck: {p}")
    if not problems:
        print("comm selfcheck: policy resolution, env round-trip, codec "
              "+ hierarchical CPU-mesh lowering, bucket partition, and "
              "metric names OK")
    return 1 if problems else 0


if __name__ == "__main__":   # pragma: no cover - exercised via format.sh
    import sys
    sys.exit(_main(sys.argv[1:]))
