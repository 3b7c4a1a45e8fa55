"""``describe.py`` for a family whose serve state is more than one pair of
arrays (``KVCacheSpec.kinds``, an accumulator beside them): compile a
serving cell's programs for a described (not attached) v5e and print what
the compiler says each needs.  By hand:

    JAX_PLATFORMS=cpu python3 -m chipbench.describe_state \
        --config command-a-plus --slots 32 --buckets 6144,8192 --init 1

``describe.py`` hands the step one array for the keys and one for the
values; this hands it whatever ``KVCacheSpec.state`` makes, which is the
same for a family of one kind.  ``--init 1`` also compiles the engine's
jitted init (``module.init_params`` from a key), whose temporaries say
how much float32 is live while the weights are made.  A compile that
passes is not a chip run and yields no time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def programs(adapter, model: dict, slots: int, buckets: list,
             init: bool) -> "list[dict]":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["RLT_DECODE_IMPL"] = "flash_decode"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ray_lightning_tpu.core import steps
    from ray_lightning_tpu.ops import flash_attention, flash_decode, moe
    from ray_lightning_tpu.serve.kvcache import KVCacheSpec

    flash_attention._use_interpret = lambda: False
    flash_decode._use_interpret = lambda: False
    moe.grouped_dot_impl = lambda impl=None: impl or "gmm"
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    module = adapter.module(model, 0)
    module.setup_model()
    net = module.configure_decode_model()
    dummy = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
    made = jax.eval_shape(
        lambda k: module.init_params(k, np.zeros((1, 128), np.int32)),
        key)["params"]
    _, captured = jax.eval_shape(
        lambda p, t: net.apply({"params": p}, t, True, mutable=["kv_cache"]),
        made, dummy)
    spec = KVCacheSpec.from_capture(
        [k for k, _ in steps.kv_layer_pairs(captured["kv_cache"])], slots,
        adapter.context(model),
        counters=len(getattr(module, "serve_counters", ())))

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    pd = getattr(module, "param_dtype", None)
    params = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, pd or a.dtype), made)
    k, v = spec.state(on_chip, jnp.bfloat16)
    ints = on_chip((slots,), jnp.int32)
    todo = [("decode", jax.jit(steps.build_decode_step(module),
                               donate_argnums=(1, 2)),
             (params, k, v, ints, ints))]
    for b in buckets:
        todo.append((f"prefill_{b}",
                     jax.jit(steps.build_prefill_step(module, b),
                             donate_argnums=(1, 2)),
                     (params, k, v, on_chip((1, b), jnp.int32),
                      on_chip((), jnp.int32), on_chip((), jnp.int32))))
    if init:
        todo.append(("init", jax.jit(lambda key: module.init_params(
            key, np.zeros((1, 128), np.int32))["params"]), (key,)))
    out = []
    for name, step, args in todo:
        try:
            compiled = step.lower(*args).compile()
        except Exception as e:   # noqa: BLE001 - the refusal is the answer
            out.append({"program": name, "slots": slots, "fits": False,
                        "compiler": str(e).splitlines()[0][:300]})
            continue
        m = compiled.memory_analysis()
        text = compiled.as_text()
        out.append({
            "program": name, "slots": slots, "fits": True,
            "cache_shapes": [list(s) for s in spec.shapes],
            "cache_gb": spec.nbytes() / 1e9,
            "arguments_gb": m.argument_size_in_bytes / 1e9,
            "outputs_gb": m.output_size_in_bytes / 1e9,
            "temporaries_gb": m.temp_size_in_bytes / 1e9,
            "kernels": sorted({w for w in (
                "gqa_decode", "splash_mqa_fwd", "gmm", "flash_decode",
                "eva_decode", "flash_fwd") if w in text})})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", type=int, required=True)
    ap.add_argument("--buckets", default="")
    ap.add_argument("--init", type=int, default=0)
    args = ap.parse_args(argv)
    from chipbench import run
    with open(os.path.join(ROOT, "chipbench", "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    adapter = run.load_adapter(config, ROOT)
    buckets = [int(b) for b in args.buckets.split(",") if b]
    for row in programs(adapter, config["model"], args.slots, buckets,
                        bool(args.init)):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
