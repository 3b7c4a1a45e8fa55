"""Size a serving cell without the chip: compile its decode program for a
described (not attached) v5e with the TPU compiler that is installed
here, and print what the compiler says the program needs.  By hand:

    JAX_PLATFORMS=cpu python3 -m chipbench.describe --config gpt2-large --slots 24,32

A compile that passes is not a chip run and yields no time.  What it
does yield: whether a program fits the chip's memory at a slot count
(``--bucket 1024`` asks about that prefill program instead of decode).
(Before PR 25, 64 and 32 slots of gpt2-large cost two chip calls to
refuse; the compiler said the same here for nothing.)

Code that asks JAX for its backend sees the CPU here and would take the
dense-einsum and interpreter branches; this script steers it onto the
chip's branches (the Pallas decode kernel, compiled by Mosaic), which is
the only place where the benchmark reaches into the program.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def program_memory(adapter, model: dict, slots: int, bucket: int = 0) -> dict:
    """The decode program's needs at ``slots`` (``bucket`` 0), or those of
    the prefill program of one bucket.  The module is the family's
    (``adapter.module``); the parameters' and the cache's shapes are the
    engine's own derivation (serve/engine.py ``setup``): the model's init
    avals, and ``KVCacheSpec.from_capture`` of a prefill capture."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["RLT_DECODE_IMPL"] = "flash_decode"
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ray_lightning_tpu.core import steps
    from ray_lightning_tpu.ops import flash_attention, flash_decode
    from ray_lightning_tpu.serve.kvcache import KVCacheSpec

    flash_attention._use_interpret = lambda: False
    flash_decode._use_interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    module = adapter.module(model, 0)
    module.setup_model()
    net = module.configure_decode_model()
    dummy = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0), dummy)["params"]
    _, captured = jax.eval_shape(
        lambda p, t: net.apply({"params": p}, t, True, mutable=["kv_cache"]),
        params, dummy)
    spec = KVCacheSpec.from_capture(
        [k for k, _ in steps.kv_layer_pairs(captured["kv_cache"])], slots,
        adapter.context(model))

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, jnp.bfloat16), params)
    cache = on_chip(spec.shape, jnp.bfloat16)
    ints = on_chip((slots,), jnp.int32)
    if bucket:
        step = jax.jit(steps.build_prefill_step(module, bucket),
                       donate_argnums=(1, 2))
        args = (params, cache, cache, on_chip((1, bucket), jnp.int32),
                on_chip((), jnp.int32), on_chip((), jnp.int32))
    else:
        step = jax.jit(steps.build_decode_step(module), donate_argnums=(1, 2))
        args = (params, cache, cache, ints, ints)
    try:
        compiled = step.lower(*args).compile()
    except Exception as e:   # noqa: BLE001 - the compiler's refusal is the answer
        first = str(e).splitlines()[0]
        return {"slots": slots, "bucket": bucket, "fits": False,
                "compiler": first[:300]}
    m = compiled.memory_analysis()
    return {"slots": slots, "bucket": bucket, "fits": True,
            "cache_shape": list(spec.shape),
            "arguments_gb": m.argument_size_in_bytes / 1e9,
            "temporaries_gb": m.temp_size_in_bytes / 1e9,
            "kernel": "tpu_custom_call" in compiled.as_text()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", required=True)
    ap.add_argument("--bucket", type=int, default=0,
                    help="a prefill bucket; 0 (default) is the decode program")
    args = ap.parse_args(argv)
    from chipbench import run
    with open(os.path.join(ROOT, "chipbench", "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    adapter = run.load_adapter(config, ROOT)
    for slots in (int(s) for s in args.slots.split(",")):
        print(json.dumps(program_memory(adapter, config["model"], slots,
                                        args.bucket)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
