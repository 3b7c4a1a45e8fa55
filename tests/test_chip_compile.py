"""What can be checked about the chip without one.

1. Compile-for-the-chip: the Pallas kernels of the main paths are
   compiled at gpt2-small widths for a DESCRIBED v5e (the TPU compiler is
   installed here; the chip is not attached).  Interpret mode cannot see
   what Mosaic refuses — the decode kernel passed every interpret-mode
   test while Mosaic rejected it for bf16 caches.  Nothing runs, so
   these say nothing about results or times; a compile that passes is
   not a chip run.  Skipped where the topology cannot be described.
2. The decisions around the chip that are plain Python: which decode
   kernel lowers, which process may start workers, which peak prices
   MFU, which native artefact loads.
3. ``chip_smoke.py`` itself: it must fail fast without an accelerator,
   its phases are rehearsed at ``tiny`` on the CPU by patching its
   constants from here (the program has no option for that), and its
   parent must stay off JAX.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # compiler logs stay out of /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# gpt2-small serve/train geometry
H, D, L, S, T, B = 12, 64, 1024, 8, 1024, 8


@pytest.fixture(scope="module")
def v5e():
    """One described (not attached) v5e device, with the persistent
    compilation cache off around the compiles: a described-device entry
    cannot be read back without a chip, and the next compile would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiles_with_kernel(fn, *args) -> None:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_flash_attention_compiles_for_v5e(v5e, grad):
    from ray_lightning_tpu.ops.flash_attention import flash_attention
    x = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16, sharding=v5e)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    _compiles_with_kernel(bwd if grad else fwd, x, x, x)


_HLO_RESULT = re.compile(
    r"= \(?(?:bf16|f32)\[([0-9,]+)\][^ ]* (copy|copy-start|slice|"
    r"dynamic-slice|concatenate|transpose)\(")


def _layer_movers(text: str, layer_elems: int) -> list:
    """The operations of a compiled program that copy, slice or
    transpose something as large as one layer of a cache array."""
    return [m.group(0) for m in _HLO_RESULT.finditer(text)
            if np.prod([int(d) for d in m.group(1).split(",")])
            >= layer_elems]


#: the decode kernels at the geometries that are served: (H, D, rows a
#: slot, slots); gpt2-small's, and the two benchmark cells' own
#: (gpt2-large's 20 heads of 64 are no sublane multiple and half a vreg
#: each; EvaByte's 32 of 128 with 2048 exact + 2048 summary rows a slot)
_DECODE_GEOMETRY = {
    "gpt2-small": (H, D, L, S),
    "gpt2-large": (20, 64, 1024, 24),
    "evabyte": (32, 128, 2048 + 2048, 32),
}


@pytest.mark.parametrize("geometry,call", [
    ("gpt2-small", "flash_decode"), ("gpt2-small", "flash_decode_paged"),
    ("gpt2-large", "flash_decode"), ("gpt2-large", "flash_decode_paged"),
    ("evabyte", "eva_decode"),
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_decode_kernels_compile_for_v5e(monkeypatch, v5e, dtype, geometry,
                                        call):
    """Mosaic takes the decode kernels' shared body (every head of a
    block at once: ops/flash_decode.py ``_decode_body``) under each of
    its calls: the result is a ``tpu_custom_call`` under the call's old
    name (the benchmark's readers find the kernels by it), the cache
    enters whole, and nothing beside the kernel copies or slices a
    layer of it."""
    from ray_lightning_tpu.ops import eva_attention, flash_decode
    h, d, rows, slots = _DECODE_GEOMETRY[geometry]
    monkeypatch.setattr(flash_decode, "_use_interpret", lambda: False)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=v5e)

    # layer 1 of a two-layer resident cache [n_layer, S, rows, H*D]
    cache = sds((2, slots, rows, h * d), dtype)
    args = [sds((slots, 1, h, d), dtype), cache, cache,
            sds((slots,), jnp.int32)]
    if call == "flash_decode_paged":
        args.append(sds((slots, rows // 128), jnp.int32))

    def decode(q, k, v, pos, table=None):
        if call == "eva_decode":
            return eva_attention.eva_cached_attention(
                q, k, v, pos, layer=1, window=2048, chunk=16, dtype=dtype,
                impl="flash_decode")
        return flash_decode.flash_decode_attention(
            q, k, v, pos, layer=1, dtype=dtype, page_table=table,
            interpret=False)

    compiled = jax.jit(decode).lower(*args).compile()
    text = compiled.as_text()
    assert re.search(rf"%{call}(\.\d+)? = [^\n]* custom-call\([^\n]*"
                     r'custom_call_target="tpu_custom_call"', text), text
    layer = slots * rows * h * d
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 0.05 * layer * jnp.dtype(dtype).itemsize
    assert not _layer_movers(text, layer)


@pytest.mark.parametrize("kernel", ["mla_decode", "splash_two_widths"])
def test_latent_attention_kernels_compile_for_v5e(monkeypatch, v5e, kernel):
    """models/xing.py at the published widths, bfloat16: the latent decode
    call (32 query heads against ONE row of 640 lanes, 576 of them values
    and the first 512 the value, 64 slots x 10,240 rows in blocks of 1024;
    the cache enters whole and nothing copies it: with 576 as the array's
    minor dimension the compiler copied all of it), and jax's splash
    attention with a query / key width of 192 and a value width of 128 (a
    shorter prompt than the cell's: the tables of an 8192 mask are the
    slow part)."""
    from ray_lightning_tpu.ops import flash_decode
    from ray_lightning_tpu.ops import latent_attention as la

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=v5e)

    monkeypatch.setattr(flash_decode, "_use_interpret", lambda: False)
    if kernel == "mla_decode":
        slots, rows, width = 64, 10240, 640
        fn = lambda q, cache, at: la.cached_attention(  # noqa: E731
            q, cache, at, layer=3, value_dim=512, sm_scale=0.1447,
            impl="flash_decode")
        args = (sds((slots, 32, width)), sds((5, slots, rows, width)),
                sds((slots,), jnp.int32))
    else:
        monkeypatch.setattr(la, "select_prefill_kernel",
                            lambda T, dv: "splash")
        fn = lambda q, k, v: la.causal_attention(  # noqa: E731
            q, k, v, sm_scale=0.1447)
        args = (sds((1, 2048, 32, 192)), sds((1, 2048, 32, 192)),
                sds((1, 2048, 32, 128)))
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert {"mla_decode": "mla_decode",
            "splash_two_widths": "splash_mha_fwd"}[kernel] in text
    if kernel == "mla_decode":
        assert flash_decode.latent_block_k(rows) == 1024
        layer = slots * rows * width
        assert compiled.memory_analysis().temp_size_in_bytes \
            < 0.05 * layer * 2
        assert not _layer_movers(text, layer)


@pytest.mark.parametrize("case", ["command_full", "latent_9984",
                                  "flat_1000"])
def test_decode_calls_with_a_ragged_last_block_compile_for_v5e(
        monkeypatch, v5e, case):
    """A cache whose rows the call's block does not tile (PR 42), bfloat16:
    Command A+'s full layer (32 slots x 8,960 rows x 8 K/V heads of 128
    under 128 query heads: 17 blocks of 512 and one of 256), a latent
    cache of 9,984 rows (64 slots x 640 lanes: nine blocks of 1,024 and one
    of 768) and gpt2-large's row over 1,000 rows (seven blocks of 128 and
    one of 104, which ends inside a sublane tile).  Each is a
    ``tpu_custom_call`` under its name; the cache enters whole and nothing
    copies or slices a layer of it."""
    from ray_lightning_tpu.ops import attention, flash_decode
    from ray_lightning_tpu.ops import latent_attention as la
    from ray_lightning_tpu.ops import window_attention as wa

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=v5e)

    monkeypatch.setattr(flash_decode, "_use_interpret", lambda: False)
    if case == "command_full":
        slots, rows, width = 32, 8960, 1024
        fn = lambda q, k, v, at: wa.cached_attention(  # noqa: E731
            q, k, v, at, layer=1, ring=False, impl="flash_decode")
        cache = sds((2, slots, rows, width))
        args = (sds((slots, 1, 128, 128)), cache, cache,
                sds((slots,), jnp.int32))
        name, blocks = "gqa_decode", [512, 18, 256]
    elif case == "latent_9984":
        slots, rows, width = 64, 9984, 640
        fn = lambda q, cache, at: la.cached_attention(  # noqa: E731
            q, cache, at, layer=1, value_dim=512, sm_scale=0.1447,
            impl="flash_decode")
        args = (sds((slots, 32, width)), sds((2, slots, rows, width)),
                sds((slots,), jnp.int32))
        name, blocks = "mla_decode", [1024, 10, 768]
    else:
        slots, rows, width = 24, 1000, 1280
        fn = lambda q, k, v, at: attention.cached_attention(  # noqa: E731
            q, k, v, at, layer=1, impl="flash_decode")
        cache = sds((2, slots, rows, width))
        args = (sds((slots, 1, 20, 64)), cache, cache,
                sds((slots,), jnp.int32))
        name, blocks = "flash_decode", [128, 8, 104]
    with flash_decode.record_decode_kernels() as lowered:
        compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert re.search(rf"%{name}(\.\d+)? = [^\n]* custom-call\([^\n]*"
                     r'custom_call_target="tpu_custom_call"', text), text
    assert lowered == {name: [blocks]}
    layer = slots * rows * width
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05 * layer * 2
    assert not _layer_movers(text, layer)


@pytest.mark.parametrize("T", [512, 768, 1024])
def test_a_short_prompts_splash_attention_compiles_for_v5e(monkeypatch, v5e,
                                                           T):
    """models/zaya.py's prompt: 8 query heads over 2 K/V heads of 128 at
    the cell's three buckets.  768 is one block of 768 keys, which 512 keys
    a product does not divide (the chip's first run of the cell refused it,
    PR 41): the product takes a whole divisor, 256."""
    from ray_lightning_tpu.ops import window_attention as wa
    monkeypatch.setattr(wa, "select_prefill_kernel", lambda T, D: "splash")
    q = jax.ShapeDtypeStruct((1, T, 8, 128), jnp.bfloat16, sharding=v5e)
    kv = jax.ShapeDtypeStruct((1, T, 2, 128), jnp.bfloat16, sharding=v5e)
    text = jax.jit(lambda q, k, v: wa.banded_attention(
        q, k, v, window=None)).lower(q, kv, kv).compile().as_text()
    assert "tpu_custom_call" in text and "splash_mqa_fwd" in text


@pytest.mark.parametrize("what", ["gqa_decode", "sublayer"])
def test_compressed_attention_decode_compiles_for_v5e(monkeypatch, v5e, what):
    """models/zaya.py at the published widths, bfloat16, 128 slots x 3,328
    rows: the shared grouped call at 4 query heads a K/V head over a row
    of 2 x 128 lanes (six blocks of 512 rows and a seventh whose last 256
    lie past the array: PR 42), and the whole attention
    sublayer of a decode step: the projections, the tail's read and write
    (two generations of 2,688 float32 values a slot), both convolutions,
    the kernel.  The cache enters whole and nothing copies or slices a
    layer of it; the tails are 27.5 MB and may be copied."""
    from ray_lightning_tpu.models import zaya
    from ray_lightning_tpu.ops import flash_decode
    from ray_lightning_tpu.ops import window_attention as wa

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=v5e)

    monkeypatch.setattr(flash_decode, "_use_interpret", lambda: False)
    monkeypatch.setenv("RLT_DECODE_IMPL", "flash_decode")
    slots, rows, width, layers = 128, 3328, 256, 10
    cache = sds((layers, slots, rows, width))
    at = sds((slots,), jnp.int32)
    if what == "gqa_decode":
        fn = lambda q, k, v, at: wa.cached_attention(  # noqa: E731
            q, k, v, at, layer=7, ring=False)
        args = (sds((slots, 1, 8, 128)), cache, cache, at)
    else:
        cfg = zaya.ZayaConfig(num_hidden_layers=layers,
                              served_positions=rows)
        attn = zaya.CompressedAttention(cfg, 7)
        u = sds((slots, 1, cfg.hidden_size))
        tail = sds((layers, slots, 2, cfg.tail_width), jnp.float32)
        made = jax.eval_shape(lambda: zaya.resident({"attn": attn.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, cfg.hidden_size),
                                             jnp.bfloat16))["params"]}))
        params = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), made)
        fn = lambda p, u, k, v, tail, at: attn.apply(  # noqa: E731
            {"params": p["attn"]}, u, cache=(k, v, tail), positions=at)
        args = (params, u, cache, cache, tail, at)
    with flash_decode.record_decode_kernels() as lowered:
        compiled = jax.jit(fn, donate_argnums=(2, 3, 4) if what == "sublayer"
                           else ()).lower(*args).compile()
    text = compiled.as_text()
    assert re.search(r"%gqa_decode(\.\d+)? = [^\n]* custom-call\([^\n]*"
                     r'custom_call_target="tpu_custom_call"', text), text
    assert lowered == {"gqa_decode": [[512, 7, 256]]}
    layer = slots * rows * width
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05 * layer * 2
    assert not _layer_movers(text, layer)


def test_the_kda_decode_call_compiles_for_v5e_in_place(monkeypatch, v5e):
    """ops/kda.py ``kda_decode`` at the published sizes: 192 slots x 32
    matrices of 128 x 128 float32 in one layer of the resident ``[7, 192,
    4096, 128]`` array (3.08 GB), a slot a grid step.  The array enters
    whole, donated, and comes back aliased: no temporary of a layer's
    size (0.40 GB), let alone of the array's."""
    from ray_lightning_tpu.ops import flash_decode, kda
    monkeypatch.setattr(flash_decode, "_use_interpret", lambda: False)

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=v5e)

    row = sds(192, 32, 128)
    compiled = jax.jit(
        lambda q, k, v, g, b, st: kda.kda_decode(q, k, v, g, b, st, layer=5),
        donate_argnums=(5,)).lower(
            row, row, row, row, sds(192, 32), sds(7, 192, 4096, 128)).compile()
    text = compiled.as_text()
    assert re.search(r"%kda_decode(\.\d+)? = [^\n]* custom-call\([^\n]*"
                     r'custom_call_target="tpu_custom_call"', text), text
    assert compiled.memory_analysis().temp_size_in_bytes < 20e6


#: one v5e chip's memory as the runtime reports it (PERF.md section 3)
BYTES_LIMIT = 16_909_336_064


@pytest.mark.parametrize("program", ["decode", "prefill_4096"])
def test_the_kimi_linear_cells_programs_fit_the_described_v5e(
        monkeypatch, v5e, program):
    """``kimi-linear-serve-turns4k``'s decode program and its largest
    prefill at the cell's slots and the published widths, as
    ``chipbench/describe_latent.py`` compiles them by hand: 9 layers, 192
    slots x 5,248 latent rows in two of them, 2 MB of float32 matrix a
    slot in each of the other seven.  Arguments (weights 4.75 GB, rows
    2.58, state 3.08) and temporaries together lie under the chip's
    ``bytes_limit`` with the room a deployment wants, and the state is
    not held twice: the temporaries stay under one layer's matrices of
    every slot (0.40 GB) twice over for the decode run, and under 2 GB
    for the prompt."""
    from chipbench import describe_state, run
    from ray_lightning_tpu.core import steps
    from ray_lightning_tpu.ops import flash_decode, moe
    from ray_lightning_tpu.ops import latent_attention as la

    # describe_state steers these for the described chip: put them back
    for mod, name in ((flash_decode, "_use_interpret"),
                      (moe, "grouped_dot_impl")):
        monkeypatch.setattr(mod, name, getattr(mod, name))
    monkeypatch.setenv("RLT_DECODE_IMPL", "flash_decode")
    # (this process has eight CPU devices: the prompt would go dense)
    monkeypatch.setattr(la, "select_prefill_kernel", lambda T, dv: "splash")
    captured = steps.kv_layer_pairs
    monkeypatch.setattr(steps, "kv_layer_pairs", lambda tree: [
        (entry, None) for entry in captured(tree)])
    with open(os.path.join(REPO, "chipbench", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "chipbench", "traffic",
                           "turns4k-saturated.json")) as f:
        slots = int(json.load(f)["slots"])
    adapter = run.load_adapter(config, REPO)
    rows = describe_state.programs(
        adapter, config["model"], slots,
        [4096] if program != "decode" else [], False)
    got = next(r for r in rows if r["program"] == program)
    assert got["fits"], got
    assert got["cache_shapes"] == [[2, slots, 5248, 640]]
    total = got["arguments_gb"] + got["temporaries_gb"]
    assert slots == 192 and 10.3 < got["arguments_gb"] < 10.5
    assert total * 1e9 < 0.8 * BYTES_LIMIT, got
    assert got["temporaries_gb"] < (0.81 if program == "decode" else 3.0)
    assert "gmm" in got["kernels"]


# -- the cache's trip through the serve programs ----------------------------
#
# The K/V cache is resident as [n_layer, S, L, H*D] and every program
# that advances it gets it donated.  What the chip's compiler makes of
# that is the whole point (PERF.md, PR 25): the old trip — a layer
# sliced out, its heads unpacked, the layers stacked back — compiled to
# 7.74 GB of temporaries beside a 4.53 GB cache at gpt2-large x 24 slots
# and to 88.7 of the decode step's 110.1 ms.  These compile the programs
# for the described v5e from the engine's own cache shape and hold them
# to "no scratch worth the name, no copy and no slice of a layer".

_GEOMETRY = {
    # name: (n_layer, n_head, n_embd), block_size 1024
    "gpt2-small": (12, 12, 768),
    "gpt2-large": (36, 20, 1280),
}


def _serve_program(monkeypatch, v5e, config, slots, program, paged):
    """``(compiled, cache_bytes, layer_elems)`` of one serve program at
    ``slots``, lowered for the described chip with the Pallas decode
    kernel (this process's backend is the CPU, so the test steers the
    code that asks: the program has no option for it)."""
    from ray_lightning_tpu.core import steps
    from ray_lightning_tpu.models.gpt import GPTConfig, GPTLightningModule
    from ray_lightning_tpu.ops import flash_decode
    from ray_lightning_tpu.serve.fleet.pages import identity_page_table
    from ray_lightning_tpu.serve.kvcache import KVCacheSpec

    monkeypatch.setenv("RLT_DECODE_IMPL", "paged" if paged else
                       "flash_decode")
    monkeypatch.setattr(flash_decode, "_use_interpret", lambda: False)
    n_layer, n_head, n_embd = _GEOMETRY[config]
    module = GPTLightningModule(GPTConfig(
        block_size=L, n_layer=n_layer, n_head=n_head, n_embd=n_embd,
        remat=False))
    module.setup_model()
    net = module.configure_decode_model()

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    # the engine's own derivation (serve/engine.py setup): params from
    # the model's init avals, the cache's shape from a prefill capture
    dummy = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            dummy)["params"]
    _, cap = jax.eval_shape(
        lambda p, t: net.apply({"params": p}, t, True,
                               mutable=["kv_cache"]), params, dummy)
    spec = KVCacheSpec.from_capture(
        [k for k, _ in steps.kv_layer_pairs(cap["kv_cache"])], slots, L)
    assert spec.shape == (n_layer, slots, L, n_embd)
    params = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, jnp.bfloat16), params)
    cache = on_chip(spec.shape, jnp.bfloat16)
    table = identity_page_table(slots, L, 128) if paged else None
    i32 = lambda *shape: on_chip(shape, jnp.int32)   # noqa: E731
    if program == "decode":
        fn = steps.build_decode_step(module, page_table=table)
        args = (i32(slots), i32(slots))
    elif program == "verify":
        fn = steps.build_verify_step(module, 3, page_table=table)
        args = (i32(slots, 4), i32(slots, 4))
    else:
        fn = steps.build_suffix_step(module, page_table=table)
        args = (i32(), i32(), i32())
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, cache, cache, *args).compile()
    return compiled, spec.nbytes() // 2, slots * L * n_embd


@pytest.mark.parametrize("config,slots,program,paged", [
    ("gpt2-large", 24, "decode", False),
    ("gpt2-small", 24, "decode", False),
    ("gpt2-small", 24, "decode", True),
    ("gpt2-small", 24, "verify", False),
    ("gpt2-small", 24, "verify", True),
    ("gpt2-small", 24, "suffix", False),
    ("gpt2-small", 24, "suffix", True),
], ids=lambda v: str(v))
def test_serve_program_leaves_the_cache_where_it_lies(
        monkeypatch, v5e, config, slots, program, paged):
    compiled, cache_bytes, layer_elems = _serve_program(
        monkeypatch, v5e, config, slots, program, paged)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.05 * cache_bytes, (
        f"{temp / 1e9:.3f} GB of temporaries beside a "
        f"{cache_bytes / 1e9:.3f} GB cache array")
    movers = _layer_movers(text, layer_elems)
    assert not movers, movers[:5]


# -- which decode kernel lowers ---------------------------------------------

@pytest.mark.parametrize("impl,n_pages,want", [
    ("dense", None, "dense"),
    ("auto", None, "dense"),            # CPU: auto never opts in
    ("flash_decode", None, "flash_decode"),
    ("paged", 8, "paged"),
    ("paged", None, "flash_decode"),    # no table plumbed: same kernel
])
def test_select_decode_kernel(monkeypatch, impl, n_pages, want):
    from ray_lightning_tpu.ops.flash_decode import select_decode_kernel
    monkeypatch.delenv("RLT_DECODE_IMPL", raising=False)
    assert select_decode_kernel(L, H, D, dtype=jnp.bfloat16, impl=impl,
                                n_pages=n_pages) == want


@pytest.mark.parametrize("rows,want", [
    (3328, "gqa_decode"),       # 512 does not tile it: before PR 42 the
    (8960, "gqa_decode"),       # block was halved to 256; now ragged
    (1000, "gqa_decode"),       # halved to 8, no bfloat16 tile: was dense
    (4096, "gqa_decode"),
    (1001, "dense"),            # no whole tiles of 8 rows: the compiler
                                # would copy the cache before the call
    (24, "dense"),              # one block of 24 rows: no bfloat16 tile
])
def test_a_cache_the_block_does_not_tile_takes_the_kernel_under_auto(
        monkeypatch, rows, want):
    """``auto`` on the chip (steered here: the backend is the CPU): the
    grouped call is chosen whatever the cache's length, the flat call
    likewise, and the page walks keep asking for pages that tile."""
    from ray_lightning_tpu.ops import flash_decode as fd
    from ray_lightning_tpu.ops import window_attention as wa
    monkeypatch.delenv("RLT_DECODE_IMPL", raising=False)
    monkeypatch.setattr(fd, "_use_interpret", lambda: False)
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeDevice()])
    assert wa.select_decode_kernel(rows, 2, 128, dtype=jnp.bfloat16) == want
    assert fd.decode_kernel_supported(
        rows, 2, 128, block_k=fd.grouped_block_k(rows), dtype=jnp.bfloat16,
        ragged=True) == (want != "dense")
    flat = fd.select_decode_kernel(rows, 20, 64, dtype=jnp.bfloat16)
    assert flat == ("flash_decode" if want != "dense" else "dense")
    # rows that name their slots walk pages of _pick_block_k rows, and a
    # page table's pages have to tile
    by_slot = fd.select_decode_kernel(rows, 20, 64, dtype=jnp.bfloat16,
                                      by_slot=True)
    assert by_slot == ("flash_decode" if fd._pick_block_k(rows) % 16 == 0
                       else "dense")
    assert not fd.decode_kernel_supported(rows, 20, 64, block_k=48,
                                          dtype=jnp.bfloat16) or rows % 48 == 0
    if rows % 7:
        with pytest.raises(ValueError, match="requested explicitly"):
            fd.select_decode_kernel(rows, 20, 64, dtype=jnp.bfloat16,
                                    impl="paged", n_pages=7)


@pytest.mark.parametrize("impl", ["flash_decode", "paged"])
def test_explicit_decode_kernel_never_falls_back(monkeypatch, impl):
    """A geometry the kernel cannot lower on the chip (H*D not a lane
    multiple): ``auto`` follows the shape to dense, an EXPLICIT request
    raises instead of quietly lowering the dense einsum."""
    from ray_lightning_tpu.ops import flash_decode as fd
    from ray_lightning_tpu.ops.attention import cached_attention
    monkeypatch.setattr(fd, "_use_interpret", lambda: False)  # as on TPU
    q = jnp.zeros((2, 1, 3, 24), jnp.bfloat16)
    kv = jnp.zeros((1, 2, 128, 3 * 24), jnp.bfloat16)
    pos = jnp.zeros((2,), jnp.int32)
    table = jnp.zeros((2, 8), jnp.int32)
    with pytest.raises(ValueError, match="requested explicitly"):
        cached_attention(q, kv, kv, pos, layer=0, impl=impl,
                         page_table=table)
    with fd.record_decode_kernels() as lowered:
        cached_attention(q, kv, kv, pos, layer=0, impl="dense")
    assert lowered == {"dense": []}


# -- one process for each chip ----------------------------------------------

class _FakeDevice:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def test_driver_holding_the_chip_cannot_start_workers(monkeypatch):
    """Train-then-serve in one script: the driver that already holds the
    accelerator fails AT ONCE with the reason and the remedy — it does
    not wait out the serve setup timeout."""
    from ray_lightning_tpu.models.gpt import GPTLightningModule
    from ray_lightning_tpu.serve import Server
    from ray_lightning_tpu.utils import platform as plat

    plat.require_chip_free("x", "y")        # a CPU driver passes
    jax.devices()                           # this process holds a backend
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeDevice()])
    with pytest.raises(RuntimeError, match="one process at a time") as e:
        Server(GPTLightningModule("tiny"), use_tpu=True).start()
    assert "checkpoint" in str(e.value) and "fresh process" in str(e.value)


def test_actor_plugin_refuses_a_driver_holding_the_chip(monkeypatch):
    from ray_lightning_tpu import RayXlaPlugin, Trainer
    from ray_lightning_tpu.models import BoringModel
    jax.devices()
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeDevice()])
    trainer = Trainer(plugins=[RayXlaPlugin(num_workers=2, use_tpu=True)],
                      max_steps=1, enable_checkpointing=False)
    with pytest.raises(RuntimeError, match="one process at a time"):
        trainer.fit(BoringModel())


# -- peaks, native artefact --------------------------------------------------

def test_device_peak_unknown_kind_is_an_error():
    from ray_lightning_tpu.telemetry.goodput import device_peak
    v5e_peak = device_peak("TPU v5 lite")
    assert (v5e_peak["tflops_bf16"], v5e_peak["hbm_gbps"],
            v5e_peak["hbm_gb"]) == (197.0, 819.0, 16.0)
    with pytest.raises(ValueError, match="no published peak"):
        device_peak("cpu")


def test_native_artefact_is_keyed_by_source_not_mtime(monkeypatch, tmp_path):
    """A tree copied to another machine rebuilds exactly when the source
    (or the flags) differ; the flags name no host CPU."""
    from ray_lightning_tpu import native
    assert "-march=native" not in native._CXXFLAGS
    here = native._lib_path()
    src = tmp_path / "prefetch.cpp"
    src.write_bytes(open(native._SRC, "rb").read())
    monkeypatch.setattr(native, "_SRC", str(src))
    assert native._lib_path() == here        # same bytes, other mtime
    src.write_bytes(src.read_bytes() + b"\n// changed\n")
    assert native._lib_path() != here


# -- chip_smoke.py -----------------------------------------------------------

def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_fails_fast_without_an_accelerator(tmp_path):
    """The script as the driver runs it, on the CPU: a quick non-zero
    exit and no result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, os.path.join(REPO,
                                                       "chip_smoke.py")],
                         capture_output=True, text=True, timeout=120,
                         env=env, cwd=REPO)
    assert out.returncode != 0, out.stdout
    assert '"ok": true' not in out.stdout
    assert "no tpu device" in out.stdout.lower()


def test_chip_smoke_parent_stays_off_jax_and_fails_with_its_phases(tmp_path):
    """The parent, in a clean interpreter with its children faked: it
    prints the result line only when every phase passed and reported the
    accelerator, and it never imports jax (so it cannot hold the chip)."""
    driver = tmp_path / "drive.py"
    driver.write_text(f"""
import json, sys
sys.path.insert(0, {REPO!r})
import chip_smoke as cs
dev = {{"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
fail = sys.argv[1]
def fake(name, workdir):
    if name == fail:
        return 1, {{"phase": name, "ok": False, "error": "boom"}}
    return 0, {{"phase": name, "ok": True,
               "device": dict(dev, platform="cpu") if fail == "cpu" else dev}}
cs.run_child = fake
rc = cs.main([])
assert "jax" not in sys.modules, "the parent imported jax"
sys.exit(rc)
""")
    def run(fail):
        return subprocess.run([sys.executable, str(driver), fail],
                              capture_output=True, text=True, timeout=60,
                              cwd=str(tmp_path))
    good = run("none")
    assert good.returncode == 0, good.stdout + good.stderr
    assert json.loads(good.stdout.strip().splitlines()[-1]) == {
        "ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite",
                               "count": 1}}
    for fail in ("train", "serve", "cpu"):
        bad = run(fail)
        assert bad.returncode != 0, (fail, bad.stdout)
        assert '"ok": true' not in bad.stdout, (fail, bad.stdout)


def test_chip_smoke_phases_rehearsed_at_tiny(monkeypatch, tmp_path):
    """Both one-chip phases, end to end, on the CPU at ``tiny``: one
    fit, its checkpoint handed to two servers, the token comparison —
    and each refusal the script owes: no accelerator, a requested
    kernel that was not the one lowered, tokens that differ."""
    cs = _load_smoke()
    monkeypatch.setattr(cs, "MODEL", "tiny")
    monkeypatch.setattr(cs, "TRAIN_STEPS", 8)
    monkeypatch.setattr(cs, "PROMPT_LENS", (5, 40))
    monkeypatch.setattr(cs, "NEW_TOKENS", 6)
    monkeypatch.setattr(cs, "SLOTS", 2)
    monkeypatch.setattr(cs, "SERVER_KW", {"platform": "cpu"})
    monkeypatch.setattr(cs, "DECODE_ENV",
                        {"RLT_DECODE_IMPL": "flash_decode"})
    # the expensive parts run once (keyed by their name argument); the
    # refusals replay them
    def once(real):
        memo: dict = {}

        def call(workdir, name, *a, **k):
            if name not in memo:
                memo[name] = real(workdir, name, *a, **k)
            return memo[name]
        return call

    for fn in ("_fit", "_serve_once"):
        monkeypatch.setattr(cs, fn, once(getattr(cs, fn)))
    work = str(tmp_path)

    # the process that should hold the chip sees the CPU
    with pytest.raises(cs.NoAccelerator):
        cs.phase_train(work)
    monkeypatch.setattr(cs, "PLATFORM", "cpu")
    from ray_lightning_tpu.telemetry import goodput
    monkeypatch.setitem(goodput.DEVICE_PEAKS, "cpu",
                        {"tflops_bf16": 1.0, "hbm_gbps": 1.0, "hbm_gb": 1.0})
    # the flash kernel was asked for and is not in the program (no
    # Mosaic on the CPU)
    with pytest.raises(cs.SmokeFailure, match="tpu_custom_call"):
        cs.phase_train(work)
    monkeypatch.setattr(cs, "KERNEL_MARKER", "fusion")
    assert cs.run_phase("train", work) == 0
    train = json.load(open(os.path.join(work, "train.json")))
    assert train["ok"] and len(train["losses"]) == 8
    assert os.path.exists(train["checkpoint"])

    assert cs.run_phase("serve", work) == 0
    serve = json.load(open(os.path.join(work, "serve.json")))
    assert serve["ok"] and serve["tokens_equal_dense"]
    assert serve["decode_kernel"] == "flash_decode"
    assert serve["retraces_after_warmup"] == 0
    assert serve["device"]["platform"] == "cpu"

    # the decode kernel asked for was not the one lowered
    monkeypatch.setattr(cs, "DECODE_KERNEL", "paged")
    with pytest.raises(cs.SmokeFailure, match="lowered 'flash_decode'"):
        cs.phase_serve(work)
    monkeypatch.setattr(cs, "DECODE_KERNEL", "flash_decode")
    # tokens that differ from the dense engine's
    outs, stats = cs._serve_once(work, "serve_dense", None, {})
    monkeypatch.setattr(
        cs, "_serve_once", lambda w, name, *a, _f=cs._serve_once:
        ([[t + 1 for t in o] for o in outs], stats)
        if name == "serve_dense" else _f(w, name, *a))
    with pytest.raises(cs.SmokeFailure, match="tokens differ"):
        cs.phase_serve(work)
