"""Ops-plane selfcheck (wired into ``format.sh --check``).

Runs in a fresh interpreter pinned to CPU (the Pallas interpreter
executes the real kernel bodies there), then asserts the decode-kernel
invariants that don't need a device or a full serve run:

- ``resolve_decode_impl``: explicit arg beats ``RLT_DECODE_IMPL`` beats
  auto, every valid impl round-trips, junk raises;
- the ``kv_block_bound`` index-map clamp agrees EXACTLY with the kernel
  body's ``kb * block_k <= pos`` compute guard over an exhaustive grid
  — the DMA-skip and the masking must never disagree about which KV
  block is last;
- ``decode_kernel_supported`` geometry gating (lane alignment, sublane
  tiling) never throws, only declines;
- lowering sanity: the flash-decode kernel (interpret mode) matches the
  dense masked einsum at a ragged-position shape, fp32-tight;
- ``identity_page_table`` round-trips (flattens to ``arange``, rejects
  non-tiling page sizes) and the paged kernel over the identity table
  is BITWISE the slot-contiguous kernel at the same block size.
"""

from __future__ import annotations

import os


def _main(argv) -> int:   # noqa: ARG001 - argv kept for parity
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from ray_lightning_tpu.ops.attention import cached_attention
    from ray_lightning_tpu.ops.flash_decode import (
        VALID_DECODE_IMPLS, decode_kernel_supported,
        flash_decode_attention, kv_block_bound, resolve_decode_impl)
    from ray_lightning_tpu.serve.fleet.pages import identity_page_table
    import jax.numpy as jnp

    problems: list[str] = []

    # 1. impl resolution precedence: explicit > env > auto
    saved = os.environ.get("RLT_DECODE_IMPL")
    try:
        os.environ["RLT_DECODE_IMPL"] = "flash_decode"
        if resolve_decode_impl("dense") != "dense":
            problems.append("explicit impl did not beat the env knob")
        if resolve_decode_impl(None) != "flash_decode":
            problems.append("RLT_DECODE_IMPL not honored")
        os.environ.pop("RLT_DECODE_IMPL")
        if resolve_decode_impl(None) not in VALID_DECODE_IMPLS:
            problems.append("auto resolution left the valid set")
        for impl in VALID_DECODE_IMPLS:
            if impl != "auto" and resolve_decode_impl(impl) != impl:
                problems.append(f"impl {impl!r} does not round-trip")
        try:
            resolve_decode_impl("warp")
        except ValueError:
            pass
        else:
            problems.append("junk impl did not raise")
    finally:
        if saved is None:
            os.environ.pop("RLT_DECODE_IMPL", None)
        else:
            os.environ["RLT_DECODE_IMPL"] = saved

    # 2. the grid-skip invariant: the index-map clamp and the compute
    # guard must agree on every (kb, pos) — a block the map refuses to
    # fetch must be one the body never reads, and vice versa
    block_k = 16
    for pos in range(0, 64):
        for kb in range(0, 4):
            clamped = int(kv_block_bound(kb, pos, block_k))
            live = kb * block_k <= pos
            if live and clamped != kb:
                problems.append(
                    f"kv_block_bound skipped a LIVE block: kb={kb} "
                    f"pos={pos} -> {clamped}")
            if not live and clamped == kb:
                problems.append(
                    f"kv_block_bound fetched a DEAD block: kb={kb} "
                    f"pos={pos}")
            if not 0 <= clamped <= kb:
                problems.append(
                    f"kv_block_bound left [0, kb]: kb={kb} pos={pos} "
                    f"-> {clamped}")

    # 3. geometry gating declines, never throws
    for args in ((96, 3, 24), (128, 2, 64), (2048, 8, 64)):
        try:
            decode_kernel_supported(*args, block_k=128,
                                    dtype=jnp.bfloat16)
        except Exception as e:   # noqa: BLE001 - report, don't crash
            problems.append(f"decode_kernel_supported{args} raised "
                            f"{e!r}")

    # 4. lowering sanity: kernel (interpret) vs dense masked einsum,
    # both on layer 1 of a two-layer resident cache [n_layer, S, L, H*D]
    S, L, H, D = 2, 64, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (S, 1, H, D), jnp.float32)
    kc = jax.random.normal(ks[1], (2, S, L, H * D), jnp.float32)
    vc = jax.random.normal(ks[2], (2, S, L, H * D), jnp.float32)
    pos = jnp.asarray([3, L - 1], jnp.int32)
    dense = cached_attention(q, kc, vc, pos, layer=1, dtype=jnp.float32,
                             impl="dense")
    flash = flash_decode_attention(q, kc, vc, pos, layer=1,
                                   dtype=jnp.float32, block_k=16)
    err = float(jnp.max(jnp.abs(dense - flash)))
    if not err < 2e-5:
        problems.append(f"flash-decode kernel diverged from the dense "
                        f"reference: max abs err {err}")

    # 5. identity page table round-trip + paged == flat bitwise
    table = identity_page_table(S, L, 16)
    if not np.array_equal(table.reshape(-1), np.arange(S * L // 16)):
        problems.append("identity_page_table is not the identity")
    try:
        identity_page_table(2, 65, 16)
    except ValueError:
        pass
    else:
        problems.append("non-tiling page size did not raise")
    paged = flash_decode_attention(q, kc, vc, pos, layer=1,
                                   dtype=jnp.float32,
                                   page_table=jnp.asarray(table))
    if not np.array_equal(np.asarray(paged), np.asarray(flash)):
        problems.append("paged kernel over the identity table is not "
                        "bitwise the slot-contiguous kernel")

    for p in problems:
        print(f"ops selfcheck: {p}")
    if not problems:
        print("ops selfcheck: impl resolution, grid-skip invariant, "
              "geometry gating, interpreter lowering parity, and paged "
              "round-trip OK")
    return 1 if problems else 0


if __name__ == "__main__":   # pragma: no cover - exercised via format.sh
    import sys
    sys.exit(_main(sys.argv[1:]))
