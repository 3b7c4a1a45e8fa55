"""Attention dispatch + the shared multi-head attention block.

One home for the attention path selection used by every model family
(GPT decoder, BERT encoder) so kernel improvements land in one place:

- :func:`dot_product_attention` — XLA reference attention (materialized
  scores, fp32 softmax);
- the Pallas flash kernel (ops/flash_attention.py) — streaming online
  softmax, the fast path on TPU;
- ring attention (parallel/ring.py) — sequence-parallel flash whose KV
  blocks rotate around the mesh;
- :func:`auto_attention` — trace-time choice: on TPU, the flash kernel
  (measured faster at every seq length on v5e, and the only path at
  T≥8k) — directly on one chip, via :func:`sharded_flash_attention`'s
  shard_map over batch/head axes on multi-chip meshes whose shapes
  divide evenly; dot attention elsewhere (CPU tests; sequence-sharded
  meshes belong to ring attention; uneven shapes stay on GSPMD dot,
  which pads).

:class:`MultiHeadAttention` carries the qkv/attend/proj plumbing shared
by the model families; its submodule names (``qkv``, ``proj``) are part
of the checkpoint/partition-rule contract (``attn/qkv/kernel`` etc. in
``gpt_partition_rules`` / ``bert_partition_rules``).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn


def dot_product_attention(q, k, v, *, causal: bool = True,
                          dtype=jnp.bfloat16):
    """Reference attention: one fused softmax(QKᵀ)V in fp32 accumulation.

    q,k,v: [B, T, H, D].  XLA fuses mask+softmax into the matmuls; for
    long T prefer the pallas flash kernel (ops/flash_attention.py).
    """
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(d)
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), dtype=bool), tk - tq)
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def auto_attention(q, k, v, **kw):
    """Trace-time attention choice (see module docstring)."""
    if jax.devices()[0].platform != "tpu":
        return dot_product_attention(q, k, v, **kw)
    from ray_lightning_tpu.ops.flash_attention import flash_attention
    if jax.device_count() == 1:
        return flash_attention(q, k, v, **kw)
    from ray_lightning_tpu.parallel.mesh import (
        get_current_mesh, mesh_axis_size)
    mesh = get_current_mesh()
    if mesh is not None and mesh.shape.get("sequence", 1) == 1:
        # multi-chip without sequence sharding: batch rides data/fsdp,
        # heads ride tensor — both per-device under shard_map, so the
        # kernel applies unchanged on each device's local shard.  Only
        # when shapes divide evenly: shard_map has no padding, GSPMD
        # dot does — uneven configs keep working via the dot path.
        dp_size = mesh_axis_size(mesh, "data", "fsdp")
        t_size = mesh_axis_size(mesh, "tensor")
        if q.shape[0] % dp_size == 0 and q.shape[2] % t_size == 0:
            return sharded_flash_attention(q, k, v, mesh=mesh, **kw)
    # sequence-sharded meshes use ring attention (attention_impl="ring");
    # no mesh / uneven shapes → the XLA path, which GSPMD partitions
    return dot_product_attention(q, k, v, **kw)


def sharded_flash_attention(q, k, v, *, mesh, causal: bool = True,
                            dtype=jnp.bfloat16, **kw):
    """Flash attention over a (data[, fsdp][, tensor]) mesh: shard_map
    over batch (data/fsdp) and heads (tensor); each device runs the
    Pallas kernel on its local [b_local, T, h_local, D] block.  No
    collectives are needed — attention mixes only T and D, which stay
    unsharded here (sequence sharding is ring attention's job)."""
    from ray_lightning_tpu.ops.flash_attention import flash_attention
    from ray_lightning_tpu.parallel.mesh import data_and_tensor_axes
    from jax.sharding import PartitionSpec as P

    dp, tensor = data_and_tensor_axes(mesh)
    spec = P(dp, None, tensor, None)

    def inner(ql, kl, vl):
        return flash_attention(ql, kl, vl, causal=causal, dtype=dtype,
                               **kw)

    fn = jax.shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


def local_attention(q, k, v, **kw):
    """Per-device attention for MANUAL (shard_map) regions — e.g. inside
    the pipeline schedule (parallel/pipeline.py), where the mesh axes
    are already manual and opening another shard_map (as auto_attention's
    sharded path would) is a trace error.  Picks the pallas flash kernel
    on TPU, the XLA dot path elsewhere; never consults the mesh."""
    if jax.devices()[0].platform == "tpu":
        from ray_lightning_tpu.ops.flash_attention import flash_attention
        return flash_attention(q, k, v, **kw)
    return dot_product_attention(q, k, v, **kw)


def cached_attention(q, k_cache, v_cache, positions, *, layer,
                     slots=None, dtype=jnp.bfloat16, impl=None,
                     page_table=None):
    """Single-token attention against one layer of the slot-indexed KV
    cache (the serve plane's decode core, ray_lightning_tpu/serve/).

    ``q``: [S, 1, H, D] — one new token per batch slot; ``k_cache`` /
    ``v_cache``: [n_layer, S, L, H*D] — the WHOLE resident buffers, in
    the layout they live in (serve/kvcache.py: heads packed on the lane
    axis); ``layer``: the static layer number this call reads;
    ``positions``: [S] — the absolute position of slot s's current
    token.  Slot s attends cache indices <= positions[s]: indices beyond
    its position hold stale prefill padding or a previous tenant's
    leftovers, which decode must never read (serve/kvcache.py
    invariant).  ``slots`` ([B] int32, traced) makes ``q`` a batch of B
    rows that read cache slots ``slots[b]`` instead of slots 0..S-1
    (the one-row suffix program); ``page_table`` then has one row per
    batch row.

    The buffers are handed over whole so that nothing on the way slices
    a layer out of them: the Pallas kernel offsets its block index by
    the layer (ops/flash_decode.py), which costs nothing.  Only the
    dense path below takes ``cache[layer]`` and unpacks the heads —
    free on the CPU, one layer's relayout on a TPU geometry the kernel
    cannot lower.

    ``impl`` picks the kernel (explicit > ``RLT_DECODE_IMPL`` env >
    ``auto``): ``dense`` is the masked einsum below; ``flash_decode`` is
    the length-aware Pallas kernel (ops/flash_decode.py) that reads only
    live KV blocks; ``paged`` additionally walks ``page_table``
    ([S, pages_per_slot] int32, serve/fleet/pages.py) so the fetch is
    page-indirect.  Under ``auto`` the choice follows platform and
    geometry (``select_decode_kernel``); an explicit ``flash_decode`` /
    ``paged`` that the geometry cannot lower raises instead of quietly
    becoming the dense einsum.

    Multi-query form (speculative-decode verify, core/steps.py
    ``build_verify_step``): ``q`` [S, T, H, D] with ``positions``
    [S, T] — T queries per slot at consecutive positions, each masked
    to its OWN position bound, so one batched target forward scores all
    T drafted tokens under exactly the masks T sequential decode steps
    would have used.  Lowered as T single-query attentions (each free
    to take the flash/paged kernel) — T is the small speculation depth
    k+1, and this keeps the per-query length masking identical to plain
    decode, which is what makes greedy parity exact by construction.
    """
    from ray_lightning_tpu.ops.flash_decode import (
        NEG_INF, flash_decode_attention, note_decode_kernel,
        select_decode_kernel)

    if positions.ndim == 2:
        if q.shape[1] == 1:
            positions = positions[:, 0]
        else:
            return jnp.concatenate(
                [cached_attention(q[:, j:j + 1], k_cache, v_cache,
                                  positions[:, j], layer=layer,
                                  slots=slots, dtype=dtype, impl=impl,
                                  page_table=page_table)
                 for j in range(q.shape[1])], axis=1)

    B, _, H, D = q.shape
    L = k_cache.shape[2]
    kernel = select_decode_kernel(
        L, H, D, dtype=q.dtype, impl=impl,
        n_pages=None if page_table is None else page_table.shape[1],
        by_slot=slots is not None)
    note_decode_kernel(kernel)
    if kernel != "dense":
        return flash_decode_attention(
            q, k_cache, v_cache, positions, layer=layer, slots=slots,
            dtype=dtype,
            page_table=page_table if kernel == "paged" else None)
    k, v = k_cache[layer], v_cache[layer]
    if slots is not None:
        k, v = k[slots], v[slots]
    k, v = k.reshape(B, L, H, D), v.reshape(B, L, H, D)
    scores = jnp.einsum("sqhd,slhd->shql", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(D)
    valid = jnp.arange(L)[None, :] <= positions[:, None]
    # NEG_INF (-1e30), not finfo.min: the flash kernels' NaN-free
    # masking constant — finfo.min survives one subtract in fp32 but a
    # fully-masked row would softmax over exact -inf after scaling
    # drift; -1e30 keeps exp/log finite everywhere
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("shql,slhd->sqhd", probs, v)


def resolve_attention(impl: str) -> Callable:
    if impl == "auto":
        return auto_attention
    if impl == "dot":
        return dot_product_attention
    if impl == "local":
        return local_attention
    if impl == "flash":
        from ray_lightning_tpu.ops.flash_attention import flash_attention
        return flash_attention
    if impl == "ring":
        from ray_lightning_tpu.parallel.ring import ring_attention
        return ring_attention
    if impl == "flash_decode":
        # decode-path signature: (q, k_cache, v_cache, positions) — the
        # serve plane's cached_attention kernel (ops/flash_decode.py),
        # auto-selected on TPU the way auto_attention picks flash
        from ray_lightning_tpu.ops.flash_decode import (
            flash_decode_attention)
        return flash_decode_attention
    raise ValueError(f"Unknown attention_impl {impl!r}")


class MultiHeadAttention(nn.Module):
    """Fused-QKV multi-head attention: ``[B,T,C] -> [B,T,C]``.

    Shared by the GPT decoder (causal=True) and BERT encoder
    (causal=False).  Submodule names qkv/proj are load-bearing for
    partition rules and checkpoints.
    """

    n_head: int
    causal: bool = True
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"

    @nn.compact
    def __call__(self, x, deterministic: bool = True, *,
                 decode_cache=None, positions=None, slots=None,
                 page_table=None):
        B, T, C = x.shape
        head_dim = C // self.n_head
        qkv = nn.Dense(3 * C, dtype=self.dtype, name="qkv")(x)
        # K and V stay packed ([B, T, C]: heads side by side on the lane
        # axis) for the serve plane, whose cache rows are exactly that
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = (B, T, self.n_head, head_dim)
        if decode_cache is not None:
            # serve-plane decode: B = batch slots, T = 1;
            # ``decode_cache`` is (k_cache, v_cache, layer).  Write this
            # token's k/v row straight into the resident, donated
            # buffers ([n_layer, S, L, C]) at [layer, slot, position] —
            # the only bytes of the cache this program writes — then
            # attend the query over layer ``layer`` of the
            # (just-updated) buffers; mask handled by cached_attention's
            # per-slot position bound.  Batch row b is cache slot b,
            # unless ``slots`` ([B] int32) names the rows' slots (the
            # one-row suffix program, core/steps.py build_suffix_step).
            # Shapes are static, so slots with no live request write
            # too (the scheduler sends tokens=0/positions=0 for them):
            # a dummy entry at position 0 the serve plane must
            # overwrite via the slot's admitting prefill BEFORE the
            # slot decodes — hence ServeWorker.serve_step dispatches
            # decode before prefills.
            k_cache, v_cache, layer = decode_cache
            # profiler scope (telemetry/scopes.py): the cache write is
            # kv_cache, not the attention around it.  Decode writes one
            # row a slot; multi-query verify (T = speculation depth
            # k+1, positions [B, T]) writes every query's K/V first and
            # then attends each query under its own position bound
            # (cached_attention's multi-query form) — causal by the
            # bound, so query j never sees rows j+1..T-1.  Rows at
            # positions >= L (slots speculating past the cache end, and
            # the paging dummy row's +j offsets) are DROPPED by jax's
            # out-of-bounds scatter semantics — no per-slot gating, no
            # shape change, no retrace.
            with jax.named_scope("kv_cache"):
                rows = jnp.arange(B) if slots is None else slots
                at = (layer, rows[:, None], positions.reshape(B, T))
                k_cache = k_cache.at[at].set(k)
                v_cache = v_cache.at[at].set(v)
            y = cached_attention(q.reshape(shape), k_cache, v_cache,
                                 positions, layer=layer, slots=slots,
                                 dtype=self.dtype, page_table=page_table)
            y = nn.Dense(C, dtype=self.dtype,
                         name="proj")(y.reshape(B, T, C))
            return y, (k_cache, v_cache)
        # prefill capture: when the caller applies with
        # mutable=("kv_cache",) the per-layer K/V land in that collection
        # as the packed [B, T, C] halves of qkv — the rows the slot cache
        # holds, so the prefill program writes them with no relayout
        # (serve/engine.py, core/steps.py build_prefill_step); in every
        # other apply — training included — sow is a no-op.  Never sown
        # at init (init makes every collection mutable, which would leak
        # a kv_cache collection into the train state).
        if not self.is_initializing():
            self.sow("kv_cache", "kv", (k, v))
        q, k, v = (a.reshape(shape) for a in (q, k, v))
        attend = resolve_attention(self.attention_impl)
        y = attend(q, k, v, causal=self.causal, dtype=self.dtype)
        y = nn.Dense(C, dtype=self.dtype, name="proj")(y.reshape(B, T, C))
        if self.dropout > 0:
            y = nn.Dropout(self.dropout)(y, deterministic=deterministic)
        return y
