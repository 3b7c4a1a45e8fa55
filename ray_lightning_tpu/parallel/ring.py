"""Ring attention: sequence/context parallelism over a mesh axis.

Long-context training shards the *sequence* dimension across devices (a
capability absent from the reference — SURVEY.md §5 "long-context:
absent" — but first-class here).  Each device holds a local Q block and
rotates K/V blocks around the ``sequence`` mesh ring with
``lax.ppermute`` (lowered to ICI neighbor exchanges), folding each block
into an online-softmax accumulator — so the full [T, T] score matrix
never exists and per-device attention memory is O(T_local²) while
compute/communication overlap around the ring (Ring Attention,
arxiv.org/abs/2310.01889; blockwise attention, PAPERS.md).

Integration: the GPT family selects this with ``attention_impl="ring"``
and an ``SpmdStrategy`` whose mesh has a ``sequence`` axis; the trainer
publishes its mesh via :func:`parallel.mesh.set_current_mesh` so the op
can build the ``shard_map`` inside the jitted train step.  Without a
sequence axis (or size 1) it degrades to plain blockwise attention on
one device — same math, same results.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_lightning_tpu.parallel.mesh import get_current_mesh
from ray_lightning_tpu.telemetry.metrics import note_traced_collective

NEG_INF = -1e30


def _tensor_bytes(x) -> int:
    """Byte size from shape/dtype only — works on tracers (this runs at
    trace time, inside jit)."""
    import numpy as np
    size = 1
    for d in x.shape:
        size *= int(d)
    return size * np.dtype(x.dtype).itemsize


def _block_update(carry, q, k_blk, v_blk, q_off, k_off, causal, scale):
    """Fold one K/V block into the online-softmax accumulators.

    q: [B, Tq, H, D]; k_blk/v_blk: [B, Tk, H, D];
    carry = (m, l, acc) with m,l: [B, H, Tq, 1], acc: [B, Tq, H, D].
    """
    m, l, acc = carry
    tq, tk = q.shape[1], k_blk.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        rows = q_off + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        cols = k_off + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    s_max = jnp.max(s, axis=-1, keepdims=True)              # [B,H,Tq,1]
    m_new = jnp.maximum(m, s_max)
    p = jnp.exp(s - m_new)                                  # [B,H,Tq,Tk]
    alpha = jnp.exp(m - m_new)                              # [B,H,Tq,1]
    l_new = alpha * l + jnp.sum(p, -1, keepdims=True)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk,
                    preferred_element_type=jnp.float32)
    acc_new = acc * alpha.transpose(0, 2, 1, 3) + pv
    return m_new, l_new, acc_new


def blockwise_attention(q, k, v, *, causal: bool = True,
                        dtype=jnp.bfloat16, sm_scale: float | None = None,
                        block_size: int = 512):
    """Single-device blockwise attention (the ring's i=0 special case):
    K/V streamed in blocks, online softmax, no [T, T] materialization.
    The jnp-level sibling of ops/flash_attention.py, and the local math
    ring_attention runs per ring step."""
    b, t, h, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32)
    nblk = max(1, t // max(1, min(block_size, t)))
    tk = t // nblk
    m = jnp.full((b, h, t, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, t, 1), jnp.float32)
    acc = jnp.zeros((b, t, h, d), jnp.float32)
    carry = (m, l, acc)
    step = jax.checkpoint(
        functools.partial(_block_update, causal=causal, scale=scale))
    for i in range(nblk):
        kb = k[:, i * tk:(i + 1) * tk].astype(jnp.float32)
        vb = v[:, i * tk:(i + 1) * tk].astype(jnp.float32)
        carry = step(carry, qf, kb, vb, 0, i * tk)
    m, l, acc = carry
    return (acc / l.transpose(0, 2, 1, 3)).astype(dtype)


def _ring_inner(q, k, v, *, axis_name, causal, scale, dtype, ring_size):
    """Per-device body under shard_map: rotate K/V around the ring."""
    idx = jax.lax.axis_index(axis_name)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    qf = q.astype(jnp.float32)
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    m = jnp.full((b, h, tq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, tq, 1), jnp.float32)
    acc = jnp.zeros((b, tq, h, d), jnp.float32)
    perm = [(j, (j + 1) % ring_size) for j in range(ring_size)]
    carry = (m, l, acc)
    # rematerialize each block on backward: keeps activation memory at
    # O(Tq·D) instead of O(ring·Tq·Tk)
    step = jax.checkpoint(
        functools.partial(_block_update, causal=causal, scale=scale))
    for i in range(ring_size):
        # the block we currently hold started at device (idx - i) % ring
        src = jax.lax.rem(idx - i + ring_size, ring_size)
        carry = step(carry, qf, k, v, idx * tq, src * tk)
        if i < ring_size - 1:
            # rotate while the next step's compute is ready to issue; XLA
            # overlaps the ppermute DMA with the block matmuls
            k = jax.lax.ppermute(k, axis_name, perm)
            v = jax.lax.ppermute(v, axis_name, perm)
    m, l, acc = carry
    return (acc / l.transpose(0, 2, 1, 3)).astype(dtype)


def ring_attention(q, k, v, *, causal: bool = True, dtype=jnp.bfloat16,
                   sm_scale: float | None = None,
                   axis_name: str = "sequence", mesh=None):
    """Sequence-parallel attention over ``[B, T, H, D]`` tensors.

    Call sites inside a jitted SPMD program (the usual case) need the
    mesh: pass it or let the trainer publish it (set_current_mesh).
    """
    b, t, h, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    if mesh is None:
        mesh = get_current_mesh()
    ring = (mesh.shape[axis_name]
            if mesh is not None and axis_name in mesh.axis_names else 1)
    if ring == 1:
        return blockwise_attention(q, k, v, causal=causal, dtype=dtype,
                                   sm_scale=scale)

    # fabric traffic per invocation: every rotation moves each device's
    # local K/V block one hop, so ring devices together move the full
    # global K+V per rotation, (ring-1) rotations per call.  This runs
    # at trace time (the call sits inside the jitted step); the traced
    # cost is charged once per executed step by telemetry.metrics.
    note_traced_collective(
        "ring", (ring - 1) * (_tensor_bytes(k) + _tensor_bytes(v)))

    from ray_lightning_tpu.parallel.mesh import data_and_tensor_axes
    dp, tensor = data_and_tensor_axes(mesh)
    spec = P(dp, axis_name, tensor, None)
    inner = functools.partial(_ring_inner, axis_name=axis_name,
                              causal=causal, scale=scale, dtype=dtype,
                              ring_size=ring)
    fn = jax.shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
