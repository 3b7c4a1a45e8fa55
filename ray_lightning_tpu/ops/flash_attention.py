"""Flash attention as a TPU Pallas kernel (forward + custom VJP).

Why a kernel at all: naive attention materializes the [T, T] score
matrix in HBM — at T=8k/bf16 that is 128 MB *per head* of traffic; HBM
bandwidth is the TPU bottleneck (BASELINE.md).  Flash attention streams
K/V blocks through VMEM with an online softmax, so HBM traffic stays
O(T·D) and the MXU stays busy on [block_q × D] @ [D × block_k] tiles.

Block sizes default to 512×512 (measured best on v5e across T=2k-8k:
3.3× over 128×128 at T=4096, and 2.8× over XLA's materialized-scores
attention, which stops compiling at all by T=8192); both are clamped to
the sequence length and halved until they divide it, so any
power-of-two-ish T works.  Causal masking skips fully-masked K blocks at
the grid level (``@pl.when``) — ~2× fewer FLOPs for causal LMs.

The backward pass follows the standard two-kernel flash decomposition
(dK/dV accumulate over Q blocks; dQ accumulates over K blocks) with the
softmax statistics (LSE) and ``delta = rowsum(dO ∘ O)`` carried from the
forward pass.

On non-TPU backends the same kernels run under the Pallas interpreter so
tests execute on CPU (the gloo-for-NCCL analog of the reference's CI,
reference: .github/workflows/test.yaml CPU jobs).

Interface matches ``models.gpt.dot_product_attention``:
``flash_attention(q, k, v, causal=..., dtype=...)`` with q/k/v shaped
``[B, T, H, D]`` and output ``[B, T, H, D]``.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp/log NaN-free


def _use_interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def _pick_block(t: int, preferred: int) -> int:
    b = min(preferred, t)
    while t % b:
        b //= 2
    return max(b, 1)


# -- triangular grid (causal, square blocks) --------------------------------
#
# A causal mask kills every block strictly above the diagonal.  Guarding
# those iterations with ``pl.when`` still pays their block prefetch and
# grid-step overhead (measured: 512-tiles LOSE to one full-T block at
# T=1024 despite skipping 25% of the FLOPs).  Instead, when blocks are
# square, the grid itself enumerates only the nq(nq+1)/2 valid (qi, kb)
# pairs: linear index i walks q-rows in order, kb = 0..qi within a row,
# so output blocks are revisited contiguously (the pipelining
# requirement) and no dead iteration exists at all.


def _tri_row(i):
    """Largest r with r(r+1)/2 <= i.  The float sqrt is only an
    ESTIMATE — TPU's sqrt is not correctly rounded (e.g. i=6 evaluates
    to 2.99999976 there), so the result is corrected with exact integer
    arithmetic; the estimate is within ±1 for any realistic count."""
    f = (jnp.sqrt(8.0 * jnp.float32(i) + 1.0) - 1.0) * 0.5
    r = f.astype(jnp.int32)
    r = jnp.where((r + 1) * (r + 2) // 2 <= i, r + 1, r)
    r = jnp.where(r * (r + 1) // 2 > i, r - 1, r)
    return r


def _tri_decode(i):
    """linear triangular index -> (qi, kb), kb <= qi."""
    qi = _tri_row(i)
    return qi, i - qi * (qi + 1) // 2


def _tri_decode_rev(i, n):
    """linear index -> (ki, qi) covering qi >= ki: group r = n-1-ki has
    r+1 entries (qi descending from n-1), reusing the same triangle."""
    r, c = _tri_decode(i)
    return n - 1 - r, n - 1 - c


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, sm_scale, causal, block_q, block_k, nk):
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # causal: K block strictly above the diagonal touches no valid entry
    run = (kb * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _compute():
        # operands stay in their storage dtype (bf16): the MXU takes
        # bf16 inputs with fp32 accumulation via preferred_element_type;
        # upcasting first would quarter matmul throughput.
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale      # [bq, bk]
        if causal:
            rows = (jax.lax.broadcasted_iota(jnp.int32,
                                             (block_q, block_k), 0)
                    + qi * block_q)
            cols = (jax.lax.broadcasted_iota(jnp.int32,
                                             (block_q, block_k), 1)
                    + kb * block_k)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[:]                                        # [bq, 128]
        s_max = jnp.max(s, axis=-1, keepdims=True)               # [bq, 1]
        m_new = jnp.maximum(m_prev, s_max)                       # [bq, 128]
        alpha = jnp.exp(m_prev - m_new)                          # [bq, 128]
        p = jnp.exp(s - m_new[:, :1])                            # [bq, bk] f32
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, -1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(kb == nk - 1)
    def _final():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(l)


def _fwd_tri_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                    l_ref, *, sm_scale, block: int):
    """Triangular-grid forward: program_id(1) enumerates only valid
    (qi, kb) pairs; same online-softmax math as _fwd_kernel."""
    qi, kb = _tri_decode(pl.program_id(1))

    @pl.when(kb == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    # only the diagonal block straddles the causal boundary; off-diagonal
    # blocks are entirely valid, their mask select folds to a no-op
    rows = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    s = jnp.where((kb == qi) & (rows < cols), NEG_INF, s)
    m_prev = m_ref[:]
    s_max = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, s_max)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    l_ref[:] = alpha * l_ref[:] + jnp.sum(p, -1, keepdims=True)
    acc_ref[:] = acc_ref[:] * alpha[:, :1] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[:] = m_new

    @pl.when(kb == qi)
    def _final():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(l)


#: Rows of a causal staircase sub-block, for every T that holds at
#: least two of them (so from T = 512).  The pre-round sweep of a whole
#: gpt2-small step at T=1024 on v5e (IMPLEMENTATION_MAP.md): off 54.42 /
#: 128 52.39 / 256 51.08 / 512 51.59 ms; the train cell's
#: ``train_attn_kernel_ms`` 17.21 (ledger, PR 29) is made of 256.
_STAIRCASE_SUB = 256


def _sub_block(t: int, causal: bool) -> int:
    """Causal staircase sub-block size for the single-block kernels
    (0 = no subtiling).

    A causal single-block kernel that computes the full [T, T] score
    matrix wastes half its MXU work on positions the mask throws away.
    Splitting the q rows into T/sub row-blocks and contracting each only
    against k[:row_end] keeps the staircase of valid blocks and skips
    the rest — at sub = T/4 that is 37.5% of the score-matrix FLOPs,
    with ZERO grid overhead because the loop unrolls statically inside
    the kernel (unlike the round-2 512×512 *grid* tiles, which lost to
    the single block on per-block prefetch + pl.when dead iterations).
    """
    sub = _STAIRCASE_SUB
    return sub if causal and t >= 2 * sub and t % sub == 0 else 0


def _staircase_fold(sm_scale: float) -> bool:
    """Fold sm_scale into q when it is an exact power of two (1/√64 =
    1/8 for the d=64 model family): a [T, D] multiply instead of
    per-row [sub, u] score scaling, exact in bf16 because it only
    shifts the exponent."""
    return math.frexp(sm_scale)[0] == 0.5


def _staircase_slab(qs, k, r0, u, *, sm_scale, fold):
    """Masked fp32 score slab [sub, u] for staircase row-block
    [r0, u): the ONE place the fold/scale/mask recipe lives, shared by
    the forward and backward staircase so they cannot diverge (``qs``
    is pre-scaled iff ``fold``)."""
    s = jax.lax.dot_general(
        qs[r0:u], k[:u], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if not fold:
        s = s * sm_scale
    rows = jax.lax.broadcasted_iota(jnp.int32, (u - r0, u), 0) + r0
    cols = jax.lax.broadcasted_iota(jnp.int32, (u - r0, u), 1)
    return jnp.where(rows >= cols, s, NEG_INF)


def _staircase_fwd_math(q, k, v, *, sm_scale, block, sub):
    """Causal single-block forward over staircase row-blocks.

    Each row-block sees its complete (causally valid) score row, so a
    plain max-shifted softmax applies — no online rescaling.  Returns
    (o fp32 [T, D], lse fp32 [T, 1]).
    """
    fold = _staircase_fold(sm_scale)
    qs = q * sm_scale if fold else q
    n = block // sub
    o_rows, lse_rows = [], []
    for qi in range(n):
        r0, u = qi * sub, (qi + 1) * sub
        s = _staircase_slab(qs, k, r0, u, sm_scale=sm_scale, fold=fold)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o_rows.append(jax.lax.dot_general(
            p.astype(v.dtype), v[:u], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) / l)
        lse_rows.append(m + jnp.log(l))
    return jnp.concatenate(o_rows), jnp.concatenate(lse_rows)


# -- head-packed single-block kernels (transpose-free fast path) ------------
#
# Mosaic requires a block's last dim to be a 128 multiple (or span the
# whole array), so slicing ONE d=64 head out of a [B, T, C] array is not
# expressible.  Packing ``128 // d`` heads into one 128-lane block is:
# the kernel loops over the packed heads with static column slices (the
# loop unrolls at trace time; slices are in-VMEM).  This keeps q/k/v in
# the qkv Dense's native [B, T, C] layout end-to-end — the old
# ``[B,T,H,D] → transpose → [B·H,T,D]`` fold cost ~3.6 ms/step of pure
# data-formatting on the gpt2-small headline (roofline trace).  Engaged
# for the single-block case (T ≤ 1024 by default), where a plain
# max-shifted softmax replaces the online rescaling (whole row visible)
# and ``delta`` is computed in-kernel; longer sequences keep the folded
# multi-block kernels below.


def _head_pack(d: int, h: int) -> int:
    """Heads per 128-lane block (0 = layout not packable)."""
    if d <= 128 and 128 % d == 0:
        pack = 128 // d
    elif d % 128 == 0:
        pack = 1
    else:
        return 0
    return pack if h % pack == 0 else 0


def _fwd_packed_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                       *, sm_scale, causal, block, d, pack):
    sub = _sub_block(block, causal)
    for j in range(pack):
        sl = slice(j * d, (j + 1) * d)
        q = q_ref[0][:, sl]
        k = k_ref[0][:, sl]
        v = v_ref[0][:, sl]
        if sub:
            o, lse = _staircase_fwd_math(q, k, v, sm_scale=sm_scale,
                                         block=block, sub=sub)
            o_ref[0, :, sl] = o.astype(o_ref.dtype)
            lse_ref[0, 0, :, j:j + 1] = lse
            continue
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale      # [T, T]
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)                   # [T, 1]
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0, :, sl] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, 0, :, j:j + 1] = m + jnp.log(l)


def _bwd_packed_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                       dq_ref, dk_ref, dv_ref,
                       *, sm_scale, causal, block, d, pack):
    """Single-block packed backward: one :func:`_single_block_bwd_math`
    call per packed head, with in-kernel delta."""
    for j in range(pack):
        sl = slice(j * d, (j + 1) * d)
        o = o_ref[0][:, sl]
        do = do_ref[0][:, sl]
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)                  # [T, 1]
        dq, dk, dv = _single_block_bwd_math(
            q_ref[0][:, sl], k_ref[0][:, sl], v_ref[0][:, sl], do,
            lse_ref[0, 0][:, j:j + 1], delta,
            sm_scale=sm_scale, causal=causal, block=block)
        dv_ref[0, :, sl] = dv.astype(dv_ref.dtype)
        dk_ref[0, :, sl] = dk.astype(dk_ref.dtype)
        dq_ref[0, :, sl] = dq.astype(dq_ref.dtype)


def _fwd_tri_packed_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                           acc_ref, m_ref, l_ref,
                           *, sm_scale, block, d, pack):
    """Triangular-grid forward on head-packed [B, T, C] blocks: the
    online-softmax math of ``_fwd_tri_kernel`` looped over the packed
    heads, with per-head scratch planes (``acc_ref[j]`` etc.)."""
    qi, kb = _tri_decode(pl.program_id(1))

    @pl.when(kb == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    rows = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    for j in range(pack):
        sl = slice(j * d, (j + 1) * d)
        q = q_ref[0][:, sl]
        k = k_ref[0][:, sl]
        v = v_ref[0][:, sl]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where((kb == qi) & (rows < cols), NEG_INF, s)
        m_prev = m_ref[j]
        s_max = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, s_max)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[j] = alpha * l_ref[j] + jnp.sum(p, -1, keepdims=True)
        acc_ref[j] = acc_ref[j] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[j] = m_new

    @pl.when(kb == qi)
    def _final():
        for j in range(pack):
            sl = slice(j * d, (j + 1) * d)
            l = l_ref[j][:, :1]
            o_ref[0, :, sl] = (acc_ref[j] / l).astype(o_ref.dtype)
            lse_ref[0, 0, :, j:j + 1] = m_ref[j][:, :1] + jnp.log(l)


def _fwd_tri_packed(q, k, v, h, sm_scale, bq, nq, interpret):
    b, t, c = q.shape
    d = c // h
    pack = _head_pack(d, h)
    g2 = h // pack
    w = pack * d
    n_tri = nq * (nq + 1) // 2
    kernel = functools.partial(_fwd_tri_packed_kernel, sm_scale=sm_scale,
                               block=bq, d=d, pack=pack)

    def q_map(g, i):
        return (g // g2, _tri_decode(i)[0], g % g2)

    def k_map(g, i):
        return (g // g2, _tri_decode(i)[1], g % g2)

    def r_map(g, i):
        return (g // g2, g % g2, _tri_decode(i)[0], 0)

    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(b * g2, n_tri),
        in_specs=[
            pl.BlockSpec((1, bq, w), q_map),
            pl.BlockSpec((1, bq, w), k_map),
            pl.BlockSpec((1, bq, w), k_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, w), q_map),
            pl.BlockSpec((1, 1, bq, pack), r_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, c), q.dtype),
            jax.ShapeDtypeStruct((b, g2, t, pack), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((pack, bq, d), jnp.float32),
            pltpu.VMEM((pack, bq, 128), jnp.float32),
            pltpu.VMEM((pack, bq, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _fwd_packed(q, k, v, h, causal, sm_scale, interpret):
    b, t, c = q.shape
    d = c // h
    pack = _head_pack(d, h)
    g2 = h // pack
    w = pack * d
    kernel = functools.partial(_fwd_packed_kernel, sm_scale=sm_scale,
                               causal=causal, block=t, d=d, pack=pack)
    x_spec = pl.BlockSpec((1, t, w), lambda g: (g // g2, 0, g % g2))
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(b * g2,),
        in_specs=[x_spec, x_spec, x_spec],
        out_specs=[
            x_spec,
            pl.BlockSpec((1, 1, t, pack), lambda g: (g // g2, g % g2, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, c), q.dtype),
            jax.ShapeDtypeStruct((b, g2, t, pack), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _bwd_dkdv_tri_packed_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                                *, sm_scale, block, d, pack, n):
    """Triangular dk/dv on head-packed blocks (``_bwd_dkdv_tri_kernel``
    math looped over packed heads; per-head scratch planes)."""
    ki, qi = _tri_decode_rev(pl.program_id(1), n)

    @pl.when(qi == n - 1)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    rows = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    for j in range(pack):
        sl = slice(j * d, (j + 1) * d)
        q = q_ref[0][:, sl]
        k = k_ref[0][:, sl]
        v = v_ref[0][:, sl]
        do = do_ref[0][:, sl]
        lse = lse_ref[0, 0][:, j:j + 1]
        delta = delta_ref[0, 0][:, j:j + 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where((qi == ki) & (rows < cols), NEG_INF, s)
        p = jnp.exp(s - lse)
        dv_acc[j] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc[j] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == ki)
    def _final():
        for j in range(pack):
            sl = slice(j * d, (j + 1) * d)
            dk_ref[0, :, sl] = (dk_acc[j] * sm_scale).astype(dk_ref.dtype)
            dv_ref[0, :, sl] = dv_acc[j].astype(dv_ref.dtype)


def _bwd_dq_tri_packed_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                              delta_ref, dq_ref, dq_acc,
                              *, sm_scale, block, d, pack):
    qi, kb = _tri_decode(pl.program_id(1))

    @pl.when(kb == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    rows = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    for j in range(pack):
        sl = slice(j * d, (j + 1) * d)
        q = q_ref[0][:, sl]
        k = k_ref[0][:, sl]
        v = v_ref[0][:, sl]
        do = do_ref[0][:, sl]
        lse = lse_ref[0, 0][:, j:j + 1]
        delta = delta_ref[0, 0][:, j:j + 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where((kb == qi) & (rows < cols), NEG_INF, s)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_acc[j] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == qi)
    def _final():
        for j in range(pack):
            sl = slice(j * d, (j + 1) * d)
            dq_ref[0, :, sl] = (dq_acc[j] * sm_scale).astype(dq_ref.dtype)


def _bwd_tri_packed(q, k, v, h, lse, do, delta, sm_scale, bq, nq,
                    interpret):
    """Head-packed triangular backward on [B, T, C]; ``delta`` arrives
    in the packed lse layout [B, H/pack, T, pack]."""
    b, t, c = q.shape
    d = c // h
    pack = _head_pack(d, h)
    g2 = h // pack
    w = pack * d
    n_tri = nq * (nq + 1) // 2

    def ki_map(g, i):
        return (g // g2, _tri_decode_rev(i, nq)[0], g % g2)

    def qi_rev_map(g, i):
        return (g // g2, _tri_decode_rev(i, nq)[1], g % g2)

    def r_rev_map(g, i):
        return (g // g2, g % g2, _tri_decode_rev(i, nq)[1], 0)

    dkdv = functools.partial(_bwd_dkdv_tri_packed_kernel,
                             sm_scale=sm_scale, block=bq, d=d, pack=pack,
                             n=nq)
    dk, dv = pl.pallas_call(
        dkdv,
        name="flash_bwd_dkv",
        grid=(b * g2, n_tri),
        in_specs=[
            pl.BlockSpec((1, bq, w), qi_rev_map),               # q
            pl.BlockSpec((1, bq, w), ki_map),                   # k
            pl.BlockSpec((1, bq, w), ki_map),                   # v
            pl.BlockSpec((1, bq, w), qi_rev_map),               # do
            pl.BlockSpec((1, 1, bq, pack), r_rev_map),          # lse
            pl.BlockSpec((1, 1, bq, pack), r_rev_map),          # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bq, w), ki_map),
            pl.BlockSpec((1, bq, w), ki_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, c), k.dtype),
            jax.ShapeDtypeStruct((b, t, c), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((pack, bq, d), jnp.float32),
            pltpu.VMEM((pack, bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    def q_map(g, i):
        return (g // g2, _tri_decode(i)[0], g % g2)

    def k_map(g, i):
        return (g // g2, _tri_decode(i)[1], g % g2)

    def r_map(g, i):
        return (g // g2, g % g2, _tri_decode(i)[0], 0)

    dqk = functools.partial(_bwd_dq_tri_packed_kernel, sm_scale=sm_scale,
                            block=bq, d=d, pack=pack)
    dq = pl.pallas_call(
        dqk,
        name="flash_bwd_dq",
        grid=(b * g2, n_tri),
        in_specs=[
            pl.BlockSpec((1, bq, w), q_map),
            pl.BlockSpec((1, bq, w), k_map),
            pl.BlockSpec((1, bq, w), k_map),
            pl.BlockSpec((1, bq, w), q_map),
            pl.BlockSpec((1, 1, bq, pack), r_map),
            pl.BlockSpec((1, 1, bq, pack), r_map),
        ],
        out_specs=pl.BlockSpec((1, bq, w), q_map),
        out_shape=jax.ShapeDtypeStruct((b, t, c), q.dtype),
        scratch_shapes=[pltpu.VMEM((pack, bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# -- row-resident kernels (multi-block causal fwd + fused backward) ---------
#
# The two-kernel tri decomposition recomputes s and dp in the dQ kernel
# — 7 MXU passes over the triangle where 5 suffice (the same waste the
# single-block fused kernel eliminated at T<=1024).  One kernel cannot
# walk the (qi, kb) triangle AND finalize both dq (row-complete) and
# dk/dv (column-complete) under Pallas's contiguous-revisiting rule for
# output blocks — so this kernel changes the residency instead: the
# grid walks ROWS only; k and v stay resident in VMEM for the whole
# batch·head-group (loaded once instead of once per triangle block),
# an inner ``fori_loop`` with a DYNAMIC trip count (qi+1) walks the
# causal columns (no dead iterations, no per-block prefetch), dq
# finalizes per row step, and dk/dv accumulate in fp32 VMEM scratch
# via dynamic-slice read-modify-write, emitted once at the last row.
# Engagement differs by direction (:func:`_select_family`): the
# FORWARD (online softmax in registers, no big scratch) wins up to
# T=8192 (−15%/−16% at 4096/8192 vs the grid-tri forward; k/v residency
# is the win, loaded once per batch·head-group); the BACKWARD, whose
# fp32 [T,128] dk/dv accumulators weigh on the scoped-VMEM budget,
# caps at T=2048 (−28% whole fwd+bwd there with both kernels) — at
# 4096 its 512-tiles overflow scoped VMEM by ~0.5 MB and 256-tiles
# underfeed the MXU (24.3 vs 19.5 ms/iter), so longer sequences pair
# the rowres forward with the grid-tri backward.

#: The budgets are t·w, not t: the resident k/v (and the backward's
#: fp32 dk/dv accumulators) are [T, w] each and both points were
#: measured at w=128, so wide heads (d ≥ 256 pack to w=d) meet the same
#: VMEM ceiling at proportionally shorter t.
_ROWRES_FWD_BUDGET = 8192 * 128
_ROWRES_BWD_BUDGET = 2048 * 128


def _fwd_rowres_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                       *, sm_scale, bq, d, pack, fold):
    """Row-resident forward: k/v resident in VMEM, inner fori over the
    causal columns with the online softmax carried in registers."""
    qi = pl.program_id(1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 1)
    for j in range(pack):
        sl = slice(j * d, (j + 1) * d)
        qj = q_ref[0][:, sl]
        if fold:
            qj = qj * sm_scale

        def col(kb, carry, qj=qj, sl=sl):
            m, l, acc = carry
            kt = k_ref[0, pl.ds(kb * bq, bq), sl]
            vt = v_ref[0, pl.ds(kb * bq, bq), sl]
            s = jax.lax.dot_general(
                qj, kt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if not fold:
                s = s * sm_scale
            s = jnp.where((kb == qi) & (rows < cols), NEG_INF, s)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(vt.dtype), vt, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((bq, 1), jnp.float32)
        a0 = jnp.zeros((bq, d), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, qi + 1, col, (m0, l0, a0))
        o_ref[0, :, sl] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, 0, :, j:j + 1] = m + jnp.log(l)


def _fwd_rowres(q, k, v, h, sm_scale, bq, nq, interpret):
    b, t, c = q.shape
    d = c // h
    pack = _head_pack(d, h)
    g2 = h // pack
    w = pack * d
    fold = _staircase_fold(sm_scale)

    def row_map(g, i):
        return (g // g2, i, g % g2)

    def full_map(g, i):
        return (g // g2, 0, g % g2)

    def r_map(g, i):
        return (g // g2, g % g2, i, 0)

    kernel = functools.partial(_fwd_rowres_kernel, sm_scale=sm_scale,
                               bq=bq, d=d, pack=pack, fold=fold)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(b * g2, nq),
        in_specs=[
            pl.BlockSpec((1, bq, w), row_map),
            pl.BlockSpec((1, t, w), full_map),
            pl.BlockSpec((1, t, w), full_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, w), row_map),
            pl.BlockSpec((1, 1, bq, pack), r_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, c), q.dtype),
            jax.ShapeDtypeStruct((b, g2, t, pack), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _bwd_rowres_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                       dq_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                       *, sm_scale, bq, nq, d, pack, fold):
    qi = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 1)
    for j in range(pack):
        sl = slice(j * d, (j + 1) * d)
        qj = q_ref[0][:, sl]
        if fold:
            qj = qj * sm_scale
        doj = do_ref[0][:, sl]
        lsej = lse_ref[0, 0][:, j:j + 1]
        deltaj = delta_ref[0, 0][:, j:j + 1]

        def col(kb, dq_j, qj=qj, doj=doj, lsej=lsej, deltaj=deltaj,
                sl=sl):
            kt = k_ref[0, pl.ds(kb * bq, bq), sl]
            vt = v_ref[0, pl.ds(kb * bq, bq), sl]
            s = jax.lax.dot_general(
                qj, kt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if not fold:
                s = s * sm_scale
            s = jnp.where((kb == qi) & (rows < cols), NEG_INF, s)
            p = jnp.exp(s - lsej)
            dv_acc[pl.ds(kb * bq, bq), sl] += jax.lax.dot_general(
                p.astype(doj.dtype), doj, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                doj, vt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - deltaj)
            dsc = ds.astype(qj.dtype)
            dk_acc[pl.ds(kb * bq, bq), sl] += jax.lax.dot_general(
                dsc, qj, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dq_j + jax.lax.dot_general(
                dsc, kt, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        dq_j = jax.lax.fori_loop(
            0, qi + 1, col, jnp.zeros((bq, d), jnp.float32))
        dq_ref[0, :, sl] = (dq_j * sm_scale).astype(dq_ref.dtype)

    @pl.when(qi == nq - 1)
    def _emit():
        dk = dk_acc[...] if fold else dk_acc[...] * sm_scale
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_rowres(q, k, v, h, lse, do, delta, sm_scale, bq, nq, interpret):
    """Row-resident fused backward on head-packed [B, T, C] (delta in
    the packed lse layout, as :func:`_bwd_tri_packed`)."""
    b, t, c = q.shape
    d = c // h
    pack = _head_pack(d, h)
    g2 = h // pack
    w = pack * d
    fold = _staircase_fold(sm_scale)

    def row_map(g, i):
        return (g // g2, i, g % g2)

    def full_map(g, i):
        return (g // g2, 0, g % g2)

    def r_map(g, i):
        return (g // g2, g % g2, i, 0)

    kernel = functools.partial(_bwd_rowres_kernel, sm_scale=sm_scale,
                               bq=bq, nq=nq, d=d, pack=pack, fold=fold)
    dq, dk, dv = pl.pallas_call(
        kernel,
        name="flash_bwd_fused",
        grid=(b * g2, nq),
        in_specs=[
            pl.BlockSpec((1, bq, w), row_map),                  # q
            pl.BlockSpec((1, bq, w), row_map),                  # do
            pl.BlockSpec((1, 1, bq, pack), r_map),              # lse
            pl.BlockSpec((1, 1, bq, pack), r_map),              # delta
            pl.BlockSpec((1, t, w), full_map),                  # k resident
            pl.BlockSpec((1, t, w), full_map),                  # v resident
        ],
        out_specs=[
            pl.BlockSpec((1, bq, w), row_map),                  # dq per row
            pl.BlockSpec((1, t, w), full_map),                  # dk
            pl.BlockSpec((1, t, w), full_map),                  # dv
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, c), q.dtype),
            jax.ShapeDtypeStruct((b, t, c), k.dtype),
            jax.ShapeDtypeStruct((b, t, c), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((t, w), jnp.float32),                    # dk acc
            pltpu.VMEM((t, w), jnp.float32),                    # dv acc
        ],
        interpret=interpret,
    )(q, do, lse, delta, k, v)
    return dq, dk, dv


def _bwd_packed(q, k, v, h, o, lse, do, causal, sm_scale, interpret):
    b, t, c = q.shape
    d = c // h
    pack = _head_pack(d, h)
    g2 = h // pack
    w = pack * d
    kernel = functools.partial(_bwd_packed_kernel, sm_scale=sm_scale,
                               causal=causal, block=t, d=d, pack=pack)
    x_spec = pl.BlockSpec((1, t, w), lambda g: (g // g2, 0, g % g2))
    r_spec = pl.BlockSpec((1, 1, t, pack), lambda g: (g // g2, g % g2, 0, 0))
    dq, dk, dv = pl.pallas_call(
        kernel,
        name="flash_bwd_fused",
        grid=(b * g2,),
        in_specs=[x_spec, x_spec, x_spec, x_spec, x_spec, r_spec],
        out_specs=[x_spec, x_spec, x_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, c), q.dtype),
            jax.ShapeDtypeStruct((b, t, c), k.dtype),
            jax.ShapeDtypeStruct((b, t, c), v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, o, do, lse)
    return dq, dk, dv


def _fold(x, b, t, h, d):
    """[B, T, H·D] → [B·H, T, D] (the multi-block kernels' layout)."""
    return x.reshape(b, t, h, d).transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _unfold(x, b, t, h, d):
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3).reshape(b, t, h * d)


# -- which family runs ------------------------------------------------------

#: Default tiling: one full-T block per grid row up to T=1024 (no
#: inner-loop grid overhead — measured +7% whole-model step rate at
#: T=1024 on v5e vs fixed 512), 512×512 tiles beyond, whose VMEM
#: footprint stays safe as T grows (3.3× over 128×128 at T=4096).
_SINGLE_BLOCK_MAX_T = 1024
_TILE = 512


class _Family(NamedTuple):
    """What :func:`_select_family` chose for one call."""
    fwd: str   # packed | rowres | tri_packed | tri | rect
    bwd: str   # packed | rowres | tri_packed | fused | tri | rect
    lse: str   # "packed": [B, H/pack, T, pack]; "folded": [B·H, T, 1]
    bq: int
    bk: int


def _select_family(t: int, h: int, d: int, causal: bool,
                   block_q: int | None = None,
                   block_k: int | None = None) -> _Family:
    """The ONE place a kernel family is chosen, from the call's shape
    alone: the forward and the backward both ask here, so the ``lse``
    residual one writes is in the layout the other reads.

    ============  ==========  ==========  ======  =============================
    shape         forward     backward    lse     when
    ============  ==========  ==========  ======  =============================
    one block     packed      packed      packed  heads pack into lanes
    causal tiles  rowres      rowres      packed  … t·w ≤ the backward's budget
    causal tiles  rowres      tri_packed  packed  … t·w ≤ the forward's budget
    causal tiles  tri_packed  tri_packed  packed  … beyond both
    one block     rect        fused       folded  heads do not pack
    causal tiles  tri         tri         folded  heads do not pack
    other         rect        rect        folded  non-causal or bq ≠ bk
    ============  ==========  ==========  ======  =============================

    "causal tiles" is more than one block of a causal call with square
    blocks; ``w`` is the lane width of a packed head group and the
    budgets are ``_ROWRES_BWD_BUDGET`` < ``_ROWRES_FWD_BUDGET``.  Explicit
    ``block_q`` / ``block_k`` replace the default tiling; either is
    clamped to T and halved until it divides T.
    """
    default = t if t <= _SINGLE_BLOCK_MAX_T else _TILE
    bq = _pick_block(t, default if block_q is None else block_q)
    bk = _pick_block(t, default if block_k is None else block_k)
    single = bq == t and bk == t
    tri = causal and bq == bk and not single
    pack = _head_pack(d, h)
    if pack and single:
        return _Family("packed", "packed", "packed", bq, bk)
    if pack and tri:
        tw = t * pack * d
        return _Family(
            "rowres" if tw <= _ROWRES_FWD_BUDGET else "tri_packed",
            "rowres" if tw <= _ROWRES_BWD_BUDGET else "tri_packed",
            "packed", bq, bk)
    if tri:
        return _Family("tri", "tri", "folded", bq, bk)
    return _Family("rect", "fused" if single else "rect", "folded", bq, bk)


def _fwd(q, k, v, h, causal, sm_scale, block_q, block_k, interpret):
    """Core forward on head-packed [B, T, C] arrays.

    Single-block shapes take the transpose-free packed path; longer
    sequences fold to [B·H, T, D] for the tiled/triangular kernels.
    Returns ``(o[B,T,C], lse)``, lse in the layout
    :func:`_select_family` names.
    """
    b, t, c = q.shape
    d = c // h
    bh = b * h
    fam = _select_family(t, h, d, causal, block_q, block_k)
    bq, bk = fam.bq, fam.bk
    nq, nk = t // bq, t // bk

    if fam.fwd == "packed":
        return _fwd_packed(q, k, v, h, causal, sm_scale, interpret)
    if fam.fwd == "rowres":
        return _fwd_rowres(q, k, v, h, sm_scale, bq, nq, interpret)
    if fam.fwd == "tri_packed":
        return _fwd_tri_packed(q, k, v, h, sm_scale, bq, nq, interpret)

    q, k, v = (_fold(x, b, t, h, d) for x in (q, k, v))

    if fam.fwd == "tri":
        n_tri = nq * (nq + 1) // 2
        kernel = functools.partial(_fwd_tri_kernel, sm_scale=sm_scale,
                                   block=bq)

        def q_map(g, i):
            return (g, _tri_decode(i)[0], 0)

        def k_map(g, i):
            return (g, _tri_decode(i)[1], 0)

        o, lse = pl.pallas_call(
            kernel,
            name="flash_fwd",
            grid=(bh, n_tri),
            in_specs=[
                pl.BlockSpec((1, bq, d), q_map),
                pl.BlockSpec((1, bk, d), k_map),
                pl.BlockSpec((1, bk, d), k_map),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, d), q_map),
                pl.BlockSpec((1, bq, 1), q_map),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, t, d), q.dtype),
                jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
            ],
            interpret=interpret,
        )(q, k, v)
        return _unfold(o, b, t, h, d), lse

    grid = (bh, nq, nk)

    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_q=bq, block_k=bk, nk=nk)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda g, i, j: (g, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),      # output accumulator
            pltpu.VMEM((bq, 128), jnp.float32),    # running max
            pltpu.VMEM((bq, 128), jnp.float32),    # running sum
        ],
        interpret=interpret,
    )(q, k, v)
    return _unfold(o, b, t, h, d), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dk_acc, dv_acc,
                     *, sm_scale, causal, block_q, block_k, nq):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _compute():
        # bf16 matmul operands + fp32 accumulation (see _fwd_kernel)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                                         # [bq, 1]
        delta = delta_ref[0]                                     # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale      # [bq, bk]
        if causal:
            rows = (jax.lax.broadcasted_iota(jnp.int32,
                                             (block_q, block_k), 0)
                    + qi * block_q)
            cols = (jax.lax.broadcasted_iota(jnp.int32,
                                             (block_q, block_k), 1)
                    + ki * block_k)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)                                     # [bq, bk] f32
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [bq, bk]
        ds = p * (dp - delta)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [bk, d]

    @pl.when(qi == nq - 1)
    def _final():
        dk_ref[0] = (dk_acc[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc,
                   *, sm_scale, causal, block_q, block_k, nk):
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (kb * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _compute():
        # bf16 matmul operands + fp32 accumulation (see _fwd_kernel)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = (jax.lax.broadcasted_iota(jnp.int32,
                                             (block_q, block_k), 0)
                    + qi * block_q)
            cols = (jax.lax.broadcasted_iota(jnp.int32,
                                             (block_q, block_k), 1)
                    + kb * block_k)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [bq, d]

    @pl.when(kb == nk - 1)
    def _final():
        dq_ref[0] = (dq_acc[:] * sm_scale).astype(dq_ref.dtype)


def _single_block_bwd_math(q, k, v, do, lse, delta, *, sm_scale, causal,
                           block):
    """Shared 5-matmul single-block backward: the one place the dq/dk/dv
    math lives, used by both the folded fused kernel and the head-packed
    kernel (one call per packed head) so the two paths cannot diverge.
    Returns fp32 (dq, dk, dv) tiles; callers cast to storage dtype.

    The two-kernel decomposition exists because dK/dV and dQ accumulate
    over different grid axes — but with nq == nk == 1 there is nothing
    to accumulate, and splitting costs two extra [T,T] matmuls per head
    (s and dp recomputed in the dQ kernel): 7 MXU passes where 5
    suffice.  At the T=1024 headline that is ~29% of the backward FLOPs
    for free.  Same math, same dtypes, same order as the split kernels.

    Causal blocks additionally take the staircase path (:func:`_sub_block`):
    row-blocks of q contract only against k[:row_end], skipping the MXU
    work the mask would zero — 37.5% of the [T,T]-matmul FLOPs at
    sub = T/4, statically unrolled so there is no grid overhead.
    """
    sub = _sub_block(block, causal)
    if sub:
        return _staircase_bwd_math(q, k, v, do, lse, delta,
                                   sm_scale=sm_scale, block=block, sub=sub)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale          # [T, T]
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    p = jnp.exp(s - lse)                                         # [T, T] f32
    dv = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dsc = ds.astype(q.dtype)
    dk = jax.lax.dot_general(
        dsc, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    dq = jax.lax.dot_general(
        dsc, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    return dq, dk, dv


def _staircase_bwd_math(q, k, v, do, lse, delta, *, sm_scale, block, sub):
    """Causal single-block backward over staircase row-blocks.

    Row-block qi computes its [sub, u] score slab (u = row_end) and the
    five matmuls of :func:`_single_block_bwd_math` restricted to it;
    dq rows finalize per row-block, dk/dv accumulate into fp32 [T, D]
    buffers via static-slice adds.  ``sm_scale`` folds into q when it
    is an exact power of two (s and dk then come pre-scaled: dk =
    dSᵀ·(α·q)); dq post-scales its [sub, D] output either way — cheaper
    than scaling [sub, u] score slabs.
    """
    fold = _staircase_fold(sm_scale)
    qs = q * sm_scale if fold else q
    n = block // sub
    dq_rows = []
    # per-column-block accumulators (static slices only: Pallas kernels
    # cannot scatter into traced arrays)
    dk_blocks: list = [None] * n
    dv_blocks: list = [None] * n
    for qi in range(n):
        r0, u = qi * sub, (qi + 1) * sub
        qr = qs[r0:u]
        dor = do[r0:u]
        s = _staircase_slab(qs, k, r0, u, sm_scale=sm_scale, fold=fold)
        p = jnp.exp(s - lse[r0:u])
        dv_c = jax.lax.dot_general(
            p.astype(dor.dtype), dor, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [u, d]
        dp = jax.lax.dot_general(
            dor, v[:u], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[r0:u])
        dsc = ds.astype(q.dtype)
        dk_c = jax.lax.dot_general(
            dsc, qr, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [u, d]
        dq_rows.append(jax.lax.dot_general(
            dsc, k[:u], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale)
        for kb in range(qi + 1):
            c = slice(kb * sub, (kb + 1) * sub)
            dk_blocks[kb] = dk_c[c] if dk_blocks[kb] is None \
                else dk_blocks[kb] + dk_c[c]
            dv_blocks[kb] = dv_c[c] if dv_blocks[kb] is None \
                else dv_blocks[kb] + dv_c[c]
    dk = jnp.concatenate(dk_blocks)
    if not fold:
        dk = dk * sm_scale
    return jnp.concatenate(dq_rows), dk, jnp.concatenate(dv_blocks)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, *, sm_scale, causal, block):
    """One-pass single-block backward on folded [B·H, T, D] tiles
    (see :func:`_single_block_bwd_math`)."""
    dq, dk, dv = _single_block_bwd_math(
        q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0], delta_ref[0],
        sm_scale=sm_scale, causal=causal, block=block)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_fused(q, k, v, lse, do, delta, causal, sm_scale, interpret):
    """Single-block backward on folded [B·H, T, D] (when the packed
    layout does not apply): grid over batch·heads only."""
    bh, t, d = q.shape
    kernel = functools.partial(_bwd_fused_kernel, sm_scale=sm_scale,
                               causal=causal, block=t)
    x_spec = pl.BlockSpec((1, t, d), lambda g: (g, 0, 0))
    r_spec = pl.BlockSpec((1, t, 1), lambda g: (g, 0, 0))
    dq, dk, dv = pl.pallas_call(
        kernel,
        name="flash_bwd_fused",
        grid=(bh,),
        in_specs=[x_spec, x_spec, x_spec, x_spec, r_spec, r_spec],
        out_specs=[x_spec, x_spec, x_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _bwd_dkdv_tri_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, dk_acc, dv_acc,
                         *, sm_scale, block: int, n: int):
    """Triangular dk/dv: the grid walks k-rows, each visiting only the
    q blocks at-or-below… i.e. qi >= ki (the transposed lower triangle),
    qi descending within a k-row so the row's iterations are contiguous
    (output-block revisiting requirement)."""
    ki, qi = _tri_decode_rev(pl.program_id(1), n)

    @pl.when(qi == n - 1)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]
    delta = delta_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    rows = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    s = jnp.where((qi == ki) & (rows < cols), NEG_INF, s)
    p = jnp.exp(s - lse)
    dv_acc[:] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dk_acc[:] += jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(qi == ki)
    def _final():
        dk_ref[0] = (dk_acc[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_tri_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dq_acc, *, sm_scale, block: int):
    qi, kb = _tri_decode(pl.program_id(1))

    @pl.when(kb == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]
    delta = delta_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    rows = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    s = jnp.where((kb == qi) & (rows < cols), NEG_INF, s)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dq_acc[:] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kb == qi)
    def _final():
        dq_ref[0] = (dq_acc[:] * sm_scale).astype(dq_ref.dtype)


def _bwd_tri(q, k, v, o, lse, do, sm_scale, bq, nq, delta, interpret):
    bh, t, d = q.shape
    n_tri = nq * (nq + 1) // 2

    def ki_map(g, i):
        return (g, _tri_decode_rev(i, nq)[0], 0)

    def qi_rev_map(g, i):
        return (g, _tri_decode_rev(i, nq)[1], 0)

    dkdv = functools.partial(_bwd_dkdv_tri_kernel, sm_scale=sm_scale,
                             block=bq, n=nq)
    dk, dv = pl.pallas_call(
        dkdv,
        name="flash_bwd_dkv",
        grid=(bh, n_tri),
        in_specs=[
            pl.BlockSpec((1, bq, d), qi_rev_map),               # q
            pl.BlockSpec((1, bq, d), ki_map),                   # k
            pl.BlockSpec((1, bq, d), ki_map),                   # v
            pl.BlockSpec((1, bq, d), qi_rev_map),               # do
            pl.BlockSpec((1, bq, 1), qi_rev_map),               # lse
            pl.BlockSpec((1, bq, 1), qi_rev_map),               # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), ki_map),
            pl.BlockSpec((1, bq, d), ki_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    def q_map(g, i):
        return (g, _tri_decode(i)[0], 0)

    def k_map(g, i):
        return (g, _tri_decode(i)[1], 0)

    dqk = functools.partial(_bwd_dq_tri_kernel, sm_scale=sm_scale, block=bq)
    dq = pl.pallas_call(
        dqk,
        name="flash_bwd_dq",
        grid=(bh, n_tri),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bq, d), k_map),
            pl.BlockSpec((1, bq, d), k_map),
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bq, 1), q_map),
            pl.BlockSpec((1, bq, 1), q_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), q_map),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _bwd(q, k, v, h, o, lse, do, causal, sm_scale, block_q, block_k,
         interpret):
    """Backward on head-packed [B, T, C]; ``lse`` arrives in the layout
    :func:`_select_family` names for this shape."""
    b, t, c = q.shape
    d = c // h
    bh = b * h
    fam = _select_family(t, h, d, causal, block_q, block_k)
    bq, bk = fam.bq, fam.bk
    nq, nk = t // bq, t // bk

    if fam.bwd == "packed":
        return _bwd_packed(q, k, v, h, o, lse, do, causal, sm_scale,
                           interpret)

    if fam.bwd in ("rowres", "tri_packed"):
        # per-head delta in the packed lse layout [B, H/pack, T, pack]
        pack = _head_pack(d, h)
        delta = jnp.sum((do.astype(jnp.float32)
                         * o.astype(jnp.float32)).reshape(b, t, h, d),
                        axis=-1)
        delta = delta.reshape(b, t, h // pack, pack).transpose(0, 2, 1, 3)
        if fam.bwd == "rowres":
            return _bwd_rowres(q, k, v, h, lse, do, delta, sm_scale,
                               bq, nq, interpret)
        return _bwd_tri_packed(q, k, v, h, lse, do, delta, sm_scale, bq,
                               nq, interpret)

    q, k, v, o, do = (_fold(x, b, t, h, d) for x in (q, k, v, o, do))

    # delta_i = Σ_d dO_id · O_id — tiny elementwise+reduce; XLA fuses it
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                      # [bh, t, 1]

    if fam.bwd == "fused":
        dq, dk, dv = _bwd_fused(q, k, v, lse, do, delta, causal, sm_scale,
                                interpret)
    elif fam.bwd == "tri":
        dq, dk, dv = _bwd_tri(q, k, v, o, lse, do, sm_scale, bq, nq, delta,
                              interpret)
    else:
        q_spec = pl.BlockSpec((1, bq, d), lambda g, i, j: (g, j, 0))
        r_spec = pl.BlockSpec((1, bq, 1), lambda g, i, j: (g, j, 0))
        k_by_i = pl.BlockSpec((1, bk, d), lambda g, i, j: (g, i, 0))
        dkdv = functools.partial(_bwd_dkdv_kernel, sm_scale=sm_scale,
                                 causal=causal, block_q=bq, block_k=bk,
                                 nq=nq)
        dk, dv = pl.pallas_call(
            dkdv,
            name="flash_bwd_dkv",
            grid=(bh, nk, nq),
            in_specs=[
                q_spec,                                          # q by qi=j
                k_by_i,                                          # k by ki
                k_by_i,                                          # v by ki
                q_spec,                                          # do
                r_spec,                                          # lse
                r_spec,                                          # delta
            ],
            out_specs=[k_by_i, k_by_i],
            out_shape=[
                jax.ShapeDtypeStruct((bh, t, d), k.dtype),
                jax.ShapeDtypeStruct((bh, t, d), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
            interpret=interpret,
        )(q, k, v, do, lse, delta)

        dqk = functools.partial(_bwd_dq_kernel, sm_scale=sm_scale,
                                causal=causal, block_q=bq, block_k=bk,
                                nk=nk)
        qi_spec = pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0))
        ri_spec = pl.BlockSpec((1, bq, 1), lambda g, i, j: (g, i, 0))
        k_by_j = pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0))
        dq = pl.pallas_call(
            dqk,
            name="flash_bwd_dq",
            grid=(bh, nq, nk),
            in_specs=[
                qi_spec,
                k_by_j,
                k_by_j,
                qi_spec,
                ri_spec,
                ri_spec,
            ],
            out_specs=qi_spec,
            out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            interpret=interpret,
        )(q, k, v, do, lse, delta)
    return tuple(_unfold(x, b, t, h, d) for x in (dq, dk, dv))


# ---------------------------------------------------------------------------
# custom-vjp wrapper on head-packed [B, T, C]
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, h, causal, sm_scale, block_q, block_k, interpret):
    o, _ = _fwd(q, k, v, h, causal, sm_scale, block_q, block_k, interpret)
    return o


def _flash_fwd(q, k, v, h, causal, sm_scale, block_q, block_k, interpret):
    o, lse = _fwd(q, k, v, h, causal, sm_scale, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(h, causal, sm_scale, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    return _bwd(q, k, v, h, o, lse, g, causal, sm_scale, block_q, block_k,
                interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, dtype=jnp.bfloat16,
                    sm_scale: float | None = None,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool | None = None):
    """Flash attention over ``[B, T, H, D]`` tensors (BTHD in, BTHD out).

    Drop-in for :func:`~ray_lightning_tpu.models.gpt.dot_product_attention`
    (same scaling 1/√D, same causal semantics); differentiable via the
    Pallas backward kernels above.

    ``block_q`` / ``block_k`` ask for a tiling other than the default
    (:func:`_select_family`: one block up to T=1024, 512×512 beyond);
    they are part of the traced program, not of the process.

    Note: under a multi-device ``pjit`` program, call this inside
    ``shard_map`` (the batch/head grid is per-device); single-device jit
    works directly.  ``parallel/ring.py`` composes it with sequence
    parallelism.
    """
    b, t, h, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _use_interpret()
    # [B, T, H, D] → head-packed [B, T, C]: a FREE reshape (it is the
    # qkv Dense output layout); the kernels' index maps slice each
    # head's C columns, so no transpose ever hits HBM
    o = _flash(q.reshape(b, t, h * d), k.reshape(b, t, h * d),
               v.reshape(b, t, h * d), h, causal, sm_scale, block_q,
               block_k, interpret)
    return o.reshape(b, t, h, d).astype(dtype)


def flash_attention_lse(q, k, v, *, n_head: int | None = None,
                        causal: bool = True,
                        sm_scale: float | None = None,
                        interpret: bool | None = None):
    """The forward kernels of :func:`flash_attention` with the softmax's
    log-sum-exp beside the output: ``(o [B, T, H, D] in q's type, lse
    [B, T, H] float32)``.  Forward only (no vjp).  For a caller that
    merges this attention with another over further keys under ONE
    softmax (ops/eva_attention.py: a window's exact rows here, the
    summaries of earlier windows there): ``logaddexp`` of the two lse
    weighs the two outputs.

    Packed rows ``[B, T, H*D]`` with ``n_head`` beside them go to the
    kernels as they are (it is the layout the kernels address: a head is
    a block of columns) and come back packed: ``(o [B, T, H*D], lse
    [B, H, T, 1])``, a head's statistics one column of its own, as the
    kernels write them.  Nothing of the call's size is reshaped to a
    ``[.., H, D]`` view on either side."""
    packed = q.ndim == 3
    if packed:
        if n_head is None:
            raise ValueError("packed [B, T, H*D] rows need n_head")
        (b, t, c), h = q.shape, n_head
        d = c // h
    else:
        b, t, h, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _use_interpret()
    o, lse = _fwd(q.reshape(b, t, h * d), k.reshape(b, t, h * d),
                  v.reshape(b, t, h * d), h, causal, sm_scale, None, None,
                  interpret)
    heads_last = _select_family(t, h, d, causal).lse == "packed"
    if packed:
        if heads_last and lse.shape[-1] > 1:      # [B, H/pack, T, pack]
            lse = lse.transpose(0, 1, 3, 2)
        return o, lse.reshape(b, h, t, 1)         # (folded: [B·H, T, 1])
    if heads_last:
        lse = lse.transpose(0, 2, 1, 3)          # [B, H/pack, T, pack]
    else:
        lse = lse.reshape(b, h, t).transpose(0, 2, 1)       # [B·H, T, 1]
    return o.reshape(b, t, h, d), lse.reshape(b, t, h)
