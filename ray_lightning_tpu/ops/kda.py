"""Kimi Delta Attention (arXiv:2510.26692): the gated delta rule with a
decay a CHANNEL of the key, in its two formulations.

A head keeps a float32 matrix ``S`` [K, V] (``K`` the key's width, ``V``
the value's; 128 x 128 published).  With ``q``, ``k`` [K] (unit length,
``q`` scaled), ``v`` [V], the log decay ``g`` [K] <= 0 and ``beta`` in
(0, 1) of a position::

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

Every function here takes and returns the state VALUE-MAJOR, ``S^T`` [V,
K]: the key's channels on the lanes, so that a position's ``exp(g)``,
``k`` and ``q`` (rows of ``K`` lanes, as the projections make them)
broadcast over the state's rows as they lie, and both products with the
state are sums along the lanes.

- **one position a slot**: the recurrence as written, state in and out.
  ``g = 0`` and ``beta = 0`` leave a state exactly as it was, which is how
  a caller masks a slot.  :func:`kda_step` is plain ``jax.numpy`` over any
  leading dimensions; :func:`kda_decode` is the ``kda_decode`` Pallas call
  over the resident ``[layers, S, H V, K]`` array, donated and updated in
  place: a slot's 32 matrices (2 MB) are read once and written once a
  step, where the compiler's own schedule of ``kda_step`` read and wrote
  them about ten times over (33.0 ms a decode run of 7 layers x 192
  slots, 9.8 % of the bytes' time: my chip run, PR 43).
- **a whole prompt** (:func:`kda_chunked`): the chunkwise form, chunks of
  ``CHUNK`` = 64 positions.  With ``G_r`` the decay summed from the
  chunk's first position through ``r``, the state after ``r`` is
  ``Diag(exp(G_r)) S_0 + sum_{i <= r} Diag(exp(G_r - G_i)) k_i u_i^T``,
  where the corrected values ``u`` solve the chunk's unit lower
  triangular system ``(I + Diag(beta) A) U = Diag(beta) (V - K~ S_0)``,
  ``A_ri = sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])`` for ``i < r`` and
  ``K~_r = k_r exp(G_r)``.  With ``T`` the system's inverse times
  ``Diag(beta)``, ``W = T K~``, ``U0 = T V``, the queries' ``A^q_ri`` (``i
  <= r``), ``Q~ = Q exp(G)`` and ``K^ = K exp(G_C - G)``, a chunk is TWO
  affine maps of the state it starts from::

      S_C = (Diag(exp(G_C)) - K^^T W) S_0 + K^^T U0        = M S_0 + N
      O   = (Q~ - A^q W) S_0 + A^q U0                      = Q' S_0 + O0

  ``M``, ``N``, ``Q'`` and ``O0`` read no state and are made for all
  chunks at once; the ``lax.scan`` over the chunks carries the state with
  ONE product a chunk a head (``M S + N``) and keeps every chunk's ``S_0``,
  and the outputs are one more batched product after it.

  ``exp(G_r - G_i)`` is never split into ``exp(G_r) exp(-G_i)`` over a
  whole chunk: a channel that forgets fast reaches ``G = -100`` inside 64
  positions, and ``exp(100)`` is not a float32.  Blocks of ``SUB`` = 16
  rows take their off-diagonal part as a product of two factors measured
  from the block's first row (each <= 1), and their 16 x 16 diagonal part
  from the differences themselves.  The system is solved by forward
  substitution (row by row inside a diagonal block, block by block
  across): the Neumann product ``(I - N)(I + N^2)...`` is the same matrix
  with its digits cancelled away where keys resemble one another.

All take and return float32 and compute at ``highest``; positions whose
``g`` and ``beta`` are 0 (a bucket's padding) leave the state alone, so
the state a prompt returns is its last real position's wherever the
bucket ends.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_lightning_tpu.ops import flash_decode as _fd

CHUNK = 64
SUB = 16
KERNEL_NAME = "kda_decode"
_F32 = jnp.float32


def _dot(eq: str, a, b):
    return jnp.einsum(eq, a, b, precision="highest",
                      preferred_element_type=_F32)


# -- one position a slot ---------------------------------------------------------------

def kda_step(q, k, v, g, beta, state):
    """One position.  ``q``, ``k``, ``g`` [..., K], ``v`` [..., V],
    ``beta`` [...], ``state`` [..., V, K] (value-major), all float32.
    Returns ``(o [..., V], state')``."""
    k = k[..., None, :]
    decayed = state * jnp.exp(g)[..., None, :]
    seen = jnp.sum(decayed * k, axis=-1)                      # S'^T k
    state = decayed + (beta[..., None] * (v - seen))[..., None] * k
    return jnp.sum(state * q[..., None, :], axis=-1), state


def _decode_kernel(a_ref, k_ref, q_ref, v_ref, b_ref, s_ref, o_ref, s_out,
                   *, heads: int, width: int):
    """A slot's ``heads`` matrices, one after another: ``a`` (the decay),
    ``k``, ``q`` rows of a head's lanes; ``v`` and ``o`` a head a LANE
    (``[V, H]``), so that a head's values are a column beside the state's
    rows without a transpose in here."""
    for h in range(heads):
        rows = slice(h * width, (h + 1) * width)
        k = k_ref[0, h:h + 1, :]
        st = s_ref[0, 0, rows, :] * a_ref[0, h:h + 1, :]
        seen = jnp.sum(st * k, axis=-1, keepdims=True)
        st = st + (b_ref[0, :, h:h + 1] * (v_ref[0, :, h:h + 1] - seen)) * k
        s_out[0, 0, rows, :] = st
        o_ref[0, :, h:h + 1] = jnp.sum(st * q_ref[0, h:h + 1, :], axis=-1,
                                       keepdims=True)


def kda_decode(q, k, v, g, beta, state, *, layer: int):
    """:func:`kda_step` for every slot against layer ``layer`` of the
    resident state, in place.  ``q``, ``k``, ``g`` [S, H, K], ``v`` [S, H,
    V], ``beta`` [S, H]; ``state`` [layers, S, H V, K] WHOLE (donate it:
    the call's output aliases it, and only ``layer``'s blocks are
    touched).  Returns ``(o [S, H, V], state')``.  One grid step a slot:
    2 MB in and 2 MB out at the published sizes, double-buffered."""
    S, H, K = k.shape
    V = v.shape[-1]
    n, slots, rows, width = state.shape
    if (slots, rows, width) != (S, H * V, K) or not 0 <= layer < n:
        raise ValueError(f"state {state.shape} does not hold layer {layer} "
                         f"of {S} slots x {H} heads of [{V}, {K}]")
    row = pl.BlockSpec((1, H, K), lambda s: (s, 0, 0))
    col = pl.BlockSpec((1, V, H), lambda s: (s, 0, 0))
    mat = pl.BlockSpec((1, 1, H * V, K), lambda s: (layer, s, 0, 0))
    kernel = functools.partial(_decode_kernel, heads=H, width=V)
    kernel.__name__ = KERNEL_NAME + "_kernel"
    o, state = pl.pallas_call(
        kernel, name=KERNEL_NAME, grid=(S,),
        in_specs=[row, row, row, col,
                  pl.BlockSpec((1, 1, H), lambda s: (s, 0, 0)), mat],
        out_specs=[col, mat],
        out_shape=[jax.ShapeDtypeStruct((S, V, H), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=32 * 2 ** 20),
        interpret=_fd._use_interpret(),
    )(jnp.exp(g), k, q, jnp.swapaxes(v, 1, 2), beta[:, None, :], state)
    return jnp.swapaxes(o, 1, 2), state


def decode_kernel() -> bool:
    """Whether a decode step takes the Pallas call: on the TPU (and where
    a test or a described compile steers ``ops/flash_decode.py
    _use_interpret``); plain ``jax.numpy`` elsewhere, where the call
    would be interpreted a slot at a time."""
    return not _fd._use_interpret()


# -- a whole prompt -------------------------------------------------------------------

def _unit_lower_inverse(n):
    """``(I + n)^-1`` for ``n`` [..., C, C] strictly lower triangular, by
    forward substitution over ``SUB``-row blocks.  The batch lies on the
    LANES here (``[r, i, batch]``) and a product of two blocks is a
    multiply and a sum over whole lane tiles: as ``[batch, 16, 16]``
    matrices every one of the ~35 small steps moved its 16 lanes padded
    to 128 (3.53 ms a layer at 3,328 positions; so 0.86: my chip runs,
    PR 43)."""
    C = n.shape[-1]
    lead = n.shape[:-2]
    size = 1
    for d in lead:
        size *= d
    b = C // SUB
    t = jnp.moveaxis(n.reshape((size, b, SUB, b, SUB)), 0, -1)  # [a, r, c, i, N]

    def times(x, y):
        """``x`` [r, i, N] by ``y`` [i, j, N], a matrix a lane."""
        return jnp.sum(x[:, :, None, :] * y[None, :, :, :], axis=1)

    # the b diagonal blocks side by side on the lanes, row by row
    diag = jnp.concatenate([t[a, :, a] for a in range(b)], axis=-1)
    eye = jnp.broadcast_to(jnp.eye(SUB, dtype=_F32)[:, :, None], diag.shape)
    rows = [eye[0]]
    for r in range(1, SUB):
        rows.append(eye[r] - jnp.sum(
            diag[r, :r, None, :] * jnp.stack(rows, axis=0), axis=0))
    x = jnp.stack(rows, axis=0)
    inv = [[None] * b for _ in range(b)]
    for a in range(b):
        inv[a][a] = x[..., a * size:(a + 1) * size]
        for c in range(a):
            acc = sum(times(t[a, :, m], inv[m][c]) for m in range(c, a))
            inv[a][c] = -times(inv[a][a], acc)
    zero = jnp.zeros_like(inv[0][0])
    full = jnp.concatenate(
        [jnp.concatenate([inv[a][c] if c <= a else zero for c in range(b)],
                         axis=1) for a in range(b)], axis=0)  # [C, C, N]
    return jnp.moveaxis(full, -1, 0).reshape(lead + (C, C))


def _decayed_products(q, k, G):
    """``(A, Aq)`` [..., C, C]: ``sum_c x_r[c] k_i[c] exp(G_r[c] -
    G_i[c])`` for ``x = k`` where ``i < r`` and for ``x = q`` where ``i <=
    r``, zero elsewhere.  ``q``, ``k``, ``G`` [..., C, K]."""
    C = k.shape[-2]
    at = jnp.arange(SUB)
    upto = at[:, None] >= at[None, :]                         # i <= r
    keep = jnp.concatenate([at[:, None] > at[None, :], upto], axis=0)
    rows_k, rows_q = [], []
    for a in range(C // SUB):
        lo, hi = a * SUB, (a + 1) * SUB
        Ga, ka, qa = G[..., lo:hi, :], k[..., lo:hi, :], q[..., lo:hi, :]
        # the diagonal block from the differences themselves, the keys'
        # rows and the queries' under ONE sum over the lanes (above the
        # diagonal the difference is positive: held at 0, masked after)
        x = jnp.concatenate([ka, qa], axis=-2)                # [.., 2 SUB, K]
        Gx = jnp.concatenate([Ga, Ga], axis=-2)
        d = jnp.minimum(Gx[..., :, None, :] - Ga[..., None, :, :], 0.0)
        both = jnp.sum(x[..., :, None, :] * (jnp.exp(d) * ka[..., None, :, :]),
                       axis=-1)                               # [.., 2 SUB, SUB]
        both = jnp.where(keep, both, 0.0)
        kk, qk = both[..., :SUB, :], both[..., SUB:, :]
        pad = jnp.zeros(kk.shape[:-1] + (C - hi,), _F32)
        if a == 0:
            rows_k.append(jnp.concatenate([kk, pad], axis=-1))
            rows_q.append(jnp.concatenate([qk, pad], axis=-1))
            continue
        # the blocks before it: both factors measured from the row before
        # this block's first, so that each is at most 1
        ref = G[..., lo - 1:lo, :]
        early = k[..., :lo, :] * jnp.exp(ref - G[..., :lo, :])
        off = _dot("...rc,...ic->...ri", x * jnp.exp(Gx - ref), early)
        rows_k.append(jnp.concatenate([off[..., :SUB, :], kk, pad], axis=-1))
        rows_q.append(jnp.concatenate([off[..., SUB:, :], qk, pad], axis=-1))
    return jnp.concatenate(rows_k, axis=-2), jnp.concatenate(rows_q, axis=-2)


def kda_chunked(q, k, v, g, beta, state):
    """A sequence.  ``q``, ``k``, ``g`` [B, T, H, K], ``v`` [B, T, H, V],
    ``beta`` [B, T, H], ``state`` [B, H, V, K] (value-major: the state
    before position 0), all float32.  Returns ``(o [B, T, H, V], state
    after position T - 1)``.  ``T`` is padded to whole chunks with
    positions that leave the state alone."""
    B, T, H, K = k.shape
    n = -(-T // CHUNK)
    pad = n * CHUNK - T

    def chunks(x):
        """[B, T, H, ...] -> [n, B, H, CHUNK, ...], zeros behind ``T``."""
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((B, n, CHUNK) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 2, 3), 1, 0)

    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    beta = chunks(beta)                                       # [n, B, H, C]
    G = jnp.cumsum(g, axis=-2)
    A, Aq = _decayed_products(q, k, G)
    # solve = (I + Diag(beta) A)^-1 Diag(beta)
    solve = _unit_lower_inverse(beta[..., :, None] * A) * beta[..., None, :]
    grown = jnp.exp(G)
    W = _dot("...ri,...ic->...rc", solve, k * grown)
    U0 = _dot("...ri,...iv->...rv", solve, v)
    left = k * jnp.exp(G[..., -1:, :] - G)                    # K^
    # the chunk's two affine maps of the (value-major) state it starts from
    M = grown[..., -1, :, None] * jnp.eye(K, dtype=_F32) \
        - _dot("...ic,...id->...cd", left, W)                 # [.., K, K]
    N = _dot("...iv,...ic->...vc", U0, left)                  # [.., V, K]
    Qd = q * grown - _dot("...ri,...ic->...rc", Aq, W)        # [.., C, K]
    O0 = _dot("...ri,...iv->...rv", Aq, U0)

    def chunk(S, MN):
        M, N = MN
        return _dot("...vd,...cd->...vc", S, M) + N, S

    state, before = jax.lax.scan(chunk, state, (M, N))
    o = _dot("...rc,...vc->...rv", Qd, before) + O0
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)             # [B, n, C, H, V]
    return o.reshape(B, n * CHUNK, H, -1)[:, :T], state


def kda_recurrent(q, k, v, g, beta, state):
    """:func:`kda_chunked`'s arguments and results through :func:`kda_step`,
    a position at a time (``lax.scan``): what the chunkwise form is held
    against (tests), not a prefill."""
    def one(S, x):
        o, S = kda_step(*x, S)
        return S, o

    state, o = jax.lax.scan(
        one, state, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


__all__ = ["CHUNK", "KERNEL_NAME", "SUB", "decode_kernel", "kda_chunked",
           "kda_decode", "kda_recurrent", "kda_step"]
