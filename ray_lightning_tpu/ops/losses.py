"""Memory-lean losses for large-vocabulary language models.

At V≈50k and B·T≈8k, the fp32 logits tensor of a full-vocab
cross-entropy is ~1.6 GB — written, read and differentiated every step,
it dominates the loss's HBM traffic (the TPU bottleneck, BASELINE.md).
:func:`chunked_softmax_cross_entropy` streams the vocab projection in
row chunks under ``lax.scan`` with per-chunk rematerialization: each
chunk computes its own [rows, V] logits on the MXU (bf16 operands, fp32
accumulation), folds them into the loss, and lets the backward pass
recompute them instead of storing residuals — peak logits memory drops
by the chunk factor while the extra FLOPs are one repeated head matmul
(a few % of a transformer step).

When to use: an OPT-IN for memory-bound configs (long sequence × 50k
vocab, e.g. the gpt2-1p3b class, where full fp32 logits cost multiple
GB).  At gpt2-small scale it measured ~8% slower than the fused
full-vocab loss on v5e — XLA's own fusion wins when the logits fit —
so the default loss path stays full-vocab.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import optax

_log = logging.getLogger(__name__)


def fused_lm_cross_entropy(hidden, table, targets):
    """Full-vocab CE that never writes fp32 logits to HBM.

    The naive tied-head path (``wte.attend(h).astype(f32)`` → optax CE)
    materializes BOTH an fp32 [B,T,V] logits tensor (~1.6 GB at
    gpt2-small scale) and a bf16 copy saved for the softmax recompute —
    measured 3.76 ms at 2.56 GB accessed for the forward head fusion
    alone (a pre-round roofline trace; the head and loss today:
    ``train_head_loss_ms``, chipbench).  Here the head
    matmul emits logits in the compute dtype once, and the
    max/logsumexp/label-gather reductions upcast per-element *inside*
    their fusions (fp32 accumulators, nothing fp32 ever hits HBM).
    Forward precision matches the naive path: its fp32 logits were
    produced by a bf16-operand matmul, so they carry the same rounding
    this path keeps.

    hidden: [B, T, D] compute dtype; table: [V, D] tied embedding;
    targets: [B, T] int labels.  Returns mean token CE (fp32 scalar).
    """
    # profiler scopes (telemetry/scopes.py): the head's matmul, then the
    # loss over its logits
    with jax.named_scope("lm_head"):
        logits = jax.lax.dot_general(
            hidden, table.astype(hidden.dtype),
            (((2,), (1,)), ((), ())))                  # [B, T, V] bf16
    with jax.named_scope("loss"):
        m = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
        # upcast BEFORE the max subtraction: both casts are exact (m is
        # one of the logits) and stay elementwise inside the reduction
        # fusion, so the exp argument carries full fp32 precision —
        # identical to the naive path — while still no fp32 [B,T,V]
        # tensor hits HBM
        shifted = logits.astype(jnp.float32) \
            - m.astype(jnp.float32)[..., None]
        sumexp = jnp.sum(jnp.exp(shifted), axis=-1)
        lse = jnp.log(sumexp) + m.astype(jnp.float32)
        logit_y = jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0]
        return (lse - logit_y.astype(jnp.float32)).mean()


def chunked_softmax_cross_entropy(hidden, table, targets,
                                  n_chunks: int = 8):
    """Mean token cross-entropy of ``hidden @ table.T`` against targets,
    never materializing the full logits tensor.

    hidden: [B, T, D] (compute dtype, e.g. bf16)
    table:  [V, D] tied embedding table (any float dtype)
    targets:[B, T] int labels
    """
    B, T, D = hidden.shape
    rows_total = B * T
    requested = n_chunks
    n_chunks = max(1, min(n_chunks, rows_total))
    while rows_total % n_chunks:
        n_chunks -= 1
    if n_chunks < min(requested, rows_total):
        # silent degradation would reintroduce the very logits-memory
        # spike this function exists to avoid — make it visible
        _log.warning(
            "chunked CE: %d rows not divisible into %d chunks; using %d "
            "(peak logits memory grows by the same factor).",
            rows_total, requested, n_chunks)
    rows = rows_total // n_chunks

    h = hidden.reshape(n_chunks, rows, D)
    y = targets.reshape(n_chunks, rows)
    table = table.astype(hidden.dtype)

    def body(total, xs):
        hc, yc = xs
        with jax.named_scope("lm_head"):
            logits = jax.lax.dot_general(
                hc, table, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)      # [rows, V] f32
        with jax.named_scope("loss"):
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, yc)
            return total + ce.sum(), None

    total, _ = jax.lax.scan(jax.checkpoint(body),
                            jnp.zeros((), jnp.float32), (h, y))
    return total / rows_total
