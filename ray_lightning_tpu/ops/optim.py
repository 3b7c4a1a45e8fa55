"""Optimizer-side mixed precision: low-precision resident params with an
fp32 master copy carried in the optimizer state.

TPU-first rationale: with fp32-resident params and bf16 compute (flax
``dtype=bfloat16``), every forward re-casts every kernel fp32->bf16 and
every backward produces an fp32 cotangent — on the gpt2-small headline
that is ~8.7 ms/step of pure dtype-convert fusions (a pre-round device
trace, records since removed).  Keeping the *resident* params bf16 deletes those casts
from the hot program and halves param HBM residency.  (It does NOT
shrink the gradient all-reduce: the partitioner must resolve each
cross-replica partial sum at the f32-accumulating grad dot, BEFORE the
bf16 cotangent cast — summing bf16-rounded partials would change the
numerics — so gradient collectives ride at f32 by construction; audited
at the compiled-HLO level in tests/test_collective_audit.py.)  Full
precision is preserved where it matters — the optimizer update — by an
fp32 master copy inside the optimizer state.  This is the classic
mixed-precision recipe; on ZeRO-1/SPMD meshes the master shards with
the rest of the optimizer state, exactly as FairScale OSS shards its
fp32 copy across DDP ranks (reference: ray_ddp_sharded.py:17-34 — OSS
wraps the optimizer and owns the full-precision weights; here the same
ownership is a pytree inside ``opt_state`` whose leaves mirror the
param paths, so the strategies' path-regex sharding rules apply to the
master for free).

Exact-replacement semantics: the trainer applies updates with
``optax.apply_updates`` (``(p + u).astype(p.dtype)``, core/steps.py).
We return fp32 deltas ``cast(new_master) - p``; both operands are
bf16-representable values, so the fp32 subtraction and re-addition are
exact (a difference of two 8-bit-mantissa values fits fp32's 24 bits
whenever their exponents are within 16 — always true for a finite
optimizer step), and the final cast lands exactly on
``cast(new_master)``.  The resident params therefore track the master
bit-for-bit, with no drift between replicas.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax


class FP32MasterState(NamedTuple):
    """State of :func:`fp32_master`.

    ``master`` mirrors the param tree in fp32; it sits *before* the
    inner state so its pytree paths read ``.../master/<param path>`` and
    the strategies' path-embedding opt-state rules (parallel/strategy.py
    ``SpmdStrategy.opt_spec``) shard it like the param it shadows.
    """

    inner: Any
    master: Any


def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)


def fp32_master(inner: optax.GradientTransformation
                ) -> optax.GradientTransformation:
    """Wrap ``inner`` to run against an fp32 master copy of the params.

    Use with low-precision resident params (``LightningModule.param_dtype
    = jnp.bfloat16``): gradients are upcast to fp32, ``inner`` updates
    the fp32 master, and the emitted update replaces the resident params
    with the master re-cast to their dtype (exactly — see module
    docstring).  Non-float leaves pass through untouched.
    """

    def init(params):
        master = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.float32) if _is_float(p) else p, params)
        return FP32MasterState(inner=inner.init(master), master=master)

    def update(grads, state, params=None):
        if params is None:
            raise ValueError("fp32_master requires params in update()")
        g32 = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32) if _is_float(g) else g, grads)
        updates, new_inner = inner.update(g32, state.inner, state.master)
        new_master = optax.apply_updates(state.master, updates)
        new_resident = jax.tree_util.tree_map(
            lambda m, p: m.astype(jnp.asarray(p).dtype), new_master, params)
        out = jax.tree_util.tree_map(
            lambda n, p: (n.astype(jnp.float32) - p.astype(jnp.float32))
            if _is_float(p) else jnp.zeros_like(p),
            new_resident, params)
        return out, FP32MasterState(inner=new_inner, master=new_master)

    return optax.GradientTransformation(init, update)
