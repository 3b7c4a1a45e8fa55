"""Remat (rematerialization) as a first-class, *model-generic* lever.

Until PR 12 the remat-policy ladder lived inside ``models/gpt.py`` as a
GPT-private config knob (``GPTConfig.remat_policy``) plus an env
override — the planner could not see it, pipeline/MPMD models only had
a boolean, and BERT had nothing.  This module is the shared machinery
behind the ``LightningModule.configure_remat()`` hook:

- :func:`policy_object` — the canonical name → ``jax.checkpoint``
  policy mapping (``off | full | dots | dots_no_batch`` plus the
  ``checkpoint_name``-based MoE save lists), WITHOUT the
  ``RLT_REMAT_POLICY`` env consultation (that stays a model-build
  concern, models/gpt.py ``_remat_policy``);
- :class:`RematSpec` — what a module declares to the planner: its
  policy ladder, its current default, an ``apply`` to reconfigure the
  module in place, and a ``probe`` that prices one policy from avals;
- the probe primitives: :func:`saved_activation_bytes` (the
  eval_shape-exact bytes of every *computed* residual the policy saves
  — ``jax.ad_checkpoint``'s own ``saved_residuals`` over abstract
  args, argument-sourced residuals excluded because params/input
  residency is already accounted elsewhere) and
  :func:`grad_dot_flops` (matmul FLOPs of the backward jaxpr, counted
  by walking ``dot_general`` eqns recursively — the difference vs the
  un-remat'd baseline is exactly the matmul work the policy recomputes).

Everything here is pure tracing: no compiles, deterministic for fixed
avals — which is what lets plan/cost.py fold these numbers into the
planner's ranking keys without breaking the fleet-wide
agree-without-a-collective contract (plan/planner.py).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import numpy as np

#: the generic policy ladder every remat-capable model family supports,
#: ordered from no-recompute to max-recompute (models append their
#: checkpoint_name-based extras, e.g. GPT's MoE save lists)
POLICY_LADDER = ("off", "dots", "dots_no_batch", "full")

#: checkpoint_name-based MoE save lists (ops/moe.py checkpoint_name
#: call sites); generic here so any routed-FFN family can reuse them
MOE_POLICIES = ("dots_moe_act", "dots_moe")


def policy_object(name: str):
    """``jax.checkpoint`` policy for a canonical ladder name.

    ``"full"`` maps to ``None`` (jax's default: nothing saveable — the
    max-recompute end); ``"off"`` maps to ``everything_saveable``,
    though callers normally skip the remat wrap entirely for "off"
    (:func:`RematSpec` consumers and models/gpt.py both do).  Raises
    naming the options, mirroring the old gpt-local mapping.
    """
    cp = jax.checkpoint_policies
    policies = {
        "full": None,
        "dots": cp.dots_saveable,
        "dots_no_batch": cp.dots_with_no_batch_dims_saveable,
        # dots + the named MoE intermediates (ops/moe.py
        # checkpoint_name): between dots and off — saving them keeps
        # the expert backward's dgrad fusions off the recompute chains
        # without round-tripping EVERY intermediate the way "off" does
        "dots_moe_act": cp.save_from_both_policies(
            cp.dots_saveable, cp.save_only_these_names("moe_hact")),
        "dots_moe": cp.save_from_both_policies(
            cp.dots_saveable,
            cp.save_only_these_names("moe_hact", "moe_dispatch",
                                     "moe_combine")),
        "off": cp.everything_saveable,
    }
    if name not in policies:
        raise ValueError(
            f"remat_policy {name!r}; options: {sorted(policies)}")
    return policies[name]


@dataclasses.dataclass(frozen=True)
class RematProbe:
    """One policy's modeled cost ingredients at the probe batch size
    (plan/cost.py rescales linearly to the candidate's per-device
    batch — every quantity here is linear in the leading batch dim)."""

    saved_bytes: int        #: computed-residual bytes across ALL blocks
    recompute_flops: int    #: extra backward matmul FLOPs vs no-remat
    n_blocks: int           #: remat region count (per-region overhead)
    batch: int              #: probe leading batch dim (rescale anchor)


@dataclasses.dataclass(frozen=True)
class RematSpec:
    """What ``configure_remat()`` returns: the module's remat surface.

    ``apply(policy)`` reconfigures the module the spec was created from
    IN PLACE (resets any materialized model) — the planner applies it to
    ``copy.copy`` clones for candidate compiles and to the real module
    once a winner is picked (core/trainer.py); ``probe(policy, batch)``
    prices a policy from the example batch's avals alone.
    """

    policies: tuple           #: supported policy names, ladder-ordered
    default: str              #: the module's current effective policy
    apply: Callable           #: (policy: str) -> None, in place
    probe: Callable           #: (policy: str, batch) -> RematProbe


# -- probe primitives ------------------------------------------------------

def _aval_bytes(aval) -> int:
    return int(np.prod(aval.shape, dtype=np.int64) or 1) \
        * aval.dtype.itemsize


def saved_activation_bytes(fn, *args) -> int:
    """Bytes of the residuals ``jax.grad(fn)`` would save that are
    COMPUTED inside ``fn`` (argument-sourced residuals — params, the
    block input — excluded: their residency is charged as state/batch
    elsewhere in the cost model).  ``args`` may be ShapeDtypeStructs;
    this only traces."""
    # jax 0.9.0 exports only ``print_saved_residuals`` publicly
    from jax._src.ad_checkpoint import saved_residuals
    return sum(_aval_bytes(aval) for aval, src in saved_residuals(
        fn, *args) if "argument" not in src)


def _dot_flops_of_jaxpr(jaxpr) -> int:
    """2·M·N·K·batch summed over every ``dot_general`` in ``jaxpr``,
    recursing into sub-jaxprs (pjit / remat / scan / custom-vjp
    bodies)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
            a, b = eqn.invars[0].aval, eqn.invars[1].aval
            batch = int(np.prod([a.shape[i] for i in lb],
                                dtype=np.int64) or 1)
            k = int(np.prod([a.shape[i] for i in lc],
                            dtype=np.int64) or 1)
            m = int(np.prod([a.shape[i] for i in range(a.ndim)
                             if i not in lc and i not in lb],
                            dtype=np.int64) or 1)
            n = int(np.prod([b.shape[i] for i in range(b.ndim)
                             if i not in rc and i not in _rb],
                            dtype=np.int64) or 1)
            total += 2 * batch * m * n * k
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)   # ClosedJaxpr
            if sub is not None and hasattr(sub, "eqns"):
                total += _dot_flops_of_jaxpr(sub)
            elif hasattr(v, "eqns"):          # bare Jaxpr
                total += _dot_flops_of_jaxpr(v)
            elif isinstance(v, (list, tuple)):
                for w in v:                   # e.g. cond branches
                    ws = getattr(w, "jaxpr", w)
                    if hasattr(ws, "eqns"):
                        total += _dot_flops_of_jaxpr(ws)
    return total


def grad_dot_flops(fn, *args) -> int:
    """Matmul FLOPs of ``fn``'s full backward (grads wrt every arg —
    the training shape: a block's backward produces both param grads
    and the activation grad flowing upstream).  Pure tracing; the
    POLICY-minus-BASELINE difference of this number is the recompute
    work a checkpoint policy adds."""
    import jax.numpy as jnp

    def scalar(*a):
        out = fn(*a)
        leaves = jax.tree_util.tree_leaves(out)
        return sum(leaf.astype(jnp.float32).sum() for leaf in leaves)

    g = jax.grad(scalar, argnums=tuple(range(len(args))))
    return _dot_flops_of_jaxpr(jax.make_jaxpr(g)(*args).jaxpr)


def step_dot_flops(fn, *args) -> int:
    """Matmul FLOPs of ``fn``'s OWN jaxpr — for programs that already
    contain their backward (a built train step: forward + grad +
    optimizer update), where :func:`grad_dot_flops` would differentiate
    a second time.  The goodput plane's default ``flops_per_step``
    pricing (telemetry/goodput.py measured MFU)."""
    return _dot_flops_of_jaxpr(jax.make_jaxpr(fn)(*args).jaxpr)


def block_cost(fn, base_fn, *args, base_flops=None) -> "tuple[int, int]":
    """(saved computed-residual bytes of ``fn``, extra backward matmul
    FLOPs of ``fn`` vs the un-remat'd ``base_fn``).  Pass
    ``base_flops`` (one :func:`grad_dot_flops` of ``base_fn``) when
    pricing several policies of the same block to avoid re-tracing the
    baseline per policy."""
    if base_flops is None:
        base_flops = grad_dot_flops(base_fn, *args)
    saved = saved_activation_bytes(fn, *args)
    extra = max(0, grad_dot_flops(fn, *args) - base_flops) \
        if fn is not base_fn else 0
    return saved, extra


__all__ = [
    "MOE_POLICIES",
    "POLICY_LADDER",
    "RematProbe",
    "RematSpec",
    "block_cost",
    "grad_dot_flops",
    "policy_object",
    "saved_activation_bytes",
    "step_dot_flops",
]
