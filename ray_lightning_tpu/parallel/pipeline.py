"""Pipeline parallelism: GPipe microbatching over a ``stage`` mesh axis.

Beyond the reference's parity surface (SURVEY.md §2.3 marks PP absent),
built the TPU way rather than the torch way: instead of processes
exchanging activations through a framework RPC layer, the whole
pipeline is ONE compiled SPMD program.  Layer-stacked parameters
(leading dim = layer) shard over the ``stage`` axis, each stage scans
its local layer slice, and activations hop to the next stage with
``lax.ppermute`` — lowered to ICI neighbor DMAs that XLA overlaps with
the next microbatch's compute.  The classic GPipe schedule
(arxiv.org/abs/1811.06965; the "scaling book" pipelining recipe) falls
out of a single ``lax.scan`` over time steps:

    time t:  stage s computes microbatch (t - s); stage 0 feeds fresh
    microbatches; the last stage collects outputs for t ≥ S-1.

Bubble fraction is the usual (S-1)/(M+S-1): raise ``n_microbatches``
to amortize.  Composes with data parallelism (batch stays sharded on
``data``) in the same mesh.
"""

from __future__ import annotations

import functools
import re
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_lightning_tpu.parallel.mesh import get_current_mesh
from ray_lightning_tpu.parallel.strategy import SpmdStrategy
from ray_lightning_tpu.telemetry.metrics import note_traced_collective
from ray_lightning_tpu.parallel.ring import _tensor_bytes


def _scan_layers(stage_fn, params_stacked, h):
    """Run ``stage_fn`` once per leading-dim slice of ``params_stacked``
    (layers execute in order; XLA compiles the body once)."""
    def body(carry, p):
        return stage_fn(p, carry), None
    out, _ = lax.scan(body, h, params_stacked)
    return out


def _pipeline_inner(params_loc, x_loc, *, stage_fn, axis_name,
                    n_microbatches, n_stages):
    """Per-device GPipe body under shard_map.

    params_loc: this stage's layer slice ([L/S, ...] leaves);
    x_loc: this data shard's activations [B_loc, ...].
    """
    S, M = n_stages, n_microbatches
    sid = lax.axis_index(axis_name)
    B = x_loc.shape[0]
    mb = B // M
    x_mb = x_loc.reshape((M, mb) + x_loc.shape[1:])
    perm = [(j, (j + 1) % S) for j in range(S)]

    def step(carry, t):
        recv, outs = carry
        # stage 0 feeds microbatch t (clipped during the drain phase —
        # those time steps produce garbage that is never collected)
        feed = lax.dynamic_index_in_dim(x_mb, jnp.clip(t, 0, M - 1), 0,
                                        keepdims=False)
        inp = jnp.where(sid == 0, feed, recv)
        out = _scan_layers(stage_fn, params_loc, inp)
        nxt = lax.ppermute(out, axis_name, perm)
        # the last stage finished microbatch t-(S-1) this step
        oidx = t - (S - 1)
        cur = lax.dynamic_index_in_dim(outs, jnp.clip(oidx, 0, M - 1), 0,
                                       keepdims=False)
        keep = jnp.where((oidx >= 0) & (oidx < M), out, cur)
        outs = lax.dynamic_update_index_in_dim(
            outs, keep, jnp.clip(oidx, 0, M - 1), 0)
        return (nxt, outs), None

    init = (jnp.zeros_like(x_mb[0]), jnp.zeros_like(x_mb))
    (_, outs), _ = lax.scan(step, init, jnp.arange(M + S - 1))
    # only the last stage holds real outputs; broadcast them so the
    # (replicated-over-stage) downstream head/loss sees one consistent
    # value — gradients flow back only into stage S-1's contribution.
    # psum-of-masked-zeros IS the broadcast here: XLA has no one-hop
    # pbroadcast primitive, a ppermute chain costs S-1 serial hops, and
    # a log-tree of ppermutes moves log2(S)*|outs| per link vs the ring
    # all-reduce's 2(S-1)/S*|outs| — psum wins for S>=4 and ties below.
    outs = lax.psum(
        jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), axis_name)
    return outs.reshape((B,) + x_loc.shape[1:])


def pipeline_forward(stage_fn: Callable[[Any, jax.Array], jax.Array],
                     stacked_params: Any, x: jax.Array, *,
                     n_microbatches: int = 4, axis_name: str = "stage",
                     mesh=None) -> jax.Array:
    """Apply ``n_layer`` layers to ``x``, pipelined over ``axis_name``.

    stage_fn(layer_params, h) -> h applies ONE layer; ``stacked_params``
    is its parameter pytree with a leading layer dim on every leaf,
    sharded on the ``stage`` mesh axis (PipelineStrategy does this).
    Without a stage axis (or size 1) this is a plain sequential scan —
    same math, same results, so models are portable across meshes.
    """
    if mesh is None:
        mesh = get_current_mesh()
    S = (mesh.shape[axis_name]
         if mesh is not None and axis_name in mesh.axis_names else 1)
    if S == 1:
        return _scan_layers(stage_fn, stacked_params, x)

    n_layers = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if n_layers % S:
        raise ValueError(
            f"{n_layers} layers do not divide over {S} pipeline stages")

    from ray_lightning_tpu.parallel.mesh import data_and_tensor_axes
    dp, _ = data_and_tensor_axes(mesh)
    data_size = 1
    for a in (dp or ()):
        data_size *= mesh.shape[a]
    if x.shape[0] % max(1, data_size):
        raise ValueError(
            f"global batch {x.shape[0]} does not divide across "
            f"{data_size} data shards")
    b_loc, rem = divmod(x.shape[0] // max(1, data_size), n_microbatches)
    if rem or b_loc == 0:
        raise ValueError(
            f"per-data-shard batch {x.shape[0]}//{data_size} does not "
            f"divide into {n_microbatches} microbatches")
    # fabric traffic per invocation (trace-time accounting, charged per
    # executed step by telemetry.metrics): every GPipe time step each of
    # the S stages ppermutes one microbatch-sized activation block per
    # data shard — global bytes x_bytes/M per stage — over M+S-1 time
    # steps, plus the final psum broadcasting the last stage's outputs
    # (logical payload: the full activation tensor once).
    x_bytes = _tensor_bytes(x)
    note_traced_collective(
        "pipeline", S * (n_microbatches + S - 1) * x_bytes
        // n_microbatches + x_bytes)

    param_specs = jax.tree_util.tree_map(lambda _: P(axis_name),
                                         stacked_params)
    x_spec = P(dp)
    inner = functools.partial(
        _pipeline_inner, stage_fn=stage_fn, axis_name=axis_name,
        n_microbatches=n_microbatches, n_stages=S)
    fn = jax.shard_map(inner, mesh=mesh, in_specs=(param_specs, x_spec),
                       out_specs=x_spec, check_vma=False)
    return fn(stacked_params, x)


class PipelineStrategy(SpmdStrategy):
    """Sharding strategy for pipelined models: parameters whose path
    matches ``stage_param_regex`` (the layer-stacked blocks) shard their
    leading layer dim on ``stage``; everything else follows the usual
    SpmdStrategy rules (so data/tensor/fsdp compose).  Optimizer state
    mirrors the stage sharding — each stage also owns its layers' Adam
    moments, the PP-natural ZeRO placement.
    """

    name = "pipeline"

    def __init__(self, stages: int,
                 stage_param_regex: str = r"(^|/)blocks/",
                 rules: Sequence = (),
                 axis_names: Sequence[str] = ("data", "stage"),
                 axis_sizes=None, **kw):
        sizes = dict(axis_sizes or {})
        sizes.setdefault("stage", stages)
        super().__init__(rules=rules, axis_names=axis_names,
                         axis_sizes=sizes, **kw)
        self.stages = stages
        self._stage_rx = re.compile(stage_param_regex)

    def _stage_spec(self, path: str) -> "P | None":
        if self._stage_rx.search(path):
            return P("stage")
        return None

    def param_spec(self, mesh, path, aval) -> P:
        spec = self._stage_spec(path)
        if spec is not None:
            return spec
        return super().param_spec(mesh, path, aval)

    def opt_spec(self, mesh, path, aval) -> P:
        spec = self._stage_spec(path)
        # optax moment leaves mirror the param tree; only leaves that
        # kept the stacked layer rank can carry the stage dim (scalars
        # like the Adam step count fall through)
        if spec is not None and getattr(aval, "ndim", 0) >= 1:
            return spec
        return super().opt_spec(mesh, path, aval)
