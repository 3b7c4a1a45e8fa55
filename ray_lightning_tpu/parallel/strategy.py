"""Sharding strategies: parallelism expressed as sharding annotations.

This is the architectural inversion at the center of the framework.  The
reference implements its three parallelism flavors as *process-group
protocols* — DDP allreduce hooks (ray_ddp.py:467-468), Horovod ring
(ray_horovod.py:196), FairScale OSS/SDP wrap (ray_ddp_sharded.py:17-34).
On TPU all of them are the *same compiled program* with different sharding
annotations on the train-state pytree; XLA lowers the annotations to
ICI/DCN collectives (psum / reduce-scatter / all-gather):

- :class:`DataParallelStrategy` (≙ RayPlugin/DDP and HorovodRayPlugin):
  params+opt replicated, batch sharded on ``data`` → XLA inserts a
  gradient psum.
- :class:`Zero1Strategy` (≙ RayShardedPlugin/FairScale OSS): params
  replicated, optimizer state sharded on ``data`` → XLA reduce-scatters
  grads into the sharded update and all-gathers updated params (the
  "Automatic Cross-Replica Sharding of Weight Update" pattern,
  arxiv.org/pdf/2004.13336, see PAPERS.md).
- :class:`FullyShardedStrategy` (beyond-parity ZeRO-3/FSDP): params and
  opt state both sharded; XLA all-gathers params where consumed.
- :class:`SpmdStrategy` (beyond-parity): general mesh
  (data, fsdp, sequence, tensor, expert) with regex partition rules for
  tensor parallelism and a sequence axis for long-context.
"""

from __future__ import annotations

import logging
import re
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_logger = logging.getLogger(__name__)

from ray_lightning_tpu.parallel.mesh import build_device_mesh, mesh_axis_size


def _best_shardable_axis(shape: Sequence[int], size: int,
                         taken: set[int] | None = None) -> int | None:
    """Largest dim divisible by ``size`` (None if none)."""
    best, best_dim = None, -1
    for i, d in enumerate(shape):
        if taken and i in taken:
            continue
        if size > 0 and d % size == 0 and d >= size and d > best_dim:
            best, best_dim = i, d
    return best


def _axis_spec(shape: Sequence[int], axis: str, size: int) -> P:
    """PartitionSpec sharding the best divisible dim of ``shape`` on
    ``axis``, replicated if nothing divides."""
    i = _best_shardable_axis(shape, size)
    if i is None:
        return P()
    spec = [None] * len(shape)
    spec[i] = axis
    return P(*spec)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


class ShardingStrategy:
    """Base: maps an abstract TrainState + batch to sharding pytrees."""

    name: str = "base"
    #: outermost→innermost mesh axis names
    axis_names: tuple[str, ...] = ("data",)
    #: axes the batch's leading dim is sharded over
    data_axis_names: tuple[str, ...] = ("data",)
    #: whether this strategy's gradient sync can route through the comm
    #: plane's compressed collectives (ray_lightning_tpu/comm/): requires
    #: params replicated across the reduction axes — true for DDP and
    #: ZeRO-1, false for param-sharded strategies (FSDP/SPMD), whose
    #: mapped-region in_specs would misdeclare the param layout
    comm_compressible: bool = False

    def axis_sizes(self, n_devices: int) -> dict[str, int]:
        return {"data": n_devices}

    def build_mesh(self, devices=None, batch_hint: int | None = None) -> Mesh:
        """Build the mesh.  ``batch_hint`` (global batch size) lets a
        single-process run clamp the data axis so tiny batches still
        shard cleanly (XLA needs the batch dim divisible by the data-axis
        size); multi-process meshes always span every process's devices.
        """
        import math

        devices = list(devices) if devices is not None else jax.devices()
        n = len(devices)
        sizes = dict(self.axis_sizes(n))
        other = 1
        for a, s in sizes.items():
            if a != "data" and s not in (None, -1):
                other *= s
        data = sizes.get("data")
        if data in (None, -1):
            if n % other:
                raise ValueError(
                    f"{n} devices not divisible by non-data axes ({other})")
            data = n // other
        if batch_hint and jax.process_count() == 1:
            clamped = math.gcd(int(data), int(batch_hint)) or 1
            if clamped != data:
                _logger.warning(
                    "Global batch %d does not divide across %d data shards; "
                    "using %d of %d devices. Increase the batch size to use "
                    "the full mesh.", batch_hint, data, clamped * other, n)
            data = clamped
        sizes["data"] = data
        used = data * other
        return build_device_mesh(self.axis_names, sizes, devices[:used])

    # -- per-component specs (override points) -----------------------------

    def param_spec(self, mesh: Mesh, path: str, aval) -> P:
        return P()

    def opt_spec(self, mesh: Mesh, path: str, aval) -> P:
        return P()

    def batch_spec(self, mesh: Mesh, ndim: int) -> P:
        if ndim == 0:
            return P()
        return P(self.data_axis_names
                 if len(self.data_axis_names) > 1 else self.data_axis_names[0])

    # -- pytree-level products (used by the loop) --------------------------

    def replicated(self, mesh: Mesh) -> NamedSharding:
        return NamedSharding(mesh, P())

    def _shardings_with(self, mesh, tree, spec_fn):
        def leaf(path, aval):
            if getattr(aval, "ndim", 0) == 0:
                return NamedSharding(mesh, P())
            return NamedSharding(mesh, spec_fn(mesh, _path_str(path), aval))
        return jax.tree_util.tree_map_with_path(leaf, tree)

    def state_shardings(self, mesh: Mesh, abstract_state) -> Any:
        """TrainState-shaped pytree of NamedSharding."""
        return abstract_state.replace(
            step=NamedSharding(mesh, P()),
            params=self._shardings_with(mesh, abstract_state.params,
                                        self.param_spec),
            model_state=self._shardings_with(mesh, abstract_state.model_state,
                                             self.param_spec),
            opt_state=self._shardings_with(mesh, abstract_state.opt_state,
                                           self.opt_spec),
            rng=NamedSharding(mesh, P()),
        )

    def batch_shardings(self, mesh: Mesh, batch) -> Any:
        def leaf(x):
            ndim = getattr(x, "ndim", 0)
            return NamedSharding(mesh, self.batch_spec(mesh, ndim))
        return jax.tree_util.tree_map(leaf, batch)

    def data_parallel_size(self, mesh: Mesh) -> int:
        return mesh_axis_size(mesh, *self.data_axis_names)

    def kv_cache_spec(self, mesh: Mesh, ndim: int = 4) -> P:
        """Sharding of the serve plane's slot-indexed KV cache
        ``[n_layer, slot, pos, head*dim]`` (serve/kvcache.py): slots
        shard exactly like the batch's leading dim — each data shard
        decodes its own slots with no cross-device attention traffic
        (a slot's tail, ``[n_layer, slot, r, c]``, and the blocks of
        layers that keep a state and no rows, ``[layers, slot, *block]``
        of whatever rank from 2, have their slots where the cache has
        them and shard the same way: the engine asks with each array's
        ``ndim``).
        Requires ``max_batch_slots`` divisible by the data-axis size
        (the serve engine builds its mesh with ``batch_hint=slots`` so
        single-process meshes clamp instead of erroring)."""
        if ndim < 2:
            return P()
        spec = [None] * ndim
        spec[1] = (self.data_axis_names
                   if len(self.data_axis_names) > 1
                   else self.data_axis_names[0])
        return P(*spec)

    @staticmethod
    def _tree_bytes(tree) -> int:
        import numpy as np
        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            total += int(np.prod(getattr(leaf, "shape", ()),
                                 dtype=np.int64)) \
                * np.dtype(leaf.dtype).itemsize
        return total

    @staticmethod
    def _tree_elements(tree) -> int:
        import numpy as np
        return sum(int(np.prod(getattr(leaf, "shape", ()), dtype=np.int64))
                   for leaf in jax.tree_util.tree_leaves(tree))

    # -- planner introspection hooks (plan/candidates.py) ------------------

    @classmethod
    def plan_mesh_options(cls, n_devices: int) -> tuple:
        """Feasible mesh factorizations of ``n_devices`` this strategy
        can plan over, as axis_sizes dicts — the planner enumerates one
        candidate per entry.  Single-axis strategies have exactly one
        layout; multi-axis strategies (SpmdStrategy) override with
        their divisor factorizations.  New strategies self-describe by
        overriding this pair of hooks rather than teaching the planner
        about themselves."""
        return ({"data": n_devices},)

    @classmethod
    def from_plan(cls, axis_sizes: dict) -> "ShardingStrategy":
        """Construct the strategy instance for one
        :meth:`plan_mesh_options` entry."""
        del axis_sizes   # single-axis strategies: nothing to configure
        return cls()

    def grad_transform(self, mesh: Mesh, policy):
        """Resolve a comm policy against this strategy on this mesh: a
        ``comm.GradSync`` the step builder routes the gradient reduction
        through, or ``None`` (the default — the uncompressed build,
        byte-identical to a policy-less trainer).  See
        ray_lightning_tpu/comm/collectives.py:build_grad_sync for the
        resolution rules."""
        if policy is None:
            return None
        from ray_lightning_tpu.comm import build_grad_sync
        return build_grad_sync(self, mesh, policy)

    def step_collective_bytes(self, mesh: Mesh, abstract_state,
                              comm=None) -> dict:
        """op -> logical payload bytes ONE optimizer step moves through
        the fabric as a consequence of this strategy's sharding
        annotations (XLA compiles the collectives into the step, so the
        metrics plane accounts them from the annotation, not a call
        site).  Pure DDP: one gradient all-reduce the size of the
        params.  With an active comm plane (``comm`` = the resolved
        GradSync) the charge is the COMPRESSED wire payload, so
        ``rlt_collective_*`` and the bench JSON reflect the savings; a
        hierarchical sync splits the declaration by link tier
        (``_dcn``/``_ici`` op suffixes — the planner scores each at its
        own bandwidth and the metrics plane feeds
        ``rlt_comm_dcn_bytes_total`` from the suffix)."""
        if self.data_parallel_size(mesh) <= 1:
            return {}
        if comm is not None:
            n = self._tree_elements(abstract_state.params)
            if comm.hierarchical:
                link = comm.psum_link_bytes(n)
                return {"grad_all_reduce_dcn": link["dcn"],
                        "grad_all_reduce_ici": link["ici"]}
            return {"grad_all_reduce": comm.psum_wire_bytes(n)}
        return {"grad_all_reduce": self._tree_bytes(abstract_state.params)}

    # Strategies are part of the plugin config pickled driver→worker; they
    # hold no live handles so default pickling is fine.

    def __repr__(self):
        return f"{type(self).__name__}()"


class DataParallelStrategy(ShardingStrategy):
    """Pure DDP: replicate state, shard batch, XLA psums grads."""

    name = "ddp"
    comm_compressible = True


class Zero1Strategy(ShardingStrategy):
    """ZeRO-1: shard optimizer state across data ranks.

    Parity target for ``RayShardedPlugin`` (ray_ddp_sharded.py:17-34):
    FairScale OSS shards optimizer state across DDP ranks; here the same
    partitioning is a sharding annotation on the opt-state pytree.  What
    the annotation guarantees (audited at the compiled-HLO level in
    tests/test_collective_audit.py): the optimizer update math and its
    f32 master/moment buffers are 1/N-sized per device, each rank
    slices its shard of the summed grads, and the updated params are
    re-assembled with an all-gather.  Whether the grad-sum + slice pair
    lowers to a literal reduce-scatter is an XLA backend pass
    (ReduceScatterCreator) — the audited CPU lowering emits
    all-reduce + dynamic-slice; byte-for-byte the memory story is the
    OSS one either way.

    ``min_shard_elements`` leaves tiny leaves replicated (collective
    latency beats memory savings below a threshold).
    """

    name = "zero1"
    comm_compressible = True

    def __init__(self, min_shard_elements: int = 0):
        self.min_shard_elements = min_shard_elements

    def opt_spec(self, mesh: Mesh, path: str, aval) -> P:
        if aval.size < max(2, self.min_shard_elements):
            return P()
        return _axis_spec(aval.shape, "data", mesh.shape["data"])

    def param_gather_spec(self, mesh: Mesh, path: str, aval) -> P:
        """Shard layout of the post-update params BEFORE their re-gather
        (mirrors :meth:`opt_spec` — the update is computed where its
        optimizer shard lives).  The comm plane's compressed param
        all-gather constrains the updated params to this spec, quantizes
        shard-wise, and lets the replication constraint form the
        low-precision gather."""
        return self.opt_spec(mesh, path, aval)

    def step_collective_bytes(self, mesh: Mesh, abstract_state,
                              comm=None) -> dict:
        """ZeRO step traffic: grads reduce-scatter into the sharded
        update, updated params all-gather back out — each one params'
        worth of logical payload (whether XLA lowers the pair literally
        or as all-reduce + slice, the bytes on the wire are the OSS
        story — see class docstring).  With an active comm plane the
        grad phases carry the compressed payload (+ their all-gather
        leg) and the param gather charges at its policy dtype; a
        hierarchical sync declares the grad phases per link tier
        (``_dcn``/``_ici`` suffixes, see the base class).

        An honest declaration of the LATENCY-HIDDEN gather
        (``policy.gather_bucket_bytes > 0``, comm/collectives.py
        ``regather_params``): the bytes on the wire are unchanged —
        bucketing moves WHEN the gather runs, not how much it moves —
        so the payload is identical, but the op is keyed
        ``param_all_gather_bucketed`` so the planner's cost model
        (plan/cost.py ``op_overlap_factor``) can price the portion XLA
        hides behind the next forward's compute, and the audit/drift
        guards (tests/test_plan.py) can band it separately."""
        if self.data_parallel_size(mesh) <= 1:
            return {}
        if comm is not None:
            gather_key = ("param_all_gather_bucketed"
                          if comm.policy.gather_bucket_bytes > 0
                          and not comm.policy.barrier_sync
                          else "param_all_gather")
            n = self._tree_elements(abstract_state.params)
            if comm.hierarchical:
                link = comm.psum_link_bytes(n)
                return {
                    "grad_sync_dcn": link["dcn"],
                    "grad_sync_ici": link["ici"],
                    gather_key: comm.param_gather_wire_bytes(
                        abstract_state.params),
                }
            return {
                "grad_reduce_scatter": comm.reduce_scatter_wire_bytes(n),
                "grad_all_gather": comm.all_gather_wire_bytes(n),
                gather_key: comm.param_gather_wire_bytes(
                    abstract_state.params),
            }
        params = self._tree_bytes(abstract_state.params)
        return {"grad_reduce_scatter": params,
                "param_all_gather": params}


class FullyShardedStrategy(Zero1Strategy):
    """ZeRO-3/FSDP analog: params and optimizer state both sharded on
    ``data``; XLA all-gathers parameters at their use sites.  Beyond the
    reference's parity surface (SURVEY.md §2.3 marks FSDP absent) but
    nearly free once sharding is declarative."""

    name = "fsdp"
    comm_compressible = False   # params sharded: no replicated-param
    #                             mapped region (comm plane declines)

    def param_spec(self, mesh: Mesh, path: str, aval) -> P:
        if aval.size < max(2, self.min_shard_elements):
            return P()
        return _axis_spec(aval.shape, "data", mesh.shape["data"])

    def step_collective_bytes(self, mesh: Mesh, abstract_state,
                              comm=None) -> dict:
        """FSDP step traffic: params all-gathered at their use sites in
        BOTH forward and backward (2× params' worth) plus the gradient
        reduce-scatter (one params' worth) — strictly more than
        ZeRO-1's 2× total, which the inherited declaration used to
        claim.  Declared separately so the planner's cost model ranks
        FSDP below ZeRO-1 on comm whenever both fit (the memory story
        is what FSDP buys).  The comm plane declines param-sharded
        strategies, so ``comm`` never compresses these bytes."""
        del comm
        if self.data_parallel_size(mesh) <= 1:
            return {}
        params = self._tree_bytes(abstract_state.params)
        return {"param_all_gather": 2 * params,
                "grad_reduce_scatter": params}


class SpmdStrategy(ShardingStrategy):
    """General SPMD over a multi-axis mesh with regex partition rules.

    ``rules`` is an ordered list of ``(regex, PartitionSpec)`` matched
    against the ``/``-joined parameter path (the SNIPPETS.md §1
    ``match_partition_rules`` shape); first match wins; no match →
    replicated (or fsdp-sharded when an ``fsdp`` axis exists).
    Optimizer-state leaves inherit the spec of the parameter whose path
    they embed (optax states mirror the param tree).
    """

    name = "spmd"

    def __init__(
        self,
        rules: Sequence[tuple[str, P]] = (),
        axis_names: Sequence[str] = ("data", "fsdp", "expert", "sequence",
                                     "tensor"),
        axis_sizes: dict[str, int] | None = None,
        shard_sequence_dim: bool = True,
        min_shard_elements: int = 0,
    ):
        self.rules = [(re.compile(r), spec) for r, spec in rules]
        self.axis_names = tuple(axis_names)
        self._axis_sizes = dict(axis_sizes or {})
        self.shard_sequence_dim = shard_sequence_dim and (
            "sequence" in self.axis_names)
        self.min_shard_elements = min_shard_elements
        self.data_axis_names = tuple(
            a for a in ("data", "fsdp") if a in self.axis_names)

    def axis_sizes(self, n_devices: int) -> dict[str, int]:
        sizes = dict(self._axis_sizes)
        for a in self.axis_names:
            sizes.setdefault(a, 1 if a != "data" else None)
        if sizes.get("data") is None:
            sizes["data"] = -1
        return sizes

    def _rule_spec(self, mesh: Mesh, path: str, aval) -> P | None:
        for rx, spec in self.rules:
            if rx.search(path):
                pruned = self._prune_spec(mesh, spec)
                if any(e is not None for e in spec) and \
                        not any(e is not None for e in pruned):
                    # the rule only named axes this mesh lacks (e.g. a
                    # 'tensor' rule on a (data, fsdp) mesh): treat as
                    # unmatched so the param still reaches later rules /
                    # the fsdp fallback instead of silently replicating
                    continue
                return pruned
        return None

    @staticmethod
    def _prune_spec(mesh: Mesh, spec: P) -> P:
        """Drop axes the mesh does not have, so one rule set (written for
        the full data/fsdp/sequence/tensor layout) works on any sub-mesh
        — a rules entry P('tensor', None) on a (data, sequence) mesh
        becomes P(None, None) instead of erroring."""
        def keep(entry):
            if entry is None:
                return None
            if isinstance(entry, (tuple, list)):
                kept = tuple(a for a in entry if a in mesh.axis_names)
                return kept if kept else None
            return entry if entry in mesh.axis_names else None
        return P(*(keep(e) for e in spec))

    def _fsdp_fallback(self, mesh: Mesh, aval) -> P:
        if "fsdp" in mesh.axis_names and mesh.shape["fsdp"] > 1 \
                and aval.size >= max(2, self.min_shard_elements):
            return _axis_spec(aval.shape, "fsdp", mesh.shape["fsdp"])
        return P()

    def param_spec(self, mesh: Mesh, path: str, aval) -> P:
        spec = self._rule_spec(mesh, path, aval)
        if spec is not None:
            return spec
        return self._fsdp_fallback(mesh, aval)

    def opt_spec(self, mesh: Mesh, path: str, aval) -> P:
        spec = self._rule_spec(mesh, path, aval)
        if spec is not None and len(spec) == getattr(aval, "ndim", 0):
            return spec
        return self._fsdp_fallback(mesh, aval)

    def batch_spec(self, mesh: Mesh, ndim: int) -> P:
        if ndim == 0:
            return P()
        data = (self.data_axis_names if len(self.data_axis_names) > 1
                else self.data_axis_names[0])
        if (self.shard_sequence_dim and ndim >= 2
                and mesh.shape.get("sequence", 1) > 1):
            return P(data, "sequence")
        return P(data)

    def kv_cache_spec(self, mesh: Mesh, ndim: int = 4) -> P:
        """Slots on the data axes plus the packed head axis ``C`` on
        ``tensor`` when the mesh has one: a shard of ``C`` is whole
        heads as long as ``n_head`` divides by the axis, so the decode
        attention is head-parallel the same way the training attention
        is (gpt_partition_rules)."""
        spec = list(super().kv_cache_spec(mesh, ndim))
        if ndim >= 4 and mesh.shape.get("tensor", 1) > 1:
            spec[3] = "tensor"
        return P(*spec)

    def step_collective_bytes(self, mesh: Mesh, abstract_state,
                              comm=None) -> dict:
        """Approximate SPMD step traffic for the planner/metrics byte
        model: an active ``fsdp`` axis gathers params at use in forward
        and backward and reduce-scatters grads (the FSDP story); an
        active ``data`` axis additionally all-reduces grads across
        replicas.  Tensor/sequence-rule traffic (activation
        collectives) is NOT modeled — rule-driven layouts are
        hand-written configurations the planner does not enumerate.
        The comm plane declines SPMD, so ``comm`` never applies."""
        del comm
        out: dict = {}
        params = self._tree_bytes(abstract_state.params)
        if mesh_axis_size(mesh, "fsdp") > 1:
            out["param_all_gather"] = 2 * params
            out["grad_reduce_scatter"] = params
        if mesh_axis_size(mesh, "data") > 1:
            out["grad_all_reduce"] = params
        return out

    @classmethod
    def plan_mesh_options(cls, n_devices: int) -> tuple:
        """Every ``data × fsdp`` factorization with a non-trivial fsdp
        axis (fsdp=1 would duplicate the plain DDP candidate).  The
        planner's generic SPMD candidate is rule-less — params fall to
        the fsdp-shard fallback — so the fsdp axis is the dimension
        that matters; rule-driven tensor/sequence layouts stay a
        hand-written ``SpmdStrategy`` concern."""
        return tuple({"data": n_devices // f, "fsdp": f}
                     for f in range(2, n_devices + 1)
                     if n_devices % f == 0)

    @classmethod
    def from_plan(cls, axis_sizes: dict) -> "SpmdStrategy":
        return cls(axis_names=("data", "fsdp"),
                   axis_sizes={"fsdp": int(axis_sizes.get("fsdp", 1))})


class AutoStrategy(ShardingStrategy):
    """Sentinel for ``Trainer(strategy="auto")``: the planner plane
    (ray_lightning_tpu/plan/) resolves it into a concrete strategy —
    plus a comm policy, donation and microbatch decision — once the
    module, example batch and device topology are known inside
    ``_run_stage``.  Carries an optional :class:`plan.PlanConfig`
    override; holds no other state, so it pickles driver→worker like
    any strategy.  Using it unresolved is a wiring bug and fails
    loudly."""

    name = "auto"

    def __init__(self, plan=None):
        self.plan = plan

    def build_mesh(self, devices=None, batch_hint=None) -> Mesh:
        raise RuntimeError(
            "strategy='auto' must be resolved by the planner before a "
            "mesh can be built (Trainer._resolve_auto_strategy); "
            "constructing AutoStrategy outside a Trainer is unsupported")


_STRATEGIES = {
    "ddp": DataParallelStrategy,
    "dp": DataParallelStrategy,
    "zero1": Zero1Strategy,
    "sharded": Zero1Strategy,       # reference-name alias (RayShardedPlugin)
    "fsdp": FullyShardedStrategy,
    "zero3": FullyShardedStrategy,
    "spmd": SpmdStrategy,
    "auto": AutoStrategy,
}


def strategy_names() -> list:
    """Every accepted ``Trainer(strategy=...)`` string, sorted (single
    source of truth for error messages, the planner inventory and the
    README table).  ``"mpmd"`` resolves lazily (the MPMD plane imports
    this module) and stays OUT of ``_STRATEGIES`` — it is a routing
    strategy the planner/comm planes never enumerate."""
    return sorted([*_STRATEGIES, "mpmd"])


def resolve_strategy(strategy: "str | ShardingStrategy | None") -> ShardingStrategy:
    """Resolve ``Trainer(strategy=...)`` into a :class:`ShardingStrategy`.

    Accepted values — an instance passes through; ``None`` defaults to
    DDP; a string selects by name (THE canonical list; the README
    "Parallelism" table mirrors it):

    =====================  ===============================================
    name                   strategy
    =====================  ===============================================
    ``"ddp"`` / ``"dp"``   :class:`DataParallelStrategy` — state
                           replicated, batch sharded, XLA psums grads
    ``"zero1"`` /          :class:`Zero1Strategy` — optimizer state
    ``"sharded"``          sharded across data ranks (FairScale-OSS
                           parity; "sharded" is the reference's name)
    ``"fsdp"`` /           :class:`FullyShardedStrategy` — params AND
    ``"zero3"``            optimizer state sharded, gathered at use
    ``"spmd"``             :class:`SpmdStrategy` — general multi-axis
                           mesh with regex partition rules
    ``"auto"``             :class:`AutoStrategy` — the planner plane
                           (ray_lightning_tpu/plan/) picks strategy,
                           mesh, comm policy, donation and microbatch
                           from a cost model over the candidates above
    ``"mpmd"``             ``MpmdPipelineStrategy`` — pipeline
                           parallelism as N per-stage programs over
                           DCN with driver-side schedules
                           (ray_lightning_tpu/mpmd/; ``RLT_MPMD*``
                           env knobs configure it)
    =====================  ===============================================

    Unknown names raise a ``ValueError`` listing the valid set.
    """
    if strategy is None:
        return DataParallelStrategy()
    if isinstance(strategy, ShardingStrategy):
        return strategy
    if isinstance(strategy, str):
        key = strategy.lower()
        if key == "mpmd":
            from ray_lightning_tpu.mpmd.strategy import (
                MpmdPipelineStrategy)
            return MpmdPipelineStrategy()
        if key not in _STRATEGIES:
            raise ValueError(
                f"Unknown strategy {strategy!r}; valid strategy names: "
                f"{strategy_names()} (see resolve_strategy's docstring "
                f"or the README 'Parallelism' table for what each "
                f"selects)")
        return _STRATEGIES[key]()
    raise TypeError(f"Bad strategy: {strategy!r}")
