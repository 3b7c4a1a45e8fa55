"""Memory-fit audit for BASELINE config #5 (gpt2-1p3b) on its target
meshes, BEFORE any pod exists (VERDICT r3 next #7).

Two layers of evidence, both computed on the 8-virtual-device CPU mesh:

- **backend-reported**: ``compile().memory_analysis()`` per-device
  argument bytes of the real train step — the authoritative sharded
  TrainState footprint (params + fp32 master + Adam moments at the
  documented precision recipe).  Asserted to match the analytic
  per-leaf shard byte account within 10%, so a precision regression
  (params silently fp32, master un-sharded, moments widened) fails
  here no matter which side drifted.
- **analytic transients**: grads (bf16 tree), the fp32 update deltas
  (gathered full-size per device — audited f32 in
  tests/test_collective_audit.py), remat-saved layer-boundary
  activations, and the chunked-CE logit slab.  CPU ``temp`` bytes are
  deliberately NOT used: the CPU lowering materializes full attention
  scores that the TPU flash kernels never allocate.

Budgets: v5e = 16 GB HBM/chip (one v5e-8 host's eight chips), v4 =
32 GB/chip (BASELINE.md config #5's v4-128, 64 chips).  A 10% headroom
is reserved for XLA workspace/fragmentation.
"""

from __future__ import annotations

import functools

import jax
import numpy as np
import pytest

from ray_lightning_tpu.core.steps import build_init_fn, build_train_step
from ray_lightning_tpu.models.gpt import (CONFIGS, GPTLightningModule,
                                          gpt_partition_rules)
from ray_lightning_tpu.parallel.strategy import (FullyShardedStrategy,
                                                 SpmdStrategy, Zero1Strategy)

GB = 1024 ** 3
V5E_HBM = 16 * GB
V4_HBM = 32 * GB
HEADROOM = 0.90          # fraction of HBM the accounted residents may use
GLOBAL_BATCH = 8

CFG = CONFIGS["gpt2-1p3b"]


def _abstract_state(module, tx, batch):
    return jax.eval_shape(build_init_fn(module, tx),
                          jax.random.PRNGKey(0), batch)


def _sharded_bytes(abstract, shardings, n_devices: int) -> int:
    """Per-device bytes of the state under the given shardings (exact:
    per-leaf shard shapes)."""
    total = 0
    for aval, sh in zip(jax.tree_util.tree_leaves(abstract),
                        jax.tree_util.tree_leaves(
                            shardings, is_leaf=lambda x: hasattr(x, "spec"))):
        shape = sh.shard_shape(aval.shape) if hasattr(sh, "shard_shape") \
            else aval.shape
        total += int(np.prod(shape, dtype=np.int64)) * aval.dtype.itemsize
    return total


def _n_params(abstract) -> int:
    return sum(int(np.prod(a.shape, dtype=np.int64))
               for a in jax.tree_util.tree_leaves(abstract.params))


def _transient_bytes(n_params: int, batch_local: int,
                     grads_sharded_by: int = 1,
                     updates_sharded_by: int = 1) -> int:
    """Analytic peak of the big per-device transients the state bytes
    miss (documented in the module docstring).  Grad and fp32-update
    trees mirror the PARAM sharding: replicated-param strategies
    (ddp/zero1) materialize them full-size per device (the audited f32
    all-gather of updates); param-sharded strategies keep both
    shard-sized."""
    cfg = CFG
    grads_bf16 = 2 * n_params // grads_sharded_by
    updates_f32 = 4 * n_params // updates_sharded_by
    acts = cfg.n_layer * batch_local * cfg.block_size * cfg.n_embd * 2
    block_peak = 12 * batch_local * cfg.block_size * cfg.n_embd * 2
    ce_chunk = (batch_local * (cfg.block_size // max(1, cfg.chunked_ce))
                * cfg.vocab_size * 4) * 2      # fwd + bwd slabs
    return grads_bf16 + updates_f32 + acts + block_peak + ce_chunk


def _shard_factors(name: str, n_dev: int) -> tuple:
    """(grads_sharded_by, updates_sharded_by) — conservative lower
    bounds on how the grad/update trees shard per strategy."""
    if name == "fsdp":
        return n_dev, n_dev
    if name == "spmd":
        # every large param is sharded by at least one size-2 axis
        # (tensor rules or the fsdp fallback); use the conservative min
        return 2, 2
    return 1, 1


STRATEGIES = {
    "zero1": lambda: Zero1Strategy(),
    "fsdp": lambda: FullyShardedStrategy(),
    # memory-first mesh for 1.3B on 8 chips: audited at fsdp=2,tensor=2
    # (data=2) the state alone is 7.35 GB/device and the total BREAKS
    # the v5e budget — fsdp=4 is the fitting layout this test pins
    "spmd": lambda: SpmdStrategy(rules=gpt_partition_rules(),
                                 axis_names=("data", "fsdp", "tensor"),
                                 axis_sizes={"fsdp": 4, "tensor": 2}),
}


@pytest.fixture(scope="module", params=sorted(STRATEGIES))
def audited(request):
    """Compile the REAL 1.3B train step under one strategy on the
    8-device mesh; yield every number the assertions need (compile is
    ~2 min per strategy).  The heavy body is memoized by strategy name:
    pytest's fixture-param regrouping re-instantiates module-scoped
    parametrized fixtures when single-param tests (plugin_path, the
    un-donated audits) interleave with the generic groups, and without
    the memo each re-instantiation re-pays the full compile (measured:
    4-7 compiles per run instead of 3)."""
    return _audited(request.param)


@functools.lru_cache(maxsize=None)
def _audited(name):
    strat = STRATEGIES[name]()
    module = GPTLightningModule("gpt2-1p3b", dataset_size=2 * GLOBAL_BATCH,
                                batch_size=GLOBAL_BATCH)
    module.setup_model()
    tx = module.configure_optimizers()
    mesh = strat.build_mesh(batch_hint=GLOBAL_BATCH)
    batch = jax.tree_util.tree_map(
        np.asarray, next(iter(module.train_dataloader())))
    abstract = _abstract_state(module, tx, batch)
    shardings = strat.state_shardings(mesh, abstract)
    jitted = jax.jit(build_train_step(module, tx), donate_argnums=0,
                     in_shardings=(shardings,
                                   strat.batch_shardings(mesh, batch)),
                     out_shardings=(shardings, None))
    comp = jitted.lower(abstract, batch).compile()
    n_dev = int(np.prod(list(mesh.shape.values())))
    mem = comp.memory_analysis()
    return {
        "name": name,
        "mesh": dict(mesh.shape),
        "n_dev": n_dev,
        "n_params": _n_params(abstract),
        "abstract": abstract,
        "compiled_args": mem.argument_size_in_bytes,
        "compiled_out": mem.output_size_in_bytes,
        "compiled_alias": mem.alias_size_in_bytes,
        "analytic_args": _sharded_bytes(abstract, shardings, n_dev),
        "batch_local": max(1, GLOBAL_BATCH // n_dev),
        "module": module,
        "batch": batch,
    }


def test_compiled_args_match_sharded_account(audited):
    """The compiled program's per-device argument bytes must match the
    per-leaf shard account within 10% — catches any precision or
    sharding regression on either side."""
    got, want = audited["compiled_args"], audited["analytic_args"]
    assert abs(got - want) <= 0.10 * want, (
        f"{audited['name']}: compiled args {got / GB:.2f} GB vs sharded "
        f"account {want / GB:.2f} GB")


def test_fits_v5e_8(audited):
    """Config #5's model class must fit a v5e-8 (16 GB a chip) under
    every sharded strategy: zero1 and fsdp at data=8, spmd at fsdp=4 ×
    tensor=2 (fsdp=2 × tensor=2 does not: state alone 7.35 GB a device,
    ~15 GB in all against the 14.4 GB budget)."""
    g_by, u_by = _shard_factors(audited["name"], audited["n_dev"])
    total = audited["compiled_args"] + _transient_bytes(
        audited["n_params"], audited["batch_local"],
        grads_sharded_by=g_by, updates_sharded_by=u_by)
    budget = HEADROOM * V5E_HBM
    assert total <= budget, (
        f"{audited['name']}: {total / GB:.2f} GB accounted vs "
        f"{budget / GB:.2f} GB budget on v5e-8 "
        f"(state {audited['compiled_args'] / GB:.2f})")


class _StubMesh:
    """Just enough mesh for the data-axis strategies' spec functions
    (they read only ``mesh.shape``), so per-device bytes at a target
    shard count can be accounted without 64 real devices."""

    def __init__(self, sizes: dict):
        self.shape = dict(sizes)
        self.axis_names = tuple(sizes)


def _state_bytes_at_dp(strat, abstract, dp: int) -> int:
    """Per-device state bytes under ``strat``'s own spec functions on a
    stub data=dp mesh (exact per-leaf shard shapes, divisibility
    honored the same way _axis_spec does)."""
    mesh = _StubMesh({"data": dp})

    def tree_bytes(tree, spec_fn):
        total = 0
        for path, aval in jax.tree_util.tree_flatten_with_path(tree)[0]:
            if getattr(aval, "ndim", 0) == 0:
                total += aval.dtype.itemsize
                continue
            pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                            for p in path)
            spec = spec_fn(mesh, pstr, aval)
            shape = list(aval.shape)
            for i, entry in enumerate(spec):
                if entry is not None:
                    shape[i] //= dp
            total += int(np.prod(shape, dtype=np.int64)) \
                * aval.dtype.itemsize
        return total

    return (tree_bytes(abstract.params, strat.param_spec)
            + tree_bytes(abstract.model_state, strat.param_spec)
            + tree_bytes(abstract.opt_state, strat.opt_spec))


def test_fits_v4_128_target(audited):
    """BASELINE.md config #5 names v4-128 (64 chips, 32 GB each): the
    same sharding decisions at data-parallel 64 must fit with room.
    (The SPMD case targets custom meshes, covered by the v5e-8 test.)"""
    if audited["name"] == "spmd":
        pytest.skip("spmd targets custom meshes; audited on v5e-8")
    strat = STRATEGIES[audited["name"]]()
    scaled_args = _state_bytes_at_dp(strat, audited["abstract"], 64)
    g_by, u_by = _shard_factors(audited["name"], 64)
    total = scaled_args + _transient_bytes(
        audited["n_params"], 1,
        grads_sharded_by=g_by, updates_sharded_by=u_by)
    budget = HEADROOM * V4_HBM
    assert total <= budget, (
        f"{audited['name']}: {total / GB:.2f} GB vs {budget / GB:.2f} GB "
        f"on v4-128")


def _full_state_bytes(n_params: int) -> int:
    """Unsharded TrainState bytes at the documented precision recipe:
    bf16 params + fp32 master + bf16 mu + fp32 nu (+ small scalars)."""
    return n_params * (2 + 4 + 2 + 4)


def test_single_chip_cannot_train_this(audited):
    """The README's negative claim, kept honest: at data-parallel 1 the
    state plus a gradient tree (the irreducible training residents)
    exceed one v5e chip's 16 GB (Adam state + grads alone, at 1.3B,
    even at B=1 with remat and chunked CE) — this workload NEEDS the
    sharded strategies."""
    n = audited["n_params"]
    assert _full_state_bytes(n) + 2 * n > V5E_HBM


@pytest.mark.parametrize("audited", ["zero1"], indirect=True)
def test_plugin_path_program_matches_direct_jit(audited, tmp_path):
    """Config #5 dress rehearsal THROUGH the plugin wiring (VERDICT r4
    next #8): the pod run reaches the 1.3B program via
    ``RayXlaShardedPlugin`` → ``Trainer._build_compiled``, not via the
    direct ``jax.jit`` the audit above uses — so compile (lower +
    memory_analysis, no execute) the trainer's OWN train step built
    through that wiring and assert its per-device argument bytes equal
    the direct-jit audit's exactly.  A plugin-layer regression (wrong
    strategy resolution, mesh built over the wrong devices, dropped
    in_shardings) can no longer hide behind the direct audit.

    The test drives the worker-side prefix of ``Trainer._run_stage``
    (module setup → loader build → batch peek → ``strategy.build_mesh``
    with ``plugin.local_devices()`` → ``_build_compiled``) with the
    real methods, stopping before ``_init_state`` — materializing the
    1.3B state on the CPU mesh is neither needed nor affordable here.
    """
    from ray_lightning_tpu import Trainer
    from ray_lightning_tpu.core.trainer import _peek_first_batch
    from ray_lightning_tpu.plugins import RayXlaShardedPlugin

    plugin = RayXlaShardedPlugin(num_workers=1, platform="cpu")
    assert plugin.strategy.name == "zero1"
    trainer = Trainer(plugins=[plugin], default_root_dir=str(tmp_path),
                      enable_checkpointing=False, logger=False, seed=0)
    module = GPTLightningModule("gpt2-1p3b", dataset_size=2 * GLOBAL_BATCH,
                                batch_size=GLOBAL_BATCH)

    # worker-side _run_stage prefix, via the real methods
    trainer._stage = "fit"
    trainer.lightning_module = module
    module.trainer = trainer
    module.setup_model()
    strategy = trainer.plugin.strategy
    loaders = trainer._build_loaders("fit")
    example_batch, _ = _peek_first_batch(loaders["train"])
    leaves = jax.tree_util.tree_leaves(example_batch)
    batch_hint = leaves[0].shape[0] * jax.process_count()
    assert batch_hint == GLOBAL_BATCH
    trainer._mesh = strategy.build_mesh(trainer.plugin.local_devices(),
                                        batch_hint=batch_hint)
    assert dict(trainer._mesh.shape) == audited["mesh"]
    trainer._build_compiled(module, example_batch, strategy)

    comp = trainer._train_step.lower(audited["abstract"],
                                     example_batch).compile()
    got = comp.memory_analysis().argument_size_in_bytes
    assert got == audited["compiled_args"], (
        f"plugin-path program args {got / GB:.3f} GB != direct-jit audit "
        f"{audited['compiled_args'] / GB:.3f} GB")


# -- the donation SKIP region (round-5 verdict gap; ROADMAP item 5) --------
#
# On v4-64 the auto heuristic (core/trainer.py _donation_cutoff) SKIPS
# donation for the 1.3B ZeRO-1 state (~2.85 GB/device < the 0.3x cut at
# 32 GB), so the program v4 actually runs is the UN-donated one — whose
# peak carries BOTH the old state (arguments) and the new state
# (outputs, un-aliased).  The donated-program audits above do not cover
# that peak; these do.  (These tests sit at the END of the file ON
# PURPOSE: pytest groups module-scoped parametrized fixtures by param
# order of appearance, and a [zero1]-only test inserted mid-file would
# fragment the fsdp/spmd/zero1 groups and recompile the ~2 min 1.3B
# fixtures several extra times.)


@pytest.mark.parametrize("audited", ["zero1"], indirect=True)
def test_undonated_zero1_budget_in_v4_skip_region(audited):
    """Tier-1 leg: (a) v4-64 really is in the heuristic's skip region
    for this config, and (b) the un-donated residents — old state +
    un-aliased new state (the extra copy donation would have elided) +
    transients — fit 0.9 x 32 GB at data=64.  State sizes come from the
    compiled program's own memory_analysis (argument/output bytes of
    the audited fixture; aliasing changes neither), scaled to dp=64 by
    the strategy's spec walk like test_fits_v4_128_target."""
    from ray_lightning_tpu.core.trainer import Trainer

    strat = Zero1Strategy()
    state64 = _state_bytes_at_dp(strat, audited["abstract"], 64)
    # (a) the heuristic skips donation here (and the v5e-8 mesh —
    # ~2.9 GB/device state against 16 GB — donates; the decision table
    # in tests/test_trainer_local.py pins both)
    assert Trainer._donation_cutoff(state64, V4_HBM) is False, \
        f"expected v4-64 donation-skip, state {state64 / GB:.2f} GB"
    # (b) un-donated budget: outputs carry a FULL un-aliased state copy
    # on top of the argument state.  The fixture's compiled output
    # bytes confirm outputs are state-sized (metrics are scalars).
    assert audited["compiled_out"] >= 0.9 * audited["compiled_args"]
    out_over_args = audited["compiled_out"] / audited["compiled_args"]
    g_by, u_by = _shard_factors("zero1", 64)
    total = state64 * (1 + out_over_args) + _transient_bytes(
        audited["n_params"], 1,
        grads_sharded_by=g_by, updates_sharded_by=u_by)
    budget = HEADROOM * V4_HBM
    assert total <= budget, (
        f"un-donated zero1: {total / GB:.2f} GB accounted vs "
        f"{budget / GB:.2f} GB on v4-64")


@functools.lru_cache(maxsize=None)
def _audited_undonated():
    """Compile the SAME zero1 program WITHOUT donation — the
    executable the v4 skip region actually dispatches — and return its
    own ``memory_analysis``.  Memoized like ``_audited`` so the two
    tests below share one ~2 min compile."""
    audited = _audited("zero1")
    module = audited["module"]
    strat = Zero1Strategy()
    mesh = strat.build_mesh(batch_hint=GLOBAL_BATCH)
    tx = module.configure_optimizers()
    shardings = strat.state_shardings(mesh, audited["abstract"])
    jitted = jax.jit(build_train_step(module, tx),   # no donate_argnums
                     in_shardings=(shardings,
                                   strat.batch_shardings(
                                       mesh, audited["batch"])),
                     out_shardings=(shardings, None))
    return jitted.lower(audited["abstract"],
                        audited["batch"]).compile().memory_analysis()


@pytest.mark.parametrize("audited", ["zero1"], indirect=True)
def test_undonated_zero1_compile_audit(audited):
    """The ROADMAP item-5 verdict gap, closed in tier-1: the un-donated
    1.3B ZeRO-1 program's OWN ``memory_analysis`` (not numbers inferred
    from the donated fixture) pins the skip-region story — identical
    argument bytes, ZERO aliasing (the second state copy is real), a
    state-sized output — and the 2x-state residents fit v4's budget at
    data=64.  (Previously slow-gated behind a duplicate compile; the
    memoized ``_audited_undonated`` makes the direct audit affordable
    in the tier-1 window.)"""
    mem = _audited_undonated()
    assert mem.argument_size_in_bytes == audited["compiled_args"]
    assert mem.alias_size_in_bytes == 0, \
        "un-donated program must not alias state buffers"
    # the un-donated output state copy really is state-sized
    assert mem.output_size_in_bytes >= 0.9 * audited["compiled_args"]
    strat = Zero1Strategy()
    state64 = _state_bytes_at_dp(strat, audited["abstract"], 64)
    g_by, u_by = _shard_factors("zero1", 64)
    # scale the audited per-device outputs to dp=64 via the measured
    # out/args ratio so the budget uses THIS program's numbers
    out_over_args = (mem.output_size_in_bytes
                     / mem.argument_size_in_bytes)
    total = state64 * (1 + out_over_args) + _transient_bytes(
        audited["n_params"], 1, grads_sharded_by=g_by,
        updates_sharded_by=u_by)
    assert total <= HEADROOM * V4_HBM
