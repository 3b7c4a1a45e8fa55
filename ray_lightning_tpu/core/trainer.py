"""The Trainer: ``pl.Trainer`` capability analog, re-designed TPU-first.

Structure of a run (compare SURVEY.md §3.1 call stack):

  driver:  Trainer.fit(module)
    └─ plugin.run(...)            — LocalPlugin executes in-process;
                                    RayXlaPlugin ships (trainer, module,
                                    datamodule) to actor workers and
                                    round-trips results (plugins/)
  worker:  trainer._run_stage(...)
    ├─ strategy.build_mesh()      — Mesh over all chips of all hosts
    ├─ jit(init_fn, out_shardings=state_shardings)   — params born sharded
    ├─ jit(train_step, donate_argnums=0)             — ONE compiled SPMD
    │                                                   program; gradient
    │                                                   sync is a sharding
    │                                                   consequence
    └─ host loop: batches → global arrays → compiled step; callbacks and
       checkpointing run host-side between steps.

The host loop never inspects device values except at logging/validation
boundaries (JAX async dispatch keeps the device pipeline full — the
explicit host-transfer-point discipline flagged in SURVEY.md §7).
"""

from __future__ import annotations

import contextlib
import logging
import os
import tempfile
import time
import warnings
from typing import Any, Optional

import fsspec
import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import serialization

from ray_lightning_tpu.compile import (
    AotPrecompiler,
    CompileCacheConfig,
    global_batch_abstract,
    stack_abstract,
)
from ray_lightning_tpu.compile import cache as compile_cache
from ray_lightning_tpu.core.callbacks import Callback, ModelCheckpoint
from ray_lightning_tpu.core.state import TrainState
from ray_lightning_tpu.core.steps import (
    build_eval_step,
    build_init_fn,
    build_predict_step,
    build_train_step,
)
from ray_lightning_tpu.parallel.gather import fetch_tree
from ray_lightning_tpu.parallel.mesh import set_current_mesh
from ray_lightning_tpu.parallel.strategy import resolve_strategy
from ray_lightning_tpu.telemetry import TelemetryConfig, span
from ray_lightning_tpu.telemetry import clocks as _clocks
from ray_lightning_tpu.telemetry import scopes as _scopes
from ray_lightning_tpu.telemetry import spans as _spans
from ray_lightning_tpu.telemetry import metrics as _metrics
from ray_lightning_tpu.utils.seed import reset_seed, seed_everything

_log = logging.getLogger(__name__)

#: what the fit loop's clock is charged with (``Trainer.loop_stats()``):
#: the loader (``data_wait``), user hooks around a dispatch
#: (``callbacks``), the body of the ``step`` span (``dispatch``:
#: ``run_one`` / ``run_chunk``) and fetches that block on the device
#: (``device_wait``); the span sites of the same names
LOOP_PHASES = ("data_wait", "callbacks", "dispatch", "device_wait")

_RUNTIME_FIELDS = (
    "state", "_mesh", "_train_step", "_eval_steps", "_predict_step",
    "_state_shardings", "_abstract_state", "_tx", "_init_fn", "_init_rng",
    "_multi_train_step", "_stacked_batch_shardings",
    "_cache_source", "_cached_multi_step", "_cached_single_step",
    "_precompiler", "_abstract_batch", "_grad_sync", "_snapshotter",
    "_redundancy",
)

# every spelling (PL 1.x and 2.x) that means "half-precision inputs";
# on TPU they all resolve to bfloat16 (no loss-scaling machinery)
_BF16_PRECISIONS = ("bf16", "bf16-mixed", "bf16-true",
                    "16", "16-mixed", "16-true")
_FP32_PRECISIONS = ("32", "32-true", "64")


class Trainer:
    """Drives fit / validate / test / predict for a LightningModule."""

    def __init__(
        self,
        max_epochs: Optional[int] = None,
        max_steps: int = -1,
        callbacks: Optional[list[Callback]] = None,
        plugins: Optional[list] = None,
        strategy: Any = None,
        default_root_dir: Optional[str] = None,
        enable_checkpointing: bool = True,
        limit_train_batches: Optional[int] = None,
        limit_val_batches: Optional[int] = None,
        limit_test_batches: Optional[int] = None,
        limit_predict_batches: Optional[int] = None,
        check_val_every_n_epoch: int = 1,
        val_check_interval: Optional[int] = None,
        log_every_n_steps: int = 50,
        num_sanity_val_steps: int = 2,
        accumulate_grad_batches: int = 1,
        steps_per_execution: int = 1,
        cache_train_dataset: bool = False,
        gradient_clip_val: Optional[float] = None,
        precision: str = "32",
        seed: Optional[int] = None,
        resume_from_checkpoint: Optional[str] = None,
        use_distributed_sampler: bool = True,
        enable_progress_bar: bool = False,   # accepted for API parity
        logger: Any = True,                  # accepted for API parity
        telemetry: Any = None,
        compile_cache: Any = None,
        comm_policy: Any = None,
        elastic: Any = None,
        plan: Any = None,
    ):
        if max_epochs is None and (max_steps is None or max_steps < 0):
            max_epochs = 1000
        self.max_epochs = max_epochs
        self.max_steps = max_steps if max_steps is not None else -1
        self.callbacks: list[Callback] = list(callbacks or [])
        self.default_root_dir = default_root_dir or os.path.join(
            os.getcwd(), "rlt_logs")
        self.enable_checkpointing = enable_checkpointing
        self.limit_train_batches = limit_train_batches
        self.limit_val_batches = limit_val_batches
        self.limit_test_batches = limit_test_batches
        self.limit_predict_batches = limit_predict_batches
        self.check_val_every_n_epoch = max(1, check_val_every_n_epoch)
        self.val_check_interval = val_check_interval
        self.log_every_n_steps = max(1, log_every_n_steps)
        self.num_sanity_val_steps = num_sanity_val_steps
        self.accumulate_grad_batches = max(1, accumulate_grad_batches)
        # opt-in multi-step dispatch: fold k optimizer steps into ONE
        # compiled program (lax.scan over stacked batches), cutting host
        # dispatches k× — decisive for small models where per-step
        # dispatch latency dominates compute (BASELINE config #1).
        # Batch-granular callbacks coarsen to once per chunk.
        self.steps_per_execution = max(1, int(steps_per_execution))
        # opt-in device-resident train set: samples upload ONCE (flat,
        # dataset order), each epoch a device-side repack follows the
        # loader's own index order (shuffle-accurate membership), and
        # steps gather their batch on-device — removing the per-step
        # host→device batch transfer entirely (for small models the
        # transfer, not the compute, bounds the step — to be
        # re-measured).  See core/loop_engine.py CachedSource.
        # Works single- and multi-process (the flat cache becomes one
        # global sharded array); combine with steps_per_execution>1.
        self.cache_train_dataset = bool(cache_train_dataset)
        self.gradient_clip_val = gradient_clip_val
        self.precision = str(precision)
        if self.precision not in _BF16_PRECISIONS + _FP32_PRECISIONS:
            raise ValueError(
                f"Unknown precision {precision!r}; use one of "
                f"{_BF16_PRECISIONS + _FP32_PRECISIONS}")
        self.seed = seed
        self.resume_from_checkpoint = resume_from_checkpoint
        self.use_distributed_sampler = use_distributed_sampler
        # run telemetry (telemetry/): per-rank spans + heartbeats stream
        # to the driver, which exports trace.json / telemetry.jsonl.
        # None defers to RLT_TELEMETRY; the config pickles to workers
        # with the trainer.
        self.telemetry = TelemetryConfig.resolve(telemetry)
        #: exported artifact paths, set by the execution plugin after a
        #: telemetry-enabled run ({"trace": ..., "jsonl": ..., "summary"})
        self._telemetry_paths: Optional[dict] = None
        # persistent XLA compilation cache (compile/): None defers to
        # the RLT_COMPILE_CACHE* env knobs and — inside a builtin tune
        # trial — the experiment's shared cache dir.  Resolved HERE (the
        # trainer is constructed inside the trial thread / on the
        # driver) so the pickled config carries the tune session's dir
        # into actor workers that have no session of their own.
        self.compile_cache = CompileCacheConfig.resolve(compile_cache)
        # compressed gradient collectives (comm/): blockwise-quantized
        # cross-replica reductions with error feedback.  None defers to
        # the RLT_COMM* env knobs; "none" (the default) keeps the train
        # step bit-identical to a policy-less build.  The frozen policy
        # pickles driver→worker with the trainer.
        from ray_lightning_tpu.comm import CommPolicy
        self.comm_policy = CommPolicy.resolve(comm_policy)
        # elastic plane (elastic/): async snapshots + shrink-to-continue
        # fault tolerance.  None defers to the RLT_ELASTIC* env knobs;
        # off (the default) keeps every path below inert.  The frozen
        # config pickles driver→worker with the trainer.
        from ray_lightning_tpu.elastic import ElasticConfig
        self.elastic = ElasticConfig.resolve(elastic)
        # planner plane (plan/): cost-model-driven auto-parallelism
        # behind Trainer(strategy="auto").  None defers to the RLT_PLAN*
        # env knobs; the frozen config pickles driver→worker with the
        # trainer so every rank plans from identical inputs.
        from ray_lightning_tpu.plan import PlanConfig
        self.plan = PlanConfig.resolve(plan)
        from ray_lightning_tpu.utils.logger import resolve_logger
        self.logger = resolve_logger(logger, self.default_root_dir)

        # execution plugin (LocalPlugin unless a distributed one is given)
        from ray_lightning_tpu.plugins.base import LocalPlugin
        dist = [p for p in (plugins or []) if hasattr(p, "run")]
        if len(dist) > 1:
            raise ValueError("At most one execution plugin is supported.")
        self.plugin = dist[0] if dist else LocalPlugin()
        if strategy is not None:
            # explicit Trainer(strategy=...) overrides the plugin default
            self.plugin.strategy = resolve_strategy(strategy)

        if enable_checkpointing and not any(
                isinstance(c, ModelCheckpoint) for c in self.callbacks):
            self.callbacks.append(ModelCheckpoint())

        # run state
        self.lightning_module = None
        self.datamodule = None
        self.current_epoch = 0
        self.global_step = 0
        self.should_stop = False
        self.sanity_checking = False
        self.num_val_batches = 0
        self.callback_metrics: dict[str, float] = {}
        self.logged_metrics: dict[str, float] = {}
        self.state: Optional[TrainState] = None
        self._world = {"world_size": 1, "global_rank": 0, "local_rank": 0,
                       "node_rank": 0}
        self._cache_bytes_hint = None
        self._mesh = None
        #: seconds from stage entry to the first completed train step
        #: (compile + init + upload startup cost; chip_smoke.py and
        #: ``rlt_time_to_first_step_seconds`` report it)
        self.time_to_first_step: Optional[float] = None
        self._stage_t0: Optional[float] = None
        self._precompiler: Optional[AotPrecompiler] = None
        #: the open set-up window and its spans (root, then first_step)
        self._setup_spans: Optional[contextlib.ExitStack] = None
        self._epoch_metric_acc: dict[str, list] = {}
        self._warned_skip = False
        self._stage = None
        self._sharded_checkpointers: dict = {}
        self._snapshotter = None
        #: shrink-to-continue bookkeeping, set by the elastic driver on
        #: the driver trainer (rides the pickle to workers — the loader
        #: rescale reads it) and summarized into _elastic_report
        self._elastic_state: Optional[dict] = None
        self._elastic_report: Optional[dict] = None
        self._elastic_worker_stats: Optional[dict] = None
        #: in-memory reconstruct-and-continue package built by the
        #: elastic driver from harvested parity escrows — RIDES the
        #: pickle to the shrunken fleet (unlike the runtime fields
        #: below), where _init_state restores it instead of a snapshot
        self._elastic_recovery: Optional[dict] = None
        #: worker-side parity manager (elastic/redundancy.py), rebuilt
        #: per stage like the snapshotter
        self._redundancy = None
        #: sharded-checkpoint restores executed by THIS process during
        #: the stage — the zero-replay proof reads it (a parity
        #: recovery must show 0)
        self._snapshot_restores = 0
        self._warned_rescale = False
        #: the planner's machine-readable verdict (PlanReport dict) when
        #: strategy="auto" ran; rank-0's copy rides the worker result
        #: package back to the driver (plugins/xla.py)
        self._plan_report: Optional[dict] = None
        #: the winning plan's donation decision, consulted by
        #: _should_donate between the RLT_DONATE force and the heuristic
        self._plan_donate: Optional[bool] = None
        #: goodput plane (telemetry/goodput.py): this rank's finalized
        #: ledger doc, and the driver-side fleet aggregate the bench
        #: harness reads (plugins set it in their teardown)
        self._goodput_local: Optional[dict] = None
        self._goodput_report: Optional[dict] = None
        #: where the fit loop's wall time goes (telemetry/clocks.py): a
        #: new one each stage, read by ``loop_stats()``
        self._loop_clock = _clocks.PhaseClock(LOOP_PHASES)

    # ------------------------------------------------------------------
    # pickling across the driver→worker boundary (ray_ddp.py:164-172
    # analog: drop live handles / compiled functions / device arrays)
    # ------------------------------------------------------------------

    def __getstate__(self):
        state = self.__dict__.copy()
        for f in _RUNTIME_FIELDS:
            state[f] = None
        state["lightning_module"] = None
        state["datamodule"] = None
        state["_sharded_checkpointers"] = {}  # live orbax managers
        return state

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def fit(self, module, datamodule=None, ckpt_path: Optional[str] = None):
        ckpt_path = ckpt_path or self.resume_from_checkpoint
        return self.plugin.run(self, module, datamodule, "fit", ckpt_path)

    def validate(self, module, datamodule=None,
                 ckpt_path: Optional[str] = None):
        return self.plugin.run(self, module, datamodule, "validate", ckpt_path)

    def test(self, module, datamodule=None, ckpt_path: Optional[str] = None):
        return self.plugin.run(self, module, datamodule, "test", ckpt_path)

    def predict(self, module, datamodule=None,
                ckpt_path: Optional[str] = None):
        return self.plugin.run(self, module, datamodule, "predict", ckpt_path)

    # -- world info -----------------------------------------------------

    @property
    def world_size(self) -> int:
        return self._world["world_size"]

    @property
    def global_rank(self) -> int:
        return self._world["global_rank"]

    @property
    def local_rank(self) -> int:
        return self._world["local_rank"]

    @property
    def node_rank(self) -> int:
        return self._world["node_rank"]

    @property
    def is_global_zero(self) -> bool:
        return self.global_rank == 0

    @property
    def strategy(self):
        return self.plugin.strategy

    @property
    def checkpoint_callback(self) -> Optional[ModelCheckpoint]:
        for c in self.callbacks:
            if isinstance(c, ModelCheckpoint):
                return c
        return None

    @property
    def early_stopping_callback(self):
        from ray_lightning_tpu.core.callbacks import EarlyStopping
        for c in self.callbacks:
            if isinstance(c, EarlyStopping):
                return c
        return None

    # ------------------------------------------------------------------
    # stage execution (runs in-process locally, or inside each worker)
    # ------------------------------------------------------------------

    def _run_stage(self, module, datamodule, stage: str,
                   ckpt_path: Optional[str] = None):
        self._stage = stage
        self._stage_t0 = time.monotonic()
        self.time_to_first_step = None
        # where the loop's wall time goes, always on: starts at the first
        # step's result (telemetry/clocks.py; ``loop_stats()``)
        self._loop_clock = _clocks.PhaseClock(LOOP_PHASES)
        # set-up is kept whatever the telemetry flag says (dozens of
        # spans, none in a loop): one root from here to the end of the
        # first step, read afterwards from telemetry.spans.kept(<root>)
        self._close_setup_spans()
        self._setup_spans = contextlib.ExitStack()
        self._setup_spans.enter_context(_spans.keep(f"{stage}_setup"))
        self._setup_spans.enter_context(span(f"{stage}_setup"))
        try:
            return self._run_stage_spanned(module, datamodule, stage,
                                           ckpt_path)
        finally:
            self._close_setup_spans()

    def _close_setup_spans(self) -> None:
        """Close ``first_step``, the root and the window (idempotent:
        the fit closes them at its first step's result, every other
        path at the stage's end)."""
        if self._setup_spans is not None:
            self._setup_spans.close()
            self._setup_spans = None

    def _run_stage_spanned(self, module, datamodule, stage: str,
                           ckpt_path: Optional[str]):
        self.lightning_module = module
        module.trainer = self
        self.datamodule = datamodule
        if datamodule is not None:
            datamodule.trainer = self

        if self.seed is not None:
            seed_everything(self.seed)
        else:
            reset_seed()

        self._world = {
            "world_size": jax.process_count(),
            "global_rank": jax.process_index(),
            "local_rank": 0,
            "node_rank": jax.process_index(),
        }

        # deterministic fault injection (elastic/faults.py): RLT_FAULT
        # in this process's env arms kill/wedge/slow-rank-k-at-step-s
        # for chaos tests and benches
        from ray_lightning_tpu.elastic.faults import (FaultInjector,
                                                      maybe_injector_from_env)
        if not any(isinstance(c, FaultInjector) for c in self.callbacks):
            injector = maybe_injector_from_env()
            if injector is not None:
                self.callbacks.append(injector)
        # elastic snapshotting (elastic/snapshot.py): cadence-driven
        # async sharded saves off the critical path, fit only
        self._snapshotter = None
        if stage == "fit" and self.elastic.enabled \
                and self.elastic.snapshot_every_n_steps > 0:
            from ray_lightning_tpu.elastic.snapshot import Snapshotter
            self._snapshotter = Snapshotter(self, self.elastic)
        # parity redundancy (elastic/redundancy.py): cadence-driven
        # optimizer-shard parity over the worker↔worker peer channel,
        # enabling zero-replay recovery on a single-rank loss
        self._redundancy = None
        self._snapshot_restores = 0
        if stage == "fit" and self.elastic.enabled \
                and self.elastic.redundancy > 0:
            self._redundancy = self._build_redundancy()

        # persistent XLA compilation cache: activated before the first
        # jit so every program of this stage (init, train, eval) is a
        # disk hit when a previous process — an earlier tune trial, a
        # pre-restart worker, yesterday's run — compiled it (compile/)
        compile_cache.activate(self.compile_cache)

        # data lifecycle (reference: prepare_data per worker, ray_ddp.py:446)
        with span("setup_model"):
            if datamodule is not None:
                datamodule._call_prepare_data()
                datamodule._call_setup(stage)
            module.prepare_data()
            module.setup(stage)
            module.setup_model()

        strategy = self.plugin.strategy
        if strategy is None:
            strategy = resolve_strategy(None)
            self.plugin.strategy = strategy

        with span("loaders"):
            loaders = self._build_loaders(stage)
            first_loader = loaders.get(
                {"fit": "train", "validate": "val", "test": "test",
                 "predict": "predict"}[stage])
            if first_loader is None:
                raise ValueError(
                    f"No dataloader available for stage {stage!r}")
            example_batch, replacement = _peek_first_batch(first_loader)
        if replacement is not first_loader:
            key = {"fit": "train", "validate": "val", "test": "test",
                   "predict": "predict"}[stage]
            loaders[key] = replacement
        leaves = jax.tree_util.tree_leaves(example_batch)
        batch_hint = (leaves[0].shape[0] * jax.process_count()
                      if leaves and getattr(leaves[0], "ndim", 0) > 0
                      else None)
        if getattr(strategy, "name", "") == "auto":
            # planner plane (plan/): everything the cost model needs —
            # module, example batch, topology — is known exactly here,
            # one line before the mesh would be built
            strategy = self._resolve_auto_strategy(
                module, example_batch, batch_hint, strategy, stage)
            self.plugin.strategy = strategy
        if getattr(strategy, "name", "") == "mpmd":
            # MPMD plane (mpmd/): no SPMD mesh or monolithic train step
            # exists — the engine builds per-stage programs and runs
            # the driver-side schedule.  Fit only; evaluate with a
            # non-mpmd strategy (without a stage axis the model is the
            # same sequential math).
            if stage != "fit":
                raise ValueError(
                    f"strategy='mpmd' supports fit only (got "
                    f"{stage!r}); run {stage} under 'ddp' — the model "
                    f"math is identical without a stage split")
            from ray_lightning_tpu.mpmd.engine import run_mpmd_fit
            return run_mpmd_fit(self, module, loaders, example_batch)
        with span("mesh"):
            # the first question about devices: JAX starts its backend
            # here when the caller has not
            self._mesh = strategy.build_mesh(self.plugin.local_devices(),
                                             batch_hint=batch_hint)
        set_current_mesh(self._mesh)  # for mesh-aware ops (ring attention)
        # goodput plane (telemetry/goodput.py): one ledger per fit run,
        # backdated to the stage clock so the partition covers every
        # second of stage wall (compile and init included).  The plugin
        # armed the plane (or didn't); start_run is a no-op when off.
        self._goodput_ledger = None
        if stage == "fit":
            from ray_lightning_tpu.telemetry import goodput as _goodput
            self._goodput_ledger = _goodput.start_run("fit")
            if self._goodput_ledger is not None:
                self._goodput_ledger._t0 = self._stage_t0
                self._goodput_ledger.devices = int(self._mesh.devices.size)
                # MFU denominator: the configured peak, else the
                # published peak of THIS device kind; an unknown kind
                # (CPU tests included) prices no MFU at all — the
                # planner's modeled ACHIEVED rate is not a peak
                tfl = self.telemetry.resolved_goodput_tflops()
                if tfl is None:
                    kind = self._mesh.devices.flat[0].device_kind
                    tfl = _goodput.DEVICE_PEAKS.get(
                        kind, {}).get("tflops_bf16")
                self._goodput_ledger.device_tflops = tfl
        self._cache_bytes_hint = (
            _cache_bytes_estimate(loaders.get("train"), example_batch)
            if stage == "fit" and self.cache_train_dataset else 0)
        # "compile" covers trace construction + jit setup; the first
        # "step" span additionally contains the XLA compile of the train
        # program (jax compiles lazily at first dispatch)
        t_compile = time.monotonic()
        with span("compile"):
            self._build_compiled(module, example_batch, strategy)
        _metrics.on_compile()
        if self._goodput_ledger is not None:
            self._goodput_ledger.add("compile",
                                     time.monotonic() - t_compile)
            self._goodput_ledger.set_flops_per_step(
                self._price_flops_per_step(module))
        if _metrics.metrics_enabled():
            # the gradient/param collectives XLA compiles into the step
            # from the strategy's shardings have no host call site; the
            # strategy declares their per-step byte cost so the metrics
            # plane can charge it per executed step.  An active comm
            # plane shrinks the declared bytes to the compressed wire
            # payload, so rlt_collective_* and bench JSON see the savings
            from ray_lightning_tpu.comm.audit import declared_dcn_bytes
            op_bytes = strategy.step_collective_bytes(
                self._mesh, self._abstract_state, comm=self._grad_sync)
            if self._redundancy is not None:
                # the parity tick's amortized wire cost is a declared
                # per-step collective like the gradient traffic — the
                # redundancy overhead is a scrapeable series, not a
                # hidden tax (elastic/redundancy.py)
                from ray_lightning_tpu.elastic.redundancy import (
                    declared_parity_bytes)
                pb = declared_parity_bytes(
                    self._abstract_state.opt_state,
                    self._state_shardings.opt_state,
                    self.elastic.redundancy,
                    self.elastic.redundancy_every_n_steps)
                if pb:
                    op_bytes = {**op_bytes, "parity_update": pb}
            _metrics.note_step_collectives(
                op_bytes,
                dcn_bytes=declared_dcn_bytes(op_bytes,
                                             jax.process_count() > 1))
        t_init = time.monotonic()
        with span("init"):
            self._init_state(module, example_batch, strategy, ckpt_path)
        if self._goodput_ledger is not None:
            self._goodput_ledger.add("init", time.monotonic() - t_init)

        with span("hooks", hook="setup"):
            for cb in self.callbacks:
                cb.setup(self, module, stage)
        try:
            if stage == "fit":
                result = self._fit_loop(module, loaders)
            elif stage in ("validate", "test"):
                result = self._run_eval_stage(module, stage, loaders)
            else:
                result = self._predict_loop(module, loaders)
        except BaseException as e:
            for cb in self.callbacks:
                cb.on_exception(self, module, e)
            raise
        finally:
            set_current_mesh(None)
            for cb in self.callbacks:
                cb.teardown(self, module, stage)
            self._close_goodput_ledger()
            self._close_loop_clock(stage)
        return result

    def loop_stats(self) -> dict:
        """Where this stage's loop spent its wall time since its first
        step's result (``PhaseClock.snapshot()``): per phase of
        ``LOOP_PHASES`` the ``seconds``, the intervals ``n`` and the
        ``longest`` one with its ``step`` and ``ts``; ``steps``;
        ``wall_s``, whose rest is ``seconds["other"]`` (transfers, hook
        plans, evaluation, epoch ends).  After the stage the same is
        ``telemetry.clocks.last(<stage>)``."""
        return self._loop_clock.snapshot()

    def _close_loop_clock(self, stage: str) -> None:
        """The stage is over: stop its clock and keep the snapshot for a
        reader that holds no trainer; and where a profiler session was
        open over this stage's programs (a span site saw one), keep
        their scope tables before anything lets go of the programs
        (telemetry/scopes.py).  No session: one boolean."""
        self._loop_clock.stop()
        _clocks.keep(stage, self._loop_clock.snapshot())
        if _spans.session_seen():
            _scopes.remember()

    def _close_goodput_ledger(self) -> None:
        """Finalize this stage's goodput ledger: fold the snapshotter's
        off-loop costs in, attach the latest measured anatomy window as
        the useful bucket's sub-split, close the partition against the
        stage wall, and keep the doc (``_goodput_local``) for the rank-0
        result package + the telemetry sink."""
        ledger = getattr(self, "_goodput_ledger", None)
        if ledger is None:
            return
        self._goodput_ledger = None
        if self._snapshotter is not None:
            stats = self._snapshotter.stats
            ledger.add("snapshot", stats.get("save_seconds", 0.0))
            ledger.add("snapshot_stall", stats.get("stall_seconds", 0.0))
            try:
                from ray_lightning_tpu import telemetry as _telemetry
                agg = _telemetry.get_active()
                if agg is not None and stats.get("snapshots"):
                    # incident-plane correlation events: a snapshot (and
                    # any stall it exposed on the step path) is a named
                    # cause candidate, not background noise
                    agg.note_event("snapshot",
                                   saves=int(stats.get("snapshots", 0)),
                                   seconds=round(
                                       stats.get("save_seconds", 0.0), 6))
                    if stats.get("stall_seconds", 0.0) > 0:
                        agg.note_event("snapshot_stall",
                                       seconds=round(
                                           stats["stall_seconds"], 6))
            except Exception:
                pass
        try:
            from ray_lightning_tpu.telemetry import anatomy as _anatomy
            ctl = _anatomy.get_anatomy_controller()
            if ctl is not None and ctl.last:
                ledger.set_anatomy(ctl.last)
        except Exception:   # anatomy must never break the partition
            pass
        from ray_lightning_tpu.telemetry import goodput as _goodput
        self._goodput_local = _goodput.finish_run()

    def _attach_observed_divergence(self, agg) -> None:
        """Close the planner's loop against the run's measurements:
        when a plan report exists and anatomy windows landed, attach
        the MEASURED per-step wall + exposed comm next to the winner's
        modeled ``comm_seconds`` (the ``observed`` field of
        plan/report.py) so model-vs-reality divergence is a number.
        No re-ranking happens here — the next plan still starts from
        the model; this only makes the model's error visible."""
        report = getattr(self, "_plan_report", None)
        if not report:
            return
        try:
            anatomy = agg.anatomy_stats()
        except Exception:
            return
        per_rank = (anatomy or {}).get("per_rank") or {}
        walls = [a.get("wall_s", 0.0) for a in per_rank.values()]
        exposed = [a.get("exposed_s", 0.0) for a in per_rank.values()]
        if not walls or max(walls) <= 0:
            return
        winner = next((e for e in report.get("candidates", ())
                       if e.get("status") == "winner"), None)
        modeled_comm = ((winner or {}).get("modeled") or {}) \
            .get("comm_seconds")
        # fleet step = the slowest rank's measured wall (SPMD lockstep)
        step_wall = max(walls)
        exposed_comm = max(exposed)
        observed = {
            "step_wall_s": round(step_wall, 6),
            "exposed_comm_s": round(exposed_comm, 6),
            "modeled_comm_s": (round(float(modeled_comm), 6)
                               if modeled_comm is not None else None),
            "ratio": (round(exposed_comm / float(modeled_comm), 3)
                      if modeled_comm else None),
        }
        report["observed"] = observed
        try:
            # live calibration (ROADMAP 5(a) leg): persist the measured
            # vs modeled comm ratio so the NEXT plan under
            # RLT_PLAN_CALIBRATE=live ranks with corrected bandwidths
            from ray_lightning_tpu.comm.calibrate import (
                save_live_calibration)
            save_live_calibration(step_wall, exposed_comm, modeled_comm)
        except Exception:
            pass
        try:
            # divergence past the band = the plan's model no longer
            # describes this run: a replan-recommended incident verdict
            # (telemetry/incident.py note_divergence)
            agg.incidents.note_divergence(observed)
        except Exception:
            pass

    # -- data -----------------------------------------------------------

    def _get_loader(self, name: str):
        src = None
        if self.datamodule is not None:
            src = getattr(self.datamodule, f"{name}_dataloader")()
        if src is None:
            src = getattr(self.lightning_module, f"{name}_dataloader")()
        if src is not None:
            src = self._elastic_rescale_loader(src, name)
        if src is not None and self.use_distributed_sampler \
                and self.world_size > 1 and hasattr(src, "shard"):
            src = src.shard(self.world_size, self.global_rank)
        return src

    def _build_redundancy(self):
        """Worker-side parity manager for this stage, or None when the
        topology cannot support it (single process, no peer-name map —
        a local in-process fit has no worker↔worker channel)."""
        from ray_lightning_tpu.elastic import redundancy as _red
        world = self.world_size
        if world < 2:
            _log.debug("elastic redundancy: single-process run, "
                       "parity disabled (snapshot replay only)")
            return None
        names = os.environ.get("RLT_PEER_NAMES", "").strip()
        peer_names = [n for n in names.split(",") if n]
        if len(peer_names) != world:
            _log.warning(
                "elastic redundancy: no rank→actor-name map for %d "
                "ranks (RLT_PEER_NAMES=%r); parity disabled, snapshot "
                "replay only", world, names)
            return None
        transport = _red.PeerParityTransport(
            peer_names, self.global_rank, _red.parity_timeout_s())
        _log.info(
            "elastic redundancy: parity over %d neighbor shard(s) "
            "every %d step(s) on %d ranks", self.elastic.redundancy,
            self.elastic.redundancy_every_n_steps, world)
        return _red.RedundancyManager(self, self.elastic,
                                      self.global_rank, world, transport)

    def _elastic_rescale_loader(self, src, name: str):
        """After a shrink-to-continue restart the fleet has fewer
        workers than the run started with; preserve the GLOBAL batch
        (world × per-worker batch — the quantity the optimization
        trajectory depends on) by scaling each survivor's loader batch
        by ``initial_workers / current_workers``.  This is the batch
        half of the resume-with-fewer-workers redistribution the
        checkpoint re-shard does for state (:meth:`_restore_sharded`).
        No-op outside an elastic restart."""
        es = getattr(self, "_elastic_state", None)
        if not es or not self.elastic.enabled \
                or not self.elastic.preserve_global_batch:
            return src
        initial = es.get("initial_workers") or 0
        current = self.world_size
        if initial <= 0 or initial == current:
            return src
        bs = getattr(src, "batch_size", None)
        if bs is None or not hasattr(src, "shard"):
            if not self._warned_rescale:
                self._warned_rescale = True
                _log.warning(
                    "elastic: cannot rescale %s loader %r (no "
                    "batch_size); global batch shrinks %d -> %d "
                    "workers' worth", name, type(src).__name__,
                    initial, current)
            return src
        total = int(bs) * initial
        if total % current:
            if not self._warned_rescale:
                self._warned_rescale = True
                _log.warning(
                    "elastic: global batch %d does not divide across "
                    "%d surviving workers; keeping per-worker batch "
                    "%d", total, current, bs)
            return src
        import copy
        clone = copy.copy(src)
        clone.batch_size = total // current
        _log.info(
            "elastic: %s loader batch %d -> %d on each of %d "
            "survivors (global batch %d preserved from the %d-worker "
            "topology)", name, bs, clone.batch_size, current, total,
            initial)
        return clone

    def _build_loaders(self, stage: str) -> dict:
        if stage == "fit":
            return {"train": self._get_loader("train"),
                    "val": self._get_loader("val")}
        if stage == "validate":
            return {"val": self._get_loader("val")}
        if stage == "test":
            return {"test": self._get_loader("test")}
        return {"predict": self._get_loader("predict")}

    # -- auto-parallelism (plan/) ----------------------------------------

    def _resolve_auto_strategy(self, module, example_batch, batch_hint,
                               auto, stage: str):
        """Run the planner and apply its winning plan: the concrete
        strategy is returned; the comm policy, donation decision and
        microbatch land on the trainer directly (they are trainer
        concerns the strategy object cannot carry).  The full
        :class:`PlanReport` dict lands on ``_plan_report`` and the
        ``rlt_plan_*`` gauges.  Planning scores the TRAIN step, so
        eval/predict-only stages fall back to DDP with a log line
        instead of paying candidate compiles they would never use."""
        from ray_lightning_tpu.comm import CommPolicy
        if stage != "fit":
            _log.info("strategy='auto' plans the train step; %s stage "
                      "falls back to ddp", stage)
            return resolve_strategy("ddp")
        from ray_lightning_tpu.plan import Planner
        cfg = auto.plan if getattr(auto, "plan", None) is not None \
            else self.plan
        planner = Planner(cfg)
        # a user-set accumulate_grad_batches pins the microbatch
        # dimension; the default (1) lets the config's options explore
        mb = (self.accumulate_grad_batches,) \
            if self.accumulate_grad_batches > 1 else None
        with span("plan"):
            report = planner.plan(
                module, self._host_cast(example_batch),
                devices=self.plugin.local_devices(),
                batch_hint=batch_hint,
                base_comm_policy=self.comm_policy,
                microbatch_options=mb,
                tx_factory=lambda gs: self._configure_tx(module, gs))
        self._plan_report = d = report.to_dict()
        winner = report.winner_candidate
        if winner.comm:
            self.comm_policy = report.winner_policy
        else:
            self.comm_policy = CommPolicy()
        self._plan_donate = bool(winner.donate)
        self.accumulate_grad_batches = int(winner.microbatch)
        remat_pick = getattr(winner, "remat", "")
        if remat_pick:
            # apply the winning remat policy to the REAL module (the
            # planner verified candidates on copy.copy clones, so the
            # user's module still carries its default); resets the
            # materialized model so _build_compiled traces the pick
            spec = module.configure_remat()
            if spec is not None and remat_pick != spec.default:
                spec.apply(remat_pick)
                module.setup_model()   # apply() dropped the stale wrap
                _log.info("plan: remat policy %r applied (module "
                          "default was %r)", remat_pick, spec.default)
        _log.info("plan: %s", report.summary())
        try:
            from ray_lightning_tpu import telemetry as _telemetry
            agg = _telemetry.get_active()
            if agg is not None:
                # incident-plane correlation event: a (re-)plan is a
                # step-time discontinuity with a name
                agg.note_event("plan", winner=d.get("winner"),
                               seconds=round(d.get("plan_seconds", 0.0),
                                             6))
        except Exception:
            pass
        reg = _metrics.get_registry()
        if reg is not None:
            reg.gauge("rlt_plan_candidates_total").set(d["enumerated"])
            reg.gauge("rlt_plan_pruned_total").set(d["pruned"])
            reg.gauge("rlt_plan_rejected_total").set(d["rejected"])
            reg.gauge("rlt_plan_compiled_total").set(d["compiled"])
            reg.gauge("rlt_plan_seconds").set(round(d["plan_seconds"], 6))
        return winner.build_strategy()

    # -- compilation -----------------------------------------------------

    def _configure_tx(self, module, grad_sync=None):
        tx = module.configure_optimizers()
        if isinstance(tx, dict):
            tx = tx["optimizer"]
        if self.gradient_clip_val:
            tx = optax.chain(
                optax.clip_by_global_norm(self.gradient_clip_val), tx)
        if grad_sync is not None:
            # outermost wrap: the optimizer state becomes a CommState
            # carrying the error-feedback residual (comm/collectives.py)
            tx = grad_sync.wrap_tx(tx)
        return tx

    # HBM per chip for device kinds whose runtime reports no
    # ``bytes_limit`` in memory_stats (libtpu 0.0.34 on a v5e reports
    # it; the table is the stand-in elsewhere); donation falls back to
    # ON for unknown kinds, so a missing entry is safe, not wrong
    _HBM_BY_KIND = {
        "TPU v4": 32 << 30,
        "TPU v5 lite": 16 << 30,
        "TPU v5e": 16 << 30,
        "TPU v5": 95 << 30,      # v5p
        "TPU v5p": 95 << 30,
        "TPU v6 lite": 32 << 30,
        "TPU v6e": 32 << 30,
    }

    def _device_memory_budget(self) -> "int | None":
        # one of THIS process's devices: memory_stats of another
        # process's device raises; None on the CPU backend
        dev = next(d for d in self._mesh.devices.flat
                   if d.process_index == jax.process_index())
        stats = dev.memory_stats()
        if stats and "bytes_limit" in stats:
            return int(stats["bytes_limit"])
        if getattr(dev, "platform", None) == "tpu":
            return self._HBM_BY_KIND.get(getattr(dev, "device_kind", ""))
        return None

    def _should_donate(self, abstract, shardings) -> bool:
        """Donate the TrainState into the step only when memory needs it.

        Donation (in-place state update) halves peak state residency —
        what lets the large configs fit their budgets — but it
        CONSTRAINS XLA's scheduling: the round-5 A/B measured the
        identical gpt2-small program at 51.08 ms/step donated vs
        49.35 ms un-donated on v5e, and BERT at 91.59 vs 90.24.  The
        win does NOT extend up the size axis: gpt2-moe-8e (state
        ~3.6 GB, ~22% of v5e HBM) measured 81.85 un-donated vs 80.08
        donated — so auto skips donation only for SMALL states (the
        measured win region: state ≤ ~10% of the budget, the
        ``_donation_cutoff`` factors put the v5e cut at ~1.9 GB,
        between BERT's win and MoE's loss), and donates whenever the
        budget is unknown (virtual CPU meshes, profiler-less backends).

        NOTE the relationship to the memory-fit audits
        (tests/test_memory_fit.py): the donated-program audits compile
        with ``donate_argnums=0`` EXPLICITLY and are valid whatever
        this heuristic picks; the SKIP region is audited separately —
        the un-donated 1.3B ZeRO-1 program (the config this heuristic
        actually skips on v4-64, state ~2.85 GB/device at data=64) is
        budget-checked against v4's 32 GB with its extra un-aliased
        state copy accounted
        (test_undonated_zero1_budget_in_v4_skip_region and the direct
        memory_analysis audit test_undonated_zero1_compile_audit, both
        tier-1).  The per-config donation decisions are
        additionally pinned in
        tests/test_trainer_local.py::test_donation_decision_table, so a
        change to either side must show up against that table, not
        silently diverge.  ``RLT_DONATE=1``/``0`` forces either way.
        """
        env = os.environ.get("RLT_DONATE", "").strip()
        if env in ("0", "1"):
            return env == "1"
        if env:
            warnings.warn(
                f"RLT_DONATE={env!r} is neither '0' nor '1'; using the "
                "auto heuristic")
        if self._plan_donate is not None:
            # strategy="auto": the planner already decided donation per
            # candidate (same cutoff logic, budget-checked and — for the
            # top-k — verified against the compiled memory_analysis);
            # RLT_DONATE above still force-overrides either way
            return self._plan_donate
        limit = self._device_memory_budget()
        if limit is None:
            return True
        if self.cache_train_dataset:
            # the device-resident dataset cache shares the budget; debit
            # a conservative (un-sharded) estimate, and donate outright
            # when the cache size cannot be bounded up front
            hint = self._cache_bytes_hint
            if hint is None:
                return True
            limit -= hint
        state_bytes = 0
        leaves = jax.tree_util.tree_leaves(abstract)
        shs = jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: hasattr(x, "spec"))
        if len(shs) != len(leaves):
            return True     # unrecognized shardings tree: stay safe
        for aval, sh in zip(leaves, shs):
            shape = sh.shard_shape(aval.shape) \
                if hasattr(sh, "shard_shape") else aval.shape
            state_bytes += int(np.prod(shape, dtype=np.int64)) \
                * aval.dtype.itemsize
        return self._donation_cutoff(state_bytes, limit)

    @staticmethod
    def _donation_cutoff(state_bytes: int, limit: int) -> bool:
        """The auto decision given per-device state bytes and the HBM
        budget: un-donated peak carries old+new state (2x) on top of the
        activations/grads the donated program also needs; the 0.3
        ceiling both keeps the skip far from any OOM edge and encodes
        the MEASURED win boundary (small states win, ~22%-of-HBM states
        lose — see the _should_donate docstring).  Pinned per config in
        tests/test_trainer_local.py::test_donation_decision_table."""
        return not (2.5 * state_bytes < 0.3 * limit)

    def _build_compiled(self, module, example_batch, strategy):
        # comm plane: resolve the policy against this strategy/mesh —
        # None (the overwhelmingly common case) keeps every jit below
        # identical to a policy-less build
        self._grad_sync = strategy.grad_transform(self._mesh,
                                                  self.comm_policy)
        if self._grad_sync is not None:
            _log.info("comm plane active: compressed gradient "
                      "collectives %s (error_feedback=%s, "
                      "param_gather=%s, bucket_bytes=%d)",
                      self._grad_sync.describe(),
                      self._grad_sync.error_feedback,
                      self.comm_policy.param_gather,
                      self.comm_policy.bucket_bytes)
        self._tx = self._configure_tx(module, self._grad_sync)
        self._init_fn = build_init_fn(module, self._tx)
        rng = jax.random.PRNGKey(
            int(os.environ.get("RLT_GLOBAL_SEED", "0")) if self.seed is None
            else self.seed)
        self._init_rng = rng
        abstract = jax.eval_shape(self._init_fn, rng, example_batch)
        self._abstract_state = abstract
        shardings = strategy.state_shardings(self._mesh, abstract)
        if self._grad_sync is not None:
            # the error-feedback residual's [world, ...] stacked dim
            # shards on the compressed axes, not per the strategy's
            # generic opt_spec walk
            shardings = shardings.replace(
                opt_state=self._grad_sync.fix_opt_shardings(
                    shardings.opt_state, abstract.opt_state))
        self._state_shardings = shardings
        # Batch placement rides the jit call (in_shardings) instead of an
        # explicit per-step device_put: a numpy batch is transferred and
        # sharded as part of async dispatch.  (Per-array device_put with a
        # NamedSharding is a blocking transfer per leaf, so on
        # single-device meshes the batch stays unconstrained and takes
        # the default transfer path; how much that saves is to be
        # re-measured.)
        donate = self._should_donate(abstract, shardings)
        dkw = {"donate_argnums": 0} if donate else {}
        jit_kwargs = dict(out_shardings=(shardings, None), **dkw)
        batch_sh = None
        if self._mesh.devices.size > 1:
            batch_sh = strategy.batch_shardings(self._mesh, example_batch)
            jit_kwargs["in_shardings"] = (shardings, batch_sh)
        step_fn = build_train_step(module, self._tx,
                                   self.accumulate_grad_batches,
                                   grad_sync=self._grad_sync)
        #: un-jitted step for the goodput plane's default FLOP pricing
        #: (tracing only — never dispatched)
        self._pricing_step_fn = step_fn
        self._train_step = jax.jit(step_fn, **jit_kwargs)
        self._multi_train_step = None
        self._stacked_batch_shardings = None
        self._cache_source = None
        self._cache_disabled = False
        self._cached_multi_step = None
        self._cached_single_step = None
        want_stacked = self.steps_per_execution > 1 or self.cache_train_dataset
        if want_stacked and batch_sh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._stacked_batch_shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(
                    self._mesh,
                    PartitionSpec(*((None,) + tuple(s.spec)))),
                batch_sh)
        if self.steps_per_execution > 1:
            def multi_step(state, batches):
                # k steps as one XLA program; metrics stack to [k, ...]
                return jax.lax.scan(step_fn, state, batches)

            mkw = dict(out_shardings=(shardings, None), **dkw)
            if self._stacked_batch_shardings is not None:
                mkw["in_shardings"] = (shardings,
                                       self._stacked_batch_shardings)
            self._multi_train_step = jax.jit(multi_step, **mkw)
        if self.cache_train_dataset:
            # multi-process included: the cache is a global array (one
            # shard per host's devices) and these programs are ordinary
            # SPMD — every process dispatches them in lockstep exactly
            # like the streamed train step (core/loop_engine.py
            # CachedSource.build for the global-assembly details)
            def gather(dataset, i):
                return jax.tree_util.tree_map(
                    lambda d: jax.lax.dynamic_index_in_dim(
                        d, i, 0, keepdims=False), dataset)

            def cached_multi(state, dataset, idxs):
                return jax.lax.scan(
                    lambda s, i: step_fn(s, gather(dataset, i)),
                    state, idxs)

            def cached_single(state, dataset, i):
                return step_fn(state, gather(dataset, i))

            ckw = dict(out_shardings=(shardings, None), **dkw)
            if self._stacked_batch_shardings is not None:
                ckw["in_shardings"] = (
                    shardings, self._stacked_batch_shardings, None)
            self._cached_multi_step = jax.jit(cached_multi, **ckw)
            self._cached_single_step = jax.jit(cached_single, **ckw)
        self._eval_steps = {
            s: _ShardedStepCache(build_eval_step(module, s), self, strategy)
            for s in ("validate", "test")}
        self._predict_step = _ShardedStepCache(build_predict_step(module),
                                               self, strategy)
        self._submit_precompiles(example_batch)

    def _price_flops_per_step(self, module) -> "Optional[float]":
        """FLOPs one optimizer step executes, for measured MFU: the
        module's ``flops_per_step()`` hook when it answers, else the
        default pricing — count every ``dot_general`` in the train-step
        jaxpr (forward + backward + update) over the abstract state and
        global abstract batch, the same dot-counting machinery the
        remat planner prices policies with (core/remat.py).  None when
        neither source can answer; MFU is then simply absent — never
        fabricated."""
        try:
            flops = module.flops_per_step()
        except Exception:
            _log.debug("goodput: flops_per_step() hook raised; falling "
                       "back to jaxpr pricing", exc_info=True)
            flops = None
        if flops is not None:
            return float(flops)
        step_fn = getattr(self, "_pricing_step_fn", None)
        abstract_batch = getattr(self, "_abstract_batch", None)
        if step_fn is None or abstract_batch is None:
            return None
        try:
            from ray_lightning_tpu.core.remat import step_dot_flops
            return float(step_dot_flops(step_fn, self._abstract_state,
                                        abstract_batch))
        except Exception:
            _log.debug("goodput: default train-step FLOP pricing "
                       "failed; MFU unavailable", exc_info=True)
            return None

    def _submit_precompiles(self, example_batch) -> None:
        """AOT-compile the step programs in the background (compile/):
        their input avals are fully known here — abstract state from
        ``eval_shape``, abstract batch from the peeked example — so XLA
        compilation starts NOW and hides under state init, the
        rendezvous, the sanity check and the dataset upload instead of
        serializing at first dispatch.  The compiled artifact reaches
        dispatch through the persistent cache (the background compile
        writes the entry; the first dispatch's compile collapses to a
        disk retrieval), which is why the precompiler only engages when
        the cache is active (compile/aot.py).  The engine's
        ``barrier()`` before the first train dispatch keeps a lazy
        compile from racing a background one; everything here is
        best-effort (a mispredicted aval logs and falls back to lazy)."""
        self._precompiler = AotPrecompiler.resolve()
        ab = global_batch_abstract(self._host_cast(example_batch),
                                   jax.process_count())
        self._abstract_batch = ab
        if self._stage != "fit":
            # eval/predict stages never dispatch the train programs;
            # compiling them in the background would be pure waste (the
            # lazy _ShardedStepCache path still benefits from the
            # persistent cache across runs)
            return
        self._precompiler.submit("train_step", self._train_step,
                                 (self._abstract_state, ab))
        if self._multi_train_step is not None:
            self._precompiler.submit(
                "multi_step", self._multi_train_step,
                (self._abstract_state,
                 stack_abstract(ab, self.steps_per_execution)))
        # cached-dataset programs submit from CachedSource.build once the
        # repacked shape is known (core/loop_engine.py).  The validate
        # step precompiles only when no sanity check will compile it on
        # the main thread first anyway — and against the TRAIN batch
        # structure, the common case (same dataset shapes); a divergent
        # val structure just wastes one background compile.
        if self.num_sanity_val_steps == 0:
            try:
                ev = self._eval_steps["validate"].jitted_for(ab)
                self._precompiler.submit("eval_step", ev,
                                         (self._abstract_state, ab))
            except Exception:       # noqa: BLE001 - overlap only
                _log.debug("eval-step precompile skipped", exc_info=True)

    def _put_batch(self, batch, strategy, stacked: bool = False):
        """Host numpy batch → step input.  Multi-process: each process
        contributes its local shard (``make_array_from_process_local_data``)
        to a global array — the TPU-native equivalent of DistributedSampler
        feeding per-rank DDP replicas.  Single-process: numpy passes
        straight into the jitted step, whose ``in_shardings`` shard it
        during dispatch.

        ``Trainer(precision="bf16")`` casts floating batch leaves to
        bfloat16 here (halving host→device transfer); parameter/compute
        dtypes belong to the model config (e.g. ``GPTConfig.dtype``) —
        on TPU there is no loss-scaling AMP machinery to port, bf16 runs
        natively on the MXU (reference precision flow: PL AMP +
        ShardedGradScaler, ray_ddp_sharded.py:26-29).
        """
        batch = self._host_cast(batch)
        if jax.process_count() > 1:
            shardings = (self._stacked_batch_shardings if stacked
                         else strategy.batch_shardings(self._mesh, batch))
            return jax.tree_util.tree_map(
                lambda x, s: jax.make_array_from_process_local_data(s, x),
                batch, shardings)
        return batch

    def _host_cast(self, batch):
        """numpy-ify a host batch, casting floats to bf16 under
        ``precision="bf16"`` (halves host→device transfer)."""
        cast_bf16 = self.precision in _BF16_PRECISIONS

        def to_host(x):
            a = np.asarray(x)
            if cast_bf16 and np.issubdtype(a.dtype, np.floating):
                a = a.astype(jnp.bfloat16)
            return a

        return jax.tree_util.tree_map(to_host, batch)

    def _batch_ok(self, batch, strategy) -> bool:
        """Leading dim must divide over data shards (XLA static shapes)."""
        dp = strategy.data_parallel_size(self._mesh) // max(
            1, jax.process_count())
        leaves = jax.tree_util.tree_leaves(batch)
        sizes = {l.shape[0] for l in leaves if getattr(l, "ndim", 0) > 0}
        ok = all(s % max(1, dp) == 0 for s in sizes)
        if not ok and not self._warned_skip:
            _log.warning(
                "Skipping batch whose size %s does not divide across %d "
                "data shards; use drop_last or a divisible batch size.",
                sizes, dp)
            self._warned_skip = True
        return ok

    # -- state init / restore -------------------------------------------

    def _init_state(self, module, example_batch, strategy, ckpt_path):
        gbatch = self._put_batch(example_batch, strategy)
        init_jit = jax.jit(self._init_fn,
                           out_shardings=self._state_shardings)
        self.state = init_jit(self._init_rng, gbatch)

        trained = getattr(module, "_trained_variables", None)
        recovery = getattr(self, "_elastic_recovery", None)
        if recovery:
            # zero-replay path (elastic/redundancy.py): the driver
            # reconstructed the dead rank's shard from parity escrows;
            # restore the in-memory package at its escrowed step — the
            # snapshot directory (and ckpt_path) is deliberately NOT
            # read, which the rlt_snapshot_restore_total counter proves
            from ray_lightning_tpu.elastic.redundancy import (
                apply_recovery)
            apply_recovery(self, recovery, module)
            self._elastic_recovery = None   # one-shot, worker copy
        elif ckpt_path:
            self._restore_checkpoint(ckpt_path, module)
        elif trained is not None:
            # Reuse weights from a previous fit with this module (the
            # reference keeps trained weights on the model object after
            # post_dispatch loads them, ray_ddp.py:375-377).
            restored = serialization.from_state_dict(
                {"params": fetch_tree(self.state.params),
                 "model_state": fetch_tree(self.state.model_state)},
                trained)
            self.state = self.state.replace(
                params=jax.device_put(restored["params"],
                                      self._state_shardings.params),
                model_state=jax.device_put(
                    restored["model_state"],
                    self._state_shardings.model_state))

    # -- fit loop --------------------------------------------------------

    def _fit_loop(self, module, loaders):
        train_loader, val_loader = loaders["train"], loaders.get("val")
        strategy = self.plugin.strategy
        self.num_val_batches = self._loader_len(val_loader,
                                                self.limit_val_batches)

        with span("hooks", hook="on_fit_start"):
            for cb in self.callbacks:
                cb.on_fit_start(self, module)
            module.on_fit_start()

        if val_loader is not None and self.num_sanity_val_steps > 0 \
                and self.num_val_batches > 0:
            with span("sanity_val"):
                self._sanity_check(module, val_loader)

        with span("hooks", hook="on_train_start"):
            for cb in self.callbacks:
                cb.on_train_start(self, module)
            module.on_train_start()

        start_epoch = self.current_epoch
        epoch = start_epoch
        ran_epoch = False
        try:
            for epoch in range(start_epoch, self.max_epochs or 10**9):
                if self.should_stop or self._max_steps_reached():
                    break  # e.g. resumed from a checkpoint at max_steps
                ran_epoch = True
                self.current_epoch = epoch
                if hasattr(train_loader, "set_epoch"):
                    train_loader.set_epoch(epoch)
                self._epoch_metric_acc = {}
                for cb in self.callbacks:
                    cb.on_train_epoch_start(self, module)
                module.on_train_epoch_start()

                self._train_epoch(module, train_loader, val_loader, strategy)

                self._flush_epoch_metrics()
                module.on_train_epoch_end()
                for cb in self.callbacks:
                    cb.on_train_epoch_end(self, module)

                if val_loader is not None and self.num_val_batches > 0 \
                        and (epoch + 1) % self.check_val_every_n_epoch == 0:
                    self._eval_loop(module, "validate", val_loader,
                                    self.limit_val_batches)
                if self.should_stop or self._max_steps_reached():
                    break
        finally:
            if ran_epoch:
                self.current_epoch = min(
                    epoch + 1, self.max_epochs or epoch + 1) \
                    if not self.should_stop else epoch
            # else: zero epochs ran (resumed at max_steps) — the restored
            # epoch counter must not drift upward per save/resume cycle
            try:
                module.on_train_end()
                for cb in self.callbacks:
                    cb.on_train_end(self, module)
                module.on_fit_end()
                for cb in self.callbacks:
                    cb.on_fit_end(self, module)
            finally:
                # in-flight async sharded saves must become durable (and
                # their orbax worker threads released) unconditionally —
                # even when the fit is unwinding on an exception or a
                # user hook raises during the unwind; _finalize_fit only
                # runs on the happy path
                self._close_sharded_checkpointers()
        return self._finalize_fit(module)

    def _max_steps_reached(self) -> bool:
        return self.max_steps is not None and self.max_steps >= 0 \
            and self.global_step >= self.max_steps

    def _allowed_chunk(self) -> int:
        """How many steps the next chunk may run without crossing a
        host-decision boundary (max_steps, val_check_interval).  Shared
        by the chunked and cached epoch loops."""
        allowed = self.steps_per_execution
        if self.max_steps is not None and self.max_steps >= 0:
            allowed = min(allowed, self.max_steps - self.global_step)
        if self.val_check_interval:
            allowed = min(
                allowed,
                self.val_check_interval
                - self.global_step % self.val_check_interval)
        return allowed

    def _publish_if_crossed(self, before: int, last_metrics) -> None:
        """Publish when the chunk crossed a log_every_n_steps boundary
        (``last_metrics`` = the chunk's final-step scalars)."""
        if before // self.log_every_n_steps \
                != self.global_step // self.log_every_n_steps:
            self._publish_metrics(last_metrics)

    def _train_source(self, train_loader, strategy):
        """Pick this epoch's batch source (core/loop_engine.py): the
        device-resident cache when enabled and buildable, the streamed
        loader otherwise.  The cache is built once per fit and refreshed
        per epoch from the loader's own index order."""
        from ray_lightning_tpu.core.loop_engine import (
            CachedSource, StreamSource)
        if self._cached_single_step is not None \
                and not self._cache_disabled:
            if self._cache_source is None \
                    and CachedSource.usable(self, train_loader):
                src = CachedSource(self, train_loader, strategy)
                if src.build():
                    self._cache_source = src
            if self._cache_source is None:
                # unusable with THIS loader: remember, so the build is
                # not re-attempted (and the loader not re-read) per epoch
                self._cache_disabled = True
            else:
                return self._cache_source.new_epoch()
        return StreamSource(self, train_loader, strategy)

    def _train_epoch(self, module, train_loader, val_loader, strategy):
        """THE training loop — one engine for every dispatch shape.

        The source decides how batches reach the device (streamed host
        batches, k-step stacked chunks, device-resident gathers); this
        loop owns the semantics exactly once: stop conditions, chunk
        boundaries (``_allowed_chunk`` keeps a chunk from crossing
        max_steps / val_check_interval), ``limit_train_batches``
        position counting (inside the sources' ``take``), callback
        cadence (per batch when dispatching singly, per chunk when k
        ride one dispatch) and the val-interval check after every
        dispatch.  Replaces the round-2 trio of divergent loops.
        """
        if self.time_to_first_step is None \
                and self._setup_spans is not None:
            # from here to the first step's result: the wait for the
            # AOT thread, the first batches, the dispatch (where a
            # cached program loads, or a missed one compiles) and the
            # first run.  _note_first_step closes it, and the root.
            self._setup_spans.enter_context(span("first_step"))
        source = self._train_source(train_loader, strategy)
        if self._precompiler is not None:
            # close the overlap window: everything submitted (train /
            # chunk / cached-step programs) must land in the executable
            # caches before the first dispatch, or a lazy compile on
            # this thread would race the background one for the same
            # program.  Instant from epoch 2 on (nothing pending).
            with span("aot_wait"):
                self._precompiler.barrier()
        k = self.steps_per_execution
        while not (self.should_stop or self._max_steps_reached()):
            allowed = self._allowed_chunk()
            if allowed <= 0:
                break
            pending = source.take(allowed)
            if not pending:
                if source.exhausted:
                    break
                continue
            if len(pending) == k and k > 1 and source.chunkable(pending):
                self._engine_chunk(module, source, pending)
                self._maybe_interval_val(module, val_loader)
            else:
                for item in pending:
                    self._engine_one(module, source, item)
                    self._maybe_interval_val(module, val_loader)
                    if self.should_stop or self._max_steps_reached():
                        break

    def _maybe_interval_val(self, module, val_loader) -> None:
        if self.val_check_interval \
                and self.global_step % self.val_check_interval == 0 \
                and val_loader is not None and self.num_val_batches > 0:
            self._eval_loop(module, "validate", val_loader,
                            self.limit_val_batches)

    def _batch_hook_plan(self) -> tuple:
        """(invoke, materialize): does any callback override a per-batch
        hook, and does any overriding one actually read ``batch``
        (``Callback.needs_batch``)?  When nothing overrides, the engine
        skips the hook calls; when overriders all declare
        ``needs_batch = False`` at or below the class that defines the
        overriding hook, they are invoked with ``batch=None`` —
        either way cached (especially shuffled) epochs never pay host
        collation for arguments nobody reads (the whole point of the
        cached path is removing per-step host work).  Detection goes
        through ``__func__`` so instance-assigned hooks
        (``cb.on_train_batch_end = fn``) count as overrides too.
        Recomputed per engine call (a few attribute reads on a short
        list) so callbacks added or hook-assigned MID-epoch are honored
        exactly as they were before the skip existed.
        """
        def overrides(cb, name):
            fn = getattr(cb, name, None)
            return getattr(fn, "__func__", fn) is not getattr(Callback, name)

        def hook_needs_batch(cb, name):
            # ``needs_batch`` counts only when declared at or below (as
            # derived as) the definition of the overriding hook.  A user
            # subclass of a needs_batch=False callback that overrides a
            # batch hook without restating the flag gets the
            # conservative default (True) — its new hook body may well
            # read the batch the base class promised to ignore.
            # getattr, not vars(): __slots__ callbacks have no __dict__
            inst = getattr(cb, "__dict__", {})
            if "needs_batch" in inst:
                return inst["needs_batch"]         # instance: most derived
            if name in inst:                       # instance-assigned hook
                return True                        # outranks any class flag
            mro = type(cb).__mro__
            hook_at = next(
                (i for i, k in enumerate(mro) if name in vars(k)), len(mro))
            for k in mro[:hook_at + 1]:
                if "needs_batch" in vars(k):
                    return vars(k)["needs_batch"]
            return True

        invoke = materialize = False
        for cb in self.callbacks:
            for name in ("on_train_batch_start", "on_train_batch_end"):
                if overrides(cb, name):
                    invoke = True
                    if hook_needs_batch(cb, name):
                        materialize = True
        return invoke, materialize

    def _engine_one(self, module, source, item) -> None:
        invoke, want_batch = self._batch_hook_plan()
        clock, step = self._loop_clock, self.global_step
        t0 = time.monotonic()
        if invoke:
            # user code between two dispatches
            with span("callbacks", hook="on_train_batch_start"):
                batch = item.batch() if want_batch else None
                for cb in self.callbacks:
                    cb.on_train_batch_start(self, module, batch,
                                            item.batch_idx)
            t0 = clock.add("callbacks", t0, step=step)
        with span("step", step=step):
            metrics = source.run_one(self, item)
        step_s = self._note_dispatch(metrics, t0, 1)
        _metrics.on_step(step_s, step=self.global_step)
        if self._redundancy is not None:
            # parity BEFORE the snapshot: a rank that dies inside the
            # save (snapkill) has already escrowed this step
            self._redundancy.maybe_tick()
        if self._snapshotter is not None:
            self._snapshotter.maybe_snapshot()
        self._accumulate_metrics(metrics)
        if self.global_step % self.log_every_n_steps == 0:
            self._publish_metrics(metrics)
        if invoke:
            t0 = time.monotonic()
            with span("callbacks", hook="on_train_batch_end"):
                for cb in self.callbacks:
                    cb.on_train_batch_end(self, module, metrics, batch,
                                          item.batch_idx)
            clock.add("callbacks", t0, step=step)

    def _engine_chunk(self, module, source, items) -> None:
        """k steps in ONE dispatch; batch-granular callbacks coarsen to
        once per chunk (starts for every batch, one end with the chunk's
        stacked metrics and its last batch)."""
        invoke, want_batch = self._batch_hook_plan()
        clock, before = self._loop_clock, self.global_step
        t0 = time.monotonic()
        if invoke:
            with span("callbacks", hook="on_train_batch_start"):
                for it in items:
                    for cb in self.callbacks:
                        cb.on_train_batch_start(
                            self, module,
                            it.batch() if want_batch else None,
                            it.batch_idx)
            t0 = clock.add("callbacks", t0, step=before)
        # k steps ride one span; the aggregator normalizes per-step time
        # by the "k" attribute when computing percentiles
        with span("step", step=before, k=len(items)):
            metrics = source.run_chunk(self, items)
        step_s = self._note_dispatch(metrics, t0, len(items))
        _metrics.on_step(step_s, k=len(items), step=self.global_step)
        if self._redundancy is not None:
            # chunked dispatch coarsens the parity cadence to chunk
            # boundaries, exactly like the snapshot cadence below
            self._redundancy.maybe_tick()
        if self._snapshotter is not None:
            # chunked dispatch coarsens the snapshot cadence to chunk
            # boundaries, like the batch-granular callbacks do
            self._snapshotter.maybe_snapshot()
        self._accumulate_metrics(metrics)
        self._publish_if_crossed(before, jax.tree_util.tree_map(
            lambda a: a[-1], metrics))
        if invoke:
            t0 = time.monotonic()
            with span("callbacks", hook="on_train_batch_end"):
                for cb in self.callbacks:
                    cb.on_train_batch_end(
                        self, module, metrics,
                        items[-1].batch() if want_batch else None,
                        items[-1].batch_idx)
            clock.add("callbacks", t0, step=before)

    def _note_dispatch(self, metrics, t0: float, k: int) -> float:
        """The ``step`` span just closed on a dispatch of ``k`` steps
        that began at ``t0``: count the steps, charge the loop's clock
        and the goodput ledger, and return the seconds.  One pair of
        clock reads serves all three.  The first dispatch of a stage is
        timed to its RESULT (``_note_first_step`` waits for it, and the
        loop's clock starts there): the ledger books it as compile."""
        clock, step = self._loop_clock, self.global_step
        t1 = time.monotonic()
        self.global_step += k
        first = self._note_first_step(metrics)
        if first:
            t1 = clock.t_start
        elif clock.t_start is not None:
            clock.add("dispatch", t0, t1, step=step)
            clock.steps += k
        if self._goodput_ledger is not None:
            self._goodput_ledger.note_step(t1 - t0, k=k, first=first)
        return t1 - t0

    def _note_first_step(self, metrics) -> bool:
        """Record time-to-first-step once per stage: the startup cost
        (compile + init + rendezvous + upload) the compile plane exists
        to shrink.  Blocks on the first step's metrics so the number
        covers execution, not just async dispatch — one sync, once.
        True for that first step (the goodput ledger books it as
        compile time, not as a step)."""
        if self.time_to_first_step is not None or self._stage_t0 is None:
            return False
        with span("device_wait", what="first_step"):
            jax.block_until_ready(metrics)
        # compile and the first step lie before the loop's clock
        self.time_to_first_step = \
            self._loop_clock.start() - self._stage_t0
        compile_cache.note_first_step(self.time_to_first_step)
        self._close_setup_spans()
        return True

    # -- metrics ---------------------------------------------------------

    def _accumulate_metrics(self, metrics: dict) -> None:
        for k, v in metrics.items():
            self._epoch_metric_acc.setdefault(k, []).append(v)

    def _device_wait(self, what: str, tree):
        """Fetch ``tree``: the loop blocks here until the steps that
        made it are done (the host runs ahead of the device), so the
        seconds are step time: the ledger's mean step wall would else
        miss the steps still in flight at the end of a fit."""
        t0 = time.monotonic()
        with span("device_wait", what=what):
            out = jax.device_get(tree)
        t1 = self._loop_clock.add("device_wait", t0, step=self.global_step)
        if self._goodput_ledger is not None:
            self._goodput_ledger.add("step", t1 - t0)
        return out

    def _publish_metrics(self, metrics: dict) -> None:
        vals = self._device_wait("publish_metrics", list(metrics.values()))
        for k, v in zip(metrics, vals):
            self.callback_metrics[k] = self.logged_metrics[k] = float(v)
        if self.logger is not None and self.is_global_zero and metrics:
            self.logger.log_metrics(
                {k: self.logged_metrics[k] for k in metrics},
                self.global_step)

    def _flush_epoch_metrics(self) -> None:
        flushed = {}
        fetched = self._device_wait("epoch_metrics", self._epoch_metric_acc)
        for k, vals in fetched.items():
            # entries are scalars (per-step) or [k] vectors (per-chunk,
            # steps_per_execution>1); flatten to one per-step series
            arr = np.concatenate([
                np.atleast_1d(np.asarray(v, dtype=np.float64))
                for v in vals])
            self.callback_metrics[k] = flushed[k] = float(arr.mean())
            self.logged_metrics[k] = float(arr[-1])
        self._epoch_metric_acc = {}
        if self.logger is not None and self.is_global_zero and flushed:
            # _epoch suffix: step-level rows already carry the bare names
            # at this same step; suffixing disambiguates mean-over-epoch
            # from last-step values (PL's convention)
            self.logger.log_metrics(
                {f"{k}_epoch": v for k, v in flushed.items()},
                self.global_step)

    def log_metric(self, name: str, value) -> None:
        """Record a host-side scalar into ``callback_metrics`` (public
        entry point for callbacks; with distributed plugins rank-0's
        metrics ride the normal result relay back to the driver)."""
        self.callback_metrics[name] = float(np.asarray(value))

    # internal alias kept for module-side logging paths
    _log_host_metric = log_metric

    # -- evaluation ------------------------------------------------------

    def _loader_len(self, loader, limit) -> int:
        if loader is None:
            return 0
        if limit == 0:
            return 0
        try:
            n = len(loader)
        except TypeError:
            n = 10**9
        return min(n, limit) if limit is not None else n

    def _sanity_check(self, module, val_loader):
        self.sanity_checking = True
        for cb in self.callbacks:
            cb.on_sanity_check_start(self, module)
        self._eval_loop(module, "validate", val_loader,
                        self.num_sanity_val_steps)
        for cb in self.callbacks:
            cb.on_sanity_check_end(self, module)
        self.sanity_checking = False

    def _eval_loop(self, module, stage: str, loader, limit) -> dict:
        strategy = self.plugin.strategy
        step = self._eval_steps[stage]
        if stage == "validate":
            for cb in self.callbacks:
                cb.on_validation_start(self, module)
            for cb in self.callbacks:
                cb.on_validation_epoch_start(self, module)
            module.on_validation_epoch_start()
        else:
            for cb in self.callbacks:
                cb.on_test_start(self, module)

        acc: list[tuple[dict, int]] = []
        with span("eval", stage=stage):
            for batch_idx, batch in enumerate(loader):
                if limit is not None and batch_idx >= limit:
                    break
                if not self._batch_ok(batch, strategy):
                    continue
                gbatch = self._put_batch(batch, strategy)
                logged = step(self.state, gbatch)
                leaves = jax.tree_util.tree_leaves(batch)
                bsz = leaves[0].shape[0] if leaves and getattr(
                    leaves[0], "ndim", 0) > 0 else 1
                acc.append((logged, bsz))
                if stage == "validate":
                    for cb in self.callbacks:
                        cb.on_validation_batch_end(self, module, logged,
                                                   batch, batch_idx)

        means: dict[str, float] = {}
        if acc:
            keys = acc[0][0].keys()
            total = sum(b for _, b in acc)
            for k in keys:
                vals = np.asarray(
                    jax.device_get([d[k] for d, _ in acc]), dtype=np.float64)
                weights = np.asarray([b for _, b in acc], dtype=np.float64)
                means[k] = float((vals * weights).sum() / max(total, 1))
        if not self.sanity_checking:
            self.callback_metrics.update(means)
            self.logged_metrics.update(means)
            if self.logger is not None and self.is_global_zero and means:
                self.logger.log_metrics(means, self.global_step)

        if stage == "validate":
            module.on_validation_epoch_end()
            for cb in self.callbacks:
                cb.on_validation_epoch_end(self, module)
            for cb in self.callbacks:
                cb.on_validation_end(self, module)
        else:
            for cb in self.callbacks:
                cb.on_test_epoch_end(self, module)
            for cb in self.callbacks:
                cb.on_test_end(self, module)
        return means

    def _run_eval_stage(self, module, stage, loaders):
        loader = loaders["val" if stage == "validate" else "test"]
        limit = (self.limit_val_batches if stage == "validate"
                 else self.limit_test_batches)
        means = self._eval_loop(module, stage, loader, limit)
        return [means]

    def _predict_loop(self, module, loaders):
        strategy = self.plugin.strategy
        loader = loaders["predict"]
        for cb in self.callbacks:
            cb.on_predict_start(self, module)
        outputs = []
        for batch_idx, batch in enumerate(loader):
            if self.limit_predict_batches is not None \
                    and batch_idx >= self.limit_predict_batches:
                break
            if not self._batch_ok(batch, strategy):
                continue
            gbatch = self._put_batch(batch, strategy)
            out = self._predict_step(self.state, gbatch)
            fetched = fetch_tree(out)   # all-gathered: the GLOBAL batch
            if jax.process_count() > 1:
                fetched = _deinterleave_global_batch(
                    fetched, jax.process_count())
            outputs.append(fetched)
        outputs = self._trim_predict_padding(outputs, loader)
        for cb in self.callbacks:
            cb.on_predict_end(self, module)
        return outputs

    @staticmethod
    def _trim_predict_padding(outputs, loader):
        """Drop trailing wrap-around rows added by strided sharding
        (DataLoader._indices pads so every shard is equal length)."""
        if not outputs or getattr(loader, "num_shards", 1) <= 1:
            return outputs
        ds = getattr(loader, "dataset", None)
        if ds is None or not hasattr(ds, "__len__"):
            return outputs
        def rows(o):
            leaves = [l for l in jax.tree_util.tree_leaves(o)
                      if getattr(l, "ndim", 0) > 0]
            return leaves[0].shape[0] if leaves else None

        counts = [rows(o) for o in outputs]
        if any(c is None for c in counts):
            return outputs   # scalar outputs: nothing to trim
        excess = sum(counts) - len(ds)
        if excess <= 0:
            return outputs
        keep = counts[-1] - excess
        if keep <= 0:
            return outputs[:-1]
        outputs[-1] = jax.tree_util.tree_map(
            lambda a: a[:keep] if getattr(a, "ndim", 0) > 0 else a,
            outputs[-1])
        return outputs

    # -- finalization / results round-trip -------------------------------

    def _finalize_fit(self, module):
        self._flush_epoch_metrics()
        trained = {"params": fetch_tree(self.state.params),
                   "model_state": fetch_tree(self.state.model_state)}
        module._trained_variables = trained
        return {"callback_metrics": dict(self.callback_metrics)}

    # ------------------------------------------------------------------
    # checkpointing
    # ------------------------------------------------------------------

    def dump_checkpoint(self) -> dict:
        """Assemble the full checkpoint dict.  Collective: every process
        participates in the state gather (reference analog:
        ``trainer.checkpoint_connector.dump_checkpoint()``, consumed by the
        Tune checkpoint relay, tune.py:172)."""
        module = self.lightning_module
        ckpt = {
            "epoch": int(self.current_epoch),
            "global_step": int(self.global_step),
            "state": serialization.to_state_dict(fetch_tree(self.state)),
            "hparams": _sanitize(dict(module.hparams)) if module else {},
            "callbacks": {type(cb).__name__: _sanitize(cb.state_dict())
                          for cb in self.callbacks},
            "world_size": int(self.world_size),
            "strategy": self.plugin.strategy.name
            if self.plugin.strategy else "none",
        }
        if module is not None:
            module.on_save_checkpoint(ckpt)
        for cb in self.callbacks:
            cb.on_save_checkpoint(self, module, ckpt)
        return ckpt

    @staticmethod
    def serialize_checkpoint(ckpt: dict) -> bytes:
        return serialization.msgpack_serialize(ckpt)

    def save_checkpoint(self, filepath: str) -> None:
        """Collective: every process participates in the gather; only
        global-zero writes (fsspec so GCS paths work on pods —
        SURVEY.md §7 best-path/locality hazard)."""
        with span("checkpoint", step=self.global_step):
            ckpt = self.dump_checkpoint()
            if self.is_global_zero:
                payload = self.serialize_checkpoint(ckpt)
                dirname = os.path.dirname(filepath)
                if dirname and "://" not in filepath:
                    os.makedirs(dirname, exist_ok=True)
                # atomic-ish local write; remote filesystems via fsspec
                if "://" in filepath:
                    with fsspec.open(filepath, "wb") as f:
                        f.write(payload)
                else:
                    fd, tmp = tempfile.mkstemp(dir=dirname or ".")
                    with os.fdopen(fd, "wb") as f:
                        f.write(payload)
                    os.replace(tmp, filepath)

    def _sharded_checkpointer(self, directory: str,
                              max_to_keep: Optional[int] = None):
        """The live orbax manager for ``directory`` (created on first
        use, cached per fit — the elastic snapshotter probes it for
        backpressure before each save)."""
        from ray_lightning_tpu.utils.checkpoint import ShardedCheckpointer
        ckpt = self._sharded_checkpointers.get(directory)
        if ckpt is not None and ckpt.max_to_keep != max_to_keep:
            # retention changed (or two callbacks share the dirpath with
            # conflicting settings): recreate so the new policy applies
            # instead of silently keeping the first one.
            ckpt.wait()
            ckpt.close()
            ckpt = None
        if ckpt is None:
            ckpt = ShardedCheckpointer(directory, max_to_keep=max_to_keep)
            self._sharded_checkpointers[directory] = ckpt
        return ckpt

    def save_sharded_checkpoint(self, directory: str,
                                step: Optional[int] = None,
                                max_to_keep: Optional[int] = None) -> None:
        """Sharded (orbax) save: every process writes only its own array
        shards, asynchronously — no host gather, unlike
        :meth:`save_checkpoint` (utils/checkpoint.py rationale).  All
        processes must call this (collective)."""
        ckpt = self._sharded_checkpointer(directory, max_to_keep)
        module = self.lightning_module
        meta = {
            "epoch": int(self.current_epoch),
            "global_step": int(self.global_step),
            "world_size": int(self.world_size),
            "strategy": self.plugin.strategy.name
            if self.plugin.strategy else "none",
            "hparams": _sanitize(dict(module.hparams)) if module else {},
            "callbacks": {type(cb).__name__: _sanitize(cb.state_dict())
                          for cb in self.callbacks},
        }
        from ray_lightning_tpu.comm.collectives import CommState
        if isinstance(self.state.opt_state, CommState):
            res = jax.tree_util.tree_leaves(self.state.opt_state.residual)
            if res:
                # the error-feedback residual's stacked world size — the
                # reshard restore re-buckets this axis N→M on a topology
                # change (elastic/reshard.py; recorded for forensics,
                # the restore itself reads orbax metadata)
                meta["comm_world"] = int(res[0].shape[0])
        ckpt.save(step if step is not None else int(self.global_step),
                  self.state, meta)

    def wait_for_checkpoints(self) -> None:
        """Block until in-flight async sharded saves are durable."""
        for ckpt in self._sharded_checkpointers.values():
            ckpt.wait()

    def elastic_stats(self) -> Optional[dict]:
        """Elastic-plane numbers for THIS process: snapshot counters
        (snapshots / skipped / save_seconds / stall_seconds) plus the
        shrink bookkeeping the driver stamped on the trainer.  Rank-0's
        copy rides the worker result package back to the driver, which
        folds it into ``trainer._elastic_report``."""
        out: dict = {}
        if self._snapshotter is not None:
            out.update(self._snapshotter.stats)
        if self._redundancy is not None:
            out.update(self._redundancy.stats)
        if self._snapshot_restores:
            out["snapshot_restores"] = self._snapshot_restores
        if self._elastic_state:
            out.update(self._elastic_state)
        return out or None

    def _close_sharded_checkpointers(self) -> None:
        """Wait + release orbax managers (their async worker threads
        outlive the fit otherwise).  A later save simply re-opens."""
        for ckpt in self._sharded_checkpointers.values():
            try:
                ckpt.wait()
                ckpt.close()
            except Exception:  # closing must never mask fit results
                _log.warning("sharded checkpointer close failed",
                             exc_info=True)
        self._sharded_checkpointers = {}

    @staticmethod
    def load_checkpoint_dict(filepath: str) -> dict:
        with fsspec.open(filepath, "rb") as f:
            return serialization.msgpack_restore(f.read())

    def _restore_checkpoint(self, filepath: str, module) -> None:
        from ray_lightning_tpu.utils.checkpoint import ShardedCheckpointer
        if ShardedCheckpointer.is_sharded_checkpoint(filepath):
            self._restore_sharded(filepath, module)
            return
        ckpt = self.load_checkpoint_dict(filepath)
        # Re-shard on load: checkpoints always hold the full (gathered)
        # state, so resuming with a different world size / strategy just
        # re-distributes (covers the reference's resume-with-fewer-workers
        # case, test_ddp_sharded.py:119-138).
        restored = serialization.from_state_dict(
            fetch_tree(self.state), ckpt["state"])
        self.state = jax.device_put(restored, self._state_shardings)
        self.current_epoch = int(ckpt.get("epoch", 0))
        self.global_step = int(ckpt.get("global_step", 0))
        cb_states = ckpt.get("callbacks", {})
        for cb in self.callbacks:
            st = cb_states.get(type(cb).__name__)
            if st:
                cb.load_state_dict(st)
        if module is not None:
            module.on_load_checkpoint(ckpt)
        for cb in self.callbacks:
            cb.on_load_checkpoint(self, module, ckpt)

    def _restore_sharded(self, directory: str, module) -> None:
        """Restore from an orbax directory (root → latest step; a
        specific step dir works too), re-sharding straight into the
        CURRENT mesh — the full state never materializes on one host
        (utils/checkpoint.py).  The topology may differ from the one
        that saved (N→M hosts, strategy swap): global shapes are
        topology-independent except the comm plane's ``[world, ...]``
        error-feedback residual, which elastic/reshard.py re-buckets
        instead of blindly reloading.  Consequently the
        ``on_load_checkpoint`` hooks receive the checkpoint *metadata*
        (same top-level keys as :meth:`dump_checkpoint` minus
        ``state``) — see LightningModule.on_load_checkpoint."""
        from ray_lightning_tpu.elastic.reshard import restore_resharded
        from ray_lightning_tpu.utils.checkpoint import ShardedCheckpointer
        root, step = ShardedCheckpointer.split_step_dir(directory)
        ckpt = ShardedCheckpointer(root)
        try:
            state, meta = restore_resharded(
                ckpt, self.state, self._state_shardings, step=step)
        finally:
            ckpt.close()
        # the replay counter the zero-replay acceptance reads: a parity
        # recovery must finish the fit with this still at 0
        self._snapshot_restores += 1
        reg = _metrics.get_registry()
        if reg is not None:
            reg.counter("rlt_snapshot_restore_total").inc()
        self.state = state
        self.current_epoch = int(meta.get("epoch", 0))
        self.global_step = int(meta.get("global_step", 0))
        cb_states = meta.get("callbacks", {})
        for cb in self.callbacks:
            st = cb_states.get(type(cb).__name__)
            if st:
                cb.load_state_dict(st)
        if module is not None:
            module.on_load_checkpoint(meta)
        for cb in self.callbacks:
            cb.on_load_checkpoint(self, module, meta)

    # elapsed-time helper for example scripts
    @staticmethod
    def _now() -> float:
        return time.monotonic()


def _deinterleave_global_batch(tree, w: int):
    """Global fetched batch rows are process-major ([shard0; shard1; …]);
    strided sharding means shard r holds dataset indices r, r+W, … — so
    dataset order is the (position, shard) transpose."""
    def fix(a):
        if getattr(a, "ndim", 0) == 0 or a.shape[0] % w:
            return a
        lb = a.shape[0] // w
        return a.reshape((w, lb) + a.shape[1:]).swapaxes(0, 1).reshape(
            (w * lb,) + a.shape[1:])
    return jax.tree_util.tree_map(fix, tree)


class _ShardedStepCache:
    """Lazily jit a (state, batch) step per batch *structure* with the
    strategy's ``in_shardings``.

    Eval/predict loaders may yield a different batch pytree than the
    train loader the trainer compiled against (e.g. ``(x, y)`` vs ``x``),
    so the jit — whose ``in_shardings`` must match the arg structure — is
    built on first use per structure and cached."""

    def __init__(self, fn, trainer, strategy):
        self._fn = fn
        self._trainer = trainer
        self._strategy = strategy
        self._cache: dict = {}

    def jitted_for(self, batch):
        """The jitted step for this batch *structure* (built on first
        use, cached).  ``batch`` may be concrete or a tree of
        ``ShapeDtypeStruct`` — the key and the shardings only read
        treedef + ndim, which lets the AOT precompiler warm the SAME
        jit object the eval loop later dispatches through."""
        leaves, treedef = jax.tree_util.tree_flatten(batch)
        key = (treedef, tuple(getattr(l, "ndim", 0) for l in leaves))
        jitted = self._cache.get(key)
        if jitted is None:
            if self._trainer._mesh.devices.size > 1:
                batch_sh = self._strategy.batch_shardings(
                    self._trainer._mesh, batch)
                jitted = jax.jit(
                    self._fn,
                    in_shardings=(self._trainer._state_shardings, batch_sh))
            else:
                jitted = jax.jit(self._fn)
            self._cache[key] = jitted
        return jitted

    def __call__(self, state, batch):
        return self.jitted_for(batch)(state, batch)


def _cache_bytes_estimate(loader, example_batch) -> "int | None":
    """Upper-bound bytes of the device-resident train cache (per batch ×
    batch count), for the donation heuristic's budget debit.  None when
    the loader has no length (the same loaders the cache itself refuses,
    core/loop_engine.py) — the caller then donates, the safe default.

    ``limit_train_batches`` deliberately does NOT shrink the debit:
    ``CachedSource.build()`` uploads the FULL dataset regardless of the
    limit (the limit trims the epoch plan, not the flat cache).  And a
    shuffling loader keeps that flat upload resident for the whole fit
    *alongside* each epoch's repacked view, so its debit doubles
    (shuffle=False drops the flat copy right after the first repack —
    the steady-state residency the budget protects is single there).
    (Advisor r5 medium: the old limit-capped single-copy estimate let
    donation skip with far less real headroom than computed.)
    """
    try:
        n = len(loader)
    except TypeError:
        return None
    batch_bytes = sum(
        int(getattr(leaf, "nbytes", 0) or np.asarray(leaf).nbytes)
        for leaf in jax.tree_util.tree_leaves(example_batch))
    total = n * batch_bytes
    if getattr(loader, "shuffle", False):
        total *= 2
    return total


def _peek_first_batch(loader):
    """Grab one batch for shape inference without losing it.

    Re-iterable loaders (anything with ``__len__``) are returned as-is;
    one-shot iterables are wrapped so the peeked batch is replayed at the
    start of the (single) pass.
    """
    it = iter(loader)
    first = next(it)
    if hasattr(loader, "__len__"):
        return first, loader
    return first, _ChainedLoader(first, it)


class _ChainedLoader:
    def __init__(self, first, rest_iter):
        self._first = first
        self._rest = rest_iter
        self._consumed = False

    def __iter__(self):
        if self._consumed:
            return iter(())  # one-shot source: second pass is empty
        self._consumed = True
        import itertools
        return itertools.chain([self._first], self._rest)


def _sanitize(obj):
    """Make a nested structure msgpack-serializable (tuples→lists, numpy
    scalars→python, drop non-serializable leaves)."""
    if isinstance(obj, dict):
        return {str(k): _sanitize(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_sanitize(v) for v in obj]
    if isinstance(obj, (np.generic,)):
        return obj.item()
    if isinstance(obj, (str, bytes, int, float, bool, type(None),
                        np.ndarray)):
        return obj
    if isinstance(obj, jax.Array):
        return np.asarray(jax.device_get(obj))
    return repr(obj)
