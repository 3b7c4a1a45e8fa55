"""Distributed execution plugins: Ray-style actors driving TPU hosts.

``RayXlaPlugin`` is the flagship (reference: ``RayPlugin``,
ray_ddp.py:67-544).  Driver side, it:

  1. creates ``num_workers`` executor actors — one per TPU host, not one
     per device (the PJRT inversion, SURVEY.md §7) — with env plumbing
     (_setup_env_vars analog, ray_ddp.py:206-219);
  2. elects worker 0's node as the PJRT coordinator and broadcasts
     ``ip:port`` (replacing the MASTER_ADDR/PORT TCP store rendezvous);
  3. ships one pickled payload (trainer, module, datamodule) to all
     workers (ray.put fan-out analog, ray_ddp.py:331);
  4. busy-polls results while relaying queue side-effects
     (execution_loop → process_results, ray_ddp.py:308-351);
  5. unpacks rank-0's results: state stream → module weights on the
     driver, callback metrics, best checkpoint path; kills the actors
     (post_dispatch analog, ray_ddp.py:353-386).

Worker side (``_worker_run``), each actor joins ``jax.distributed``,
builds the global mesh spanning every chip of every host, and re-enters
``trainer._run_stage`` — the same double-life the reference's plugin
leads via its ``_is_remote`` flag (ray_ddp.py:127, :450).

Gradient sync is *not here*: it is compiled into the train step by XLA
from the strategy's shardings and rides ICI/DCN.  The plugin moves only
control, specs and results.

``HorovodRayPlugin`` has no analog because TPU has one collective fabric:
``RayXlaPlugin`` subsumes it (BASELINE.json north star).
"""

from __future__ import annotations

import logging
import os
import uuid
from typing import Any, Callable, Optional

from ray_lightning_tpu.cluster.backend import get_backend
from ray_lightning_tpu.cluster.executor import RLTExecutor
from ray_lightning_tpu.cluster.queue import WorkerQueueProxy
from ray_lightning_tpu.plugins.base import ExecutionPlugin
from ray_lightning_tpu.parallel.strategy import resolve_strategy
from ray_lightning_tpu.session import init_session, reset_session
from ray_lightning_tpu.util import process_results
from ray_lightning_tpu.utils.platform import (host_device_count_flags,
                                              require_chip_free)
from ray_lightning_tpu.utils.seed import SEED_ENV_VAR
from ray_lightning_tpu.utils.states import load_state_stream, to_state_stream

_log = logging.getLogger(__name__)


def _configure_worker_jax() -> None:
    """Apply platform config inside a worker before first backend init."""
    import jax
    platform = os.environ.get("RLT_PLATFORM")
    if platform:
        jax.config.update("jax_platforms", platform)
        if platform == "cpu" \
                and int(os.environ.get("RLT_NUM_PROCESSES", "1")) > 1:
            # gloo carries cross-process CPU collectives — the test-time
            # stand-in for ICI, as gloo was the reference's CI stand-in
            # for NCCL (ray_ddp.py:149-151).  Multi-process ONLY: current
            # jaxlib's gloo backend requires a live distributed client,
            # so enabling it in a single-worker run (which never calls
            # jax.distributed.initialize) kills CPU backend init.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")


def _worker_run(payload: tuple, rank: int, queue,
                cache_seed=None) -> Optional[dict]:
    """Runs inside each actor: join the distributed runtime, re-enter the
    trainer loop, package rank-0 results (execute_remote analog,
    ray_ddp.py:428-502)."""
    _configure_worker_jax()
    import jax

    trainer, module, datamodule, stage, ckpt_path = payload
    if cache_seed is not None:
        # no shared filesystem with the driver: seed this node's local
        # compilation-cache dir from the driver's packed snapshot BEFORE
        # the first compile (compile/shipping.py).  Additive and
        # best-effort — a failed seed just means cold compiles.
        try:
            from ray_lightning_tpu.compile import shipping
            shipping.unpack_cache_dir(cache_seed,
                                      trainer.compile_cache.root)
        except Exception:
            _log.warning("compile-cache seeding failed; compiling cold",
                         exc_info=True)
    nproc = int(os.environ.get("RLT_NUM_PROCESSES", "1"))
    if nproc > 1:
        jax.distributed.initialize(
            coordinator_address=os.environ["RLT_COORDINATOR"],
            num_processes=nproc,
            process_id=rank,
        )
    if queue is not None:
        reset_session()
        init_session(rank, queue)

    plugin = trainer.plugin
    plugin._is_remote = True

    hb = _setup_worker_telemetry(trainer, rank, queue)
    try:
        result = trainer._run_stage(module, datamodule, stage, ckpt_path)
    finally:
        _teardown_worker_telemetry(trainer, hb)
        if nproc > 1:
            # Disconnect from the coordination service before the driver
            # kills actors, so teardown is clean (otherwise surviving
            # workers see the coordinator vanish and abort fatally).
            try:
                jax.distributed.shutdown()
            except RuntimeError:
                pass

    if rank != 0:
        return None
    package: dict[str, Any] = {
        "result": result,
        "callback_metrics": dict(trainer.callback_metrics),
        "epoch": int(trainer.current_epoch),
        "global_step": int(trainer.global_step),
        # startup cost as rank 0 saw it (chip_smoke.py reports it;
        # tests/test_compile_cache.py's cold/warm A/B reads this number)
        "time_to_first_step": trainer.time_to_first_step,
        # the planner's verdict when strategy="auto" ran in the workers
        # (every rank plans identically; rank 0's copy is THE report)
        "plan_report": trainer._plan_report,
        # rank 0's finalized goodput doc (telemetry/goodput.py) — the
        # driver's fallback when the queue-shipped copy was dropped
        "goodput": getattr(trainer, "_goodput_local", None),
    }
    if stage == "fit":
        # Weights return in-band as a state stream — PL's temp-file
        # handoff breaks multi-node (rationale at ray_ddp.py:480-486).
        package["state_stream"] = to_state_stream(module._trained_variables)
        # elastic-plane numbers (snapshot counters etc.) for the
        # driver's _elastic_report / bench JSON
        package["elastic"] = trainer.elastic_stats()
        ckpt_cb = trainer.checkpoint_callback
        if ckpt_cb is not None:
            package["best_model_path"] = ckpt_cb.best_model_path
            package["best_model_score"] = ckpt_cb.best_model_score
    return package


def _setup_worker_telemetry(trainer, rank: int, queue):
    """Enable span recording, the metrics registry and heartbeats inside
    an actor: span batches and cumulative metrics windows ride the
    worker→driver queue to the driver aggregator.  Returns the heartbeat
    sender to stop (None when telemetry is off or the process-level
    sender from worker_main already beats)."""
    cfg = getattr(trainer, "telemetry", None)
    if cfg is None or not cfg.enabled or queue is None:
        return None
    from ray_lightning_tpu import telemetry
    from ray_lightning_tpu.telemetry import heartbeat as hb_mod

    def sink(records, _q=queue, _rank=rank):
        _q.put((_rank, telemetry.spans_item(_rank, records)))

    telemetry.enable(rank=rank, sink=sink, capacity=cfg.capacity,
                     flush_every=cfg.flush_every)
    if cfg.metrics:
        telemetry.enable_metrics(
            rank=rank,
            sink=lambda item, _q=queue, _rank=rank: _q.put((_rank, item)),
            interval=cfg.metrics_interval)
    every_n, window = cfg.resolved_anatomy()
    if every_n is not None:
        # cadence-armed anatomy windows (telemetry/anatomy.py): each
        # rank captures + parses its OWN trace and ships only the
        # compact anatomy dict over the queue — never the raw capture
        telemetry.enable_anatomy(
            rank=rank, every_n=every_n, window=window,
            sink=lambda item, _q=queue, _rank=rank: _q.put((_rank, item)))
    if cfg.resolved_goodput():
        # goodput plane (telemetry/goodput.py): the run ledger opens
        # inside _run_stage; the finalized doc rides the same queue
        telemetry.enable_goodput(
            rank=rank,
            sink=lambda item, _q=queue, _rank=rank: _q.put((_rank, item)))
    if hb_mod.process_heartbeat_active():
        return None  # worker_main (built-in backend) already beats
    return hb_mod.HeartbeatSender(
        lambda item, _q=queue, _rank=rank: _q.put((_rank, item)),
        rank=rank, interval=cfg.heartbeat_interval).start()


def _teardown_worker_telemetry(trainer, hb) -> None:
    cfg = getattr(trainer, "telemetry", None)
    if cfg is None or not cfg.enabled:
        return
    from ray_lightning_tpu import telemetry
    # abandon any mid-capture anatomy window first (a partial trace is
    # not an anatomy), then the final metrics window: its cumulative
    # counters must be on the queue before the spans flush that follows
    # the last step
    telemetry.disable_goodput()
    telemetry.disable_anatomy()
    telemetry.flush_metrics()
    telemetry.disable_metrics()
    telemetry.flush()
    telemetry.disable()
    if hb is not None:
        hb.stop()


class RayXlaPlugin(ExecutionPlugin):
    """Data-parallel training over Ray-style actors, one per TPU host."""

    def __init__(
        self,
        num_workers: int = 1,
        num_cpus_per_worker: float = 1,
        use_tpu: bool = False,
        devices_per_worker: Optional[int] = None,
        platform: Optional[str] = None,
        strategy: Any = "ddp",
        init_hook: Optional[Callable] = None,
        resources_per_worker: Optional[dict] = None,
        worker_env: Optional[dict] = None,
    ):
        if num_workers < 1:
            raise ValueError("num_workers must be >= 1")
        self.num_workers = int(num_workers)
        self.num_cpus_per_worker = num_cpus_per_worker
        self.use_tpu = use_tpu
        self.devices_per_worker = devices_per_worker
        self.platform = platform or ("tpu" if use_tpu else None)
        self.strategy = resolve_strategy(strategy)
        self.init_hook = init_hook
        self.worker_env = dict(worker_env or {})
        # resources_per_worker overrides the convenience args; leftover
        # keys become custom resources (precedence parity with
        # ray_ddp.py:128-153, tested at test_ddp.py:136-174).
        resources = dict(resources_per_worker or {})
        self.num_cpus_per_worker = resources.pop("CPU",
                                                 self.num_cpus_per_worker)
        if "TPU" in resources:
            tpu = resources.pop("TPU")
            self.use_tpu = tpu > 0
            if self.devices_per_worker is None and tpu > 0:
                self.devices_per_worker = int(tpu)
        self.additional_resources = resources

        self._workers: list = []
        self._backend = None
        self._is_remote = False

    # -- pickling: drop live handles (ray_ddp.py:164-172 analog) ---------

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_workers"] = []
        state["_backend"] = None
        state["init_hook"] = None  # already executed before shipping
        state.pop("_telemetry_agg", None)  # live driver-side aggregator
        state.pop("_metrics_server", None)  # live driver HTTP listener
        # harvested escrow blobs are driver-side recovery state; only
        # the assembled package (trainer._elastic_recovery) ships
        state.pop("_last_escrows", None)
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)

    # -- resources --------------------------------------------------------

    def _worker_resources(self) -> dict:
        res = {"CPU": self.num_cpus_per_worker, **self.additional_resources}
        if self.use_tpu:
            res["TPU"] = self.devices_per_worker or 1
        return res

    def _worker_env_base(self) -> dict:
        env = {
            "RLT_NUM_PROCESSES": str(self.num_workers),
        }
        if SEED_ENV_VAR in os.environ:  # PL_GLOBAL_SEED propagation parity
            env[SEED_ENV_VAR] = os.environ[SEED_ENV_VAR]
        if os.environ.get("RLT_REMAT_POLICY", "").strip():
            # model-build remat override (models/gpt.py _remat_policy,
            # pinned by the planner's remat axis): actor fleets must
            # build the same program as the driver — ships like the
            # RLT_COMM*/RLT_MPMD* knobs below
            env["RLT_REMAT_POLICY"] = os.environ["RLT_REMAT_POLICY"]
        if self.platform:
            env["RLT_PLATFORM"] = self.platform
            env["JAX_PLATFORMS"] = self.platform
        if self.platform == "cpu":
            # each CPU worker gets exactly devices_per_worker virtual
            # devices (default 1)
            n = self.devices_per_worker or 1
            env["XLA_FLAGS"] = host_device_count_flags(n)
            env["RLT_NUM_LOCAL_DEVICES"] = str(n)
        env.update(self.worker_env)
        return env

    # -- driver-side run ---------------------------------------------------

    def run(self, trainer, module, datamodule, stage: str,
            ckpt_path: Optional[str]):
        if self._is_remote:
            raise RuntimeError("plugin.run called inside a worker")
        elastic = getattr(trainer, "elastic", None)
        if stage == "fit" and elastic is not None and elastic.enabled \
                and elastic.max_restarts > 0:
            # shrink-to-continue: a dead rank tears the fleet down, the
            # elastic driver rebuilds it with the survivors and resumes
            # from the latest snapshot (elastic/driver.py)
            from ray_lightning_tpu.elastic.driver import run_elastic_fit
            return run_elastic_fit(self, trainer, module, datamodule,
                                   ckpt_path)
        return self._run_attempt(trainer, module, datamodule, stage,
                                 ckpt_path)

    def _run_attempt(self, trainer, module, datamodule, stage: str,
                     ckpt_path: Optional[str]):
        """One fleet lifecycle: create actors, rendezvous, execute,
        tear down.  The elastic driver calls this repeatedly with a
        shrinking ``num_workers``; everything per-fleet (actors,
        aggregator, metrics server) is rebuilt per attempt."""
        if self.platform != "cpu":
            require_chip_free(
                f"{type(self).__name__}.{stage}()",
                "Keep the driver off JAX before an actor-plugin run "
                "(no in-process fit, no jax.devices()), or run the "
                "earlier in-process work in its own child process.")
        backend = get_backend()
        self._backend = backend
        base_env = self._worker_env_base()
        cfg = trainer.telemetry
        profile_ctl = None
        incident_cfg = None
        incident_control = None
        if cfg.enabled:
            incident_cfg = cfg.resolved_incident()
            # workers heartbeat from process start (worker_main) and
            # record spans once the fit payload arrives (_worker_run)
            base_env["RLT_TELEMETRY"] = "1"
            base_env["RLT_HEARTBEAT_INTERVAL"] = str(cfg.heartbeat_interval)
            # anatomy cadence (RLT_ANATOMY* — telemetry/anatomy.py):
            # every rank must arm the same windows the driver resolved,
            # whether the cadence came from the config or the env
            base_env.update(cfg.worker_env())
            if cfg.metrics and getattr(backend, "shared_filesystem",
                                       False):
                # on-demand profiling for fits (POST /debug/profile):
                # shared-FS backends get a control file the loop engine
                # polls each dispatch; its location ships via env
                # (telemetry/tracing.py FileProfileController)
                from ray_lightning_tpu.telemetry import tracing
                control = os.path.join(
                    cfg.resolve_dir(trainer.default_root_dir),
                    "profile", "control.json")
                profile_ctl = tracing.FileProfileController(control)
                base_env[tracing.PROFILE_CONTROL_ENV] = control
            if incident_cfg.enabled and getattr(
                    backend, "shared_filesystem", False):
                # incident-plane arm channel (telemetry/incident.py):
                # on detector trip the driver writes this file; every
                # rank's AnatomyController polls it and forces an
                # off-cadence evidence window — same shared-FS idiom
                # as the profile control file above
                from ray_lightning_tpu.telemetry import anatomy as _anatomy
                incident_control = os.path.join(
                    cfg.resolve_dir(trainer.default_root_dir),
                    "incident", "arm.json")
                base_env[_anatomy.INCIDENT_CONTROL_ENV] = incident_control
        # persistent-compilation-cache knobs: the pickled trainer already
        # carries the config, but the env keeps worker-side tooling that
        # consults RLT_COMPILE_CACHE* (e.g. a nested fit) consistent.
        # Shared-FS backends (builtin subprocess actors) thereby point
        # every worker at the DRIVER'S cache root — sharing, not seeding.
        base_env.update(trainer.compile_cache.worker_env())
        # comm-plane knobs ride the same way: the pickled trainer carries
        # the resolved CommPolicy; the env keeps worker-side tooling that
        # consults RLT_COMM* (e.g. a nested fit) consistent with it
        base_env.update(trainer.comm_policy.worker_env())
        # elastic knobs too (RLT_ELASTIC* — elastic/config.py)
        base_env.update(trainer.elastic.worker_env())
        # planner knobs (RLT_PLAN* — plan/config.py): the pickled
        # trainer carries the resolved PlanConfig; the env keeps
        # worker-side tooling consistent, and identical config on every
        # rank is what the planner's deterministic-winner contract needs
        base_env.update(trainer.plan.worker_env())
        # MPMD knobs (RLT_MPMD* — mpmd/config.py): the strategy carries
        # the resolved config; the env keeps worker-side tooling that
        # consults RLT_MPMD* consistent with the driver's resolution
        strat = getattr(self, "strategy", None)
        if getattr(strat, "name", "") == "mpmd":
            base_env.update(strat.config.worker_env())
        from ray_lightning_tpu.core import datacheck
        if datacheck.enabled():
            # driver-set RLT_DATA_CHECK=1 reaches workers explicitly
            # (backends that don't inherit the driver env included)
            base_env[datacheck.ENV_DATA_CHECK] = "1"
        # unique per fit: reusing names across fits in one driver process
        # lets a late/stale connection from a previous run race the new
        # worker's attach
        run_tag = uuid.uuid4().hex[:8]
        worker_names = [f"rlt-worker-{os.getpid()}-{run_tag}-{i}"
                        for i in range(self.num_workers)]
        # rank-ordered actor names reach every worker so rank r can
        # peer_send to rank s by name — the worker↔worker channel the
        # elastic parity tick rides (elastic/redundancy.py)
        base_env["RLT_PEER_NAMES"] = ",".join(worker_names)
        self._workers = [
            backend.create_actor(
                RLTExecutor,
                # rank at spawn time so even pre-setup heartbeats carry
                # it (set_env_vars re-sends the same value later)
                env={**base_env, "RLT_PROCESS_ID": str(i)},
                resources=self._worker_resources(),
                name=worker_names[i],
                # Ray: peer deliveries + escrow harvests are concurrent
                # actor calls and must run beside a busy main call; the
                # builtin backend serves both from its reader thread
                # and ignores this
                max_concurrency=2,
            )
            for i in range(self.num_workers)
        ]
        agg = None
        server = None
        if cfg.enabled:
            from ray_lightning_tpu import telemetry
            from ray_lightning_tpu.telemetry import exporter as _exporter
            agg = telemetry.TelemetryAggregator(
                cfg.resolve_dir(trainer.default_root_dir),
                heartbeat_timeout=cfg.heartbeat_timeout,
                hard_timeout=cfg.hard_timeout,
                flight_capacity=cfg.flight_capacity,
                incident_cfg=incident_cfg)
            if incident_control is not None:
                agg.incidents.arm_path = incident_control
            # elastic restart count survives the per-attempt aggregator
            # rebuild so /metrics' rlt_restarts_total is cumulative,
            # and the recovery route the driver chose for THIS attempt
            # (parity vs replay) is a scrapeable series
            agg.set_restarts(getattr(self, "_elastic_restarts", 0))
            agg.set_recovery(getattr(self, "_elastic_recovery_mode", None),
                             getattr(self, "_elastic_recovery_seconds",
                                     None))
            # snapshot-replay badput: steps this attempt re-executes
            # because the snapshot was behind the crash step
            # (elastic/driver.py sets it when routing to replay)
            agg.set_replayed_steps(
                getattr(self, "_elastic_replayed_steps", 0))
            for i, w in enumerate(self._workers):
                agg.register_worker(i, w)
            telemetry.set_active(agg)
            self._telemetry_agg = agg
            if cfg.metrics:
                # live /metrics + /status on the driver: workers' metric
                # windows arrive over the queue during _execution_loop
                server = _exporter.start_metrics_server(
                    agg, cfg, profile_controller=profile_ctl)
                self._metrics_server = server
        from ray_lightning_tpu.core import datacheck
        dc = None
        if datacheck.enabled() \
                or self.worker_env.get(datacheck.ENV_DATA_CHECK) == "1":
            # opt-in divergent-loader detection: workers relay per-step
            # batch fingerprints over the queue; the driver cross-checks
            # ranks in process_results and raises on divergence
            dc = datacheck.DataCheckValidator()
            datacheck.set_active_validator(dc)
        try:
            return self._execution_loop(trainer, module, datamodule, stage,
                                        ckpt_path, backend)
        except BaseException:
            # probe fleet liveness BEFORE teardown kills everyone: the
            # elastic driver classifies the failure (a dead process is
            # restartable, a deterministic user exception is not) and
            # sizes the shrink from this list.  process_alive, not
            # alive: the strict probe never misreads a busy survivor
            # as dead (cluster/backend.py)
            self._last_dead_ranks = [
                i for i, w in enumerate(self._workers)
                if w.process_alive() is False]
            # harvest survivor escrows BEFORE the finally below kills
            # them: the parity-tick state deposited on each survivor
            # (elastic/redundancy.py) is what reconstruct-and-continue
            # recovers from, served by the workers' reader threads even
            # when their main threads are wedged in a dead collective
            self._last_escrows = {}
            elastic = getattr(trainer, "elastic", None)
            if stage == "fit" and elastic is not None \
                    and elastic.enabled and elastic.redundancy > 0:
                for i, w in enumerate(self._workers):
                    if i in self._last_dead_ranks:
                        continue
                    try:
                        esc = w.harvest_escrow(timeout=15.0)
                    except Exception:   # noqa: BLE001 - best-effort
                        esc = None
                    if esc is not None:
                        self._last_escrows[i] = esc
            raise
        finally:
            if dc is not None:
                datacheck.set_active_validator(None)
            for w in self._workers:
                w.kill()  # no_restart parity, ray_ddp.py:383-386
            self._workers = []
            if agg is not None:
                from ray_lightning_tpu import telemetry
                telemetry.set_active(None)
                if server is not None:
                    server.stop()
                trainer._telemetry_paths = agg.export()
                if server is not None:
                    trainer._telemetry_paths["metrics_url"] = server.url
                # fleet goodput aggregate + the planner's measured-vs-
                # modeled divergence, from the docs the workers shipped
                # over the queue (rank-0 package fallback in
                # _post_dispatch when the queue copy was dropped)
                gp = agg.goodput_stats()
                if gp:
                    trainer._goodput_report = gp.get("fleet")
                trainer._attach_observed_divergence(agg)

    def _execution_loop(self, trainer, module, datamodule, stage, ckpt_path,
                        backend):
        workers = self._workers
        if self.init_hook is not None:
            # dataset-download style hook on every worker before training
            # (examples/ray_ddp_tune.py:22-25 parity)
            process_results(
                [w.call("execute", self.init_hook) for w in workers], backend)

        # rendezvous: worker-0's node hosts the PJRT coordinator
        # (MASTER_ADDR/PORT analog, ray_ddp.py:206-219)
        if self.num_workers > 1:
            ip = workers[0].call("get_node_ip").result(timeout=120)
            port = workers[0].call("get_free_port").result(timeout=120)
            coord_env = {"RLT_COORDINATOR": f"{ip}:{port}"}
        else:
            coord_env = {}
        node_info = process_results(
            [w.call("get_node_and_device_info") for w in workers], backend)
        ranks = self._assign_local_ranks(node_info)
        tpu_env = self._tpu_partition_envs(node_info, ranks, backend)
        env_futs = []
        for i, w in enumerate(workers):
            node_rank, local_rank = ranks[i]
            env_futs.append(w.call("set_env_vars", {
                **coord_env,
                **tpu_env.get(i, {}),
                "RLT_PROCESS_ID": str(i),
                "RLT_NODE_RANK": str(node_rank),
                "RLT_LOCAL_RANK": str(local_rank),
            }))
        process_results(env_futs, backend)

        queue = None
        if stage == "fit" or trainer.telemetry.enabled:
            # telemetry needs the worker→driver queue on every stage
            queue = (backend.worker_queue_proxy()
                     if hasattr(backend, "worker_queue_proxy")
                     else WorkerQueueProxy())

        cache_seed, cache_seed_ref = self._pack_cache_seed(trainer, backend)
        payload = (trainer, module, datamodule, stage, ckpt_path)
        payload_ref = None
        if backend.supports_object_store:
            # ship once via the object store; workers deref on delivery
            payload = payload_ref = backend.put(payload)

        try:
            futures = [
                w.call("execute", _worker_run, payload, i, queue,
                       cache_seed)
                for i, w in enumerate(workers)
            ]
            results = process_results(futures, backend)
        finally:
            if payload_ref is not None:
                backend.free(payload_ref)
            if cache_seed_ref is not None:
                backend.free(cache_seed_ref)
        return self._post_dispatch(trainer, module, stage, results)

    @staticmethod
    def _pack_cache_seed(trainer, backend):
        """(seed, ref) for compile-cache seeding: a packed snapshot of
        the driver's cache root for backends whose workers cannot see
        the driver's filesystem (compile/shipping.py), shipped once via
        the object store when available.  (None, None) when the cache is
        off, the backend shares a filesystem, or the root is empty."""
        cc = trainer.compile_cache
        if not cc.enabled or getattr(backend, "shared_filesystem", False):
            return None, None
        from ray_lightning_tpu.compile import shipping
        blob = shipping.pack_cache_dir(cc.root)
        if blob is None:
            return None, None
        if backend.supports_object_store:
            ref = backend.put(blob)
            return ref, ref
        return blob, None

    def _tpu_partition_envs(self, node_info, ranks, backend) -> dict[int, dict]:
        """Per-worker TPU chip-visibility env for co-located actors
        (``_share_cuda_visible_devices`` analog, ray_ddp.py:221-265).

        Whenever several TPU workers share one node IP, each gets a
        ``TPU_*`` partition of that host's chips (utils/tpu_topology.py);
        impossible splits raise before any worker touches libtpu.  A
        worker alone on its host owns every chip and needs nothing.
        """
        if not self.use_tpu:
            return {}
        by_node: dict[int, list[int]] = {}
        for i in range(len(node_info)):
            node_rank, _local = ranks[i]
            by_node.setdefault(node_rank, []).append(i)
        out: dict[int, dict] = {}
        d = int(self.devices_per_worker or 1)
        from ray_lightning_tpu.utils.tpu_topology import partition_env
        for members in by_node.values():
            if len(members) < 2:
                continue  # sole owner of the host: no scoping needed
            members = sorted(members, key=lambda i: ranks[i][1])
            ports = process_results(
                [self._workers[i].call("get_free_port") for i in members],
                backend)
            ip = node_info[members[0]].get("ip", "?")
            for i in members:
                out[i] = partition_env(d, ranks[i][1], ip, ports)
        return out

    @staticmethod
    def _assign_local_ranks(node_info: list[dict]) -> dict[int, tuple[int, int]]:
        """Global rank → (node_rank, local_rank) from node IPs
        (get_local_ranks analog, ray_ddp.py:282-306)."""
        by_ip: dict[str, list[int]] = {}
        for i, info in enumerate(node_info):
            by_ip.setdefault(info.get("ip", "?"), []).append(i)
        out: dict[int, tuple[int, int]] = {}
        for node_rank, (_ip, members) in enumerate(sorted(by_ip.items())):
            for local_rank, grank in enumerate(members):
                out[grank] = (node_rank, local_rank)
        return out

    def _post_dispatch(self, trainer, module, stage, results):
        rank0 = next(r for r in results if r is not None)
        trainer.callback_metrics.update(rank0.get("callback_metrics", {}))
        trainer.current_epoch = rank0.get("epoch", trainer.current_epoch)
        trainer.global_step = rank0.get("global_step", trainer.global_step)
        trainer.time_to_first_step = rank0.get("time_to_first_step")
        trainer._elastic_worker_stats = rank0.get("elastic")
        if rank0.get("plan_report") is not None:
            trainer._plan_report = rank0.get("plan_report")
        if rank0.get("goodput") is not None:
            # rank 0's own doc as the provisional report; _run_attempt's
            # teardown upgrades it to the fleet aggregate when the
            # queue-shipped docs reached the aggregator
            trainer._goodput_report = rank0.get("goodput")
        if stage == "fit":
            stream = rank0.get("state_stream")
            if stream is not None:
                # driver-side weight rehydration (ray_ddp.py:375-377 analog)
                module._trained_variables = load_state_stream(stream)
            ckpt_cb = trainer.checkpoint_callback
            best = rank0.get("best_model_path")
            if ckpt_cb is not None and best:
                # a path on rank-0's node; valid on shared FS / GCS
                # (locality caveat, ray_ddp.py:378-380 / SURVEY.md §7)
                ckpt_cb.best_model_path = best
                ckpt_cb.best_model_score = rank0.get("best_model_score")
        return rank0.get("result")

    # -- worker-side mesh devices -----------------------------------------

    def local_devices(self):
        return None  # the global mesh spans all devices of all processes


class RayXlaShardedPlugin(RayXlaPlugin):
    """ZeRO-1 flavor (reference: ``RayShardedPlugin``,
    ray_ddp_sharded.py:17-34).  Identical orchestration; the difference is
    purely the sharding strategy — optimizer state sharded across data
    ranks, grads reduce-scattered, params all-gathered by XLA — where the
    reference swaps in FairScale OSS/SDP via PL's
    ``DDPSpawnShardedPlugin`` MRO."""

    def __init__(self, *args, **kwargs):
        kwargs.setdefault("strategy", "zero1")
        super().__init__(*args, **kwargs)


class RayXlaSpmdPlugin(RayXlaPlugin):
    """General SPMD flavor (beyond reference parity): tensor/sequence/
    expert-parallel meshes via partition rules (parallel/strategy.py
    SpmdStrategy).  Same actor orchestration."""

    def __init__(self, *args, **kwargs):
        kwargs.setdefault("strategy", "spmd")
        super().__init__(*args, **kwargs)
