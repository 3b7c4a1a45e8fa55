"""The public serving endpoint: ``Server`` — trained checkpoint in,
multi-tenant generation out.

Driver-side composition of the serve plane (module docstrings of the
parts hold the details): a :class:`~ray_lightning_tpu.serve.scheduler.
Scheduler` forms continuous batches over bucketed sequence lengths, a
fleet of persistent :class:`~ray_lightning_tpu.serve.worker.ServeWorker`
actors (one per TPU host, same cluster backends and rendezvous plumbing
as the fit path) executes them against AOT-compiled prefill/decode
programs and a strategy-sharded KV cache, and the PR 2 metrics plane
serves TTFT / TPOT / queue depth / tokens-per-second live on the
driver's ``/metrics`` endpoint.

::

    server = Server(GPTLightningModule("tiny"), checkpoint=ckpt_path,
                    num_workers=2, platform="cpu",
                    buckets=(16, 32), max_batch_slots=8,
                    telemetry={"metrics_port": 0}).start()
    req = server.submit(prompt_tokens, tenant="alice")
    tokens = req.result(timeout=60)          # np.int32 generated ids
    tokens = server.generate(prompt_tokens)  # submit + wait
    server.shutdown()                        # graceful drain first

Prompts and completions are token-id arrays — tokenization lives with
the caller, like every dataset concern in this framework.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
import uuid
from dataclasses import dataclass
from typing import Any, Optional, Sequence

import numpy as np

from ray_lightning_tpu.cluster.backend import get_backend
from ray_lightning_tpu.cluster.queue import WorkerQueueProxy
from ray_lightning_tpu.compile import CompileCacheConfig
from ray_lightning_tpu.parallel.strategy import resolve_strategy
from ray_lightning_tpu.serve.buckets import resolve_buckets
from ray_lightning_tpu.serve.scheduler import Scheduler, ServeRequest
from ray_lightning_tpu.serve.worker import ServeWorker
from ray_lightning_tpu.telemetry import TelemetryConfig, span, spans
from ray_lightning_tpu.util import _handle_queue_item
from ray_lightning_tpu.utils.platform import (host_device_count_flags,
                                              require_chip_free)

_log = logging.getLogger(__name__)


@dataclass
class ServeSpec:
    """Picklable engine configuration shipped to every serve worker."""

    module: Any
    strategy: Any
    buckets: tuple
    slots: int
    max_seq_len: int
    seed: int
    telemetry: TelemetryConfig
    compile_cache: CompileCacheConfig
    #: paged-KV prefix reuse (serve/fleet/pages.py PageConfig); None or
    #: disabled keeps the engine's pre-fleet program set
    paged: Any = None
    #: speculative decoding (serve/spec.py SpecConfig); None/disabled
    #: keeps the plain-decode program set
    spec: Any = None
    #: build the per-bucket kv_import programs (fleet KV shipping)
    kvship: Any = None


class Server:
    """Multi-tenant generation endpoint over a trained module."""

    def __init__(
        self,
        module,
        checkpoint: Optional[str] = None,
        *,
        strategy: Any = None,
        buckets: Optional[Sequence[int]] = None,
        max_batch_slots: int = 8,
        num_workers: int = 1,
        platform: Optional[str] = None,
        use_tpu: bool = False,
        devices_per_worker: Optional[int] = None,
        max_seq_len: Optional[int] = None,
        max_new_tokens: int = 32,
        eos_token: Optional[int] = None,
        tenant_quotas: "dict[str, int] | int | None" = None,
        max_prefills_per_step: int = 1,
        seed: int = 0,
        default_root_dir: Optional[str] = None,
        telemetry: Any = None,
        compile_cache: Any = None,
        paged: Any = None,
        spec: Any = None,
        kvship: bool = False,
        worker_env: Optional[dict] = None,
    ):
        if num_workers < 1:
            raise ValueError("num_workers must be >= 1")
        self.module = module
        self.strategy = resolve_strategy(strategy)
        if max_seq_len is None:
            cfg = getattr(module, "config", None)
            max_seq_len = getattr(cfg, "block_size", None)
            if max_seq_len is None:
                raise ValueError(
                    "pass max_seq_len= (module.config has no block_size)")
        self.max_seq_len = int(max_seq_len)
        self.buckets = resolve_buckets(buckets, self.max_seq_len)
        self.max_batch_slots = int(max_batch_slots)
        self.num_workers = int(num_workers)
        self.platform = platform or ("tpu" if use_tpu else None)
        self.use_tpu = use_tpu
        self.devices_per_worker = devices_per_worker
        self.seed = int(seed)
        self.default_root_dir = default_root_dir or os.path.join(
            os.getcwd(), "rlt_serve")
        self.telemetry = TelemetryConfig.resolve(telemetry)
        self.compile_cache = CompileCacheConfig.resolve(compile_cache)
        from ray_lightning_tpu.serve.fleet.pages import PageConfig
        from ray_lightning_tpu.serve.spec import SpecConfig
        self.paged = PageConfig.resolve(paged)
        self.spec = SpecConfig.resolve(spec)
        self.kvship = bool(kvship)
        # a model family says here, before anything starts, what it
        # cannot be served with and why (models/evabyte.py)
        refuse = getattr(module, "refuse_serve_options", None)
        if refuse is not None:
            refuse(paged=self.paged.enabled, spec=self.spec.enabled,
                   kvship=self.kvship)
        self.worker_env = dict(worker_env or {})
        self.scheduler = Scheduler(
            self.buckets, self.max_batch_slots, self.max_seq_len,
            quotas=tenant_quotas,
            max_prefills_per_step=max_prefills_per_step,
            default_max_new_tokens=max_new_tokens, eos_token=eos_token,
            paged=self.paged, spec=self.spec,
            live_rows=getattr(module, "live_cache_rows", None))
        self._weights = self._resolve_weights(module, checkpoint)
        self._backend = None
        self._workers: list = []
        self._queue = None
        self._agg = None
        self._metrics_server = None
        self._profile_ctl = None
        self._pump: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._work = threading.Event()
        self._draining = False
        self._started = False
        self._error: Optional[BaseException] = None
        #: postmortem of a mid-serve fleet failure: classified cause +
        #: the flight-recorder dump paths (telemetry/flight.py), linked
        #: from the fleet router's failover report
        self.failure_report: Optional[dict] = None
        self._setup_info: list = []
        self.telemetry_paths: Optional[dict] = None
        #: goodput plane (telemetry/goodput.py): the pump's wall-clock
        #: ledger (decode / prefill / queue_idle split) and its
        #: finalized doc — the serve half of the goodput surface
        self._goodput_ledger = None
        self.goodput_doc: Optional[dict] = None
        self._next_peek = 0.0
        self._kept_steps = 0   # steps left of a profile window's spans
        self._kept_window = contextlib.ExitStack()   # holds its keep()

    @staticmethod
    def _resolve_weights(module, checkpoint: Optional[str]):
        """Weights for the fleet: an msgpack checkpoint path, a module
        carrying ``_trained_variables`` from a previous ``fit``, or
        ``None`` (seeded fresh init — benches and smoke tests)."""
        if checkpoint is not None:
            from ray_lightning_tpu.core.trainer import Trainer
            ckpt = Trainer.load_checkpoint_dict(checkpoint)
            return {"params": ckpt["state"]["params"]}
        trained = getattr(module, "_trained_variables", None)
        if trained is not None:
            return {"params": trained["params"]}
        return None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "Server":
        """Spawn the fleet, rendezvous, build+warm every engine, start
        the scheduler pump.  Blocking; returns self."""
        if self._started:
            return self
        if self.platform != "cpu":
            require_chip_free(
                "Server.start()",
                "Train and serve in separate processes: write a "
                "checkpoint from the fit, then build "
                "Server(module, checkpoint=path) in a fresh process "
                "that has not touched JAX.")
        # set-up is kept whatever the telemetry flag says, under one
        # root: a reader in this process reads it after start() returned
        # (telemetry/spans.py ``kept("server_start")``)
        with spans.keep("server_start") as kept:
            with span("server_start"):
                self._start_fleet(kept)
        info = self._setup_info[0]
        _log.info("serve fleet ready: %d worker(s), mesh=%s, buckets=%s, "
                  "slots=%d", self.num_workers, info["mesh"],
                  info["buckets"], info["slots"])
        self._started = True
        self._pump = threading.Thread(target=self._pump_loop, daemon=True,
                                      name="rlt-serve-pump")
        self._pump.start()
        return self

    def _start_fleet(self, kept) -> None:
        backend = get_backend()
        self._backend = backend
        base_env = self._worker_env_base()
        run_tag = uuid.uuid4().hex[:8]
        with span("spawn"):
            self._workers = [
                backend.create_actor(
                    ServeWorker,
                    env={**base_env, "RLT_PROCESS_ID": str(i)},
                    resources=self._worker_resources(),
                    name=f"rlt-serve-{os.getpid()}-{run_tag}-{i}",
                )
                for i in range(self.num_workers)
            ]
        try:
            with span("rendezvous"):
                # the first call a worker answers: its process start
                # and the package's imports are in here
                self._rendezvous()
            self._start_telemetry()
            self._queue = (backend.worker_queue_proxy()
                           if hasattr(backend, "worker_queue_proxy")
                           else WorkerQueueProxy())
            spec = ServeSpec(
                module=self.module, strategy=self.strategy,
                buckets=self.buckets, slots=self.max_batch_slots,
                max_seq_len=self.max_seq_len, seed=self.seed,
                telemetry=self.telemetry,
                compile_cache=self.compile_cache,
                paged=self.paged, spec=self.spec,
                kvship=self.kvship)
            payload = (spec, self._weights)
            ref = None
            try:
                with span("ship"):
                    if backend.supports_object_store:
                        payload = ref = backend.put(payload)
                    futures = [
                        w.call("setup_serve", payload, i, self._queue)
                        for i, w in enumerate(self._workers)]
                with span("worker_setup") as waiting:
                    self._setup_info = self._wait_all(futures, timeout=600)
                    for info in self._setup_info:
                        # the worker's own set-up spans, on this host's
                        # wall clock already: hang them here
                        spans.adopt(kept, info.pop("spans", []),
                                    parent=waiting.id, rank=info["rank"])
            finally:
                if ref is not None:
                    backend.free(ref)
        except BaseException:
            self._kill_workers()
            raise

    def _worker_env_base(self) -> dict:
        """Mirror of the fit path's worker env plumbing
        (plugins/xla.py RayXlaPlugin._worker_env_base)."""
        env = {"RLT_NUM_PROCESSES": str(self.num_workers)}
        if self.platform:
            env["RLT_PLATFORM"] = self.platform
            env["JAX_PLATFORMS"] = self.platform
        if self.platform == "cpu":
            n = self.devices_per_worker or 1
            env["XLA_FLAGS"] = host_device_count_flags(n)
            env["RLT_NUM_LOCAL_DEVICES"] = str(n)
        if self.telemetry.enabled:
            env["RLT_TELEMETRY"] = "1"
            env["RLT_HEARTBEAT_INTERVAL"] = str(
                self.telemetry.heartbeat_interval)
        env.update(self.compile_cache.worker_env())
        env.update(self.paged.worker_env())
        env.update(self.spec.worker_env())
        if self.kvship:
            env["RLT_SERVE_KVSHIP"] = "1"
        env.update(self.worker_env)
        return env

    def _worker_resources(self) -> dict:
        res: dict = {"CPU": 1.0}
        if self.use_tpu:
            res["TPU"] = self.devices_per_worker or 1
        return res

    def _rendezvous(self) -> None:
        """PJRT coordinator election + rank env, exactly like a fit
        (plugins/xla.py)."""
        workers = self._workers
        coord_env = {}
        if self.num_workers > 1:
            ip = workers[0].call("get_node_ip").result(timeout=120)
            port = workers[0].call("get_free_port").result(timeout=120)
            coord_env = {"RLT_COORDINATOR": f"{ip}:{port}"}
        futs = [w.call("set_env_vars", {**coord_env,
                                        "RLT_PROCESS_ID": str(i)})
                for i, w in enumerate(workers)]
        self._wait_all(futs, timeout=120)

    def _start_telemetry(self) -> None:
        cfg = self.telemetry
        if not cfg.enabled:
            return
        from ray_lightning_tpu import telemetry
        from ray_lightning_tpu.telemetry import exporter as _exporter
        agg = telemetry.TelemetryAggregator(
            cfg.resolve_dir(self.default_root_dir),
            heartbeat_timeout=cfg.heartbeat_timeout,
            hard_timeout=cfg.hard_timeout,
            flight_capacity=cfg.flight_capacity,
            incident_cfg=cfg.resolved_incident(),
            run_kind="serve")
        for i, w in enumerate(self._workers):
            agg.register_worker(i, w)
        telemetry.set_active(agg)
        self._agg = agg
        if cfg.metrics:
            # driver-side registry (rank -1): the scheduler's
            # TTFT/TPOT/queue-depth/tokens instruments flush straight
            # into the aggregator and ride the same /metrics exposition
            # as the workers' windows
            telemetry.enable_metrics(rank=-1, sink=agg.ingest_metrics,
                                     interval=cfg.metrics_interval)
            # POST /debug/profile?steps=N: the pump attaches the armed
            # window to the next plan broadcast (tracing.py)
            from ray_lightning_tpu.telemetry.tracing import (
                ServeProfileController)
            self._profile_ctl = ServeProfileController(agg.out_dir)
            self._metrics_server = _exporter.start_metrics_server(
                agg, cfg, profile_controller=self._profile_ctl)

    @property
    def metrics_url(self) -> Optional[str]:
        return self._metrics_server.url \
            if self._metrics_server is not None else None

    def profile_status(self) -> Optional[dict]:
        """State of the on-demand jax.profiler window (same document
        ``/status`` serves under ``profile``); None when telemetry
        metrics are off."""
        return self._profile_ctl.status() \
            if self._profile_ctl is not None else None

    # -- request surface ---------------------------------------------------

    def submit(self, prompt, tenant: str = "default",
               max_new_tokens: Optional[int] = None,
               ship_kv: bool = False) -> ServeRequest:
        """Enqueue a prompt (token ids); returns a handle whose
        ``result()`` blocks for the generated tokens.  ``ship_kv``
        marks a disaggregation prefill leg: its prefill step exports
        the whole-page KV rows into the kv outbox alongside the step
        result (``export_kv(..., req_id=...)`` claims them)."""
        if not self._started:
            raise RuntimeError("Server.start() first")
        if self._draining:
            raise RuntimeError("server is draining; no new requests")
        if self._error is not None:
            raise RuntimeError("serve fleet failed") from self._error
        req = self.scheduler.submit(prompt, tenant=tenant,
                                    max_new_tokens=max_new_tokens,
                                    ship_kv=ship_kv)
        self._work.set()
        return req

    def generate(self, prompt, tenant: str = "default",
                 max_new_tokens: Optional[int] = None,
                 timeout: Optional[float] = 300.0) -> np.ndarray:
        """Blocking submit-and-wait."""
        return self.submit(prompt, tenant=tenant,
                           max_new_tokens=max_new_tokens).result(timeout)

    # -- KV-page shipping (fleet disaggregation) ---------------------------

    def can_ship_kv(self) -> bool:
        """Both ends of the KV-ship channel need paging (the prefix
        index addresses donor pages) and the kv_import programs."""
        return self._started and self.kvship and self.paged.enabled

    def export_kv(self, prompt_tokens, req_id: "int | None" = None):
        """Donor rows for the fleet's KV-ship leg — both the push
        path (disaggregation ships a just-prefilled request's pages
        to its decode replica) and the pull path (prefix federation
        fetches a RETAINED donor another replica advertised): the
        longest registered prefix of ``prompt_tokens`` on this
        replica as ``(k_rows, v_rows, matched_tokens)``, or ``None``
        (no donor — the federation caller treats that as a stale
        directory entry and invalidates it).
        Rows are exported at bucket granularity — the import side's
        AOT programs are per-bucket — and the importer registers only
        the matched whole pages, so the bucket tail never decodes.

        ``req_id`` (a ``submit(ship_kv=True)`` request) claims the
        rows the prefill step piggybacked into the kv outbox — the
        fast path with no worker round-trip; the donor match below is
        the fallback when the outbox entry was capped out."""
        sched = self.scheduler
        if sched.pages is None or not self._started:
            return None
        if req_id is not None:
            boxed = sched.pop_kv_export(int(req_id))
            if boxed is not None:
                return boxed
        prompt_tokens = np.asarray(prompt_tokens,
                                   dtype=np.int32).reshape(-1)
        # match and pin under ONE lock hold: an admission evicting (and
        # re-admitting) the donor between the match and the worker row
        # fetch would ship a DIFFERENT prompt's rows under this
        # prompt's registration
        with sched._lock:
            hit = sched.pages.match(prompt_tokens)
            if hit is None:
                return None
            src, matched = hit
            sched.pages.pin(src)
        try:
            from ray_lightning_tpu.serve.buckets import bucket_for
            bucket = bucket_for(matched, self.buckets)
            results = self._wait_all(
                [w.call("serve_export_kv", int(src), int(bucket))
                 for w in self._workers], timeout=120)
            rows = next(r for r in results if r is not None)
            return rows[0], rows[1], int(matched)
        finally:
            with sched._lock:
                sched.pages.unpin(src)

    def can_adopt_kv(self) -> bool:
        """Cheap capacity probe for the router's ship policy: is there
        a slot this replica could host shipped rows in RIGHT NOW (free,
        or reclaimable from an LRU donor)?  Racy by design — a ship
        admitted on a stale yes still fails safe in ``import_kv`` — but
        it lets the router skip the quantize/mailbox/install cost of a
        ship that is doomed before it starts (a saturated decode
        replica under burst)."""
        sched = self.scheduler
        if sched.pages is None or not self._started:
            return False
        with sched._lock:
            return (sched.allocator.free_count > 0
                    or sched.pages.donor_count > 0)

    def import_kv(self, prompt_tokens, k_rows, v_rows) -> bool:
        """Adopt shipped donor rows: acquire a donor slot, install the
        rows on every worker, then register the prefix (the order is
        the soundness story — scheduler.adopt_commit docstring).
        False = no adoptable slot (router falls back to pooled
        prefill)."""
        prompt_tokens = np.asarray(prompt_tokens,
                                   dtype=np.int32).reshape(-1)
        slot = self.scheduler.adopt_imported(prompt_tokens)
        if slot is None:
            return False
        try:
            self._wait_all(
                [w.call("serve_import_kv", int(slot), k_rows, v_rows)
                 for w in self._workers], timeout=120)
        except BaseException:
            self.scheduler.adopt_abort(slot)
            raise
        self.scheduler.adopt_commit(slot, prompt_tokens)
        return True

    # -- the pump ----------------------------------------------------------

    def _pump_loop(self) -> None:
        sched = self.scheduler
        if self._agg is not None:
            # the active aggregator is THREAD-local (aggregator.py: the
            # tune runner's per-trial threads need their own); the pump
            # is the thread draining the worker queue, so it must bind
            # the fleet's aggregator itself or every relayed telemetry
            # item would be dropped silently
            from ray_lightning_tpu import telemetry
            telemetry.set_active(self._agg)
        ledger = self._goodput_ledger = self._make_goodput_ledger()
        try:
            self._pump_iterations(sched, ledger)
        finally:
            self._finish_goodput()

    def _pump_iterations(self, sched, ledger) -> None:
        pump = sched.pump
        t = pump.start()
        self._next_peek = t + 2.0
        try:
            while not self._stop.is_set():
                with span("pump.step", step=pump.steps):
                    t, what = self._pump_step(sched, ledger, pump, t)
                if what == "stop":
                    return
                if what == "step" and self._kept_steps:
                    # after the step's root span closed, so that the
                    # window's last step keeps its root
                    self._kept_steps -= 1
                    if self._kept_steps == 0:
                        self._kept_window.close()
        finally:
            pump.stop()
            self._kept_steps = 0
            self._kept_window.close()

    def _pump_step(self, sched, ledger, pump, t: float):
        """One iteration: ``(now, what)`` with ``what`` one of ``step``,
        ``idle`` and ``stop``.  ``t`` is the clock at the last
        iteration's end: the phases partition the pump's wall time
        (scheduler.py ``PumpClock``)."""
        self._drain_queue()
        self._watchdog()
        if t >= self._next_peek:
            if ledger is not None:
                # live /status: ship a mid-run peek of the open
                # ledger (the finalized doc replaces it at pump exit)
                self._ship_goodput(ledger.peek())
            if self._agg is not None:
                # incident-plane serve detectors (queue depth,
                # TTFT/TPOT p99) tick at the same cadence
                self._agg.note_serve_signals(
                    queue_depth=sched.queued_count,
                    ttft_p99_s=sched.recent_ttft_p99(),
                    tpot_p99_s=sched.recent_tpot_p99())
            self._next_peek = t + 2.0
        t_loop = pump.now()
        with span("pump.plan"):
            plan = sched.plan()
        if plan is None:
            if self._draining and sched.idle():
                return t, "stop"
            with span("pump.idle"):
                self._work.wait(0.02)
                self._work.clear()
            now = pump.add("idle", t)
            if ledger is not None:
                ledger.add("queue_idle", now - t)
            return now, "idle"
        pump.add("loop", t, t_loop)
        t_plan = pump.add("plan", t_loop)
        if self._profile_ctl is not None:
            # armed profile window rides the SAME broadcast as the
            # trace ids — every worker starts its capture on this
            # plan and the driver counts the window's steps
            pending = self._profile_ctl.take_pending()
            if pending is not None:
                plan["profile"] = pending
        # the step's number rides the plan too: the worker's spans of
        # this step carry the same one
        plan["step"] = pump.steps
        if plan.get("profile") is not None and not self._kept_steps:
            # the workers capture this many steps from this plan on; the
            # pump is not in a captured process, so its spans of those
            # steps are kept in this one, telemetry on or off
            # (telemetry/spans.py ``kept("pump")``).  This step's own
            # root and plan span are already behind it.
            self._kept_steps = int(plan["profile"]["steps"])
            self._kept_window.enter_context(
                spans.keep("pump", own_thread=True))
        try:
            with span("pump.call", step=plan["step"]):
                futures = [w.call("serve_step", plan)
                           for w in self._workers]
            t_call = pump.add("call", t_plan)
            with span("pump.wait", step=plan["step"]):
                results = self._wait_all(futures, timeout=300)
            t_wait = pump.add("wait", t_call)
            # rank 0 alone carries the tokens (worker.py lockstep
            # contract); all-None means the backend lost it — a
            # fleet failure like any other, so it must raise INSIDE
            # this try or the pump dies without failing the
            # in-flight requests
            result = next((r for r in results if r is not None), None)
            if result is None:
                raise RuntimeError(
                    "no serve worker returned a step result "
                    "(rank 0's return value was lost)")
        except BaseException as e:   # noqa: BLE001 - fleet failure
            _log.error("serve step failed; failing %d live request(s)",
                       sched.active_count + sched.queued_count,
                       exc_info=True)
            self._error = e
            # black boxes FIRST: dump every rank's flight ring with
            # the serve cause while the evidence is fresh (the
            # elastic fit driver's death-classification discipline,
            # now on the serve pump too), then fail the waiters
            self.failure_report = self._dump_flights(e)
            sched.fail_all(e)
            return t_plan, "stop"
        timing = result.get("timing") or {}
        pump.worker_s += float(timing.get("serve_step", 0.0))
        pump.ahead_hits += timing.get("ahead") == "hit"
        pump.ahead_misses += timing.get("ahead") == "miss"
        if ledger is not None:
            # attribution rule: a dispatch that decodes produced
            # tokens (useful); a prefill-only dispatch is context
            # build — measured, but not goodput.  A speculative
            # round splits out its draft/verify wall (worker-
            # measured) so the ledger shows what speculation costs;
            # the verify IS the token-producing target forward, so
            # it stays in the useful "decode" bucket.
            step_s = t_wait - t_plan
            draft_s = float(timing.get("draft", 0.0))
            if plan.get("decode") is not None:
                ledger.add("draft", min(draft_s, step_s))
                ledger.note_step(max(0.0, step_s - draft_s))
            else:
                ledger.add("prefill", step_s)
        with span("pump.apply", step=plan["step"]):
            sched.apply(plan, result)
        if self._profile_ctl is not None:
            self._profile_ctl.note_step()
        t_apply = pump.add("apply", t_wait)
        # (``_kept_steps``: this step rode an on-demand profile window)
        pump.note_step(plan, (t, t_loop, t_plan, t_call, t_wait, t_apply),
                       profiled=self._kept_steps > 0)
        return t_apply, "step"

    # -- goodput (telemetry/goodput.py) ------------------------------------

    def _make_goodput_ledger(self):
        """Open the serve-side wall-clock ledger when the plane is
        armed: every pump second lands in decode / prefill /
        queue_idle (residual → other; the router adds autoscale
        actuation at the fleet level)."""
        cfg = self.telemetry
        if self._agg is None or not cfg.resolved_goodput():
            return None
        from ray_lightning_tpu.telemetry import goodput as _goodput
        devices = self.num_workers * int(self.devices_per_worker or 1)
        return _goodput.GoodputLedger(
            "serve", device_tflops=cfg.resolved_goodput_tflops(),
            devices=devices).start()

    def _ship_goodput(self, doc: dict) -> None:
        if self._agg is None or not doc:
            return
        from ray_lightning_tpu.telemetry import goodput as _goodput
        try:
            self._agg.ingest_goodput(_goodput.goodput_item(0, doc))
        except Exception:
            _log.debug("serve goodput ingest failed", exc_info=True)

    def _finish_goodput(self) -> None:
        ledger = self._goodput_ledger
        if ledger is None:
            return
        self._goodput_ledger = None
        from ray_lightning_tpu.telemetry import goodput as _goodput
        self.goodput_doc = doc = ledger.finalize()
        self._ship_goodput(doc)
        _goodput.publish_metrics(doc)

    def goodput(self) -> Optional[dict]:
        """This replica's goodput doc: the finalized partition after
        the pump exits, a live peek while it runs, None when the plane
        is disarmed.  The fleet router aggregates these
        (serve/fleet/router.py)."""
        if self.goodput_doc is not None:
            return self.goodput_doc
        ledger = self._goodput_ledger
        return ledger.peek() if ledger is not None else None

    def _dump_flights(self, error: BaseException) -> dict:
        """Per-rank ``flight_<rank>.json`` dumps for a mid-serve fleet
        failure (telemetry/flight.py).  Never raises — this runs inside
        the pump's failure handling."""
        report: dict = {"cause": repr(error), "flight_paths": {}}
        if self._agg is None:
            return report
        try:
            self._agg.log_failure_diagnosis()
            self._agg.dump_flights(
                range(self.num_workers),
                cause=f"serve fleet failure: {error!r}")
            report["flight_paths"] = {
                int(r): p for r, p in self._agg.flight.dumped.items()}
        except Exception:
            _log.warning("serve flight dump failed", exc_info=True)
        return report

    def _drain_queue(self) -> None:
        backend = self._backend
        while True:
            item = backend.queue_get_nowait()
            if item is None:
                return
            _handle_queue_item(item)

    def _watchdog(self) -> None:
        if self._agg is not None:
            try:
                self._agg.watchdog_check()
            except Exception:
                _log.warning("serve watchdog error", exc_info=True)

    def _wait_all(self, futures, timeout: float) -> list:
        """Resolve every worker future, relaying queue traffic while
        waiting (the fit path's process_results discipline)."""
        deadline = time.monotonic() + timeout
        while not all(f.done() for f in futures):
            if self._backend is not None:
                self._drain_queue()
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"serve worker call not done after {timeout}s")
            time.sleep(0.002)
        return [f.result() for f in futures]

    # -- drain / shutdown --------------------------------------------------

    def drain(self, timeout: Optional[float] = 300.0) -> None:
        """Graceful drain: stop admitting, finish every in-flight and
        queued request, stop the pump.  Idempotent."""
        self._draining = True
        self._work.set()
        if self._pump is not None and self._pump.is_alive():
            self._pump.join(timeout)
            if self._pump.is_alive():
                raise TimeoutError(f"drain incomplete after {timeout}s")

    def stats(self) -> dict:
        """Scheduler + worker evidence (trace counts, compile-cache
        hits) in one dict."""
        out = {"scheduler": self.scheduler.stats(),
               "setup": self._setup_info}
        gp = self.goodput()
        if gp:
            out["goodput"] = gp
        if self.failure_report is not None:
            out["failure"] = self.failure_report
        if self._started and self._workers:
            try:
                out["workers"] = self._wait_all(
                    [w.call("serve_stats") for w in self._workers],
                    timeout=60)
                # what the model's steps counted on the device
                # (serve/engine.py ``_counters``), beside the scheduler's
                # own counts of the same steps
                counted = out["workers"][0].get("counters")
                if counted is not None:
                    out["scheduler"]["device_counters"] = counted
            except Exception:
                _log.warning("serve_stats failed", exc_info=True)
        return out

    def shutdown(self, graceful: bool = True) -> None:
        """Drain (when ``graceful``), tear down telemetry and the
        fleet.  The process-wide cluster backend stays up (it is shared
        with any co-resident trainer)."""
        if graceful and self._started and self._error is None:
            try:
                self.drain()
            except TimeoutError:
                _log.warning("graceful drain timed out; killing fleet")
        self._stop.set()
        self._work.set()
        if self._pump is not None and self._pump.is_alive():
            self._pump.join(10)
        if self._started:
            try:
                self._wait_all([w.call("teardown_serve")
                                for w in self._workers], timeout=30)
            except Exception:
                _log.warning("serve teardown failed", exc_info=True)
        self._kill_workers()
        if self._agg is not None:
            from ray_lightning_tpu import telemetry
            telemetry.set_active(None)
            if self.telemetry.metrics:
                # only tear down the process-wide registry when THIS
                # server enabled it — a fleet replica running with
                # metrics=False must not disable the FleetServer's
                # driver registry on shrink (serve/fleet/router.py)
                telemetry.flush_metrics()
                telemetry.disable_metrics()
            if self._metrics_server is not None:
                self._metrics_server.stop()
            self.telemetry_paths = self._agg.export()
            if self._metrics_server is not None:
                self.telemetry_paths["metrics_url"] = \
                    self._metrics_server.url
            self._agg = None
            self._metrics_server = None
            self._profile_ctl = None
        self._started = False

    def _kill_workers(self) -> None:
        for w in self._workers:
            try:
                w.kill()
            except Exception:
                pass
        self._workers = []

    def __enter__(self) -> "Server":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown(graceful=exc[0] is None)


__all__ = ["Server", "ServeSpec"]
