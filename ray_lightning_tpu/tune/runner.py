"""The trial runner: ``tune.run`` executed locally, no Ray required.

Reference shape being reproduced (SURVEY.md §3.3): ``tune.run(train_fn,
config, num_samples, scheduler, resources_per_trial)`` → per-trial driver
runs ``train_fn(config)``, which builds a Trainer (possibly with a
distributed plugin whose actors train remotely) and reports metrics /
checkpoints through the session.  Returns an ``ExperimentAnalysis`` with
``best_config`` / ``best_checkpoint`` / per-trial ``last_result``.

Trials run in threads (``max_concurrent_trials``); the compute inside a
trial lives either in-process (LocalPlugin SPMD) or in actor
subprocesses (RayXlaPlugin), so threads are purely coordination.
"""

from __future__ import annotations

import inspect
import logging
import os
import threading
import time
import traceback
from typing import Any, Callable, Optional

from ray_lightning_tpu.tune.schedulers import (
    EXPLOIT, STOP, FIFOScheduler,
    PopulationBasedTraining, TrialScheduler)
from ray_lightning_tpu.tune.search import generate_variants
from ray_lightning_tpu.tune.session import TrialSession, set_session

_log = logging.getLogger(__name__)


class _StopTrial(Exception):
    pass


class _ExploitTrial(Exception):
    def __init__(self, config: dict, checkpoint: str):
        self.config = config
        self.checkpoint = checkpoint


class Trial:
    def __init__(self, trial_id: str, config: dict, logdir: str):
        self.trial_id = trial_id
        self.config = config
        self.logdir = logdir
        self.status = "PENDING"
        self.last_result: dict = {}
        self.history: list[dict] = []
        self.latest_checkpoint: Optional[str] = None
        self.error: Optional[str] = None
        #: where a telemetry-enabled Trainer inside this trial writes
        #: its trace.json/telemetry.jsonl: TelemetryConfig.resolve_dir
        #: resolves against the live trial session (tune/session.py), so
        #: concurrent trials never interleave into one shared dir
        self.telemetry_dir = os.path.join(logdir, "telemetry")
        #: /metrics endpoint of the trial's Trainer when the metrics
        #: exporter is enabled (always an ephemeral port inside a trial
        #: — concurrent trials never contend for one bind); recorded by
        #: telemetry/exporter.py; the listener dies with the trial's
        #: run, so the URL is only live while the trial executes
        self.metrics_url: Optional[str] = None
        #: device lease this trial ran on (in-process trials only;
        #: populated at first acquire — tune/session.py) for post-hoc
        #: "which chips ran this trial" debugging via ExperimentAnalysis
        self.leased_devices: list[str] = []
        #: PlanReport dict of a Trainer(strategy="auto") run inside
        #: this trial (tune/session.py note_plan_report) — which plan
        #: each trial trained under, for post-hoc sweep analysis; trial
        #: N>0 of a same-shaped sweep reuses trial 0's plan via the
        #: planner memo + the experiment's shared compile cache
        self.plan_report: Optional[dict] = None

    def __repr__(self):
        return f"Trial({self.trial_id}, {self.status})"


class ExperimentAnalysis:
    def __init__(self, trials: list[Trial], metric: Optional[str],
                 mode: str):
        self.trials = trials
        self.default_metric = metric
        self.default_mode = mode

    # -- reference-surface accessors (ray.tune.ExperimentAnalysis) ------

    @property
    def results(self) -> dict[str, dict]:
        return {t.trial_id: t.last_result for t in self.trials}

    def get_best_trial(self, metric: Optional[str] = None,
                       mode: Optional[str] = None) -> Optional[Trial]:
        metric = metric or self.default_metric
        mode = mode or self.default_mode
        sign = -1.0 if mode == "min" else 1.0
        best, best_v = None, None
        for t in self.trials:
            if t.status == "ERROR" or metric not in t.last_result:
                continue
            v = sign * float(t.last_result[metric])
            if best_v is None or v > best_v:
                best, best_v = t, v
        return best

    @property
    def best_trial(self) -> Optional[Trial]:
        return self.get_best_trial()

    @property
    def best_config(self) -> Optional[dict]:
        t = self.best_trial
        return t.config if t else None

    @property
    def best_checkpoint(self) -> Optional[str]:
        t = self.best_trial
        return t.latest_checkpoint if t else None

    @property
    def best_result(self) -> Optional[dict]:
        t = self.best_trial
        return t.last_result if t else None


def _accepts_checkpoint_dir(fn: Callable) -> bool:
    try:
        return "checkpoint_dir" in inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False


def _trial_device_demand(resources_per_trial: Any) -> Optional[int]:
    """Chips one trial wants, from ``get_tune_resources(...)`` output
    (TrialResources) or a plain ``{"TPU": n}`` dict.  None = no device
    demand declared (CPU-only bundles)."""
    if resources_per_trial is None:
        return None
    bundles = getattr(resources_per_trial, "bundles", None)
    if bundles is not None:
        demand = sum(int(b.get("TPU", 0)) for b in bundles)
    elif isinstance(resources_per_trial, dict):
        demand = int(resources_per_trial.get("TPU", 0))
    else:
        return None
    return demand or None


class _DeviceLeaser:
    """Partitions the visible devices into disjoint per-trial chunks.

    The reference gets trial isolation for free from Ray placement
    groups (tune.py:50-56: bundles exist precisely so trials never share
    devices); the local runner provides the same guarantee for
    *in-process* (LocalPlugin) trials — a trial leases its chunk when
    its Trainer first asks for devices and holds it for the trial's
    lifetime (including PBT exploit restarts); trials wanting more
    chips than remain simply wait, which serializes full-mesh trials.

    Everything is lazy: ``jax`` is imported (and the backend
    initialized) only inside a trial thread that actually trains
    in-process.  Actor-based trials never acquire, so a CPU-only tune
    driver stays free of any JAX backend and cluster-level chip demands
    are left to the cluster backend — exactly the reference's split,
    where placement groups size *cluster* resources and the trial
    driver itself stays thin.
    """

    def __init__(self, per_trial: int):
        self._per_trial = per_trial
        self._chunks: Optional[list] = None
        self._cond = threading.Condition()

    def _ensure_chunks(self) -> None:
        if self._chunks is not None:
            return
        import jax
        devices = list(jax.devices())
        if self._per_trial > len(devices):
            raise ValueError(
                f"resources_per_trial wants {self._per_trial} devices "
                f"but only {len(devices)} are visible to this process")
        stranded = len(devices) % self._per_trial
        if stranded:
            # the reference's placement groups make trial placement
            # inspectable (reference tune.py:50-56); the least we owe the
            # operator is a loud note that part of the host sits idle
            _log.warning(
                "resources_per_trial=%d does not divide the %d visible "
                "devices: %d device(s) (%s) will sit idle under the "
                "trial lease partition.", self._per_trial, len(devices),
                stranded,
                ", ".join(str(d) for d in devices[-stranded:]))
        self._chunks = [
            devices[i:i + self._per_trial]
            for i in range(0, len(devices) - self._per_trial + 1,
                           self._per_trial)]

    def acquire(self) -> list:
        with self._cond:
            self._ensure_chunks()
            while not self._chunks:
                self._cond.wait()
            return self._chunks.pop()

    def release(self, chunk: list) -> None:
        with self._cond:
            self._chunks.append(chunk)
            self._cond.notify()


def run(
    trainable: Callable,
    config: Optional[dict] = None,
    *,
    num_samples: int = 1,
    scheduler: Optional[TrialScheduler] = None,
    metric: Optional[str] = None,
    mode: Optional[str] = None,
    stop: Optional[dict] = None,
    resources_per_trial: Any = None,
    local_dir: Optional[str] = None,
    name: Optional[str] = None,
    max_concurrent_trials: Optional[int] = None,
    max_failures: int = 0,
    fail_fast: bool = False,
    raise_on_failed_trial: bool = True,
    seed: int = 0,
    verbose: int = 1,
) -> ExperimentAnalysis:
    """Run ``num_samples`` trials of ``trainable`` over ``config``.

    ``trainable(config)`` or ``trainable(config, checkpoint_dir=None)``
    (the latter enables PBT exploit restores and checkpoint-resumed
    trial retries, reference-PBT/Tune contract).

    ``max_failures``: retry a crashed trial up to this many times
    (``ray.tune`` ``max_failures`` parity — the reference's recovery
    story is exactly "Tune trial retries + checkpoints", SURVEY.md §5);
    a trainable with a ``checkpoint_dir`` parameter resumes from the
    trial's latest checkpoint.

    Telemetry: a trial whose Trainer enables telemetry writes its
    trace/jsonl under the trial's own logdir (``Trial.telemetry_dir``)
    — the thread-local trial session scopes both the output dir and
    the active driver-side aggregator per trial.

    Compilation: trials use the one persistent XLA compilation cache
    every Trainer uses (compile/cache.py: ``JAX_COMPILATION_CACHE_DIR``
    or the fixed in-checkout directory; ``RLT_COMPILE_CACHE=0`` opts
    out), so same-shape trials after the first — crash-retried trials
    and LATER experiments included — warm-start instead of re-paying
    XLA compilation.

    Device isolation: when ``resources_per_trial`` declares a TPU chip
    count (``get_tune_resources(...)`` bundles or ``{"TPU": n}``), the
    visible devices are partitioned into disjoint n-chip leases that
    *in-process* (LocalPlugin) trials acquire when their Trainer first
    asks for devices — each such trial's mesh spans only its lease,
    effective concurrency is ``len(devices) // n``, and trials wanting
    the full mesh serialize.  Trials whose compute runs in actor
    subprocesses never acquire a lease (their chip demand is a cluster
    resource, the backend's job), so the tune driver itself never
    initializes a JAX backend.  Without a declared chip count,
    concurrent in-process trials share every visible device — declare
    resources to isolate them.
    """
    scheduler = scheduler or FIFOScheduler(metric or "loss", mode or "min")
    # metric/mode default from the scheduler as one unit, so analysis
    # ranking agrees with the scheduling direction
    if metric is None:
        metric = scheduler.metric
    if mode is None:
        mode = scheduler.mode
    local_dir = local_dir or os.path.join(os.getcwd(), "rlt_tune")
    exp_name = name or f"exp_{int(time.time())}"
    exp_dir = os.path.join(local_dir, exp_name)
    os.makedirs(exp_dir, exist_ok=True)

    variants = generate_variants(dict(config or {}), num_samples, seed)
    trials = []
    for i, cfg in enumerate(variants):
        tid = f"trial_{i:05d}"
        logdir = os.path.join(exp_dir, tid)
        os.makedirs(logdir, exist_ok=True)
        trials.append(Trial(tid, cfg, logdir))

    stop = dict(stop or {})
    takes_ckpt = _accepts_checkpoint_dir(trainable)
    errors: list[BaseException] = []
    errors_lock = threading.Lock()
    abort = threading.Event()  # fail_fast: first error stops the sweep

    if max_concurrent_trials is None:
        # PBT is population-based: the population must coexist.
        max_concurrent_trials = (
            len(trials) if isinstance(scheduler, PopulationBasedTraining)
            else 1)
    demand = _trial_device_demand(resources_per_trial)
    leaser = _DeviceLeaser(demand) if demand is not None else None
    sem = threading.Semaphore(max(1, max_concurrent_trials))

    def on_report(trial: Trial, metrics: dict) -> None:
        trial.last_result = dict(metrics)
        trial.history.append(dict(metrics))
        if abort.is_set():
            raise _StopTrial()
        it = int(metrics.get("training_iteration", 0))
        stop_it = stop.get("training_iteration")
        decision = scheduler.on_result(trial, metrics)
        if decision.action == EXPLOIT:
            trial.config = dict(decision.config)
            raise _ExploitTrial(decision.config, decision.checkpoint)
        if decision.action == STOP or (stop_it and it >= stop_it):
            raise _StopTrial()
        for key, bound in stop.items():
            if key in metrics and key != "training_iteration" \
                    and float(metrics[key]) >= float(bound):
                raise _StopTrial()

    def run_trial(trial: Trial) -> None:
        with sem:
            if abort.is_set():
                return  # fail_fast tripped; leave trial PENDING
            trial.status = "RUNNING"
            session = TrialSession(trial, on_report, device_leaser=leaser)
            set_session(session)
            restore_from: Optional[str] = None
            failures = 0
            try:
                while True:
                    try:
                        if takes_ckpt:
                            trainable(dict(trial.config),
                                      checkpoint_dir=restore_from)
                        else:
                            trainable(dict(trial.config))
                        trial.status = "TERMINATED"
                        return
                    except _StopTrial:
                        trial.status = "TERMINATED"
                        return
                    except _ExploitTrial as e:
                        if not takes_ckpt:
                            _log.warning(
                                "PBT exploit requested but %s has no "
                                "checkpoint_dir parameter; continuing "
                                "without restore.", trainable)
                        restore_from = e.checkpoint
                        # the donor checkpoint is now this trial's
                        # restore source: a crash-retry after the
                        # exploit must resume the exploited weights,
                        # not the trial's stale pre-exploit checkpoint
                        trial.latest_checkpoint = e.checkpoint
                        _log.info("%s exploiting: restart from %s",
                                  trial.trial_id, e.checkpoint)
                        continue  # restart with mutated config
                    except Exception:
                        # trial retry — the reference's ONLY recovery
                        # story (SURVEY.md §5 failure detection: "Tune
                        # trial retries + checkpoints"): restart the
                        # trainable, resuming from its latest checkpoint
                        # when it takes one.  Exception only: SystemExit
                        # / KeyboardInterrupt are deliberate exits, not
                        # retryable crashes (ray.tune parity) — the
                        # outer handler records them once.
                        failures += 1
                        if failures > max_failures or abort.is_set():
                            raise
                        restore_from = (trial.latest_checkpoint
                                        if takes_ckpt else None)
                        _log.warning(
                            "%s failed (attempt %d/%d); retrying%s:\n%s",
                            trial.trial_id, failures, max_failures + 1,
                            f" from {restore_from}" if restore_from
                            else "", traceback.format_exc())
                        continue
            except BaseException as e:          # noqa: BLE001
                trial.status = "ERROR"
                trial.error = traceback.format_exc()
                with errors_lock:
                    errors.append(e)
                if fail_fast:
                    abort.set()
                if verbose:
                    _log.error("%s failed:\n%s", trial.trial_id, trial.error)
            finally:
                scheduler.on_trial_complete(trial)
                set_session(None)
                session.release_devices()

    threads = [threading.Thread(target=run_trial, args=(t,), daemon=True)
               for t in trials]
    for th in threads:
        th.start()
    for th in threads:
        th.join()

    if errors and (fail_fast or raise_on_failed_trial):
        # ray.tune parity: any failed trial raises by default, so partial
        # failures can't be misread as complete sweeps
        failed = [t.trial_id for t in trials if t.status == "ERROR"]
        raise RuntimeError(
            f"{len(failed)} trial(s) failed: {failed}. First error "
            f"below; pass raise_on_failed_trial=False to get a partial "
            f"ExperimentAnalysis instead.") from errors[0]
    return ExperimentAnalysis(trials, metric, mode)
