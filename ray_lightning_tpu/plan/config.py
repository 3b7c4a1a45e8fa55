"""Planner configuration (``Trainer(strategy="auto")`` knobs).

``PlanConfig`` is the frozen, picklable settings object of the planner
plane, following the ``CommPolicy`` / ``ElasticConfig`` construction
pattern (first match wins):

- ``Trainer(plan=PlanConfig(...))`` — full control;
- ``Trainer(plan={...})`` — kwargs dict;
- ``AutoStrategy(plan=...)`` — per-strategy override;
- ``RLT_PLAN_TOPK`` / ``RLT_PLAN_ICI_GBPS`` / ``RLT_PLAN_DCN_GBPS`` /
  ``RLT_PLAN_STRATEGIES`` / ``RLT_PLAN_MICROBATCH`` /
  ``RLT_PLAN_REMAT`` / ``RLT_PLAN_HBM_GBPS`` / ``RLT_PLAN_TFLOPS`` /
  ``RLT_PLAN_HBM_BYTES`` / ``RLT_PLAN_HEADROOM`` — env knobs, read when
  the Trainer arg is ``None``.
- ``RLT_PLAN_CALIBRATE=1`` — replace the bandwidth constants with
  MEASURED link speeds (comm/calibrate.py: a tiny collective
  microbench, run once and cached per topology fingerprint).
  ``RLT_PLAN_CALIBRATE=live`` (or ``anatomy``) goes further: the last
  instrumented run's anatomy-measured exposed-comm vs modeled-comm
  ratio scales the constants (comm/calibrate.py live_calibration),
  falling back to the microbench when no live sample exists yet.
  Explicit ``RLT_PLAN_{ICI,DCN}_GBPS`` values still win.

The resolved config pickles driver→worker on the Trainer and
round-trips through ``worker_env()`` like the comm/compile/elastic
knobs do, so every rank of a fleet plans from identical inputs — the
planner's ranking keys are deterministic by construction (see
plan/planner.py) and identical config is what keeps an SPMD fleet
agreeing on one winner without a collective.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

from ray_lightning_tpu.comm.audit import DCN_GBPS, ICI_GBPS

#: strategy names the planner may enumerate (canonical spellings only —
#: aliases like "dp"/"sharded" resolve to the same classes)
PLANNABLE_STRATEGIES = ("ddp", "zero1", "fsdp", "spmd")

ENV_TOPK = "RLT_PLAN_TOPK"
ENV_ICI = "RLT_PLAN_ICI_GBPS"
ENV_DCN = "RLT_PLAN_DCN_GBPS"
ENV_STRATEGIES = "RLT_PLAN_STRATEGIES"
ENV_MICROBATCH = "RLT_PLAN_MICROBATCH"
ENV_HBM = "RLT_PLAN_HBM_BYTES"
ENV_HEADROOM = "RLT_PLAN_HEADROOM"
ENV_CALIBRATE = "RLT_PLAN_CALIBRATE"
ENV_REMAT = "RLT_PLAN_REMAT"
ENV_HBM_GBPS = "RLT_PLAN_HBM_GBPS"
ENV_TFLOPS = "RLT_PLAN_TFLOPS"
ENV_KNOBS = (ENV_TOPK, ENV_ICI, ENV_DCN, ENV_STRATEGIES, ENV_MICROBATCH,
             ENV_HBM, ENV_HEADROOM, ENV_CALIBRATE, ENV_REMAT,
             ENV_HBM_GBPS, ENV_TFLOPS)

#: modeled HBM bandwidth the remat cost term charges saved-activation
#: round-trips at (v5e-class default, same convention as the comm-plane
#: link constants); override per device generation
HBM_GBPS = 819.0
#: modeled ACHIEVED matmul rate for recompute chains — deliberately
#: below a v5e's ~197 bf16 peak TFLOPs because remat'd forward
#:re-execution runs inside backward fusions at well under peak MFU
#: (calibrated against a pre-round gpt2-medium full-vs-dots walk on
#: one v5e: 177.4 vs 150.3 ms/step device; no cell re-measures it)
DEVICE_TFLOPS = 65.0


@dataclasses.dataclass(frozen=True)
class PlanConfig:
    """What the planner enumerates and how it scores.

    topk: how many model-ranked survivors get AOT-compiled for the
        verify stage (0 skips verification: pick on the byte model
        alone).  The compile-cache miss counters bound the real compile
        work at ``topk`` — the acceptance invariant tests/test_plan.py
        pins.
    ici_gbps / dcn_gbps: modeled per-link payload bandwidths for the
        byte→seconds conversion (comm/audit.py constants by default;
        override per fabric generation).
    strategies: candidate strategy inventory (subset of
        :data:`PLANNABLE_STRATEGIES`).
    microbatch: candidate ``accumulate_grad_batches`` values.  ``(1,)``
        by default — microbatching only trades step time for memory, so
        it is an opt-in dimension.
    remat: candidate remat-policy names.  ``()`` (the default) sweeps
        every policy the module's ``configure_remat()`` ladder
        declares; a non-empty tuple restricts the sweep (unsupported
        names are pruned as ``remat_unsupported``).  An
        ``RLT_REMAT_POLICY`` env override pins the axis to that single
        policy (plan/candidates.py ``resolve_remat_options``) — the
        sweep would compile programs the env forces to one policy
        anyway.
    hbm_gbps: modeled HBM bandwidth for the remat activation-traffic
        term (saved activations cost one store + one load per step).
    device_tflops: modeled achieved matmul rate for the remat
        recompute-FLOPs term (below peak — see DEVICE_TFLOPS note).
    hbm_budget_bytes: per-device memory budget override (None = ask the
        device, like the donation heuristic does).
    headroom: fraction of the budget modeled residents may use (the
        rest absorbs XLA workspace/fragmentation — same 0.9 convention
        as tests/test_memory_fit.py).
    activation_factor: crude activations-per-batch-byte multiplier for
        the no-compile peak estimate; the AOT verify stage replaces it
        with the compiled program's real ``memory_analysis`` bytes.
    max_candidates: hard cap on scored candidates; overflow is recorded
        in the report (never silently dropped).
    reuse: allow per-trial plan reuse inside a tune experiment (the
        memoized report short-circuits re-planning for same-shaped
        trials; the shared compile cache already makes their verify
        compiles warm).
    """

    topk: int = 3
    ici_gbps: float = ICI_GBPS
    dcn_gbps: float = DCN_GBPS
    strategies: tuple = PLANNABLE_STRATEGIES
    microbatch: tuple = (1,)
    remat: tuple = ()
    hbm_gbps: float = HBM_GBPS
    device_tflops: float = DEVICE_TFLOPS
    hbm_budget_bytes: Optional[int] = None
    headroom: float = 0.9
    activation_factor: float = 8.0
    # the remat axis multiplies the space (a 6-policy MoE ladder over
    # the PR-8 axes lands near 100); the cap exists against runaway
    # enumeration, not to truncate the default sweep
    max_candidates: int = 256
    reuse: bool = True

    def __post_init__(self):
        if self.topk < 0:
            raise ValueError("plan topk must be >= 0")
        if self.ici_gbps <= 0 or self.dcn_gbps <= 0:
            raise ValueError("plan bandwidths must be positive")
        if not (0.0 < self.headroom <= 1.0):
            raise ValueError("plan headroom must be in (0, 1]")
        if self.max_candidates < 1:
            raise ValueError("plan max_candidates must be >= 1")
        object.__setattr__(self, "strategies", tuple(self.strategies))
        unknown = [s for s in self.strategies
                   if s not in PLANNABLE_STRATEGIES]
        if unknown:
            raise ValueError(
                f"unplannable strategies {unknown}; "
                f"options: {PLANNABLE_STRATEGIES}")
        mb = tuple(int(m) for m in self.microbatch)
        if not mb or any(m < 1 for m in mb):
            raise ValueError("plan microbatch values must be >= 1")
        object.__setattr__(self, "microbatch", mb)
        rm = tuple(str(p) for p in self.remat)
        if any(not p for p in rm):
            raise ValueError("plan remat policy names must be non-empty")
        object.__setattr__(self, "remat", rm)
        if self.hbm_gbps <= 0 or self.device_tflops <= 0:
            raise ValueError(
                "plan hbm_gbps / device_tflops must be positive")

    # -- construction ----------------------------------------------------

    @classmethod
    def resolve(cls, value) -> "PlanConfig":
        if isinstance(value, cls):
            return value
        if isinstance(value, dict):
            return cls(**value)
        if value is not None:
            raise TypeError(f"bad plan config: {value!r}")
        kw = {}
        raw = os.environ.get(ENV_TOPK, "").strip()
        if raw:
            kw["topk"] = int(raw)
        raw_cal = os.environ.get(ENV_CALIBRATE, "").strip().lower()
        if raw_cal in ("1", "true"):
            # measured link bandwidths (cached per topology) replace
            # the constants; explicit RLT_PLAN_*_GBPS still win below
            from ray_lightning_tpu.comm.calibrate import calibrated_gbps
            kw["ici_gbps"], kw["dcn_gbps"] = calibrated_gbps()
        elif raw_cal in ("live", "anatomy"):
            # live anatomy calibration (ROADMAP 5(a) leg): the previous
            # instrumented run's measured-exposed / modeled-comm ratio
            # (comm/calibrate.py save_live_calibration) scales BOTH link
            # constants — modeled comm seconds are linear in 1/gbps, so
            # dividing by comm_scale makes the next plan's model match
            # what the fabric delivered.  No stored sample yet falls
            # back to the microbench path.
            from ray_lightning_tpu.comm import calibrate as _cal
            live = _cal.live_calibration()
            if live is not None:
                scale = float(live["comm_scale"])
                kw["ici_gbps"] = round(_cal.ICI_GBPS / scale, 3)
                kw["dcn_gbps"] = round(_cal.DCN_GBPS / scale, 3)
            else:
                kw["ici_gbps"], kw["dcn_gbps"] = _cal.calibrated_gbps()
        raw = os.environ.get(ENV_ICI, "").strip()
        if raw:
            kw["ici_gbps"] = float(raw)
        raw = os.environ.get(ENV_DCN, "").strip()
        if raw:
            kw["dcn_gbps"] = float(raw)
        raw = os.environ.get(ENV_STRATEGIES, "").strip()
        if raw:
            kw["strategies"] = tuple(s for s in raw.split(",") if s)
        raw = os.environ.get(ENV_MICROBATCH, "").strip()
        if raw:
            kw["microbatch"] = tuple(int(m) for m in raw.split(",") if m)
        raw = os.environ.get(ENV_REMAT, "").strip()
        if raw:
            kw["remat"] = tuple(p for p in raw.split(",") if p)
        raw = os.environ.get(ENV_HBM_GBPS, "").strip()
        if raw:
            kw["hbm_gbps"] = float(raw)
        raw = os.environ.get(ENV_TFLOPS, "").strip()
        if raw:
            kw["device_tflops"] = float(raw)
        raw = os.environ.get(ENV_HBM, "").strip()
        if raw:
            kw["hbm_budget_bytes"] = int(raw)
        raw = os.environ.get(ENV_HEADROOM, "").strip()
        if raw:
            kw["headroom"] = float(raw)
        return cls(**kw)

    # -- env round-trip --------------------------------------------------

    def worker_env(self) -> dict:
        """Env mapping reproducing this config via :meth:`resolve` in a
        worker process (only non-default fields are emitted — a default
        config leaves the worker env untouched)."""
        default = PlanConfig()
        env = {}
        if self.topk != default.topk:
            env[ENV_TOPK] = str(self.topk)
        if self.ici_gbps != default.ici_gbps:
            env[ENV_ICI] = repr(self.ici_gbps)
        if self.dcn_gbps != default.dcn_gbps:
            env[ENV_DCN] = repr(self.dcn_gbps)
        if self.strategies != default.strategies:
            env[ENV_STRATEGIES] = ",".join(self.strategies)
        if self.microbatch != default.microbatch:
            env[ENV_MICROBATCH] = ",".join(str(m) for m in self.microbatch)
        if self.remat != default.remat:
            env[ENV_REMAT] = ",".join(self.remat)
        if self.hbm_gbps != default.hbm_gbps:
            env[ENV_HBM_GBPS] = repr(self.hbm_gbps)
        if self.device_tflops != default.device_tflops:
            env[ENV_TFLOPS] = repr(self.device_tflops)
        if self.hbm_budget_bytes is not None:
            env[ENV_HBM] = str(self.hbm_budget_bytes)
        if self.headroom != default.headroom:
            env[ENV_HEADROOM] = repr(self.headroom)
        return env
