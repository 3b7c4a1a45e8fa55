"""Compile-free candidate scoring: bytes → seconds, avals → HBM fit.

Two estimates per candidate, both computed WITHOUT compiling anything:

- **communication seconds**: the strategy's own per-step traffic
  declaration (``step_collective_bytes`` — the same numbers the metrics
  plane charges, pinned against audited HLO wire bytes by
  tests/test_plan.py's drift guard) converted through the per-link
  bandwidth model (comm/audit.py ``bytes_to_seconds``): each op is
  scored at ITS link's bandwidth — ``_ici``-suffixed ops (the fp32
  intra-host phases of a hierarchical sync) always ride ICI, everything
  else rides DCN when the run spans processes (the mesh construction
  puts the data axis across hosts) and ICI otherwise.  Without the
  split, a hierarchical candidate's 8-bytes/element ICI phases would be
  charged at DCN speed and the planner would mis-rank it below the flat
  codec it strictly beats on the slow link.
- **HBM peak**: the sharded TrainState residency from ``eval_shape``
  avals + the strategy's shardings (exact per-leaf shard bytes, the
  tests/test_memory_fit.py account), plus the big transients (grads at
  param dtype and fp32 update deltas, mirroring the PARAM sharding —
  replicated-param strategies materialize them full-size, param-sharded
  ones keep them shard-sized) and an activation term: when the module
  declares a ``configure_remat()`` ladder, the candidate policy's
  SAVED-ACTIVATION bytes (core/remat.py probe — eval_shape of each
  block's saveable residual set, scaled to the candidate's per-device
  microbatch and damped by :data:`REMAT_RESIDENCY_FACTOR` for XLA's
  buffer sharing); otherwise the crude batch-proportional proxy of
  PR 8.  Donation follows the measured decision logic: an un-donated
  step carries a second state copy (old + new — the
  ``Trainer._donation_cutoff`` story).
- **remat seconds** (:func:`remat_terms`): what the candidate's remat
  policy costs per step — saved activations pay one HBM store + one
  load (``2·bytes / hbm_gbps``), recomputed matmuls pay
  ``flops / device_tflops`` at the deliberately-sub-peak achieved
  rate, and every remat region pays a small fixed scheduling overhead
  per microbatch (:data:`REMAT_BLOCK_OVERHEAD_S`) — the term that
  makes "off" win on small models where recompute latency, not bytes,
  dominates.  This is the score that trades memory against recompute
  against comm: it adds to the comm seconds in :func:`rank_key`.

Candidates whose modeled peak exceeds the headroom-scaled budget are
rejected with a named reason; the AOT verify stage later replaces these
estimates with the compiled program's real ``memory_analysis`` bytes
and audited wire bytes for the top-k survivors.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np

from ray_lightning_tpu.comm.audit import bytes_to_seconds
from ray_lightning_tpu.plan.candidates import Candidate
from ray_lightning_tpu.plan.config import PlanConfig


def sharded_bytes(abstract_tree, shardings_tree) -> int:
    """Per-device bytes of ``abstract_tree`` under the given shardings
    (exact: per-leaf ``shard_shape``)."""
    leaves = jax.tree_util.tree_leaves(abstract_tree)
    shs = jax.tree_util.tree_leaves(
        shardings_tree, is_leaf=lambda x: hasattr(x, "spec"))
    total = 0
    for aval, sh in zip(leaves, shs):
        shape = sh.shard_shape(aval.shape) \
            if hasattr(sh, "shard_shape") else aval.shape
        total += int(np.prod(shape, dtype=np.int64)) * aval.dtype.itemsize
    return total


def _sharded_elements(abstract_tree, shardings_tree) -> int:
    leaves = jax.tree_util.tree_leaves(abstract_tree)
    shs = jax.tree_util.tree_leaves(
        shardings_tree, is_leaf=lambda x: hasattr(x, "spec"))
    total = 0
    for aval, sh in zip(leaves, shs):
        shape = sh.shard_shape(aval.shape) \
            if hasattr(sh, "shard_shape") else aval.shape
        total += int(np.prod(shape, dtype=np.int64))
    return total


#: fraction of a policy's RAW saved-residual bytes modeled as live HBM
#: (and round-tripped): ``saved_residuals`` lists every residual at its
#: own dtype while XLA's buffer assignment shares/dedups aggressively —
#: calibrated against compiled ``memory_analysis`` temp deltas of the
#: tiny-GPT programs (tests/test_plan.py remat drift leg) and a
#: pre-round gpt2-medium walk on one v5e (off 18.95 GB vs dots ~10 GB;
#: no cell re-measures it)
REMAT_RESIDENCY_FACTOR = 0.3

#: modeled fixed cost of one remat region's backward re-entry (extra
#: kernel launches + the fusion break at the region boundary) per
#: microbatch — the term that keeps "off" the winner on tiny models
#: where the saved bytes are microseconds of traffic
REMAT_BLOCK_OVERHEAD_S = 5e-6


def remat_terms(probe, policy: str, config: PlanConfig,
                process_count: int, dp: int,
                microbatch: int) -> "tuple[int, float]":
    """(peak activation bytes, remat seconds) for one candidate.

    ``probe`` is the module's :class:`~ray_lightning_tpu.core.remat.
    RematProbe` at the process-LOCAL example batch; every probe
    quantity is linear in batch, so the per-device step scale is
    ``process_count / dp`` (global batch = local × processes, split
    over dp data shards).  Peak residency divides by the microbatch
    count (only one microbatch's activations are live); traffic and
    recompute do not (every microbatch pays them each step).
    """
    scale = process_count / max(1, dp)
    saved = probe.saved_bytes * REMAT_RESIDENCY_FACTOR * scale
    act_bytes = int(saved / max(1, microbatch))
    seconds = bytes_to_seconds(2 * saved, config.hbm_gbps)
    seconds += (probe.recompute_flops * scale
                / (config.device_tflops * 1e12))
    if policy != "off":
        seconds += probe.n_blocks * microbatch * REMAT_BLOCK_OVERHEAD_S
    return act_bytes, seconds


def link_gbps(op: str, config: PlanConfig, process_count: int) -> float:
    """The modeled bandwidth ONE declared collective op rides (module
    docstring): ``_ici``-suffixed ops always score at ICI speed; every
    other op crosses DCN exactly when the run spans processes."""
    if op.endswith("_ici"):
        return config.ici_gbps
    return config.dcn_gbps if process_count > 1 else config.ici_gbps


#: modeled fraction of a ``_bucketed`` collective's time that stays
#: EXPOSED after XLA's latency-hiding scheduler overlaps it with
#: adjacent compute.  Deliberately conservative (half hidden): the
#: planner must not promise overlap the fabric can't deliver; the
#: measured judge is ``train_exposed_comm_ms`` once a cell runs on
#: four chips (ROADMAP R11), and the
#: declared bytes stay the full payload (only seconds are discounted —
#: bucketing moves WHEN bytes travel, never how many).
BUCKETED_EXPOSED_FRACTION = 0.5


def op_overlap_factor(op: str) -> float:
    """Multiplier on one declared op's modeled seconds: ``_bucketed``
    ops (the latency-hidden ZeRO-1 param gather,
    comm/collectives.py ``regather_params``) count only their modeled
    exposed fraction; every other op is fully exposed."""
    return BUCKETED_EXPOSED_FRACTION if op.endswith("_bucketed") else 1.0


def device_memory_budget(device, config: PlanConfig) -> Optional[int]:
    """Per-device HBM budget: the config override, the runtime's
    reported limit, or the known-HBM-by-kind table the donation
    heuristic uses (core/trainer.py) — ``None`` when nothing knows
    (virtual CPU meshes), in which case memory never rejects."""
    if config.hbm_budget_bytes is not None:
        return int(config.hbm_budget_bytes)
    try:
        stats = device.memory_stats()
        if stats and "bytes_limit" in stats:
            return int(stats["bytes_limit"])
    except Exception:
        pass
    if getattr(device, "platform", None) == "tpu":
        from ray_lightning_tpu.core.trainer import Trainer
        return Trainer._HBM_BY_KIND.get(getattr(device, "device_kind", ""))
    return None


@dataclasses.dataclass
class Estimate:
    """One candidate's compile-free score."""

    comm_bytes: int
    comm_seconds: float
    state_bytes: int           # sharded TrainState residency per device
    peak_bytes: int            # state + transients (+ un-donated copy)
    budget: Optional[int]
    donate_preferred: bool     # what the measured donation heuristic
    #                            would pick for this state/budget pair
    reason: Optional[str] = None   # rejection reason (None = fits)
    remat_policy: str = ""     # candidate's policy ("" = no remat axis)
    act_bytes: int = 0         # modeled live activations (remat-aware
    #                            when the module declares a ladder)
    remat_seconds: float = 0.0  # traffic + recompute + region overhead

    @property
    def fits(self) -> bool:
        return self.reason is None

    @property
    def step_seconds(self) -> float:
        """The modeled per-step cost that ranks: comm + remat."""
        return self.comm_seconds + self.remat_seconds

    def to_dict(self) -> dict:
        return {
            "comm_bytes": int(self.comm_bytes),
            "comm_seconds": float(self.comm_seconds),
            "state_bytes": int(self.state_bytes),
            "peak_bytes": int(self.peak_bytes),
            "budget_bytes": self.budget,
            "donate_preferred": self.donate_preferred,
            "remat_policy": self.remat_policy or None,
            "act_bytes": int(self.act_bytes),
            "remat_seconds": float(self.remat_seconds),
        }


def estimate_candidate(
    candidate: Candidate,
    strategy,
    mesh,
    abstract_state,
    shardings,
    batch_bytes_global: int,
    config: PlanConfig,
    process_count: int,
    grad_sync=None,
    remat_probe=None,
) -> Estimate:
    """Score one candidate from avals alone (module docstring).

    ``remat_probe`` is the module's priced :class:`RematProbe` for THIS
    candidate's policy (None when the module has no remat ladder — the
    activation term then falls back to the PR-8 batch proxy)."""
    from ray_lightning_tpu.core.trainer import Trainer

    op_bytes = strategy.step_collective_bytes(mesh, abstract_state,
                                              comm=grad_sync)
    comm_bytes = int(sum(op_bytes.values()))
    comm_seconds = sum(
        bytes_to_seconds(b, link_gbps(op, config, process_count))
        * op_overlap_factor(op)
        for op, b in op_bytes.items())

    state_bytes = sharded_bytes(abstract_state, shardings)
    # grads mirror the param sharding at param dtype; fp32 update deltas
    # likewise (replicated-param strategies materialize both full-size —
    # the audited f32 all-gather of updates, tests/test_memory_fit.py)
    grads_bytes = sharded_bytes(abstract_state.params, shardings.params)
    updates_bytes = 4 * _sharded_elements(abstract_state.params,
                                          shardings.params)
    dp = max(1, strategy.data_parallel_size(mesh))
    remat_seconds = 0.0
    if remat_probe is not None:
        act_bytes, remat_seconds = remat_terms(
            remat_probe, candidate.remat, config, process_count, dp,
            max(1, candidate.microbatch))
    else:
        act_bytes = int(batch_bytes_global / dp * config.activation_factor
                        / max(1, candidate.microbatch))
    peak = (state_bytes * (1 if candidate.donate else 2)
            + grads_bytes + updates_bytes + act_bytes)

    budget = device_memory_budget(mesh.devices.flat[0], config)
    donate_preferred = True if budget is None \
        else Trainer._donation_cutoff(state_bytes, budget)
    reason = None
    if budget is not None and peak > config.headroom * budget:
        reason = (f"hbm_over_budget: modeled peak {peak >> 20} MiB "
                  f"({'donated' if candidate.donate else 'un-donated'}) "
                  f"> {int(config.headroom * budget) >> 20} MiB "
                  f"({config.headroom:.0%} of {budget >> 20} MiB/device)")
    return Estimate(comm_bytes=comm_bytes, comm_seconds=comm_seconds,
                    state_bytes=state_bytes, peak_bytes=peak,
                    budget=budget, donate_preferred=donate_preferred,
                    reason=reason, remat_policy=candidate.remat,
                    act_bytes=act_bytes, remat_seconds=remat_seconds)


def rank_key(candidate: Candidate, est: Estimate) -> tuple:
    """Deterministic ranking key for modeled scores: fewest modeled
    per-step seconds first (comm + remat — the remat term is what lets
    recompute-vs-HBM trade against wire bytes in one total order);
    between otherwise-equal candidates the donation flag agreeing with
    the MEASURED donation heuristic wins (small states run faster
    un-donated, large/unknown donate — ``Trainer._donation_cutoff``);
    then lower peak, then label (total order — every rank of an SPMD
    fleet computes the same key from the same pickled config, which is
    what lets ``strategy="auto"`` agree on one winner without a
    collective)."""
    mismatch = 0 if candidate.donate == est.donate_preferred else 1
    return (est.step_seconds, mismatch, est.peak_bytes, candidate.label)


def expected_accepted(acceptance: float, k: int) -> float:
    """Expected draft tokens accepted per spec-decode round at
    per-token acceptance probability ``acceptance`` and depth ``k``:
    the mean of the truncated geometric run-length,
    ``sum_{m=1..k} a^m = a(1 - a^k)/(1 - a)``.  The verify's corrected
    token rides on top, so tokens-per-target-forward is
    ``1 + expected_accepted`` — the serve plane's measured
    ``tokens_per_target_forward`` converges to this (scheduler spec
    block; serve/selfcheck.py pins the shape)."""
    a = min(1.0, max(0.0, float(acceptance)))
    k = max(1, int(k))
    if a >= 1.0:
        return float(k)
    return a * (1.0 - a ** k) / (1.0 - a)


def speculative_speedup(acceptance: float, k: int,
                        draft_cost_ratio: float) -> float:
    """Modeled wall-clock speedup of speculative decoding over plain
    decode.  One spec round emits ``1 + expected_accepted`` tokens for
    the price of one target forward plus ``k`` draft forwards, each
    ``draft_cost_ratio`` of a target forward (layer-truncated drafts:
    roughly ``draft_layers / n_layer``).  Plain decode pays one target
    forward per token, so::

        speedup = (1 + E[accepted]) / (1 + k * draft_cost_ratio)

    < 1 means speculation LOSES at this operating point (acceptance
    collapsed or the draft is too expensive) — the scheduler's
    ``min_accept`` fallback exists precisely for that regime."""
    r = max(0.0, float(draft_cost_ratio))
    return (1.0 + expected_accepted(acceptance, k)) \
        / (1.0 + max(1, int(k)) * r)
