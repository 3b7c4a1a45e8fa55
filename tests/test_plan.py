"""Planner plane (ray_lightning_tpu/plan/): enumeration, cost-model
scoring, top-k AOT verification, and ``Trainer(strategy="auto")``
end-to-end — plus the model-drift guard pinning each strategy's
declared ``step_collective_bytes`` against the audited HLO wire bytes
of its actually-lowered train step, so the planner's inputs can't
silently rot.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from ray_lightning_tpu.comm import CommPolicy
from ray_lightning_tpu.comm.audit import total_wire_bytes
from ray_lightning_tpu.compile import cache as compile_cache
from ray_lightning_tpu.core.steps import build_init_fn, build_train_step
from ray_lightning_tpu.models.boring import BoringModel
from ray_lightning_tpu.plan import (Candidate, PlanConfig, Planner,
                                    clear_plan_memo, enumerate_candidates,
                                    estimate_candidate)
from ray_lightning_tpu.parallel.strategy import resolve_strategy

BATCH = 16


def _boring():
    module = BoringModel(batch_size=BATCH, dataset_length=4 * BATCH)
    module.prepare_data()
    module.setup("fit")
    module.setup_model()
    return module


def _example_batch(module):
    return jax.tree_util.tree_map(
        np.asarray, next(iter(module.train_dataloader())))


# -- enumeration -----------------------------------------------------------

def test_enumeration_covers_inventory():
    cfg = PlanConfig(microbatch=(1, 2))
    cands, _ = enumerate_candidates(8, 16, cfg, process_count=2)
    by_strategy = {c.strategy for c in cands}
    assert by_strategy == {"ddp", "zero1", "fsdp", "spmd"}
    # spmd enumerates every data×fsdp divisor factorization
    assert {c.mesh_sizes["fsdp"] for c in cands if c.strategy == "spmd"} \
        == {2, 4, 8}
    # comm rides only the compressible strategies
    assert {c.strategy for c in cands if c.comm} == {"ddp", "zero1"}
    # donation and microbatch double the feasible combinations
    assert any(not c.donate for c in cands)
    assert any(c.microbatch == 2 for c in cands)
    # labels are unique (the report keys on them)
    labels = [c.label for c in cands]
    assert len(set(labels)) == len(labels)


def test_enumeration_prunes_with_named_reasons():
    cfg = PlanConfig(microbatch=(1, 4))
    # batch 8 over 8 shards: microbatch 4 cannot split 8/(8*4)
    _, pruned = enumerate_candidates(8, 8, cfg, process_count=2)
    reasons = {r.split(":")[0] for _, r in pruned}
    assert "microbatch_indivisible" in reasons, pruned
    assert "comm_unsupported" in reasons, pruned    # fsdp/spmd × comm
    # batch 12 cannot divide across 8 shards at all
    _, pruned12 = enumerate_candidates(8, 12, cfg, process_count=2)
    assert any(r.startswith("batch_indivisible") for _, r in pruned12)
    # single process: no DCN hop, comm pruned by name
    _, pruned1p = enumerate_candidates(8, 16, cfg, process_count=1)
    assert any(r.startswith("comm_no_dcn") for _, r in pruned1p)
    # every pruned entry names a candidate label AND a reason
    for label, reason in pruned + pruned12 + pruned1p:
        assert label and reason


# -- cost model ------------------------------------------------------------

def _fixture_scoring(strategy_name="ddp", donate=True, budget=None):
    module = _boring()
    batch = _example_batch(module)
    cand = Candidate(strategy=strategy_name, axis_sizes=(("data", 8),),
                     donate=donate)
    strategy = cand.build_strategy()
    mesh = strategy.build_mesh(batch_hint=BATCH)
    tx = module.configure_optimizers()
    abstract = jax.eval_shape(build_init_fn(module, tx),
                              jax.random.PRNGKey(0), batch)
    shardings = strategy.state_shardings(mesh, abstract)
    cfg = PlanConfig(hbm_budget_bytes=budget)
    batch_bytes = sum(np.asarray(x).nbytes
                      for x in jax.tree_util.tree_leaves(batch))
    return estimate_candidate(cand, strategy, mesh, abstract, shardings,
                              batch_bytes, cfg, process_count=1)


def test_over_budget_rejected_with_named_reason():
    est = _fixture_scoring(budget=1024)       # 1 KiB: nothing fits
    assert not est.fits
    assert est.reason.startswith("hbm_over_budget"), est.reason
    assert "MiB" in est.reason and "budget" in est.reason
    # a roomy budget accepts the same candidate
    assert _fixture_scoring(budget=1 << 30).fits


def test_undonated_peak_models_second_state_copy():
    donated = _fixture_scoring(donate=True, budget=1 << 30)
    undonated = _fixture_scoring(donate=False, budget=1 << 30)
    assert undonated.peak_bytes - donated.peak_bytes \
        == donated.state_bytes


def test_planner_raises_naming_reasons_when_nothing_fits():
    module = _boring()
    batch = _example_batch(module)
    planner = Planner(PlanConfig(hbm_budget_bytes=1024, topk=0))
    with pytest.raises(ValueError, match="hbm_over_budget"):
        planner.plan(module, batch, batch_hint=BATCH)


def test_ranking_deterministic_and_reports_everything():
    module = _boring()
    batch = _example_batch(module)
    r1 = Planner(PlanConfig(topk=0)).plan(module, batch, batch_hint=BATCH)
    r2 = Planner(PlanConfig(topk=0)).plan(module, batch, batch_hint=BATCH)
    d1, d2 = r1.to_dict(), r2.to_dict()
    assert d1["winner"] == d2["winner"]
    assert [e["label"] for e in d1["candidates"]] \
        == [e["label"] for e in d2["candidates"]]
    # every pruned/rejected entry carries its named reason
    for e in d1["candidates"]:
        if e["status"] in ("pruned", "rejected"):
            assert e["reason"], e
    # a tiny replicated model on a fast all-ICI mesh: DDP's single psum
    # beats the sharded strategies' gather traffic
    assert d1["winner"] == "ddp[data8]"


# -- top-k AOT verification (compile-cache counters) -----------------------

def test_topk_bounds_aot_compiles(tmp_path):
    module = _boring()
    batch = _example_batch(module)
    compile_cache.activate(compile_cache.CompileCacheConfig(
        enabled=True, dir=str(tmp_path / "cc")))
    try:
        compile_cache.reset_stats()
        report = Planner(PlanConfig(topk=2)).plan(module, batch,
                                                  batch_hint=BATCH)
        d = report.to_dict()
        assert d["compiled"] <= 2
        assert d["cache_misses"] <= 2, d["cache_misses"]
        assert d["winner"] is not None
        # re-planning the same shapes through the same cache compiles
        # nothing: every verify program is a disk hit
        report2 = Planner(PlanConfig(topk=2)).plan(module, batch,
                                                   batch_hint=BATCH)
        assert report2.to_dict()["cache_misses"] == 0
        assert report2.winner_label == report.winner_label
    finally:
        compile_cache.deactivate()
        compile_cache.reset_stats()


# -- strategy="auto" end-to-end --------------------------------------------

def _fit_trainer(tmp_path, name, **kw):
    from ray_lightning_tpu import Trainer
    return Trainer(
        default_root_dir=str(tmp_path / name), max_epochs=1,
        enable_checkpointing=False, num_sanity_val_steps=0,
        limit_val_batches=0, log_every_n_steps=10**9, seed=0, **kw)


def test_auto_end_to_end_matches_hand_picked(tmp_path, seed):
    """``strategy="auto"`` trains to completion and its final params
    equal the same plan hand-picked (BoringModel is deterministic:
    uses_rng=False, plain SGD)."""
    auto = _fit_trainer(tmp_path, "auto", strategy="auto", max_steps=4)
    m_auto = BoringModel(batch_size=BATCH, dataset_length=4 * BATCH)
    auto.fit(m_auto)
    assert auto.global_step == 4
    d = auto._plan_report
    assert d is not None and d["winner"] == "ddp[data8]"
    assert auto.strategy.name == "ddp"
    for e in d["candidates"]:
        if e["status"] in ("pruned", "rejected"):
            assert e["reason"], e

    hand = _fit_trainer(tmp_path, "hand", strategy="ddp", max_steps=4)
    m_hand = BoringModel(batch_size=BATCH, dataset_length=4 * BATCH)
    hand.fit(m_hand)
    assert hand._plan_report is None
    for a, b in zip(
            jax.tree_util.tree_leaves(m_auto._trained_variables),
            jax.tree_util.tree_leaves(m_hand._trained_variables)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_auto_end_to_end_two_workers(tmp_path, seed):
    """The acceptance leg: ``strategy="auto"`` on a 2-worker CPU mesh —
    every rank plans independently and deterministically, the fleet
    trains to max_steps in lockstep under the winner, rank-0's
    PlanReport rides back to the driver, and the result matches the
    same plan hand-picked."""
    from tests.utils import cpu_plugin

    auto = _fit_trainer(tmp_path, "auto", strategy="auto",
                        plugins=[cpu_plugin(2)])
    m_auto = BoringModel(batch_size=BATCH, dataset_length=4 * BATCH)
    auto.fit(m_auto)
    assert auto.global_step == 2      # 64 samples over 2 workers
    d = auto._plan_report
    assert d is not None and d["winner"] == "ddp[data2]"
    # param-sharded strategies' comm candidates pruned by name
    pruned = {e["label"]: e["reason"] for e in d["candidates"]
              if e["status"] == "pruned"}
    assert any(r.startswith("comm_unsupported") for r in pruned.values())

    hand = _fit_trainer(tmp_path, "hand", strategy="ddp",
                        plugins=[cpu_plugin(2)])
    m_hand = BoringModel(batch_size=BATCH, dataset_length=4 * BATCH)
    hand.fit(m_hand)
    for a, b in zip(
            jax.tree_util.tree_leaves(m_auto._trained_variables),
            jax.tree_util.tree_leaves(m_hand._trained_variables)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_auto_reuses_plan_inside_tune_trial(tmp_path):
    """Per-trial plan reuse: the second same-shaped plan inside a tune
    session is the memoized report (reused flag, zero compiles), and
    the report lands on the trial for post-hoc analysis."""
    from ray_lightning_tpu.tune.runner import Trial
    from ray_lightning_tpu.tune.session import TrialSession, set_session

    module = _boring()
    batch = _example_batch(module)
    clear_plan_memo()
    trial = Trial("t0", {}, str(tmp_path))
    set_session(TrialSession(trial, lambda t, m: None))
    try:
        r1 = Planner(PlanConfig(topk=0)).plan(module, batch,
                                              batch_hint=BATCH)
        assert not r1.reused
        r2 = Planner(PlanConfig(topk=0)).plan(module, batch,
                                              batch_hint=BATCH)
        assert r2.reused and r2.winner_label == r1.winner_label
        assert trial.plan_report is not None
        assert trial.plan_report["winner"] == r1.winner_label
        assert trial.plan_report["reused"]
    finally:
        set_session(None)
        clear_plan_memo()


# -- per-link scoring + measured-bandwidth calibration ---------------------

def test_link_gbps_per_op_attribution():
    """``_ici``-suffixed ops always score at ICI speed; everything else
    rides DCN exactly when the run spans processes — the attribution
    that keeps hierarchical candidates ranked right."""
    from ray_lightning_tpu.plan.cost import link_gbps

    cfg = PlanConfig(ici_gbps=100.0, dcn_gbps=10.0)
    assert link_gbps("grad_all_reduce_ici", cfg, 2) == 100.0
    assert link_gbps("grad_all_reduce_dcn", cfg, 2) == 10.0
    assert link_gbps("grad_all_reduce", cfg, 2) == 10.0
    assert link_gbps("grad_all_reduce_dcn", cfg, 1) == 100.0
    assert link_gbps("param_all_gather", cfg, 1) == 100.0


def test_hierarchical_candidate_scores_below_mischarged(seed):
    """A hierarchical GradSync declares ~8 bytes/element of fp32 ICI
    traffic; scoring it at per-link bandwidths must come out CHEAPER
    than the flat int8 candidate's all-DCN charge (the mis-ranking the
    per-op attribution exists to prevent)."""
    from ray_lightning_tpu.comm import build_grad_sync
    from ray_lightning_tpu.plan.candidates import policy_for_candidate

    module = _boring()
    batch = _example_batch(module)
    strat = resolve_strategy("ddp")
    mesh = strat.build_mesh(batch_hint=BATCH)
    tx = module.configure_optimizers()
    abstract = jax.eval_shape(build_init_fn(module, tx),
                              jax.random.PRNGKey(0), batch)
    shardings = strat.state_shardings(mesh, abstract)
    cfg = PlanConfig(ici_gbps=100.0, dcn_gbps=1.0)
    cand = Candidate(strategy="ddp", axis_sizes=(("data", 8),), comm=True)
    batch_bytes = sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(batch))

    def score(policy):
        sync = build_grad_sync(strat, mesh, policy)
        return estimate_candidate(cand, strat, mesh, abstract, shardings,
                                  batch_bytes, cfg, process_count=2,
                                  grad_sync=sync).comm_seconds

    flat = score(CommPolicy(compress="int8", axes=("data",)))
    hier = score(CommPolicy(compress="int8", axes=("data",), hierarchy=4))
    assert hier < flat, (hier, flat)
    # the planner's default comm-on candidate policy arms the hierarchy
    pol = policy_for_candidate(cand)
    assert pol.hierarchy != 0


def test_calibration_cache_roundtrip(tmp_path, monkeypatch):
    """RLT_PLAN_CALIBRATE=1: PlanConfig.resolve picks up measured link
    bandwidths, cached per topology fingerprint (second resolve reads
    the file); explicit RLT_PLAN_*_GBPS still wins."""
    import json

    from ray_lightning_tpu.comm import calibrate

    monkeypatch.setenv(calibrate.ENV_DIR, str(tmp_path))
    monkeypatch.setenv("RLT_PLAN_CALIBRATE", "1")
    cfg = PlanConfig.resolve(None)
    path = calibrate.cache_path()
    assert tmp_path.joinpath(path.split("/")[-1]).exists()
    data = json.loads(open(path).read())
    # the 8-virtual-device CPU mesh measures its ICI proxy; DCN has no
    # hop to measure and keeps the constant
    assert "ici" in data["measured"]
    assert cfg.ici_gbps == data["ici_gbps"] > 0
    assert cfg.dcn_gbps == data["dcn_gbps"]
    # cache hit: mutate the file, re-resolve, the mutated value is read
    data["ici_gbps"] = 123.456
    open(path, "w").write(json.dumps(data))
    assert PlanConfig.resolve(None).ici_gbps == 123.456
    # explicit env overrides beat calibration
    monkeypatch.setenv("RLT_PLAN_ICI_GBPS", "77.0")
    assert PlanConfig.resolve(None).ici_gbps == 77.0


# -- remat axis (PR 12): enumeration, ranking, hand-measured picks ---------

def _gpt(name="tiny", batch_size=BATCH):
    from ray_lightning_tpu.models.gpt import GPTLightningModule
    module = GPTLightningModule(name, dataset_size=4 * batch_size,
                                batch_size=batch_size)
    module.setup_model()
    return module


def test_remat_axis_enumeration_and_pruning():
    """A module with a configure_remat() ladder multiplies the
    candidate space by its policies; one without it keeps the PR-8
    space and records the requested-but-unsupported axis by name."""
    from ray_lightning_tpu.plan import resolve_remat_options

    module = _gpt()
    spec = module.configure_remat()
    assert spec is not None and spec.default == "off"
    options, pruned = resolve_remat_options(spec, PlanConfig())
    assert set(options) == {"off", "full", "dots", "dots_no_batch"}
    assert not pruned
    # restriction + unknown policy: known survive, unknown prunes by name
    options, pruned = resolve_remat_options(
        spec, PlanConfig(remat=("dots", "warp")))
    assert options == ("dots",)
    assert any(r.startswith("remat_unsupported") for _, r in pruned)
    # no ladder + explicit request -> named prune, axis collapses
    options, pruned = resolve_remat_options(None, PlanConfig(remat=("dots",)))
    assert options == ("",)
    assert any(r.startswith("remat_unsupported") for _, r in pruned)
    # the axis multiplies enumeration and labels carry the policy
    cands, _ = enumerate_candidates(8, BATCH, PlanConfig(),
                                    remat_options=("off", "dots"))
    by_remat = {c.remat for c in cands}
    assert by_remat == {"off", "dots"}
    assert any(c.label.endswith("rm-dots") for c in cands)
    labels = [c.label for c in cands]
    assert len(set(labels)) == len(labels)


def test_remat_env_pin_and_worker_round_trip(monkeypatch):
    """RLT_REMAT_POLICY pins the sweep to the forced policy (the model
    build would override every candidate anyway), ships driver→worker
    via the plugin env base, and the new RLT_PLAN_* remat knobs
    round-trip through PlanConfig.worker_env like the PR-8 set."""
    from ray_lightning_tpu.plan import resolve_remat_options
    from tests.utils import cpu_plugin

    spec = _gpt().configure_remat()
    monkeypatch.setenv("RLT_REMAT_POLICY", "dots")
    options, _ = resolve_remat_options(spec, PlanConfig())
    assert options == ("dots",)
    plugin = cpu_plugin(2)
    assert plugin._worker_env_base()["RLT_REMAT_POLICY"] == "dots"
    monkeypatch.delenv("RLT_REMAT_POLICY")
    assert "RLT_REMAT_POLICY" not in plugin._worker_env_base()
    # planner knob env round-trip (worker_env -> resolve reproduces)
    cfg = PlanConfig(remat=("dots", "off"), hbm_gbps=500.0,
                     device_tflops=90.0)
    for k, v in cfg.worker_env().items():
        monkeypatch.setenv(k, v)
    assert PlanConfig.resolve(None) == cfg


def test_remat_ranking_deterministic_and_reported():
    """The remat axis ranks deterministically, the tiny fixture's
    winner is the hand-measured ``off`` (no memory pressure; the
    modeled per-region overhead prices the recompute ladder out), and
    the report's ``remat`` field carries the per-policy modeled
    HBM/recompute deltas."""
    module = _gpt()
    batch = _example_batch(module)
    r1 = Planner(PlanConfig(topk=0)).plan(module, batch, batch_hint=BATCH)
    r2 = Planner(PlanConfig(topk=0)).plan(module, batch, batch_hint=BATCH)
    d1, d2 = r1.to_dict(), r2.to_dict()
    assert d1["winner"] == d2["winner"] == "ddp[data8]:rm-off"
    assert [e["label"] for e in d1["candidates"]] \
        == [e["label"] for e in d2["candidates"]]
    rm = d1["remat"]
    assert rm["winner"] == "off"
    assert set(rm["policies"]) == {"off", "full", "dots", "dots_no_batch"}
    for policy, row in rm["policies"].items():
        assert row["peak_bytes"] and row["remat_seconds"] is not None
    # the deltas the axis exists to expose: "off" saves everything
    # (max HBM, no recompute seconds beyond traffic), "full" saves
    # nothing (min HBM)
    pol = rm["policies"]
    assert pol["off"]["act_bytes"] > pol["dots"]["act_bytes"] \
        > pol["full"]["act_bytes"] == 0
    # planning applied nothing: the module still carries its default
    assert module.config.remat is False


@pytest.mark.parametrize("name,expected", [
    ("tiny", "off"),
    ("gpt2-medium", "dots"),
    ("gpt2-moe-8e", "dots"),
])
def test_cost_model_reproduces_hand_measured_picks(name, expected):
    """The acceptance pin: the cost model alone (topk=0 — nothing
    compiles) reproduces every hand-measured remat pick documented in
    models/gpt.py — tiny→off (recompute overhead loses, memory is
    free), gpt2-medium→dots (+17% steps/s measured walk), and
    gpt2-moe-8e→dots (beats BOTH full and off; the dots_moe* save
    lists rank below plain dots exactly as measured)."""
    module = _gpt(name, batch_size=8)
    batch = _example_batch(module)
    cfg = PlanConfig(topk=0, strategies=("ddp",),
                     hbm_budget_bytes=16 << 30)
    report = Planner(cfg).plan(module, batch, batch_hint=8)
    d = report.to_dict()
    assert d["remat"]["winner"] == expected, d["remat"]
    assert report.winner_candidate.remat == expected
    if name == "gpt2-moe-8e":
        pol = d["remat"]["policies"]
        assert pol["dots"]["remat_seconds"] \
            < pol["dots_moe_act"]["remat_seconds"] \
            < pol["dots_moe"]["remat_seconds"]


def test_auto_end_to_end_gpt_applies_remat_winner(tmp_path, seed):
    """strategy='auto' with a remat-capable module trains to
    completion, records the remat ladder in its report, and the final
    params equal the hand-picked equivalent plan (tiny's winner is the
    module default 'off', so the applied config is unchanged)."""
    from ray_lightning_tpu.models.gpt import GPTLightningModule

    def gpt_module():
        return GPTLightningModule("tiny", dataset_size=4 * BATCH,
                                  batch_size=BATCH)

    auto = _fit_trainer(tmp_path, "auto", strategy="auto",
                        plan={"topk": 0}, max_steps=3)
    m_auto = gpt_module()
    auto.fit(m_auto)
    assert auto.global_step == 3
    d = auto._plan_report
    assert d["winner"] == "ddp[data8]:rm-off"
    assert d["remat"]["winner"] == "off"
    assert m_auto.config.remat is False
    hand = _fit_trainer(tmp_path, "hand", strategy="ddp", max_steps=3)
    m_hand = gpt_module()
    hand.fit(m_hand)
    for a, b in zip(
            jax.tree_util.tree_leaves(m_auto._trained_variables),
            jax.tree_util.tree_leaves(m_hand._trained_variables)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)

    # a NON-default winner is applied in place: restricting the sweep
    # to "dots" must reconfigure the module (remat wrap on) and still
    # train to completion
    forced = _fit_trainer(tmp_path, "forced", strategy="auto",
                          plan={"topk": 0, "remat": ("dots",)},
                          max_steps=2)
    m_forced = gpt_module()
    assert m_forced.config.remat is False
    forced.fit(m_forced)
    assert forced.global_step == 2
    assert forced._plan_report["winner"] == "ddp[data8]:rm-dots"
    assert m_forced.config.remat is True
    assert m_forced.config.remat_policy == "dots"


# -- remat drift guard: modeled activation bytes vs compiled programs ------

@pytest.fixture(scope="module")
def remat_compiled_peaks():
    """Compile the tiny-GPT train step (single device, donated) under
    full / dots / off and yield each program's memory_analysis peak —
    the measured side of the activation-model drift guard.

    Compiled with the CPU backend's memory-minimising scheduler: what
    the model prices is the least a schedule must keep live, and the
    scheduler this toolchain's CPU backend runs by default (jaxlib
    0.9.0: ``xla_cpu_enable_concurrency_optimized_scheduler``) orders
    for concurrency instead, under which the three programs' temp sizes
    are one number (24,051,776 bytes give or take 128) whatever the
    policy saves."""
    from ray_lightning_tpu.models.gpt import GPTLightningModule

    peaks = {}
    for policy in ("full", "dots", "off"):
        module = GPTLightningModule("tiny", dataset_size=4 * BATCH,
                                    batch_size=BATCH)
        module.configure_remat().apply(policy)
        module.setup_model()
        batch = jax.tree_util.tree_map(
            np.asarray, next(iter(module.train_dataloader())))
        tx = module.configure_optimizers()
        abstract = jax.eval_shape(build_init_fn(module, tx),
                                  jax.random.PRNGKey(0), batch)
        jitted = jax.jit(build_train_step(module, tx), donate_argnums=0)
        mem = jitted.lower(abstract, batch).compile(compiler_options={
            "xla_cpu_enable_concurrency_optimized_scheduler": False,
        }).memory_analysis()
        peaks[policy] = (int(mem.argument_size_in_bytes)
                         + int(mem.output_size_in_bytes)
                         + int(mem.temp_size_in_bytes)
                         - int(mem.alias_size_in_bytes))
    return peaks


def test_remat_drift_modeled_vs_compiled(remat_compiled_peaks):
    """The activation model can't silently rot: per policy, the
    modeled saved-activation bytes (core/remat.py probe through
    plan/cost.py remat_terms) must track the COMPILED programs'
    memory_analysis peak deltas vs the save-nothing baseline within a
    calibrated band (measured on this toolchain: off 0.83x, dots 5.2x —
    the model lists residuals at their own dtype while XLA's buffer
    assignment shares buffers, and of the matmul outputs ``dots`` names
    the tiny program keeps a fifth), and the modeled policy ordering
    must match the compiled one."""
    from ray_lightning_tpu.plan.cost import remat_terms

    module = _gpt()
    spec = module.configure_remat()
    batch = _example_batch(module)
    cfg = PlanConfig()
    modeled = {}
    for policy in ("full", "dots", "off"):
        probe = spec.probe(policy, batch)
        act, _seconds = remat_terms(probe, policy, cfg,
                                    process_count=1, dp=1, microbatch=1)
        modeled[policy] = act
    compiled = remat_compiled_peaks
    # ordering: more saved activations -> higher compiled peak
    assert modeled["off"] > modeled["dots"] > modeled["full"] == 0
    assert compiled["off"] > compiled["dots"] > compiled["full"]
    # calibrated bands on the deltas vs the save-nothing program
    for policy, (lo, hi) in {"off": (0.4, 2.0), "dots": (0.4, 8.0)}.items():
        measured_delta = compiled[policy] - compiled["full"]
        ratio = modeled[policy] / measured_delta
        assert lo <= ratio <= hi, (policy, modeled[policy], measured_delta)


# -- resolve_strategy surface (satellite: docstring/README drift) ----------

def test_resolve_strategy_unknown_name_lists_valid_set():
    with pytest.raises(ValueError) as ei:
        resolve_strategy("warpdrive")
    msg = str(ei.value)
    for name in ("ddp", "zero1", "fsdp", "spmd", "auto", "sharded"):
        assert name in msg, msg


def test_resolve_auto_returns_sentinel():
    auto = resolve_strategy("auto")
    assert auto.name == "auto"
    with pytest.raises(RuntimeError, match="planner"):
        auto.build_mesh()


# -- model-drift guard: declared bytes vs audited HLO ----------------------

#: drift legs: (strategy, key) -> CommPolicy (None = uncompressed).
#: False/True keep PR-8's flat keys; "hier"/"fp8" are the PR-10 paths.
_DRIFT_LEGS = (
    ("ddp", False, None),
    ("ddp", True, CommPolicy(compress="int8", axes=("data",))),
    ("zero1", False, None),
    ("zero1", True, CommPolicy(compress="int8", axes=("data",))),
    ("ddp", "hier", CommPolicy(compress="int8", axes=("data",),
                               hierarchy=4)),
    ("ddp", "fp8", CommPolicy(compress="fp8", axes=("data",))),
    ("zero1", "gather", CommPolicy(compress="int8", axes=("data",),
                                   gather_bucket_bytes=1 << 14)),
)


@pytest.fixture(scope="module")
def drift_programs():
    """Compile the REAL train step for every ``_DRIFT_LEGS`` entry on
    the 8-device mesh; yield declared step_collective_bytes next to
    the audited HLO wire bytes of the same lowered program."""
    from ray_lightning_tpu.models.gpt import GPTLightningModule

    out = {}
    for name, comm, policy in _DRIFT_LEGS:
        module = GPTLightningModule("tiny", dataset_size=4 * BATCH,
                                    batch_size=BATCH)
        module.setup_model()
        strat = resolve_strategy(name)
        mesh = strat.build_mesh(batch_hint=BATCH)
        sync = strat.grad_transform(mesh, policy) if comm else None
        tx = module.configure_optimizers()
        if sync is not None:
            tx = sync.wrap_tx(tx)
        batch = jax.tree_util.tree_map(
            np.asarray, next(iter(module.train_dataloader())))
        abstract = jax.eval_shape(build_init_fn(module, tx),
                                  jax.random.PRNGKey(0), batch)
        shardings = strat.state_shardings(mesh, abstract)
        if sync is not None:
            shardings = shardings.replace(
                opt_state=sync.fix_opt_shardings(
                    shardings.opt_state, abstract.opt_state))
        jitted = jax.jit(
            build_train_step(module, tx, grad_sync=sync),
            donate_argnums=0,
            in_shardings=(shardings,
                          strat.batch_shardings(mesh, batch)),
            out_shardings=(shardings, None))
        compiled = jitted.lower(abstract, batch).compile()
        out[(name, comm)] = {
            "declared": strat.step_collective_bytes(mesh, abstract,
                                                    comm=sync),
            "text": compiled.as_text(),
        }
    return out


def test_drift_ddp_uncompressed(drift_programs):
    """DDP declares one grad all-reduce the size of the (bf16-resident)
    params.  The audited program moves more: grads ride the wire at f32
    (2× the bf16 declaration — the partitioner resolves partial sums at
    the f32 grad dots, tests/test_collective_audit.py), the all-reduce
    wire factor is 2× (reduce-scatter + all-gather phases), and the
    partitioner inserts ~25% extra reductions beyond the logical grad
    sum — measured 4.96× on this toolchain.  The band pins that
    calibration: either side silently halving or doubling leaves it."""
    p = drift_programs[("ddp", False)]
    declared = sum(p["declared"].values())
    audited = total_wire_bytes(p["text"], axis_size=8,
                               ops=("all-reduce",))
    assert 3.5 <= audited / declared <= 6.5, (audited, declared)


def test_drift_zero1_uncompressed(drift_programs):
    """ZeRO-1 declares grad reduce-scatter + param all-gather (one
    params' worth each, at residency dtype).  Audited: the CPU lowering
    spells the grad phase as f32 all-reduce + dynamic-slice (see
    Zero1Strategy's docstring) and the param gather at the param dtype —
    measured 3.48× the declaration on this toolchain (same f32-wire ×
    all-reduce-factor composition as the DDP leg).  Band pins the
    calibration against silent 2× rot on either side."""
    p = drift_programs[("zero1", False)]
    declared = sum(p["declared"].values())
    audited = total_wire_bytes(
        p["text"], axis_size=8,
        ops=("all-reduce", "all-gather", "reduce-scatter"))
    assert 2.4 <= audited / declared <= 4.6, (audited, declared)


@pytest.mark.parametrize("name", ["ddp", "zero1"])
def test_drift_compressed_declaration_tracks_audit(drift_programs, name):
    """With comm=int8 the declaration IS the compressed wire payload
    (quant.payload_bytes) and the program's collectives are the comm
    plane's own manual lowering — so declared and audited agree far
    more tightly than the partitioner legs (measured 1.05× ddp, 1.51×
    zero1: the slack is ZeRO-1's uncompressed param gather riding
    partitioner spelling).  Also re-pins that the compressed program
    moves ≥2× fewer audited bytes than the flat one — the saving the
    planner's comm dimension exists to exploit."""
    comp = drift_programs[(name, True)]
    flat = drift_programs[(name, False)]
    declared_c = sum(comp["declared"].values())
    audited_c = total_wire_bytes(comp["text"], axis_size=8)
    audited_f = total_wire_bytes(flat["text"], axis_size=8)
    assert 0.7 <= audited_c / declared_c <= 2.0, (audited_c, declared_c)
    assert audited_c * 2.0 <= audited_f, (audited_c, audited_f)


def test_drift_hierarchical_per_link_attribution(drift_programs):
    """The hierarchical (ici4 x dcn2) declaration is split by link tier
    (``_dcn``/``_ici`` op suffixes) and BOTH sides must track the
    audited per-link HLO bytes: the DCN share against the host-crossing
    replica groups, the ICI share against the intra-host ones.  The
    manual lowering is the comm plane's own, so the bands are tight
    (same 0.7-2.0 calibration as the flat compressed legs) — a planner
    scoring hierarchical candidates from a declaration that silently
    stops splitting (or an audit that loses the groups) leaves them."""
    from ray_lightning_tpu.comm.audit import wire_bytes_by_link

    p = drift_programs[("ddp", "hier")]
    declared_dcn = sum(b for op, b in p["declared"].items()
                       if op.endswith("_dcn"))
    declared_ici = sum(b for op, b in p["declared"].items()
                       if op.endswith("_ici"))
    assert declared_dcn > 0 and declared_ici > 0, p["declared"]
    audited = wire_bytes_by_link(p["text"], ici_size=4, axis_size=8,
                                 ops=("all-to-all", "all-gather"))
    assert 0.7 <= audited["dcn"] / declared_dcn <= 2.0, (
        audited, declared_dcn)
    assert 0.7 <= audited["ici"] / declared_ici <= 2.0, (
        audited, declared_ici)
    # and the hierarchy's point: declared DCN bytes are >= 2x under the
    # flat int8 declaration's total (only the 1/ici shard crosses)
    flat_declared = sum(drift_programs[("ddp", True)]["declared"].values())
    assert 2 * declared_dcn <= flat_declared, (declared_dcn, flat_declared)


def test_drift_bucketed_gather_declaration_tracks_audit(drift_programs):
    """ZeRO-1 with the EXPLICIT bucketed updated-param gather
    (gather_bucket_bytes > 0): the declaration renames the gather op
    ``param_all_gather_bucketed`` at UNCHANGED bytes (the buckets move
    the same payload — only the dependence structure differs), the
    compiled program still tracks the same calibrated band as the plain
    compressed leg, and the planner's cost model discounts ONLY the
    bucketed op's seconds (BUCKETED_EXPOSED_FRACTION), never its
    bytes."""
    from ray_lightning_tpu.plan.cost import (
        BUCKETED_EXPOSED_FRACTION, op_overlap_factor)

    p = drift_programs[("zero1", "gather")]
    plain = drift_programs[("zero1", True)]
    assert "param_all_gather_bucketed" in p["declared"], p["declared"]
    assert "param_all_gather" not in p["declared"], p["declared"]
    assert p["declared"]["param_all_gather_bucketed"] == \
        plain["declared"]["param_all_gather"], (p["declared"],
                                                plain["declared"])
    declared = sum(p["declared"].values())
    audited = total_wire_bytes(p["text"], axis_size=8)
    assert 0.7 <= audited / declared <= 2.0, (audited, declared)
    # the cost model's declared-overlap discount: half the seconds on
    # the bucketed op, full price everywhere else
    assert op_overlap_factor(
        "param_all_gather_bucketed") == BUCKETED_EXPOSED_FRACTION
    assert op_overlap_factor("param_all_gather") == 1.0
    assert op_overlap_factor("grad_reduce_scatter") == 1.0


def test_drift_fp8_declaration_tracks_audit(drift_programs):
    """fp8's declaration (same wire bytes as int8: one byte/element +
    fp32 block scales) against the audited u8 program — same calibrated
    band as the int8 legs, so a codec whose wire silently widens (the
    f16 upcast a raw f8 collective lowers to) fails the drift guard."""
    p = drift_programs[("ddp", "fp8")]
    declared = sum(p["declared"].values())
    audited = total_wire_bytes(p["text"], axis_size=8)
    assert 0.7 <= audited / declared <= 2.0, (audited, declared)
    # the wire rides 1-byte u8, never f16
    from ray_lightning_tpu.comm.audit import collective_wire_bytes
    wire = collective_wire_bytes(p["text"], axis_size=8)
    assert any(dt == "u8" for _op, dt in wire), wire
    assert not any(dt == "f16" for _op, dt in wire), wire
