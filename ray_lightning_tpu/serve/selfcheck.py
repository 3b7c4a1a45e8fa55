"""Serve-plane selfcheck for ``format.sh --check`` (CI gate).

Same contract as the comm/compile selfchecks: cheap, deterministic,
no pytest — validates the invariants that would otherwise only fail
deep inside a live fleet:

1. bucket resolution + padding (the static-shape contract);
2. scheduler invariants under a simulated multi-tenant run on a fake
   fleet: slot uniqueness, per-tenant quota, fair-share progress
   (no tenant starved), graceful completion of every request;
3. the decode program LOWERS on a CPU mesh (trace-level check of the
   KV-cache forward — no execution, no compile);
4. every serve metric name is Prometheus-clean (the PR 2 lint).
"""

from __future__ import annotations


def _check_buckets() -> None:
    from ray_lightning_tpu.serve.buckets import (bucket_for, pad_to_bucket,
                                                 resolve_buckets)
    bs = resolve_buckets(None, 300)
    assert bs[-1] == 300 and list(bs) == sorted(bs), bs
    assert resolve_buckets((16, 64), 64) == (16, 64)
    assert bucket_for(1, bs) == bs[0]
    assert bucket_for(33, (32, 64)) == 64
    for bad in (lambda: bucket_for(65, (32, 64)),
                lambda: resolve_buckets((128,), 64),
                lambda: resolve_buckets((), 64)):
        try:
            bad()
        except ValueError:
            pass
        else:
            raise AssertionError("expected ValueError")
    padded = pad_to_bucket([5, 6, 7], 8)
    assert padded.shape == (1, 8) and list(padded[0, :3]) == [5, 6, 7]
    print("serve selfcheck: bucket resolution + padding OK")


def _check_scheduler() -> None:
    import numpy as np
    from ray_lightning_tpu.serve.scheduler import Scheduler

    sched = Scheduler(buckets=(8, 16), slots=4, max_seq_len=32,
                      quotas={"greedy": 1}, max_prefills_per_step=2,
                      default_max_new_tokens=4)
    reqs = []
    for i in range(6):
        reqs.append(sched.submit(np.arange(1, 4 + i % 3), tenant="greedy"))
        reqs.append(sched.submit(np.arange(1, 5), tenant="quiet"))
    steps = 0
    while not sched.idle():
        steps += 1
        assert steps < 200, "scheduler failed to converge"
        plan = sched.plan()
        if plan is None:
            break
        # invariants on the live plan
        live = sched.allocator.in_use()
        assert len(live) == len(set(live)) <= 4
        greedy = sched.stats()["per_tenant"].get("greedy", {})
        assert greedy.get("active", 0) <= 1, "quota violated"
        result = {"prefill": {p["slot"]: 7 for p in plan["prefills"]},
                  "decode": {}}
        if plan["decode"] is not None:
            result["decode"] = {s: 9 for s in plan["decode"]["slots"]}
        sched.apply(plan, result)
    assert all(r.done() for r in reqs), "requests starved"
    assert sched.completed == len(reqs)
    st = sched.stats()
    assert st["per_tenant"]["quiet"]["served_tokens"] > 0
    assert 0 < st["batch_occupancy"] <= 1.0
    print(f"serve selfcheck: scheduler invariants OK "
          f"({sched.completed} requests in {steps} steps, occupancy "
          f"{st['batch_occupancy']:.2f})")


def _check_spec_fold() -> None:
    """Speculative-decode fold invariants, driven with fabricated
    draft/verify results (no model): the accounting identity
    ``emitted == accepted + corrected`` across ragged acceptance
    patterns (accept-0, accept-k, mid-prefix), the max_new truncation,
    and the rolling-window fallback to plain decode."""
    import numpy as np
    from ray_lightning_tpu.serve.scheduler import Scheduler
    from ray_lightning_tpu.serve.spec import SpecConfig

    spec = SpecConfig(enabled=True, k=3, window=4, min_accept=0.5)
    sched = Scheduler(buckets=(8, 16), slots=2, max_seq_len=32,
                      default_max_new_tokens=7, spec=spec)
    req = sched.submit(np.arange(1, 5))
    plan = sched.plan()
    assert plan["prefills"] and plan["prefills"][0]["draft"], plan
    slot = plan["prefills"][0]["slot"]
    sched.apply(plan, {"prefill": {slot: 7}, "decode": {}})

    def round_(draft, verify):
        plan = sched.plan()
        assert plan["decode"]["spec"] is True
        sched.apply(plan, {"prefill": {}, "decode": {
            slot: {"draft": list(draft), "verify": list(verify)}}})

    round_([10, 11, 12], [10, 11, 12, 13])    # accept-k: 4 emitted
    round_([20, 21, 22], [30, 31, 32, 33])    # accept-0: 1 corrected
    round_([40, 41, 42], [40, 50, 51, 52])    # mid-prefix: accept 1
    # 7 tokens total -> max_new reached mid-round (truncation leg)
    assert req.done() and list(req.generated) == \
        [7, 10, 11, 12, 13, 30, 40], list(req.generated)
    s = sched.stats()["spec"]
    assert s["emitted"] == s["accepted"] + s["corrected"] == 6, s
    assert s["accepted"] == 4 and s["corrected"] == 2, s
    assert s["drafted"] == 9 and s["slot_steps"] == 3, s
    assert s["tokens_per_target_forward"] == 2.0, s

    # fallback: acceptance collapses below min_accept -> spec off for
    # the request's remaining life, verify[:1] only
    req2 = sched.submit(np.arange(1, 5))
    plan = sched.plan()
    slot = plan["prefills"][0]["slot"]
    sched.apply(plan, {"prefill": {slot: 7}, "decode": {}})
    for i in range(2):       # window arms at window//2 = 2 entries
        assert not req2.spec_off, i
        round_([60 + i, 61, 62], [70 + i, 71, 72, 73])
    assert req2.spec_off, "acceptance floor did not trip"
    assert sched.stats()["spec"]["fallbacks"] == 1
    plan = sched.plan()
    assert plan["decode"].get("spec") is not True, plan["decode"]
    print("serve selfcheck: spec fold accounting + fallback OK")


def _check_spec_lowers() -> None:
    """The draft and verify programs LOWER on a CPU mesh (trace-level,
    no execution) — the program-count invariant's new members."""
    import jax
    import numpy as np

    from ray_lightning_tpu.core.steps import (build_draft_step,
                                              build_verify_step)
    from ray_lightning_tpu.models.gpt import GPTConfig, GPTLightningModule

    module = GPTLightningModule(GPTConfig(
        vocab_size=64, block_size=16, n_layer=2, n_head=2, n_embd=32,
        remat=False))
    module.setup_model()
    draft = module.configure_draft(layers=1)
    aparams = jax.eval_shape(
        module.configure_decode_model().init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 8), np.int32))["params"]
    adraft = jax.eval_shape(draft.init, jax.random.PRNGKey(0),
                            jax.ShapeDtypeStruct((1, 8), np.int32)
                            )["params"]
    S, L, C, k = 2, 16, 32, 3
    kv = jax.ShapeDtypeStruct((2, S, L, C), draft.config.dtype)
    dkv = jax.ShapeDtypeStruct((1, S, L, C), draft.config.dtype)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, np.int32)  # noqa: E731
    jax.jit(build_draft_step(module, k, model=draft)).lower(
        adraft, dkv, dkv, i32(S), i32(S))
    jax.jit(build_verify_step(module, k)).lower(
        aparams, kv, kv, i32(S, k + 1), i32(S, k + 1))
    print("serve selfcheck: draft/verify programs lower on a CPU mesh")


def _check_spec_cost_model() -> None:
    from ray_lightning_tpu.plan.cost import (expected_accepted,
                                             speculative_speedup)
    assert expected_accepted(1.0, 4) == 4.0
    assert expected_accepted(0.0, 4) == 0.0
    assert abs(expected_accepted(0.5, 2) - 0.75) < 1e-12
    assert speculative_speedup(0.9, 4, 0.25) > 1.0
    assert speculative_speedup(0.05, 4, 0.5) < 1.0
    print("serve selfcheck: speculative cost model OK")


def _check_decode_lowers() -> None:
    import jax
    import numpy as np

    from ray_lightning_tpu.core.steps import (build_decode_step,
                                              build_prefill_step)
    from ray_lightning_tpu.models.gpt import GPTConfig, GPTLightningModule

    module = GPTLightningModule(GPTConfig(
        vocab_size=64, block_size=16, n_layer=2, n_head=2, n_embd=32,
        remat=False))
    model = module.configure_decode_model()
    aparams = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                             jax.ShapeDtypeStruct((1, 8), np.int32)
                             )["params"]
    S, L, C = 2, 16, 32
    kv = jax.ShapeDtypeStruct((2, S, L, C), model.config.dtype)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, np.int32)  # noqa: E731
    jax.jit(build_decode_step(module)).lower(
        aparams, kv, kv, i32(S), i32(S))
    jax.jit(build_prefill_step(module, 8)).lower(
        aparams, kv, kv, i32(1, 8), i32(), i32())
    print("serve selfcheck: prefill/decode programs lower on a CPU mesh")


def _check_metric_names() -> None:
    from ray_lightning_tpu.telemetry.metrics import validate_metric_name
    for name in ("rlt_serve_requests_total", "rlt_serve_tokens_total",
                 "rlt_serve_queue_depth_total",
                 "rlt_serve_active_slots_total",
                 "rlt_serve_ttft_seconds", "rlt_serve_tpot_seconds",
                 "rlt_serve_queue_wait_seconds",
                 "rlt_serve_traces_total",
                 "rlt_serve_prefill_seconds_total",
                 "rlt_serve_decode_seconds_total",
                 "rlt_serve_decode_ahead_total",
                 "rlt_spec_acceptance_rate", "rlt_spec_drafted_total",
                 "rlt_spec_accepted_total", "rlt_spec_fallbacks_total"):
        validate_metric_name(name)
    print("serve selfcheck: metric names Prometheus-clean")


def _main(argv: list) -> int:
    _check_buckets()
    _check_scheduler()
    _check_spec_fold()
    _check_spec_cost_model()
    _check_metric_names()
    _check_decode_lowers()
    _check_spec_lowers()
    return 0


if __name__ == "__main__":   # pragma: no cover - exercised via format.sh
    import sys
    sys.exit(_main(sys.argv[1:]))
