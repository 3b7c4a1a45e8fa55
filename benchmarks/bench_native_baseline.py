"""BASELINE.md's stated bar, measured: framework steps/sec must be
>= 90% of a hand-tuned raw-JAX training loop of the identical workload
(BASELINE.md "≥90% of native steps/sec"; VERDICT round-1 weak #1).

For each BASELINE workload (MNIST MLP #1, ResNet-50 #2, GPT-2 #5) this
script times

- **native**: a from-scratch loop a competent JAX user would write —
  ``jax.jit`` train step (value_and_grad + optax update) driven by a
  bare Python loop over pre-collected host batches, loss fetch as the
  only sync point.  The flax model definitions are shared with the
  framework (the bar measures loop/trainer machinery, not model code).
- **framework**: the full ``Trainer`` path via benchmarks/harness.py.

Each leg runs in its OWN subprocess: residual device buffers and jit
caches from one leg measurably depress the other on a shared chip
(measured: gpt2 framework 15.5 → 13.2 steps/s when run after the
native leg in-process), so in-process sequencing would understate
whichever leg runs second.

Output: the two absolute steps/sec lines (from the leg subprocesses),
then one ratio line per workload —
``{"metric": "<w>_framework_vs_native", "value": r, "unit": "ratio",
"vs_baseline": r/0.9}`` (vs_baseline >= 1.0 means the bar is met).

    python -m benchmarks.bench_native_baseline [mnist|resnet50|gpt2|
                                                bert_zero1|moe]

Each leg also emits a DEVICE-TIME line (median device ms/step of the
dominant XLA module from a warm-tail trace) and the parent a
``<w>_device_time_ratio`` — the machinery measure that does not move
with the host: wall ratios swing with what the host is doing, device
ratios repeat far more tightly (the spread is to be re-measured).  BERT/MoE legs add an analytic
MFU estimate.  Measured round 5 (2 rounds, donated legs both sides):
wall / device — gpt2 1.00/1.003, resnet50 1.09/0.982,
bert_zero1 0.99/1.000, gpt2_medium 1.02/1.000 (matched `dots` at B=8),
moe 0.99/1.000 (at the `dots` default),
mnist 0.86-1.09/0.81 (the mnist device step is ~13-16 MICROseconds;
the residual gap is the per-step train-accuracy metric the module
logs — work the native loop doesn't do.  Deterministic modules declare
uses_rng=False so the step skips PRNG bookkeeping).  The load-bearing
claim: every transformer workload's device ratio is 1.000-1.003 and
resnet's 0.982, all >=0.97; mnist's BASELINE-specified wall bar
(>=0.9) holds within the wall clock's drift.  All of these figures are
older claims, "not measured" on today's code.

Round 5: the native steps donate their state (``donate_argnums=0`` —
standard raw-JAX practice the legs previously omitted).  That halves
native state residency, which is what let the profiler capture the
gpt2-medium/MoE native legs (round-4 RESOURCE_EXHAUSTED) and the
fp32-logits loop run `dots` at B=8 at all.
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import optax


def _collect_batches(loader, n):
    out = []
    while len(out) < n:
        for b in loader:
            out.append(b)
            if len(out) >= n:
                break
    return out


def _time_native(step, state, batches, fetch, warmup, timed,
                 trace_steps=None) -> float:
    for i in range(warmup):
        state = step(state, batches[i % len(batches)])
    fetch(state)
    t0 = time.monotonic()
    for i in range(timed):
        state = step(state, batches[(warmup + i) % len(batches)])
    fetch(state)
    rate = timed / (time.monotonic() - t0)
    _emit_device_ms(
        lambda st=state: _drive(step, st, batches, fetch, trace_steps),
        "native")
    return rate


def _drive(step, state, batches, fetch, steps=None):
    if steps is None:
        # big-model traces can exhaust the profiler's device buffer;
        # RLT_TRACE_STEPS shrinks the captured window
        steps = int(os.environ.get("RLT_TRACE_STEPS", "8"))
    for i in range(steps):
        state = step(state, batches[i % len(batches)])
    fetch(state)


_CURRENT_WORKLOAD = None  # set by --leg dispatch; names the device line


def _emit_device_ms(run, side: str) -> "float | None":
    """Trace ``run()`` (warm code) and emit the dominant XLA module's
    median device ms/step — the device-clock counterpart of the wall
    steps/sec, captured AFTER the timed window so tracing overhead never
    contaminates the wall figure."""
    from benchmarks import trace_tools
    try:
        d = trace_tools.capture_trace(run)
    except Exception as e:  # profiler unavailable on some backends
        sys.stderr.write(f"device-time capture skipped: {e}\n")
        return None
    med = trace_tools.dominant_module_ms(d)
    if med is None:
        return None
    _emit(f"{_CURRENT_WORKLOAD}_{side}_device_ms", med, unit="ms/step")
    return med


def _emit(metric, value, unit="steps/sec", vs=None):
    line = {"metric": metric, "value": round(value, 3), "unit": unit}
    if vs is not None:
        line["vs_baseline"] = round(vs, 3)
    print(json.dumps(line), flush=True)
    return value


def _emit_framework_device(result: dict) -> "float | None":
    """Emit the framework device ms/step from a harness result that ran
    with ``trace_steps`` (the trace covers WARM steps of the same fit
    the wall clock measured — a fresh Trainer would recompile inside
    the trace window)."""
    from benchmarks import trace_tools
    med = trace_tools.dominant_module_ms(result.get("trace_dir"))
    if med is None:
        return None
    _emit(f"{_CURRENT_WORKLOAD}_framework_device_ms", med, unit="ms/step")
    return med


def _emit_mfu(module, device_ms: float, metric: str,
              peak_tflops: float = 197.0) -> None:
    """Analytic MFU from the module's own config: train FLOPs/step ≈
    3 × (2·N_active·tokens + 4·L·B·T²·C) against the v5e bf16 peak
    (embedding params counted — a PaLM-style estimate, not a bound).
    For MoE configs the expert parameters count at ``top_k/n_experts``
    (only the routed fraction does FLOPs per token).  Parameter sizes
    come from ``jax.eval_shape`` — no device memory or compile."""
    import jax as _jax

    model = module.configure_model()
    cfg = module.config
    B = module.batch_size
    T = cfg.block_size if hasattr(cfg, "block_size") else cfg.max_len
    x = np.zeros((B, T), np.int32)
    shapes = _jax.eval_shape(model.init, _jax.random.PRNGKey(0), x)
    params = shapes["params"]
    flat = {"/".join(str(getattr(k, "key", k)) for k in path):
            int(np.prod(v.shape))
            for path, v in
            _jax.tree_util.tree_flatten_with_path(params)[0]}
    total = sum(flat.values())
    moe = sum(v for k, v in flat.items() if "/moe/" in f"/{k}/")
    n_active = total - moe
    if moe and getattr(cfg, "n_experts", 0):
        n_active += moe * cfg.moe_top_k / cfg.n_experts
    tokens = B * T
    L = cfg.n_layer if hasattr(cfg, "n_layer") else cfg.num_layers
    C = cfg.n_embd if hasattr(cfg, "n_embd") else cfg.hidden
    flops = 3 * (2 * n_active * tokens + 4 * L * B * T * T * C)
    mfu = flops / (device_ms / 1e3) / (peak_tflops * 1e12)
    _emit(metric, mfu, unit="mfu")


def _init_like_framework(module, params, tx):
    """Mirror build_init_fn's precision recipe in the native legs: the
    optimizer snapshots full-precision masters BEFORE any residency
    downcast, then params adopt the module's param_dtype (bf16 for the
    GPT/BERT modules) — the native loop a competent user writes against
    these modules would do the same, and it keeps the comparison (and
    the HBM footprint) apples-to-apples."""
    import jax.numpy as jnp

    opt = tx.init(params)
    pd = getattr(module, "param_dtype", None)
    if pd is not None:
        params = jax.tree_util.tree_map(
            lambda x: x.astype(pd)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    return params, opt


# -- workload: MNIST MLP (BASELINE #1) --------------------------------------

MNIST_STEPS = (3, 100)   # warmup, timed


def _mnist_module():
    from ray_lightning_tpu.models.boring import LightningMNISTClassifier

    # dataset >= warmup+timed batches: ONE epoch covers the whole
    # measurement, so no epoch-boundary metric flush (a device_get sync)
    # stalls the pipeline mid-window — the same sizing bench.py uses
    warmup, timed = MNIST_STEPS
    return LightningMNISTClassifier(
        config={"batch_size": 128}, train_size=128 * (warmup + timed + 2))


def native_mnist(platform):
    from ray_lightning_tpu.models.boring import _MLP

    warmup, timed = MNIST_STEPS
    module = _mnist_module()
    batches = _collect_batches(module.train_dataloader(), warmup + timed)

    model = _MLP(module.hidden1, module.hidden2)
    tx = optax.adam(module.lr)
    params = model.init(jax.random.PRNGKey(0), batches[0][0])
    opt = tx.init(params)

    @functools.partial(jax.jit, donate_argnums=0)
    def step(state, batch):
        params, opt, _, _ = state
        x, y = batch

        def loss_fn(p):
            logits = model.apply(p, x)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            # matched work: the framework module logs per-step train
            # accuracy in-graph (models/boring.py training_step); the
            # native leg computes the same metric so the mnist device
            # ratio compares equal programs — the round-5 README's
            # "remaining 3 µs is the accuracy metric" footnote is now a
            # measured comparison, not an explained residual
            import jax.numpy as jnp
            acc = jnp.mean((jnp.argmax(logits, -1) == y)
                           .astype(jnp.float32))
            return loss, acc

        (loss, acc), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss, acc

    native = _time_native(step, (params, opt, 0.0, 0.0), batches,
                          lambda s: float(np.asarray(s[2])), warmup, timed)
    _emit(f"mnist_native_steps_per_sec_{platform}", native)


def framework_mnist(platform):
    from benchmarks.harness import run_steps_per_sec

    warmup, timed = MNIST_STEPS
    res = run_steps_per_sec(_mnist_module(),
                            f"mnist_framework_steps_per_sec_{platform}",
                            warmup=warmup, timed=timed, trace_steps=8)
    _emit_framework_device(res)


# -- workload: ResNet-50 (BASELINE #2) --------------------------------------

RESNET_STEPS = (3, 30)


def _resnet_parts(platform):
    from ray_lightning_tpu.models.resnet import ResNetLightningModule

    cfg_name = "resnet50" if platform != "cpu" else "resnet18"
    batch = 128 if platform != "cpu" else 8
    warmup, timed = RESNET_STEPS
    module = ResNetLightningModule(
        cfg_name, batch_size=batch,
        train_size=batch * (warmup + timed + 2))
    return cfg_name, module


def native_resnet50(platform):
    from ray_lightning_tpu.models.resnet import CONFIGS, ResNet

    warmup, timed = RESNET_STEPS
    cfg_name, module = _resnet_parts(platform)
    batches = _collect_batches(module.train_dataloader(), warmup + timed)

    model = ResNet(CONFIGS[cfg_name])
    tx = module.configure_optimizers()
    variables = model.init(jax.random.PRNGKey(0), batches[0][0], True)
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt = tx.init(params)

    @functools.partial(jax.jit, donate_argnums=0)
    def step(state, batch):
        params, batch_stats, opt, _ = state
        x, y = batch

        def loss_fn(p):
            logits, new = model.apply(
                {"params": p, "batch_stats": batch_stats}, x, True,
                mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            return loss, new["batch_stats"]

        (loss, new_bs), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt = tx.update(grads, opt, params)
        return (optax.apply_updates(params, updates), new_bs, opt, loss)

    native = _time_native(step, (params, batch_stats, opt, 0.0), batches,
                          lambda s: float(np.asarray(s[3])), warmup, timed)
    _emit(f"{cfg_name}_native_steps_per_sec_{platform}", native)


def framework_resnet50(platform):
    from benchmarks.harness import run_steps_per_sec

    warmup, timed = RESNET_STEPS
    cfg_name, module = _resnet_parts(platform)
    res = run_steps_per_sec(
        module, f"{cfg_name}_framework_steps_per_sec_{platform}",
        warmup=warmup, timed=timed, trace_steps=8)
    _emit_framework_device(res)


# -- workloads: GPT-2 small (BASELINE #5 headline) and medium (the remat
# regime, gateway to config #5's 1.3B) — one shared leg body -----------------

GPT_STEPS = (3, 30)
GPT_MEDIUM_STEPS = (3, 20)


def _gpt_module(platform, cfg_name, steps, batch=8):
    from ray_lightning_tpu.models.gpt import GPTLightningModule

    resolved = cfg_name if platform != "cpu" else "tiny"
    warmup, timed = steps
    return resolved, GPTLightningModule(
        resolved, dataset_size=batch * (warmup + timed + 2),
        batch_size=batch)


def _native_gpt_leg(platform, cfg_name, steps, remat_policy=None,
                    batch=8, trace_steps=None, label=None):
    """Raw-JAX loop over the named GPT config (optax full-logits CE —
    what a competent user writes, including ``donate_argnums=0``).
    ``remat_policy`` pins the native leg's policy independently of the
    config default for A/B sweeps.  Since round 5 the donated state
    fits the gpt2-medium loop's fp32 logits alongside "dots" even at
    B=8 (the round-4 runtime OOM was the un-donated state
    double-residency), so the default gpt2-medium comparison runs at
    matched policy; ``gpt2_medium_b4`` is the reduced-batch
    cross-check.  ``trace_steps`` shrinks the device-capture window
    (big-model traces exhaust the profiler's HBM buffer at the default
    8); ``label`` overrides the emitted metric name (the b4 variant
    must not collide with the B=8 lines)."""
    import dataclasses

    from ray_lightning_tpu.models.gpt import GPT

    warmup, timed = steps
    resolved, module = _gpt_module(platform, cfg_name, steps, batch=batch)
    label = label or cfg_name
    batches = _collect_batches(module.train_dataloader(), warmup + timed)

    config = module.config
    saved_policy = os.environ.get("RLT_REMAT_POLICY")
    try:
        if remat_policy is not None and config.remat:
            config = dataclasses.replace(config, remat_policy=remat_policy)
            # the sweep env knob (models/gpt._remat_policy) outranks the
            # config; pin it too, or a sweep run would drag the native leg
            # onto a policy it cannot execute (fp32-logits OOM at "dots")
            os.environ["RLT_REMAT_POLICY"] = remat_policy
        model = GPT(config)
        tx = module.configure_optimizers()
        params = model.init(jax.random.PRNGKey(0), batches[0][0])["params"]
        params, opt = _init_like_framework(module, params, tx)

        @functools.partial(jax.jit, donate_argnums=0)
        def step(state, batch):
            params, opt, _ = state
            x, y = batch

            def loss_fn(p):
                logits = model.apply({"params": p}, x, False)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, y).mean()

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt = tx.update(grads, opt, params)
            return optax.apply_updates(params, updates), opt, loss

        native = _time_native(step, (params, opt, 0.0), batches,
                              lambda s: float(np.asarray(s[2])),
                              warmup, timed, trace_steps=trace_steps)
        _emit(f"{label}_native_steps_per_sec_{platform}", native)
    finally:
        # the policy pin must not outlive the leg when legs share a
        # process (the subprocess-per-leg runner masks the leak)
        if saved_policy is None:
            os.environ.pop("RLT_REMAT_POLICY", None)
        else:
            os.environ["RLT_REMAT_POLICY"] = saved_policy


def _framework_gpt_leg(platform, cfg_name, steps, mfu: bool = False,
                       batch=8, trace_steps=8, label=None):
    from benchmarks.harness import run_steps_per_sec

    warmup, timed = steps
    _, module = _gpt_module(platform, cfg_name, steps, batch=batch)
    label = label or cfg_name
    res = run_steps_per_sec(
        module, f"{label}_framework_steps_per_sec_{platform}",
        warmup=warmup, timed=timed, trace_steps=trace_steps)
    med = _emit_framework_device(res)
    if med and mfu:
        # analytic MFU counts the MODEL's 3x fwd+bwd FLOPs only; remat
        # recompute is real extra device work on top, so this reads LOW
        # in the remat regime by construction
        _emit_mfu(module, med, f"{label}_model_mfu_{platform}")


def native_gpt2(platform):
    _native_gpt_leg(platform, "gpt2-small" if platform != "cpu"
                    else "tiny", GPT_STEPS)


def framework_gpt2(platform):
    _framework_gpt_leg(platform, "gpt2-small" if platform != "cpu"
                       else "tiny", GPT_STEPS)


def native_gpt2_medium(platform):
    # matched policy since round 5: with donate_argnums=0 on the native
    # step (standard raw-JAX practice the legs previously omitted) the
    # fp32-logits loop fits "dots" at B=8 — the round-4 runtime OOM was
    # the un-donated state double-residency, not the logits alone
    _native_gpt_leg(platform, "gpt2-medium", GPT_MEDIUM_STEPS,
                    remat_policy="dots", trace_steps=3)


def framework_gpt2_medium(platform):
    _framework_gpt_leg(platform, "gpt2-medium", GPT_MEDIUM_STEPS,
                       mfu=True)


def native_gpt2_medium_b4(platform):
    """Reduced-batch cross-check of the matched-policy comparison
    (VERDICT r4 next #1): both legs at ``dots`` and B=4 — a second
    point confirming the B=8 device ratio isn't a batch-size
    coincidence."""
    _native_gpt_leg(platform, "gpt2-medium", GPT_MEDIUM_STEPS,
                    remat_policy="dots", batch=4, trace_steps=3,
                    label="gpt2-medium-b4")


def framework_gpt2_medium_b4(platform):
    _framework_gpt_leg(platform, "gpt2-medium", GPT_MEDIUM_STEPS,
                       batch=4, trace_steps=3, label="gpt2-medium-b4")


# -- workload: BERT-base masked-LM, ZeRO-1 (BASELINE #4) ---------------------

BERT_STEPS = (3, 30)


def _bert_parts(platform):
    from ray_lightning_tpu.models.bert import BertMLMModule

    cfg_name = "bert-base" if platform != "cpu" else "tiny"
    batch = 32 if platform != "cpu" else 4
    warmup, timed = BERT_STEPS
    module = BertMLMModule(cfg_name, batch_size=batch,
                           train_size=batch * (warmup + timed + 2))
    return cfg_name, module


def native_bert_zero1(platform):
    """Raw-JAX loop of the identical MLM workload.  On one chip the
    zero1 annotations are identity, so the native equivalent is the
    plain loop — the ratio isolates the framework's sharded-path
    machinery cost at its single-chip degenerate point."""
    warmup, timed = BERT_STEPS
    cfg_name, module = _bert_parts(platform)
    batches = _collect_batches(module.train_dataloader(), warmup + timed)

    module.setup_model()
    model = module.model
    tx = module.configure_optimizers()
    rng = jax.random.PRNGKey(0)
    # the MLM loader passes (inputs, targets) through; the steps unpack
    # tokens from batch[0] — mirror that here
    batches = [b[0] if isinstance(b, (tuple, list)) else b
               for b in batches]
    params = model.init(rng, batches[0])["params"]
    params, opt = _init_like_framework(module, params, tx)

    @functools.partial(jax.jit, donate_argnums=0)
    def step(state, tokens):
        params, opt, loss_prev, rng = state
        rng, step_rng = jax.random.split(rng)

        def loss_fn(p):
            from ray_lightning_tpu.core.module import StepContext
            ctx = StepContext(module, p, {}, step_rng, training=True)
            return module._mlm_loss(ctx, tokens, step_rng)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt = tx.update(grads, opt, params)
        return (optax.apply_updates(params, updates), opt, loss, rng)

    native = _time_native(step, (params, opt, 0.0, rng), batches,
                          lambda s: float(np.asarray(s[2])), warmup, timed)
    _emit(f"bert_{cfg_name}_zero1_native_steps_per_sec_{platform}", native)


def framework_bert_zero1(platform):
    from benchmarks.harness import run_steps_per_sec

    warmup, timed = BERT_STEPS
    cfg_name, module = _bert_parts(platform)
    res = run_steps_per_sec(
        module, f"bert_{cfg_name}_zero1_framework_steps_per_sec_{platform}",
        warmup=warmup, timed=timed, strategy="zero1", trace_steps=8)
    med = _emit_framework_device(res)
    if med:
        _emit_mfu(module, med,
                  f"bert_{cfg_name}_zero1_mfu_{platform}")


# -- workload: MoE GPT, expert-parallel showcase -----------------------------

MOE_STEPS = (3, 20)


def _moe_parts(platform):
    from ray_lightning_tpu.models.gpt import GPTLightningModule

    cfg_name = "gpt2-moe-8e" if platform != "cpu" else "moe-tiny"
    batch = 8
    warmup, timed = MOE_STEPS
    module = GPTLightningModule(
        cfg_name, dataset_size=batch * (warmup + timed + 2),
        batch_size=batch)
    return cfg_name, module


def native_moe(platform):
    from ray_lightning_tpu.core.module import StepContext

    warmup, timed = MOE_STEPS
    cfg_name, module = _moe_parts(platform)
    batches = _collect_batches(module.train_dataloader(), warmup + timed)

    module.setup_model()
    tx = module.configure_optimizers()
    rng = jax.random.PRNGKey(0)
    variables = dict(module.init_params(rng, batches[0]))
    params = variables.pop("params")
    params, opt = _init_like_framework(module, params, tx)

    @functools.partial(jax.jit, donate_argnums=0)
    def step(state, batch):
        params, model_state, opt, _, rng = state
        rng, step_rng = jax.random.split(rng)

        def loss_fn(p):
            ctx = StepContext(module, p, model_state, step_rng,
                              training=True)
            loss = module.training_step(ctx, batch)
            return loss, ctx.model_state

        (loss, new_ms), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt = tx.update(grads, opt, params)
        return (optax.apply_updates(params, updates), new_ms, opt, loss,
                rng)

    # trace_steps=3: at the dots default the routed model's residents
    # leave too little HBM for the profiler's 8-step buffer (the round-4
    # RESOURCE_EXHAUSTED) — a 3-step window fits and device times repeat
    # to <1% between steps
    native = _time_native(step, (params, variables, opt, 0.0, rng),
                          batches, lambda s: float(np.asarray(s[3])),
                          warmup, timed, trace_steps=3)
    _emit(f"moe_{cfg_name}_native_steps_per_sec_{platform}", native)


def framework_moe(platform):
    from benchmarks.harness import run_steps_per_sec

    warmup, timed = MOE_STEPS
    cfg_name, module = _moe_parts(platform)
    res = run_steps_per_sec(
        module, f"moe_{cfg_name}_framework_steps_per_sec_{platform}",
        warmup=warmup, timed=timed, trace_steps=8)
    med = _emit_framework_device(res)
    if med:
        _emit_mfu(module, med,
                  f"moe_{cfg_name}_mfu_{platform}")


WORKLOADS = {
    "mnist": (native_mnist, framework_mnist),
    "resnet50": (native_resnet50, framework_resnet50),
    "gpt2": (native_gpt2, framework_gpt2),
    "gpt2_medium": (native_gpt2_medium, framework_gpt2_medium),
    "gpt2_medium_b4": (native_gpt2_medium_b4, framework_gpt2_medium_b4),
    "bert_zero1": (native_bert_zero1, framework_bert_zero1),
    "moe": (native_moe, framework_moe),
}


def _run_leg(leg: str) -> dict:
    """Spawn one leg as a fresh process; return {metric: value} for every
    JSON line it printed (steps/sec + device ms + mfu)."""
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_native_baseline",
         "--leg", leg],
        capture_output=True, text=True, env=os.environ.copy())
    out: dict = {}
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            print(line, flush=True)     # forward the absolute numbers
            rec = json.loads(line)
            out[rec["metric"]] = rec["value"]
    if proc.returncode != 0 or not out:
        sys.stderr.write(proc.stderr)
        raise RuntimeError(f"leg {leg} failed")
    return out


def _pick(metrics: dict, suffix: str) -> "float | None":
    for k, v in metrics.items():
        if suffix in k:
            return v
    return None


def main():
    global _CURRENT_WORKLOAD
    args = sys.argv[1:]
    if args[:1] == ["--leg"]:
        kind, name = args[1].split(":")
        _CURRENT_WORKLOAD = name
        platform = jax.devices()[0].platform
        WORKLOADS[name][0 if kind == "native" else 1](platform)
        return
    # alternate legs over several rounds and take each side's best: the
    # device link's throughput drifts minute-to-minute, so a single
    # native-then-framework pair confounds drift with overhead
    rounds = int(os.environ.get("RLT_BASELINE_ROUNDS", "2"))
    for name in args or list(WORKLOADS):
        native = framework = 0.0
        ndev = fdev = None
        for _ in range(rounds):
            nm = _run_leg(f"native:{name}")
            fm = _run_leg(f"framework:{name}")
            native = max(native, _pick(nm, "_native_steps_per_sec") or 0)
            framework = max(framework,
                            _pick(fm, "_framework_steps_per_sec") or 0)
            nd = _pick(nm, "_native_device_ms")
            fd = _pick(fm, "_framework_device_ms")
            ndev = min(ndev, nd) if (ndev and nd) else (nd or ndev)
            fdev = min(fdev, fd) if (fdev and fd) else (fd or fdev)
        ratio = framework / native
        _emit(f"{name}_framework_vs_native", ratio, unit="ratio",
              vs=ratio / 0.9)
        if ndev and fdev:
            # the device-clock ratio: pure device time per step
            # (framework >= native means its compiled program is at
            # least as lean; the wall ratio adds host luck)
            dratio = ndev / fdev
            _emit(f"{name}_device_time_ratio", dratio, unit="ratio",
                  vs=dratio / 0.9)


if __name__ == "__main__":
    main()
