"""Compiled step builders.

Each stage (train / eval / predict / init) is one pure function, jitted
once with the strategy's shardings.  This replaces the reference's hot
loop — PL's ``trainer.run_stage()`` driving torch autograd + DDP hooks
inside each worker (ray_ddp.py:472) — with XLA-compiled SPMD programs:
gradient sync is not an op we call, it is a sharding consequence the
compiler lowers to ICI collectives.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax

from ray_lightning_tpu.core.module import StepContext
from ray_lightning_tpu.core.state import TrainState


def build_init_fn(module, tx) -> Callable:
    """(rng, example_batch) -> TrainState with freshly initialized params."""

    def init_fn(rng, batch):
        init_rng, state_rng = jax.random.split(rng)
        variables = dict(module.init_params(init_rng, batch))
        params = variables.pop("params")
        model_state = variables
        # opt init sees the full-precision init values: an fp32_master tx
        # snapshots its master copy *before* any residency downcast
        opt_state = tx.init(params)
        pd = getattr(module, "param_dtype", None)
        if pd is not None:
            params = jax.tree_util.tree_map(
                lambda p: p.astype(pd)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
        return TrainState.create(params, model_state, opt_state, state_rng)

    return init_fn


def _split_loss(out) -> tuple[jax.Array, dict]:
    if isinstance(out, dict):
        if "loss" not in out:
            raise ValueError("training_step dict output must contain 'loss'")
        extra = {k: v for k, v in out.items() if k != "loss"}
        return out["loss"], extra
    return out, {}


def build_train_step(module, tx,
                     accumulate_grad_batches: int = 1,
                     grad_sync=None) -> Callable:
    """(state, batch) -> (state', metrics).

    With ``accumulate_grad_batches=k`` the batch's leading dim is split
    into k microbatches folded with ``lax.scan`` (static trip count —
    XLA-friendly control flow, no data-dependent Python), gradients are
    averaged, and one optimizer step is applied.

    ``grad_sync`` (a ``comm.GradSync``, default ``None``) routes the
    gradient sync through the comm plane's compressed collectives: the
    gradient computation runs per-device under ``shard_map`` (params
    replicated, batch sharded on the data axes), local grads reduce via
    quantized reduce-scatter + all-gather with the error-feedback
    residual carried in the optimizer state, and the tiny scalars
    (loss / logged / float model-state) pmean at fp32.  The policy can
    further split the reduction across link tiers (``hierarchy`` —
    fp32 inside the ICI group, codec only across DCN) and coalesce
    leaves into overlap-schedulable buckets (``bucket_bytes`` —
    ``GradSync.sync_step`` routes).  With ``None`` the step is
    byte-identical to the pre-comm-plane build: gradient sync stays
    the partitioner's implicit fp32 all-reduce.
    """

    def grads_of(params, model_state, rng, batch):
        def loss_fn(p):
            ctx = StepContext(module, p, model_state, rng, training=True)
            loss, extra = _split_loss(module.training_step(ctx, batch))
            return loss, (ctx.model_state, {**ctx.logged, **extra})
        (loss, (new_ms, logged)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        return loss, new_ms, logged, grads

    def compute_grads(params, model_state, step_rng, batch):
        """Single or k-microbatch-accumulated gradients.  Identical math
        in global view (grad_sync None) and per-device view (inside the
        shard_map region, where ``batch`` is the local shard)."""
        if accumulate_grad_batches <= 1:
            return grads_of(params, model_state, step_rng, batch)
        k = accumulate_grad_batches

        def to_micro(x):
            if getattr(x, "ndim", 0) == 0:
                return x
            if x.shape[0] % k:
                raise ValueError(
                    f"Batch size {x.shape[0]} must be divisible by "
                    f"accumulate_grad_batches={k}")
            return x.reshape((k, x.shape[0] // k) + x.shape[1:])

        micro = jax.tree_util.tree_map(to_micro, batch)

        def body(carry, mb):
            ms, acc = carry
            rng_i = (jax.random.fold_in(step_rng, acc["_i"])
                     if step_rng is not None else None)
            loss, ms, logged, grads = grads_of(params, ms, rng_i, mb)
            acc_g = jax.tree_util.tree_map(jnp.add, acc["g"], grads)
            return (ms, {"g": acc_g, "_i": acc["_i"] + 1}), (loss, logged)

        # accumulate in fp32 regardless of param residency dtype: k
        # bf16 additions would lose low bits the optimizer needs
        zero_g = jax.tree_util.tree_map(
            lambda p: jnp.zeros(
                p.shape,
                jnp.float32 if jnp.issubdtype(p.dtype, jnp.floating)
                else p.dtype),
            params)
        (new_ms, acc), (losses, logged_seq) = jax.lax.scan(
            body, (model_state, {"g": zero_g, "_i": jnp.zeros(
                (), jnp.int32)}), micro)
        grads = jax.tree_util.tree_map(lambda g: g / k, acc["g"])
        loss = losses.mean()
        logged = jax.tree_util.tree_map(lambda x: x.mean(), logged_seq)
        return loss, new_ms, logged, grads

    def synced_grads(state: TrainState, step_rng, batch):
        """Compressed-sync path: local grads + explicit quantized
        reduction under shard_map (comm plane module docstring)."""
        from jax.sharding import PartitionSpec as P


        residual = grad_sync.residual_of(state.opt_state)
        comm_key = None
        if grad_sync.policy.stochastic_rounding:
            # derived, never consumed: state.rng advances exactly as in
            # the uncompressed step (the uses_rng contract holds)
            comm_key = jax.random.fold_in(state.rng, state.step)

        def local_fn(params, model_state, step_rng, comm_key, batch,
                     residual):
            if step_rng is not None:
                # decorrelate dropout/rng streams across data shards (in
                # global view one stream spans the global batch; here
                # each shard draws its own)
                step_rng = jax.random.fold_in(step_rng,
                                              grad_sync.axis_index())
            loss, new_ms, logged, grads = compute_grads(
                params, model_state, step_rng, batch)
            if comm_key is not None:
                comm_key = jax.random.fold_in(comm_key,
                                              grad_sync.axis_index())
            grads, new_residual = grad_sync.sync_step(grads, residual,
                                                      rng=comm_key)
            loss, logged, new_ms = grad_sync.pmean((loss, logged, new_ms))
            return loss, new_ms, logged, grads, new_residual

        batch_specs = jax.tree_util.tree_map(
            lambda x: grad_sync.batch_spec(getattr(x, "ndim", 0)), batch)
        res_specs = grad_sync.residual_specs(residual)
        mapped = jax.shard_map(
            local_fn, mesh=grad_sync.mesh,
            in_specs=(P(), P(), P(), P(), batch_specs, res_specs),
            out_specs=(P(), P(), P(), P(), res_specs), check_vma=False)
        return mapped(state.params, state.model_state, step_rng,
                      comm_key, batch, residual)

    def step_fn(state: TrainState, batch: Any):
        if getattr(module, "uses_rng", True):
            new_rng, step_rng = jax.random.split(state.rng)
            step_rng = jax.random.fold_in(step_rng, state.step)
        else:
            # module declared itself deterministic: the per-step
            # split/fold is pure scalar-core work the compiled step can
            # drop — measurable on microsecond-scale models (the MNIST
            # MLP's device step is ~2/3 rng bookkeeping)
            new_rng, step_rng = state.rng, None

        new_residual = None
        if grad_sync is None:
            loss, new_ms, logged, grads = compute_grads(
                state.params, state.model_state, step_rng, batch)
        else:
            loss, new_ms, logged, grads, new_residual = synced_grads(
                state, step_rng, batch)

        # profiler scope (telemetry/scopes.py); the model's own parts
        # enter theirs in models/ and ops/
        with jax.named_scope("optimizer"):
            updates, new_opt = tx.update(grads, state.opt_state,
                                         state.params)
            if grad_sync is not None:
                new_opt = grad_sync.with_residual(new_opt, new_residual)
            new_params = optax.apply_updates(state.params, updates)
        if grad_sync is not None:
            new_params = grad_sync.regather_params(new_params)
        metrics = {"loss": loss, **logged}
        new_state = state.replace(
            step=state.step + 1, params=new_params, model_state=new_ms,
            opt_state=new_opt, rng=new_rng)
        return new_state, metrics

    return step_fn


def build_prefill_step(module, bucket_len: int, model=None,
                       dequant=None) -> Callable:
    """Serve-plane prefill program for ONE sequence-length bucket
    (sibling of :func:`build_train_step`; consumed by serve/engine.py).

    ``(params, k_caches, v_caches, tokens, slot, length) ->
    (k', v', first_token)`` where ``tokens`` is ``[1, bucket_len]``
    (right-padded), ``slot``/``length`` are traced int32 scalars — ONE
    compiled program per (bucket, topology), whatever slot or true
    length a request lands on.  The forward is the module's decode model
    applied normally with the ``kv_cache`` collection mutable, so the
    captured per-layer K/V are numerically THE training forward's;
    positions ``>= length`` hold pad garbage the causal mask keeps out
    of the first token's logits and :func:`cached_attention`'s position
    bound keeps out of every later one.

    ``model`` overrides the forward module (the DRAFT model's prefill
    over the draft KV cache, speculative decoding); ``dequant`` maps
    the params argument inside the traced body (int8-resident draft
    weights decode inline, comm/quant.py ``dequantize_blob``).

    A model whose state is not a row per position (models/evabyte.py: a
    prompt's last window and its chunk summaries) has a ``prefill``
    method of its own, ``(tokens, length, slot, k_caches, v_caches) ->
    (logits [vocab] at length - 1, k', v')``: it needs ``length`` to
    build that state, and writes it at the slot itself.  The program's
    signature is the same.  Where such a model's layers are of more than
    one kind (models/command.py: rings beside a row per position),
    ``k_caches`` / ``v_caches`` are the small pytrees ``KVCacheSpec.
    state`` makes, a tuple an array a kind: the step hands them through
    as it hands the two arrays.
    """
    module.setup_model()
    if model is None:
        model = module.configure_decode_model()

    def step_fn(params, k_caches, v_caches, tokens, slot, length):
        if dequant is not None:
            params = dequant(params)
        if hasattr(model, "prefill"):
            logits, k_caches, v_caches = model.apply(
                {"params": params}, tokens, length, slot, k_caches,
                v_caches, method="prefill")
            with jax.named_scope("sample"):
                return k_caches, v_caches, jnp.argmax(
                    logits, axis=-1).astype(tokens.dtype)
        logits, captured = model.apply({"params": params}, tokens, True,
                                       mutable=["kv_cache"])
        with jax.named_scope("sample"):
            first = jnp.argmax(
                jax.lax.dynamic_index_in_dim(logits[0], length - 1, 0,
                                             keepdims=False),
                axis=-1).astype(tokens.dtype)
        # captured K/V ride the module tree ({'h0': {'attn': {'kv':
        # ((k, v),)}}}) as packed [1, Tb, C] rows, the cache's own row
        # layout; stack to [n_layer, 1, Tb, C] and write every layer's
        # block with one dynamic_update_slice at the slot
        with jax.named_scope("kv_cache"):
            ks, vs = _stacked_kv(captured["kv_cache"])
            k_caches = jax.lax.dynamic_update_slice(
                k_caches, ks, (0, slot) + (0,) * (k_caches.ndim - 2))
            v_caches = jax.lax.dynamic_update_slice(
                v_caches, vs, (0, slot) + (0,) * (v_caches.ndim - 2))
        return k_caches, v_caches, first

    return step_fn


def kv_layer_pairs(kv_tree) -> "list[tuple]":
    """Per-layer ``(k, v)`` pairs from the sown ``kv_cache`` collection
    (``(block,)`` where a model's row holds key and value at once),
    in layer order (sorted on the numeric suffix of the flax block
    names h0, h1, ...).  Works on concrete arrays AND on ``eval_shape``
    avals (serve/engine.py derives the cache geometry from the latter).
    """
    def layer_no(name):
        digits = "".join(ch for ch in name if ch.isdigit())
        return int(digits) if digits else 0

    pairs = []
    for name in sorted(kv_tree, key=layer_no):
        sub = kv_tree[name]
        while isinstance(sub, dict):
            sub = next(iter(sub.values()))
        pairs.append(sub[0] if isinstance(sub, tuple) and len(sub) == 1
                     and isinstance(sub[0], tuple) else sub)
    return pairs


def _stacked_kv(kv_tree):
    """[n_layer, B, Tb, C] k/v stacks from the sown collection."""
    pairs = kv_layer_pairs(kv_tree)
    ks = jnp.stack([k for k, _ in pairs])
    vs = jnp.stack([v for _, v in pairs])
    return ks, vs


def build_decode_step(module, page_table=None) -> Callable:
    """Serve-plane continuous-batching decode program (sibling of
    :func:`build_train_step`; THE serving hot path).

    ``(params, k_caches, v_caches, tokens, positions) ->
    (k', v', next_tokens)``: advances EVERY batch slot one token in one
    compiled SPMD program — ``tokens``/``positions`` are ``[S]``, the
    caches ``[n_layer, S, L, H*D]`` (serve/kvcache.py), donated by the
    engine and updated in place.  Static shapes by construction:
    request insertion/eviction is a slot-index change in the host-side
    scheduler, so decode never re-traces (serve/scheduler.py).

    ``page_table`` ([S, pages_per_slot] int32 host array,
    serve/fleet/pages.py ``identity_page_table``) selects the paged
    flash-decode kernel's indirect KV fetch.  It is closed over as a
    trace constant — the table geometry is fixed per engine, so the
    program signature (and the zero-retrace contract) is unchanged.
    """
    module.setup_model()
    model = module.configure_decode_model()
    kw = {} if page_table is None else {
        "page_table": jnp.asarray(page_table, jnp.int32)}

    def step_fn(params, k_caches, v_caches, tokens, positions):
        logits, new_k, new_v = model.apply(
            {"params": params}, tokens, positions, k_caches, v_caches,
            method="decode", **kw)
        with jax.named_scope("sample"):
            return new_k, new_v, jnp.argmax(logits, axis=-1).astype(
                tokens.dtype)

    return step_fn


def build_draft_step(module, k: int, page_table=None, model=None,
                     dequant=None) -> Callable:
    """Speculative-decode draft program: ``k`` autoregressive greedy
    decode steps of the DRAFT model, unrolled into ONE compiled
    program over its own (smaller) KV cache.

    ``(draft_params, dk_caches, dv_caches, tokens, positions) ->
    (dk', dv', drafts)``: ``tokens``/``positions`` are the [S] last
    emitted token per slot at its position (exactly the plain-decode
    inputs); ``drafts`` is [S, k] — the k greedily drafted tokens per
    slot.  Each unrolled step writes its token's draft-cache row and
    feeds its argmax forward, so after the step the draft cache holds
    rows ``[0, pos+k)``; rows drafted past the verify's accepted
    prefix are stale-but-masked and the NEXT round (restarting at the
    corrected position) overwrites them — same induction as the target
    cache (models/gpt.py ``GPT.verify``).

    ``model`` is the draft flax module
    (``LightningModule.configure_draft()``); ``dequant`` decodes
    int8-resident draft params inline (``RLT_DRAFT_QUANT``).
    """
    module.setup_model()
    if model is None:
        model = module.configure_decode_model()
    kw = {} if page_table is None else {
        "page_table": jnp.asarray(page_table, jnp.int32)}

    def step_fn(params, dk_caches, dv_caches, tokens, positions):
        if dequant is not None:
            params = dequant(params)
        toks, pos, drafts = tokens, positions, []
        for _ in range(k):
            logits, dk_caches, dv_caches = model.apply(
                {"params": params}, toks, pos, dk_caches, dv_caches,
                method="decode", **kw)
            toks = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
            pos = pos + 1
            drafts.append(toks)
        return dk_caches, dv_caches, jnp.stack(drafts, axis=1)

    return step_fn


def build_verify_step(module, k: int, page_table=None) -> Callable:
    """Speculative-decode verify program: ONE batched target forward
    over the k drafted positions per slot.

    ``(params, k_caches, v_caches, tokens, positions) ->
    (k', v', argmaxes)`` with ``tokens``/``positions`` [S, k+1] — per
    slot the last emitted token followed by its k drafts at
    consecutive positions.  ``argmaxes`` [S, k+1]: column j is the
    token the target would emit after the prefix extended by drafts
    ``1..j`` — the scheduler accepts the longest prefix where
    ``draft[j] == argmax[j]`` plus the one corrected token
    (serve/scheduler.py), which makes speculative output token-level
    IDENTICAL to target-only greedy decode.  Rides
    :meth:`models.gpt.GPT.verify`'s multi-query cached attention, so
    the flash-decode/paged kernels and per-query length masks are the
    plain decode path's.
    """
    module.setup_model()
    model = module.configure_decode_model()
    kw = {} if page_table is None else {
        "page_table": jnp.asarray(page_table, jnp.int32)}

    def step_fn(params, k_caches, v_caches, tokens, positions):
        logits, new_k, new_v = model.apply(
            {"params": params}, tokens, positions, k_caches, v_caches,
            method="verify", **kw)
        return new_k, new_v, jnp.argmax(logits, axis=-1).astype(
            tokens.dtype)

    return step_fn


def build_kv_copy() -> Callable:
    """Paged-KV page copy program (serve/fleet/pages.py prefix reuse).

    ``(k_caches, v_caches, src, dst, length) -> (k', v')`` copies cache
    rows ``[0, length)`` from slot ``src`` into slot ``dst`` across
    every layer — the device half of a prefix-cache hit: the matched
    pages move as one masked row-copy instead of being recomputed by a
    prefill.  ``src``/``dst``/``length`` are traced int32 scalars, so
    ONE compiled program serves every (donor, destination, match
    length) triple.  Sound because a cache row is a pure per-(token,
    position) value (ops/attention.py MultiHeadAttention decode path):
    identical prefixes have identical rows wherever they were computed.
    """

    def copy_fn(k_caches, v_caches, src, dst, length):
        L = k_caches.shape[2]
        mask = (jnp.arange(L) < length)[None, None, :, None]

        def one(c):
            src_rows = jax.lax.dynamic_slice_in_dim(c, src, 1, axis=1)
            dst_rows = jax.lax.dynamic_slice_in_dim(c, dst, 1, axis=1)
            merged = jnp.where(mask, src_rows, dst_rows)
            return jax.lax.dynamic_update_slice_in_dim(c, merged, dst,
                                                       axis=1)

        return one(k_caches), one(v_caches)

    return copy_fn


def build_suffix_step(module, page_table=None) -> Callable:
    """Single-slot suffix-prefill program (the compute leg of prefix
    reuse, serve/fleet/pages.py).

    ``(params, k_caches, v_caches, token, pos, slot) ->
    (k', v', next_token)``: advances ONE slot one token — the model's
    decode forward on a 1-row batch whose row IS cache slot ``slot``
    (``GPT.decode(slots=...)``): it writes that slot's row into the
    resident buffers and reads that slot's rows where they lie, with no
    slot sliced out or written back.  After a prefix-cache hit copies
    the matched pages (:func:`build_kv_copy`), the unmatched suffix is
    teacher-forced through this program one token at a time; only the
    suffix is ever computed, which is the measured ``prefill tokens
    computed vs requested`` savings.  Unlike the batched decode program
    this writes NOTHING outside ``slot`` — no dummy writes to neighbors
    — so it can run mid-step without the serve plan's dispatch-order
    contract.

    ``page_table`` is the engine's whole ``[S, pages_per_slot]`` table;
    the program takes row ``slot`` of it, so one compiled program
    serves every slot.
    """
    module.setup_model()
    model = module.configure_decode_model()
    table = None if page_table is None \
        else jnp.asarray(page_table, jnp.int32)

    def step_fn(params, k_caches, v_caches, token, pos, slot):
        kw = {} if table is None else {
            "page_table": jax.lax.dynamic_slice_in_dim(table, slot, 1)}
        logits, k_caches, v_caches = model.apply(
            {"params": params}, token[None], pos[None], k_caches, v_caches,
            method="decode", slots=slot[None], **kw)
        nxt = jnp.argmax(logits[0], axis=-1).astype(token.dtype)
        return k_caches, v_caches, nxt

    return step_fn


def build_eval_step(module, stage: str) -> Callable:
    """(state, batch) -> logged metrics dict (pure, no state mutation)."""
    step = {"validate": module.validation_step,
            "test": module.test_step}[stage]

    def step_fn(state: TrainState, batch: Any):
        ctx = StepContext(module, state.params, state.model_state,
                          rng=None, training=False)
        out = step(ctx, batch)
        logged = ctx.logged
        if out is not None and not isinstance(out, dict) and not logged:
            # A bare returned scalar with nothing logged: surface it.
            logged = {"val_loss" if stage == "validate" else "test_loss":
                      jnp.asarray(out, jnp.float32)}
        elif isinstance(out, dict):
            logged = {**logged,
                      **{k: jnp.asarray(v, jnp.float32)
                         for k, v in out.items()}}
        return logged

    # a program's name is its function's: the train step stays
    # ``jit_step_fn``; these get names of their own, so that a profiler
    # trace and the scope tables (telemetry/scopes.py, keyed by program
    # name) tell them apart
    step_fn.__name__ = step_fn.__qualname__ = f"{stage}_step"
    return step_fn


def build_predict_step(module) -> Callable:
    def predict_step(state: TrainState, batch: Any):
        ctx = StepContext(module, state.params, state.model_state,
                          rng=None, training=False)
        return module.predict_step(ctx, batch)

    return predict_step
