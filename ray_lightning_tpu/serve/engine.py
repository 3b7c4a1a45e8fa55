"""Worker-side serve engine: AOT-compiled prefill/decode over a
device-resident KV cache.

One engine lives inside each serve worker for the fleet's whole life.
At setup it:

1. builds the mesh through the TRAINING strategy
   (``strategy.build_mesh(batch_hint=slots)``) and shards params with
   the strategy's own ``param_spec`` walk — the serving layout is the
   training layout;
2. materializes params (restored weights or a seeded init) and the
   zeroed slot-indexed KV cache (``kv_cache_spec`` sharding);
3. jits one prefill program per sequence-length bucket
   (core/steps.py build_prefill_step) plus ONE decode program
   (build_decode_step), submits them to the AOT precompiler so XLA
   compiles in the background through the persistent compilation cache
   (compile/) — every (bucket, topology) program is compiled once per
   FLEET, ever: worker 2 and every restart read worker 1's disk
   entries — then dispatch-warms each program once on scratch state;
4. counts Python re-traces per program (the traced body bumps a host
   counter, so a retrace is observable as a counter increment) — the
   zero-retrace-after-warmup acceptance evidence, alongside the
   compile-cache hit counters.

The cache is the two arrays ``[n_layer, S, R, C]`` for a model whose
layers are of one kind, and a small pytree each (a tuple an array a kind,
an int32 accumulator behind the keys' where the model asked for one:
``module.serve_counters``) for one whose layers keep caches of their own
(serve/kvcache.py ``KVCacheSpec.kinds`` / ``state``): one description,
and the same programs, donation and warm-up either way.  A model whose
row holds key and value at once (models/xing.py's latent rows) has ONE
array a kind: it travels on the keys' side, and the values' side of
every program is the empty tuple.  A model whose decode step needs more
of the position before it than its rows hold (models/zaya.py: two
convolutions' inputs and a shifted value) keeps a small block a slot a
layer, its TAIL (``KVCacheSpec.tail``, read off the same capture): one
more array on the keys' side, before the accumulator, made by
``kv_init`` in the model's own dtype and donated with the rest.  A layer
that keeps NO rows (models/kimi_linear.py: a float32 matrix a head that
every step multiplies, a ring of convolution inputs and the int32
position the matrix stands at) sows a ``SlotState`` in their place; its
blocks are arrays of their own shapes and types on the keys' side too
(``KVCacheSpec.states``), behind the arrays of the layers that do keep
rows, and each array is sharded by its own rank (slots on the data axes
wherever it has them).

After setup the engine is a pure executor: ``prefill``/``decode`` calls
carry no Python branching on request state, so the decode loop shape
never changes (scheduler.py keeps insertion/eviction host-side).

Each slot's newest token also stays on the device, as one ``int32[S]``
vector: the decode program's output IS that vector, and every prefill
program takes it and returns it with ``[slot]`` set to the prompt's
first token (``_with_newest`` below wraps whatever step the family
built).  So a decode can be queued from the vector before any token of
the programs ahead of it has come back: ``dispatch_decode`` /
``dispatch_prefill`` queue a program and return a handle, ``fetch``
waits for one.  ``decode`` and ``prefill`` are the blocking pair of the
two.  ``ServeWorker._run_ahead`` (worker.py) uses the non-blocking forms
to keep the device one decode ahead of the scheduler, on an engine
whose ``runs_ahead`` says that nothing but these programs touches its
state between two plans.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Optional, Sequence

import numpy as np

from ray_lightning_tpu.compile import AotPrecompiler
from ray_lightning_tpu.core.steps import (
    build_decode_step,
    build_draft_step,
    build_kv_copy,
    build_prefill_step,
    build_suffix_step,
    build_verify_step,
    kv_layer_pairs,
)
from ray_lightning_tpu.serve.kvcache import KVCacheSpec, SlotState
from ray_lightning_tpu.telemetry import metrics as _metrics
from ray_lightning_tpu.telemetry import span

_log = logging.getLogger(__name__)


def _with_newest(step):
    """A prefill step that also keeps the newest-token vector:
    ``(params, k, v, tokens, slot, length, newest) -> (k', v', first,
    newest')`` with ``newest'[slot] = first``, around whatever
    ``(…, length) -> (k', v', first)`` the family built."""
    import jax

    def step_fn(params, k_caches, v_caches, tokens, slot, length, newest):
        k_caches, v_caches, first = step(params, k_caches, v_caches,
                                         tokens, slot, length)
        with jax.named_scope("sample"):
            newest = jax.lax.dynamic_update_index_in_dim(
                newest, first.astype(newest.dtype), slot, 0)
        return k_caches, v_caches, first, newest

    return step_fn


class ServeEngine:
    """Compiled generation executor bound to one process's devices."""

    def __init__(self, module, strategy, buckets: Sequence[int],
                 slots: int, max_seq_len: int, seed: int = 0,
                 weights: Optional[dict] = None, paged: Any = None,
                 spec: Any = None, kvship: bool = False):
        self.module = module
        self.strategy = strategy
        self.buckets = tuple(buckets)
        self.slots = int(slots)
        self.max_seq_len = int(max_seq_len)
        self.seed = int(seed)
        self._weights = weights
        #: PageConfig (serve/fleet/pages.py) — when enabled the engine
        #: additionally builds the page-copy + single-slot suffix
        #: programs that make prefix-cache hits executable
        self.paged = paged if paged is not None and paged.enabled \
            else None
        #: SpecConfig (serve/spec.py) — when enabled the engine builds
        #: the draft plane: a draft param subtree + its own KV cache,
        #: one draft prefill per bucket, the k-step draft program and
        #: the batched verify program
        self.spec = spec if spec is not None and spec.enabled else None
        #: build per-bucket kv_import programs so cross-replica KV-page
        #: shipping (serve/fleet/router.py) can install donor rows; a
        #: flag (not default-on) so non-fleet engines keep their exact
        #: pre-existing program count
        self.kvship = bool(kvship)
        #: which decode attention kernel the decode program LOWERED —
        #: dense | flash_decode | paged, as cached_attention noted it
        #: while the program traced (ops/flash_decode.py
        #: record_decode_kernels), not the RLT_DECODE_IMPL request;
        #: benches emit it so a kernel regression is visible in the
        #: JSON ledger.  None until the decode program has traced.
        self.decode_kernel: Optional[str] = None
        #: for each kernel of that program, the distinct [block rows,
        #: blocks a slot, rows in the last block] its calls read a slot
        #: in (a last block of fewer rows than a block ends past the
        #: cache and is masked, ops/flash_decode.py ``_decode_body``):
        #: {"gqa_decode": [[512, 8, 512], [512, 18, 256]]} for a ring of
        #: 4,096 rows and a full layer of 8,960
        self.decode_blocks: dict[str, list] = {}
        self.trace_counts: dict[str, int] = {}
        self.kv_spec: Optional[KVCacheSpec] = None
        self.params = None
        self._mesh = None
        self._prefills: dict[int, Any] = {}
        self._decode = None
        self._kv_copy = None
        self._suffix = None
        self._kv_init = None
        self._k = None
        self._v = None
        #: int32[S] on the device: each slot's newest token (module
        #: docstring).  Never donated: a step's tokens are read from
        #: its handle after later programs were queued.
        self._newest = None
        #: where a token vector from the host is put before the decode
        #: program sees it (None: the one device, uncommitted), so that
        #: the program is fed one type of argument whoever feeds it
        self._rep = None
        self._k_dtype = None
        # draft plane (spec decode)
        self.draft_kv_spec: Optional[KVCacheSpec] = None
        self.draft_layers = 0
        self._draft_model = None
        self._draft_params = None
        self._draft_prefills: dict[int, Any] = {}
        self._draft = None
        self._verify = None
        self._dkv_init = None
        self._dk = None
        self._dv = None
        #: extra HBM the draft residency holds (0 = pure weight-sharing
        #: views of the target tree; int8 quant holds payload+scales)
        self.draft_resident_bytes = 0
        #: what a standalone bf16 copy of the draft tree would cost —
        #: the baseline the HBM delta in stats() is measured against
        self.draft_fp_bytes = 0
        # kv-ship plane
        self._kv_imports: dict[int, Any] = {}

    # -- setup -------------------------------------------------------------

    def setup(self) -> "ServeEngine":
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_lightning_tpu.parallel.mesh import set_current_mesh

        t0 = time.monotonic()
        module = self.module
        module.setup_model()
        model = module.configure_decode_model()
        with span("devices"):
            # the first question about devices: JAX starts and claims
            # the chip in here
            mesh = self.strategy.build_mesh(batch_hint=self.slots)
        self._mesh = mesh
        set_current_mesh(mesh)

        with span("build"):
            # abstract params + cache geometry, no device work: params from
            # the model's own init avals, the K/V row width from an
            # abstract prefill capture on the smallest bucket
            dummy = jax.ShapeDtypeStruct((1, self.buckets[0]), np.int32)
            abstract_vars = jax.eval_shape(
                model.init, jax.random.PRNGKey(0), dummy)
            abstract_params = abstract_vars["params"]
            _, cap = jax.eval_shape(
                lambda p, t: model.apply({"params": p}, t, True,
                                         mutable=["kv_cache"]),
                abstract_params, dummy)
            # (a layer's captured tuple: keys and values, or the one
            # block of a model whose row holds both)
            captured = kv_layer_pairs(cap["kv_cache"])
            self.kv_spec = KVCacheSpec.from_capture(
                captured, self.slots, self.max_seq_len,
                counters=len(getattr(module, "serve_counters", ())))
            # (the rows' type: of the first layer that keeps rows)
            kv_dtype = self._k_dtype = next(
                k[0].dtype for k in captured
                if not isinstance(k, SlotState))
            if self.kv_spec.own_state:
                self._check_own_state(module)

            param_sh = self.strategy._shardings_with(
                mesh, abstract_params, self.strategy.param_spec)
            # one sharding an array of the state, by the array's rank
            k_sh, v_sh = jax.tree_util.tree_map(
                lambda a: NamedSharding(mesh, self.strategy.kv_cache_spec(
                    mesh, len(a.shape))),
                self.kv_spec.state(jax.ShapeDtypeStruct, kv_dtype))
            rep = NamedSharding(mesh, P())
            multi = mesh.devices.size > 1
            self._rep = rep if multi else None

        with span("weights"):
            # -- params: restored weights or a seeded fresh init --------------
            if self._weights is not None:
                from flax import serialization
                params = self._weights["params"] \
                    if isinstance(self._weights, dict) \
                    and "params" in self._weights else self._weights
                # normalize checkpoint/state-dict nesting onto the model's
                # own param tree structure before sharding
                params = serialization.from_state_dict(abstract_params,
                                                       params)
                self.params = jax.device_put(params, param_sh) \
                    if multi else jax.device_put(params)
            else:
                def init_fn(rng):
                    import jax.numpy as jnp
                    variables = module.init_params(
                        rng, np.zeros((1, self.buckets[0]), np.int32))
                    p = dict(variables)["params"]
                    pd = getattr(module, "param_dtype", None)
                    if pd is not None:
                        p = jax.tree_util.tree_map(
                            lambda a: a.astype(pd)
                            if jnp.issubdtype(a.dtype, jnp.floating) else a,
                            p)
                    return p

                ikw = {"out_shardings": param_sh} if multi else {}
                self.params = jax.jit(init_fn, **ikw)(
                    jax.random.PRNGKey(self.seed))
            self._weights = None
            # (not waited for: the device makes the weights while this
            # thread builds the programs and the AOT thread loads them;
            # the span holds the host's seconds, trace and dispatch)
        with span("build"):

            # -- programs ------------------------------------------------------
            import jax.numpy as jnp
            kv_spec = self.kv_spec

            def kv_init():
                # (k, v): the two arrays, or a small pytree each where
                # the layers are of more than one kind (serve/kvcache.py)
                return kv_spec.state(jnp.zeros, kv_dtype)

            kkw = {"out_shardings": (k_sh, v_sh)} if multi else {}
            self._kv_init = jax.jit(self._counted("kv_init", kv_init), **kkw)

            def jit_step(name, fn, n_scalars, n_out=1):
                kw: dict = {"donate_argnums": (1, 2)}
                if multi:
                    kw["in_shardings"] = (
                        (param_sh, k_sh, v_sh) + (rep,) * n_scalars)
                    kw["out_shardings"] = (k_sh, v_sh) + (rep,) * n_out
                return jax.jit(self._counted(name, fn), **kw)

            for b in self.buckets:
                self._prefills[b] = jit_step(
                    f"prefill_{b}",
                    _with_newest(build_prefill_step(module, b)), 4, 2)

            # the paged kernel needs a page table whose pages tile the
            # cache; with paging off or ragged no table is plumbed and
            # "paged" lowers the slot-contiguous flash kernel instead
            # (ops/flash_decode.py select_decode_kernel)
            from ray_lightning_tpu.ops.flash_decode import resolve_decode_impl
            page_table = None
            if resolve_decode_impl(None) == "paged" \
                    and self.paged is not None \
                    and self.max_seq_len % self.paged.page_size == 0:
                from ray_lightning_tpu.serve.fleet.pages import (
                    identity_page_table)
                page_table = identity_page_table(
                    self.slots, self.max_seq_len, self.paged.page_size)
            self._decode = jit_step(
                "decode", build_decode_step(module, page_table=page_table), 2)
            if self.paged is not None:
                # paged-KV programs (serve/fleet/pages.py): a masked page
                # copy for prefix-cache hits + the single-slot suffix step
                # that computes only the unmatched tail of a prompt
                self._suffix = jit_step(
                    "suffix",
                    build_suffix_step(module, page_table=page_table), 3)
                ckw: dict = {"donate_argnums": (0, 1)}
                if multi:
                    ckw["in_shardings"] = (k_sh, v_sh, rep, rep, rep)
                    ckw["out_shardings"] = (k_sh, v_sh)
                self._kv_copy = jax.jit(
                    self._counted("kv_copy", build_kv_copy()), **ckw)

            if self.spec is not None:
                # -- draft plane (speculative decoding, serve/spec.py) ---------
                draft_model = module.configure_draft(
                    self.spec.draft_layers or None)
                if draft_model is None:
                    raise ValueError(
                        f"spec= requires {type(module).__name__}."
                        f"configure_draft() to return a draft module "
                        f"(core/module.py hook); it returned None")
                self._draft_model = draft_model
                self.draft_layers = getattr(
                    getattr(draft_model, "config", None), "n_layer", 0)
                d_abstract = jax.eval_shape(
                    draft_model.init, jax.random.PRNGKey(0), dummy)["params"]

                def _subtree(target, aval, path=""):
                    """Draft params BY PATH out of the target tree — the
                    weight-sharing contract: every draft param is the
                    target's same-named array (zero extra HBM)."""
                    if isinstance(aval, dict):
                        out = {}
                        for name, sub in aval.items():
                            if name not in target:
                                raise ValueError(
                                    f"draft param {path + name!r} missing "
                                    f"from the target tree: "
                                    f"configure_draft() must share the "
                                    f"target's param naming")
                            out[name] = _subtree(target[name], sub,
                                                 path + name + "/")
                        return out
                    if tuple(target.shape) != tuple(aval.shape):
                        raise ValueError(
                            f"draft param {path!r}: shape {aval.shape} != "
                            f"target {target.shape}")
                    return target

                draft_params = _subtree(self.params, d_abstract)
                self.draft_fp_bytes = int(sum(
                    int(np.prod(a.shape)) * 2
                    for a in jax.tree_util.tree_leaves(d_abstract)))
                dequant = None
                if self.spec.draft_quant == "int8":
                    # int8 residency (RLT_DRAFT_QUANT): hold the draft tree
                    # as blockwise (payload, scale) pairs, dequantized
                    # INSIDE the draft programs (comm/quant.py).  Trades
                    # the zero-cost views for a ~2x-smaller standalone copy
                    # whose bytes stay resident even if the target tree is
                    # later offloaded; the measured delta rides stats().
                    from ray_lightning_tpu.comm.quant import (
                        dequantize_blob, quantize_blob)
                    flat, treedef = jax.tree_util.tree_flatten(draft_params)
                    shapes = [tuple(a.shape) for a in flat]
                    dtypes = [a.dtype for a in flat]
                    qflat = [tuple(quantize_blob(a, "int8")) for a in flat]
                    self._draft_params = qflat
                    self.draft_resident_bytes = int(sum(
                        p.nbytes + s.nbytes for p, s in qflat))

                    def dequant(qleaves):
                        leaves = [
                            dequantize_blob(p, s, "int8", shape, dtype=dt)
                            for (p, s), shape, dt in zip(qleaves, shapes,
                                                         dtypes)]
                        return jax.tree_util.tree_unflatten(treedef, leaves)
                else:
                    self._draft_params = draft_params

                # draft KV geometry from an abstract draft prefill capture
                _, dcap = jax.eval_shape(
                    lambda p, t: draft_model.apply(
                        {"params": p}, t, True, mutable=["kv_cache"]),
                    d_abstract, dummy)
                dk_avals = [a for a, _ in kv_layer_pairs(dcap["kv_cache"])]
                self.draft_kv_spec = KVCacheSpec.from_capture(
                    dk_avals, self.slots, self.max_seq_len)
                d_shape = self.draft_kv_spec.shape

                def dkv_init():
                    z = jnp.zeros(d_shape, kv_dtype)
                    return z, z

                self._dkv_init = jax.jit(
                    self._counted("draft_kv_init", dkv_init), **kkw)

                def jit_draft(name, fn):
                    # no in_shardings pin: the draft param tree is NOT the
                    # target tree (subtree, possibly quantized pairs) — jax
                    # reads the resident shardings of the shared views
                    kw: dict = {"donate_argnums": (1, 2)}
                    if multi:
                        kw["out_shardings"] = (k_sh, v_sh, rep)
                    return jax.jit(self._counted(name, fn), **kw)

                for b in self.buckets:
                    self._draft_prefills[b] = jit_draft(
                        f"draft_prefill_{b}",
                        build_prefill_step(module, b, model=draft_model,
                                           dequant=dequant))
                self._draft = jit_draft(
                    "draft",
                    build_draft_step(module, self.spec.k,
                                     page_table=page_table,
                                     model=draft_model, dequant=dequant))
                self._verify = jit_step(
                    "verify",
                    build_verify_step(module, self.spec.k,
                                      page_table=page_table), 2)

            if self.kvship:
                # -- KV-page import programs (fleet disaggregation) ------------
                # one per bucket: install shipped donor rows [0, b) at a
                # slot with a single dynamic_update_slice per cache — the
                # device half of cross-replica prefix donation
                # (serve/fleet/router.py ships, PrefixIndex addresses)
                def import_fn(k_caches, v_caches, ks, vs, slot):
                    zero = (0,) * (k_caches.ndim - 2)
                    k_caches = jax.lax.dynamic_update_slice(
                        k_caches, ks, (0, slot) + zero)
                    v_caches = jax.lax.dynamic_update_slice(
                        v_caches, vs, (0, slot) + zero)
                    return k_caches, v_caches

                for b in self.buckets:
                    ikw2: dict = {"donate_argnums": (0, 1)}
                    if multi:
                        ikw2["in_shardings"] = (k_sh, v_sh, rep, rep, rep)
                        ikw2["out_shardings"] = (k_sh, v_sh)
                    self._kv_imports[b] = jax.jit(
                        self._counted(f"kv_import_{b}", import_fn), **ikw2)

            # AOT avals must describe the params AS SERVED (post
            # param_dtype cast / restore), not the fp32 init avals — a
            # dtype drift here would background-compile a program the
            # dispatch never runs (cache miss instead of the hit the
            # compiled-once story is built on)
            param_avals = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self.params)
            pre = self._submit_precompiles(
                jax, param_avals,
                kv_spec.state(jax.ShapeDtypeStruct, kv_dtype))
        self._warm(jax, pre)
        _log.info(
            "serve engine ready in %.2fs: mesh=%s buckets=%s slots=%d "
            "kv=%s (%.1f MB)", time.monotonic() - t0, dict(mesh.shape),
            self.buckets, self.slots, self.kv_spec.shapes,
            self.kv_spec.nbytes(np.dtype(kv_dtype).itemsize) / 2**20)
        return self

    def _check_own_state(self, module) -> None:
        """A model that keeps its own kind of rows in the cache
        (serve/kvcache.py) sized them from its configuration's
        positions, and a prefix of a prompt is not a prefix of them."""
        positions = getattr(getattr(module, "config", None),
                            "block_size", self.max_seq_len)
        if self.max_seq_len > positions:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} exceeds the {positions} "
                f"positions the model sized its cache rows for")
        if self.paged is not None or self.kvship or self.spec is not None:
            raise ValueError(
                f"{type(module).__name__} keeps its own kind of cache "
                f"rows ({[s[2] for s in self.kv_spec.shapes]} a slot, not "
                f"a row per position): paged=, kvship= and spec= copy or "
                f"replay rows by position and are refused")

    def _submit_precompiles(self, jax, abstract_params,
                            kv_avals) -> AotPrecompiler:
        """Background-compile every program through the persistent cache
        (no-op when the cache is inactive, compile/aot.py): the AOT
        thread lowers and compiles (or loads) them while this thread
        goes on."""
        pre = AotPrecompiler.resolve()
        k_aval, v_aval = kv_avals
        kv_dtype = self._k_dtype
        i32 = lambda *s: jax.ShapeDtypeStruct(s, np.int32)  # noqa: E731
        # the newest-token vector is a device array wherever a program
        # is fed it (_put_tokens): the aval says where it lies
        newest = jax.ShapeDtypeStruct((self.slots,), np.int32,
                                      sharding=self._rep)
        for b, jitted in self._prefills.items():
            pre.submit(f"prefill_{b}", jitted,
                       (abstract_params, k_aval, v_aval,
                        i32(1, b), i32(), i32(), newest))
        pre.submit("decode", self._decode,
                   (abstract_params, k_aval, v_aval,
                    newest, i32(self.slots)))
        if self.paged is not None:
            pre.submit("suffix", self._suffix,
                       (abstract_params, k_aval, v_aval,
                        i32(), i32(), i32()))
            pre.submit("kv_copy", self._kv_copy,
                       (k_aval, v_aval, i32(), i32(), i32()))
        if self.spec is not None:
            dp_avals = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                self._draft_params)
            dkv_aval = jax.ShapeDtypeStruct(self.draft_kv_spec.shape,
                                            kv_dtype)
            for b, jitted in self._draft_prefills.items():
                pre.submit(f"draft_prefill_{b}", jitted,
                           (dp_avals, dkv_aval, dkv_aval,
                            i32(1, b), i32(), i32()))
            pre.submit("draft", self._draft,
                       (dp_avals, dkv_aval, dkv_aval,
                        i32(self.slots), i32(self.slots)))
            pre.submit("verify", self._verify,
                       (abstract_params, k_aval, v_aval,
                        i32(self.slots, self.spec.k + 1),
                        i32(self.slots, self.spec.k + 1)))
        if self.kvship:
            nl, _, _, width = self.kv_spec.shape
            for b, jitted in self._kv_imports.items():
                rows = jax.ShapeDtypeStruct((nl, 1, b, width), kv_dtype)
                pre.submit(f"kv_import_{b}", jitted,
                           (k_aval, v_aval, rows, rows, i32()))
        return pre

    def _warm(self, jax, pre: AotPrecompiler) -> None:
        """Warm each program with ONE dispatch on scratch state — after
        this, a serving trace-count increment means a real retrace (the
        acceptance counter).  One ``warm`` span per program around its
        dispatch, which is where a cached executable loads (or, on a
        cache miss, compiles); the programs run back to back on the
        device meanwhile, and ``device_wait`` is the one wait for them
        all."""
        def warm(program, fn, *args):
            with span("warm", program=program):
                return fn(*args)

        kv_dtype = self._k_dtype
        with span("warmup"):
            with span("aot_wait"):
                pre.barrier()
            # scratch warmup: the warmed cache state is garbage, so
            # re-init the real cache afterwards (slots are overwritten
            # by their admitting prefill anyway; this keeps even slot 0
            # pristine)
            k, v = warm("kv_init", self._kv_init)
            # (the newest-token vector is a device array from the
            # start, as it is in serving: _put_tokens)
            zeros = np.zeros((self.slots,), np.int32)
            newest = self._put_tokens(zeros)
            for b, jitted in self._prefills.items():
                k, v, tok, newest = warm(
                    f"prefill_{b}", jitted, self.params, k, v,
                    np.zeros((1, b), np.int32), np.int32(0), np.int32(1),
                    newest)
            k, v, newest = warm("decode", self._decode, self.params, k, v,
                                newest, zeros)
            toks = newest
            if self.paged is not None:
                k, v = warm("kv_copy", self._kv_copy, k, v, np.int32(0),
                            np.int32(self.slots - 1), np.int32(1))
                k, v, toks = warm("suffix", self._suffix, self.params, k,
                                  v, np.int32(0), np.int32(0), np.int32(0))
            if self.spec is not None:
                dk, dv = warm("draft_kv_init", self._dkv_init)
                for b, jitted in self._draft_prefills.items():
                    dk, dv, _ = warm(f"draft_prefill_{b}", jitted,
                                     self._draft_params, dk, dv,
                                     np.zeros((1, b), np.int32),
                                     np.int32(0), np.int32(1))
                dk, dv, _ = warm("draft", self._draft, self._draft_params,
                                 dk, dv, zeros, zeros)
                z2 = np.zeros((self.slots, self.spec.k + 1), np.int32)
                k, v, toks = warm("verify", self._verify, self.params, k,
                                  v, z2, z2)
                del dk, dv
            if self.kvship:
                nl, _, _, width = self.kv_spec.shape
                for b, jitted in self._kv_imports.items():
                    rows = np.zeros((nl, 1, b, width), kv_dtype)
                    k, v = warm(f"kv_import_{b}", jitted, k, v, rows,
                                rows, np.int32(0))
            with span("device_wait"):
                jax.block_until_ready(toks)
            del k, v
        with span("kv_init"):
            self._k, self._v = self._kv_init()
            self._newest = newest
            if self.spec is not None:
                # draft-cache warmup state is garbage too: re-init
                self._dk, self._dv = self._dkv_init()
        #: trace counts at the end of warmup — any later growth is a
        #: REAL decode-loop retrace (the acceptance counter)
        self.trace_counts_at_warmup = dict(self.trace_counts)

    def _counted(self, name: str, fn):
        """Wrap a step body so every TRACE bumps a host counter (the
        wrapper body only runs while jax traces; cached dispatches never
        re-enter Python)."""
        from ray_lightning_tpu.ops.flash_decode import (
            record_decode_kernels)

        def wrapped(*args):
            self.trace_counts[name] = self.trace_counts.get(name, 0) + 1
            reg = _metrics.get_registry()
            if reg is not None:
                reg.counter("rlt_serve_traces_total").inc(1, program=name)
            if name != "decode":
                return fn(*args)
            with record_decode_kernels() as lowered:
                out = fn(*args)
            # one kernel per program: every layer has the same geometry
            self.decode_kernel = "+".join(sorted(lowered)) or None
            self.decode_blocks = {name: blocks for name, blocks
                                  in lowered.items() if blocks}
            return out
        # the program's name: the profiler trace and the compile cache
        # read jit_serve_decode, jit_serve_prefill_512, ...
        wrapped.__name__ = wrapped.__qualname__ = f"serve_{name}"
        return wrapped

    # -- serving -----------------------------------------------------------

    @property
    def runs_ahead(self) -> bool:
        """Whether a decode may be queued before its plan arrives
        (worker.py ``_run_ahead``): only where the programs of a plain
        step are all that touches the engine's state between two plans.
        ``paged`` copies donor pages, ``spec`` runs draft rounds and
        ``kvship`` installs imported rows in between."""
        return self.paged is None and self.spec is None \
            and not self.kvship

    def dispatch_prefill(self, slot: int, tokens: np.ndarray, length: int,
                         bucket: int):
        """Queue the insertion of a request at ``slot`` (its K/V block,
        and its first token into the newest-token vector) and return the
        first token's handle for :meth:`fetch`."""
        t0 = time.monotonic()
        with span("dispatch"):
            self._k, self._v, first, self._newest = self._prefills[bucket](
                self.params, self._k, self._v,
                np.asarray(tokens, np.int32), np.int32(slot),
                np.int32(length), self._newest)
            first.copy_to_host_async()
        self._charge("rlt_serve_prefill_seconds_total",
                     time.monotonic() - t0)
        return first

    def dispatch_decode(self, positions: np.ndarray,
                        tokens: Optional[np.ndarray] = None):
        """Queue one continuous-batching step at ``positions`` and
        return the handle of its ``[S]`` tokens for :meth:`fetch`.
        ``tokens`` left out, each slot is fed its newest token from the
        vector on the device: what the programs queued so far will have
        produced, whether or not any of it has come back."""
        t0 = time.monotonic()
        with span("dispatch"):
            self._k, self._v, self._newest = self._decode(
                self.params, self._k, self._v,
                self._newest if tokens is None
                else self._put_tokens(tokens),
                np.asarray(positions, np.int32))
            self._newest.copy_to_host_async()
        self._charge("rlt_serve_decode_seconds_total",
                     time.monotonic() - t0)
        return self._newest

    def _put_tokens(self, tokens: np.ndarray):
        """A host token vector as the decode program is fed it: on the
        device, placed like the program's own output, so that the one
        trace and the one jit cache entry of warm-up serve a decode fed
        from the host and one fed the vector alike."""
        import jax
        return jax.device_put(np.asarray(tokens, np.int32), self._rep)

    def fetch(self, handle, charge: str) -> np.ndarray:
        """Wait for a dispatched program's tokens; the wait is charged
        to the counter ``charge`` names."""
        import jax
        t0 = time.monotonic()
        with span("fetch"):
            out = np.asarray(jax.device_get(handle))
        self._charge(charge, time.monotonic() - t0)
        return out

    def prefill(self, slot: int, tokens: np.ndarray, length: int,
                bucket: int) -> int:
        """Insert a request at ``slot``: write its K/V block, return its
        first generated token."""
        return int(self.fetch(
            self.dispatch_prefill(slot, tokens, length, bucket),
            "rlt_serve_prefill_seconds_total"))

    def prefill_reused(self, slot: int, src_slot: int,
                       tokens: np.ndarray, length: int,
                       matched: int) -> int:
        """Prefix-cache-hit insertion (serve/fleet/pages.py): copy the
        ``matched`` donor rows device-side, then teacher-force ONLY the
        unmatched suffix through the single-slot suffix program.  The
        last suffix step's argmax is the request's first generated
        token — the same greedy contract as :meth:`prefill`, at
        ``length - matched`` computed tokens instead of ``length``."""
        if self._kv_copy is None:
            raise RuntimeError("engine built without paged=; no reuse "
                               "programs")
        t0 = time.monotonic()
        toks = np.asarray(tokens, np.int32).reshape(-1)
        self._k, self._v = self._kv_copy(
            self._k, self._v, np.int32(src_slot), np.int32(slot),
            np.int32(matched))
        # a full-prompt match still replays the final prompt token (a
        # same-value rewrite) to read its logits for the first token
        out = None
        for pos in range(min(int(matched), int(length) - 1), int(length)):
            self._k, self._v, out = self._suffix(
                self.params, self._k, self._v, np.int32(toks[pos]),
                np.int32(pos), np.int32(slot))
        import jax
        first = int(np.asarray(jax.device_get(out)))
        self._charge("rlt_serve_prefill_seconds_total",
                     time.monotonic() - t0)
        return first

    def decode(self, tokens: np.ndarray,
               positions: np.ndarray) -> np.ndarray:
        """One continuous-batching step: every slot advances a token."""
        return self.fetch(self.dispatch_decode(positions, tokens),
                          "rlt_serve_decode_seconds_total")

    # -- speculative decoding ----------------------------------------------

    def draft_prefill(self, slot: int, tokens: np.ndarray, length: int,
                      bucket: int) -> None:
        """Write the DRAFT model's K/V rows for an admitted prompt.

        Runs at every admission (fresh AND prefix-reused) so the draft
        cache carries the request's history before its first spec
        round; the emitted-token contract is the target's alone, so
        the draft prefill's argmax is discarded."""
        t0 = time.monotonic()
        self._dk, self._dv, _ = self._draft_prefills[bucket](
            self._draft_params, self._dk, self._dv,
            np.asarray(tokens, np.int32), np.int32(slot),
            np.int32(length))
        self._charge("rlt_serve_draft_seconds_total",
                     time.monotonic() - t0)

    def draft(self, tokens: np.ndarray,
              positions: np.ndarray) -> np.ndarray:
        """One k-step draft round over every slot: ``[S, k]`` drafted
        tokens (core/steps.py ``build_draft_step``)."""
        t0 = time.monotonic()
        self._dk, self._dv, out = self._draft(
            self._draft_params, self._dk, self._dv,
            np.asarray(tokens, np.int32), np.asarray(positions, np.int32))
        import jax
        drafts = np.asarray(jax.device_get(out))
        self._charge("rlt_serve_draft_seconds_total",
                     time.monotonic() - t0)
        return drafts

    def verify(self, tokens: np.ndarray, positions: np.ndarray,
               drafts: np.ndarray) -> np.ndarray:
        """ONE batched target forward over the k drafted positions:
        ``[S, k+1]`` target argmaxes — column j is the token plain
        decode would emit after accepting drafts ``1..j`` (the
        scheduler folds the longest agreeing prefix + one corrected
        token).  Counts as a single target forward however many tokens
        it ends up emitting — the tokens-per-target-forward win."""
        t0 = time.monotonic()
        toks2 = np.concatenate(
            [np.asarray(tokens, np.int32)[:, None],
             np.asarray(drafts, np.int32)], axis=1)
        pos2 = (np.asarray(positions, np.int32)[:, None]
                + np.arange(self.spec.k + 1, dtype=np.int32)[None, :])
        self._k, self._v, out = self._verify(
            self.params, self._k, self._v, toks2, pos2)
        import jax
        ver = np.asarray(jax.device_get(out))
        self._charge("rlt_serve_verify_seconds_total",
                     time.monotonic() - t0)
        return ver

    # -- KV-page shipping (fleet disaggregation) ---------------------------

    def export_kv(self, slot: int, bucket: int
                  ) -> "tuple[np.ndarray, np.ndarray]":
        """Device→host copy of ``slot``'s cache rows ``[0, bucket)``
        across every layer: ``([n_layer, 1, bucket, H*D], same)`` —
        the payload a prefill replica ships to a decode replica.  Rows
        past the prompt are pad garbage; the importer only registers
        (and the reuse path only copies) the prompt's whole pages, so
        they never influence decode."""
        k_rows = np.asarray(self._k[:, slot:slot + 1, :bucket])
        v_rows = np.asarray(self._v[:, slot:slot + 1, :bucket])
        return k_rows, v_rows

    def import_kv(self, slot: int, k_rows: np.ndarray,
                  v_rows: np.ndarray) -> None:
        """Install shipped donor rows at ``slot`` via the per-bucket
        AOT ``kv_import_{b}`` program.  Sound for the same reason
        kv_copy is: a cache row is a pure per-(token, position) value,
        identical wherever it was computed — including on another
        replica."""
        if not self._kv_imports:
            raise RuntimeError("engine built without kvship=; no "
                               "import programs")
        bucket = int(k_rows.shape[2])
        dt = self._k.dtype  # codec decode yields fp32; the program's
        # aval is the cache dtype — cast host-side, never retrace
        self._k, self._v = self._kv_imports[bucket](
            self._k, self._v, np.asarray(k_rows).astype(dt),
            np.asarray(v_rows).astype(dt), np.int32(slot))

    def _counters(self, jax) -> dict:
        """What the model's steps counted on the device
        (``module.serve_counters`` names the accumulator's entries; it
        rides behind the keys' arrays, serve/kvcache.py): read here,
        where the stats are asked, after whatever is queued."""
        names = getattr(self.module, "serve_counters", ())
        if not names or self._k is None:
            return {}
        got = np.asarray(jax.device_get(self._k[-1]))
        return {"counters": {n: int(x) for n, x in zip(names, got)}}

    @staticmethod
    def _charge(name: str, seconds: float) -> None:
        reg = _metrics.get_registry()
        if reg is not None:
            reg.counter(name).inc(seconds)

    # -- evidence ----------------------------------------------------------

    def stats(self) -> dict:
        """Trace counters + compile-cache counters: the zero-retrace /
        compiled-once evidence surfaced to the driver."""
        from ray_lightning_tpu.compile import cache as compile_cache
        import jax
        s = compile_cache.stats()
        warm = getattr(self, "trace_counts_at_warmup", {})
        dev = jax.local_devices()[0]
        out = {
            # the device THIS process holds, as jax reports it — a
            # serve record names what it ran on
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": jax.device_count()},
            "memory_stats": dev.memory_stats(),
            "decode_kernel": self.decode_kernel,
            "decode_blocks": self.decode_blocks,
            # bytes of KVCacheSpec.states a slot holds (0: rows only)
            "state_bytes_per_slot": self.kv_spec.state_bytes_per_slot
            if self.kv_spec else 0,
            **self._counters(jax),
            "traces": dict(self.trace_counts),
            # traces since the warmup snapshot: 0 everywhere = the
            # decode loop never re-traced while serving
            "retraces": {name: n - warm.get(name, 0)
                         for name, n in self.trace_counts.items()},
            # kv_init + decode + prefills (+ paged copy/suffix pair)
            # (+ spec: draft_kv_init + draft prefills + draft + verify)
            # (+ kvship: one import per bucket) — the program-count
            # invariant serve/selfcheck.py pins
            "programs": 1 + 1 + len(self._prefills)
            + (2 if self.paged is not None else 0)
            + (3 + len(self._draft_prefills) if self.spec is not None
               else 0)
            + len(self._kv_imports),
            "compile_cache": {
                "active": compile_cache.active_dir() is not None,
                "dir": compile_cache.active_dir(),
                "hits": s.hits,
                "misses": s.misses,
                "backend_compile_secs": round(s.backend_compile_secs, 3),
            },
        }
        if self.spec is not None:
            out["spec"] = {
                "k": self.spec.k,
                "draft_layers": self.draft_layers,
                "draft_quant": self.spec.draft_quant,
                # what a standalone bf16 draft copy would cost vs the
                # HBM the residency actually adds (0 = weight-sharing
                # views; int8 = payload + scales) — the satellite's
                # reported HBM delta
                "draft_fp_bytes": self.draft_fp_bytes,
                "draft_resident_bytes": self.draft_resident_bytes,
                "draft_hbm_delta_bytes": self.draft_resident_bytes
                - self.draft_fp_bytes,
            }
        return out


__all__ = ["ServeEngine"]
