"""The persistent serve actor: the cluster backends' ``serve`` mode.

Training actors live for ONE blocking ``execute(_worker_run, ...)``
call; a :class:`ServeWorker` instead stays resident — ``setup_serve``
builds the engine once (jax.distributed join, compile-cache activation,
telemetry, AOT warmup), then the driver streams ``serve_step`` calls
for the fleet's whole life.  It extends the generic
:class:`~ray_lightning_tpu.cluster.executor.RLTExecutor`, so the
driver-side rendezvous plumbing (node IP / free port / env vars) is the
same one the fit path uses, under both cluster backends.

Lockstep contract: every worker of a fleet receives the IDENTICAL plan
and dispatches the same SPMD programs in the same order; rank 0 alone
returns the produced tokens (outputs are replicated, the others return
``None`` to keep the RPC thin).

One decode ahead: on an engine whose state only its plain programs
touch (``ServeEngine.runs_ahead``: built without ``paged``, ``spec`` and
``kvship``) the worker queues a plan's prefills and then the NEXT
step's decode, fed by the token vector that stays on the device, before
it waits for any of the plan's tokens; the next plan finds its decode
already running (a hit) or has it dropped (a miss).  The prediction is
a function of the plans alone, so every rank makes the same one.  Plan
and result formats, and the programs a plan runs on the device and
their order, are the blocking order's (``_run_plan``), which every
other engine and every speculative round still takes.

Trace plane (telemetry/tracing.py): the plan carries each request's
trace id (prefill entries) and a slot→trace map (decode), so this
worker's prefill/decode spans carry the ids back over the queue channel
and the driver aggregator reassembles one span tree per request.  The
plan may also carry a ``profile`` control dict — the on-demand
``jax.profiler`` window armed by ``POST /debug/profile``; every rank
captures its own subdir for the window's step count.  A window holds
exactly its plans' programs in plan order: the decode in flight is
waited for before the trace starts (that plan's own runs inside it),
and the decode after the window's last plan is held back until the
trace has stopped.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Any, Optional

import numpy as np

from ray_lightning_tpu.cluster.executor import RLTExecutor
from ray_lightning_tpu.telemetry import metrics as _metrics
from ray_lightning_tpu.telemetry import span, spans
from ray_lightning_tpu.telemetry.tracing import WorkerProfiler

_log = logging.getLogger(__name__)


class ServeWorker(RLTExecutor):
    """One per TPU host; holds the :class:`ServeEngine` across calls."""

    def __init__(self, env: Optional[dict] = None):
        super().__init__(env)
        self._engine = None
        self._rank = 0
        self._nproc = 1
        self._hb = None
        self._telemetry_cfg = None
        self._profiler: Optional[WorkerProfiler] = None
        #: the decode in flight ahead of its plan: (the positions it
        #: runs at, its tokens' handle), and the positions of the next
        #: one between its prediction and its dispatch (_run_ahead)
        self._ahead: Optional[tuple] = None
        self._next = None

    # -- lifecycle ---------------------------------------------------------

    def setup_serve(self, payload: tuple, rank: int, queue) -> dict:
        """Join the distributed runtime, enable telemetry, build and
        warm the engine.  Returns setup facts the driver logs."""
        # set-up is kept whatever the telemetry flag says: the spans
        # ride back with this call's result and the driver hangs them
        # under its own ``worker_setup`` (telemetry/spans.py adopt)
        with spans.keep("setup_serve") as kept:
            with span("setup_serve", rank=rank):
                info = self._setup_serve(payload, rank, queue)
        info["spans"] = list(kept)
        return info

    def _setup_serve(self, payload: tuple, rank: int, queue) -> dict:
        with span("imports"):
            from ray_lightning_tpu.plugins.xla import _configure_worker_jax
            _configure_worker_jax()
            import jax

            from ray_lightning_tpu.compile import cache as compile_cache
            from ray_lightning_tpu.serve.engine import ServeEngine
            from ray_lightning_tpu.serve.spec import SpecConfig

        spec, weights = payload
        self._rank = rank
        self._nproc = int(os.environ.get("RLT_NUM_PROCESSES", "1"))
        if self._nproc > 1:
            with span("devices"):
                jax.distributed.initialize(
                    coordinator_address=os.environ["RLT_COORDINATOR"],
                    num_processes=self._nproc,
                    process_id=rank,
                )
        self._setup_telemetry(spec, rank, queue)
        compile_cache.activate(spec.compile_cache)

        # spec/kvship ride the pickled ServeSpec when the driver set
        # them; otherwise the RLT_SPEC_* / RLT_SERVE_KVSHIP worker env
        # (the fleet's replica-actor round-trip) decides here
        sp = getattr(spec, "spec", None)
        if sp is None:
            sp = SpecConfig.resolve(None)
        kvship = getattr(spec, "kvship", None)
        if kvship is None:
            kvship = os.environ.get(
                "RLT_SERVE_KVSHIP", "").strip() in ("1", "true", "True")
        self._engine = ServeEngine(
            spec.module, spec.strategy, spec.buckets, spec.slots,
            spec.max_seq_len, seed=spec.seed, weights=weights,
            paged=getattr(spec, "paged", None),
            spec=sp, kvship=bool(kvship)).setup()
        shapes = self._engine.kv_spec.shapes
        return {
            "rank": rank,
            "mesh": dict(self._engine._mesh.shape),
            "buckets": list(self._engine.buckets),
            "slots": self._engine.slots,
            # one kind of layer: its [n_layer, S, R, C]; more: a list of them
            "kv_shape": list(shapes[0]) if len(shapes) == 1
            else [list(s) for s in shapes],
            "stats": self._engine.stats(),
        }

    def _setup_telemetry(self, spec, rank: int, queue) -> None:
        cfg = getattr(spec, "telemetry", None)
        self._telemetry_cfg = cfg
        if cfg is None or not cfg.enabled or queue is None:
            return
        from ray_lightning_tpu import telemetry
        from ray_lightning_tpu.telemetry import heartbeat as hb_mod
        telemetry.enable(
            rank=rank,
            sink=lambda recs, _q=queue, _r=rank: _q.put(
                (_r, telemetry.spans_item(_r, recs))),
            capacity=cfg.capacity, flush_every=cfg.flush_every)
        if cfg.metrics:
            telemetry.enable_metrics(
                rank=rank,
                sink=lambda item, _q=queue, _r=rank: _q.put((_r, item)),
                interval=cfg.metrics_interval)
        if not hb_mod.process_heartbeat_active():
            self._hb = hb_mod.HeartbeatSender(
                lambda item, _q=queue, _r=rank: _q.put((_r, item)),
                rank=rank, interval=cfg.heartbeat_interval).start()

    # -- the serving hot path ----------------------------------------------

    def serve_step(self, plan: dict) -> Optional[dict]:
        """Execute one scheduler plan: one decode over every live slot,
        then the admitting prefills (scheduler.py plan format).

        The order ON THE DEVICE is load-bearing.  The decode program has
        static shapes, so it writes K/V for EVERY slot — slots outside
        ``decode_slots`` get a dummy write (position 0 from a plan; one
        row past its last position from a decode queued ahead, for a
        slot whose request has ended since).  Decode never reads a
        same-step prefill's state (a slot admitted at step k joins the
        decode at step k+1), so decode-first lets each admitting prefill
        overwrite its slot's dummy entry; prefill-first would let the
        dummy write clobber the prompt's K/V just after the prefill
        produced it, corrupting every subsequent token.  Free slots
        that are NOT admitted this step keep the dummy entry
        harmlessly: their next prefill rewrites the whole prefix
        (kvcache.py invariant), and a row ahead of a slot's position is
        rewritten by the slot's own decode before it is ever inside the
        mask.

        The order ON THE HOST (:meth:`_run_ahead`) keeps the device one
        decode ahead of the scheduler: nothing is read back before the
        next step's decode is queued."""
        engine = self._engine
        if engine is None:
            raise RuntimeError("serve_step before setup_serve")
        t0 = time.monotonic()
        prof = plan.get("profile")
        if prof is not None:
            # on-demand jax.profiler window riding the plan broadcast
            # (POST /debug/profile, telemetry/tracing.py)
            if self._profiler is None:
                self._profiler = WorkerProfiler(rank=self._rank)
            # a window holds exactly its plans' programs, in plan
            # order: the decode in flight ends before the trace starts,
            # and this plan's runs again inside it (a miss)
            self._drop_ahead(engine, wait=True)
            self._profiler.maybe_start(prof)
        # the step's number rides the plan: the driver pump's spans of
        # this step carry the same one (children inherit it)
        with span("serve_step", step=plan.get("step")):
            result = self._run_plan(engine, plan)
        if self._profiler is not None:
            self._profiler.note_step()
        # (held back over a window's last step, so that no program of
        # the next plan starts inside the trace)
        self._dispatch_ahead(engine)
        # this call's wall seconds in the worker, a profile window's
        # start and stop included: the pump's round trip minus this is
        # what the RPC cost
        result.setdefault("timing", {})["serve_step"] = \
            time.monotonic() - t0
        return result if self._rank == 0 else None

    def _run_plan(self, engine, plan: dict) -> dict:
        decode = plan.get("decode")
        if engine.runs_ahead and not (decode or {}).get("spec"):
            return self._run_ahead(engine, plan)
        result: dict[str, Any] = {"prefill": {}, "decode": {}}
        if decode is not None and decode.get("spec"):
            # speculative round: k draft steps then ONE batched target
            # verify; the SCHEDULER decides acceptance from the raw
            # outputs (scheduler._apply_spec), workers stay stateless
            t0 = time.monotonic()
            with span("draft", traces=decode.get("traces"),
                      slots=len(decode["slots"])):
                drafts = engine.draft(decode["tokens"],
                                      decode["positions"])
            t1 = time.monotonic()
            with span("verify", traces=decode.get("traces"),
                      slots=len(decode["slots"])):
                ver = engine.verify(decode["tokens"],
                                    decode["positions"], drafts)
            t2 = time.monotonic()
            for s in decode["slots"]:
                result["decode"][s] = {
                    "draft": [int(x) for x in drafts[s]],
                    "verify": [int(x) for x in ver[s]]}
            # wall attribution for the goodput ledger (server pump):
            # draft/verify are their own buckets, not "decode"
            result["timing"] = {"draft": t1 - t0, "verify": t2 - t1}
        elif decode is not None:
            # ONE span for the shared decode program, fanned out to
            # every live request's tree via the slot→trace map
            with span("decode", traces=decode.get("traces"),
                      slots=len(decode["slots"])):
                toks = engine.decode(decode["tokens"],
                                     decode["positions"])
            for s in decode["slots"]:
                result["decode"][s] = int(toks[s])
        for p in plan["prefills"]:
            reuse = p.get("reuse")
            with span("prefill", trace=p.get("trace"),
                      bucket=p["bucket"], slot=p["slot"],
                      reused=(reuse or {}).get("matched", 0)):
                if reuse is not None:
                    # prefix-cache hit (serve/fleet/pages.py): copy the
                    # matched donor pages, compute only the suffix
                    result["prefill"][p["slot"]] = engine.prefill_reused(
                        p["slot"], reuse["src"], p["tokens"],
                        p["length"], reuse["matched"])
                else:
                    result["prefill"][p["slot"]] = engine.prefill(
                        p["slot"], p["tokens"], p["length"], p["bucket"])
            if p.get("draft"):
                # prime the draft cache for the admitted prompt (fresh
                # AND reused admissions — the draft cache has no
                # kv_copy plane, it always recomputes the full prefix)
                engine.draft_prefill(p["slot"], p["tokens"],
                                     p["length"], p["bucket"])
            exp = p.get("export_kv")
            if exp is not None:
                # ship-bound prefill (disaggregation leg 1): the donor
                # rows ride back WITH the step result, so the router's
                # KV ship never pays a second worker round-trip nor
                # races this slot's later eviction
                with span("kv_export", slot=p["slot"],
                          bucket=exp["bucket"]):
                    rows = engine.export_kv(p["slot"], exp["bucket"])
                result.setdefault("kv_export", {})[p["slot"]] = rows
        return result

    # -- one decode ahead --------------------------------------------------

    def _run_ahead(self, engine, plan: dict) -> dict:
        """A plain plan on an engine whose ``runs_ahead`` holds: the
        same programs in the same device order as above, and no result
        read back before the NEXT step's decode is queued.

        That decode is known now, for every slot that will still be
        live: its token is this step's decode's output, or the first
        token of this step's prefill — both in the engine's vector on
        the device — and its position is this step's + 1, or the
        prompt's length.  The scheduler can only take slots away (a
        request ended) or add slots this very step prefilled, and the
        program runs every slot whatever happens; so plan n+1 only says
        which of its outputs count.

        (a) The plan's decode: the one in flight, if it ran at the
        plan's positions on every slot the plan decodes (a hit); else
        that one is dropped and the plan's own is queued from the
        plan's host tokens (a miss: a decode writes the same row from
        the same token however often it runs, and a row ahead of a
        slot's position is rewritten before it is read).  (b) The
        plan's prefills.  (c) The next decode.  (d) Only now this
        plan's tokens are waited for.  Every rank does the same: the
        prediction is a function of the plans alone."""
        result: dict[str, Any] = {"prefill": {}, "decode": {}}
        decode = plan.get("decode")
        ahead = None
        handle = None
        if decode is not None:
            slots = decode["slots"]
            positions = np.asarray(decode["positions"], np.int32)
            with span("decode", traces=decode.get("traces"),
                      slots=len(slots)):
                flying, self._ahead = self._ahead, None
                if flying is not None and np.array_equal(
                        flying[0][slots], positions[slots]):
                    ahead, handle = "hit", flying[1]
                else:
                    ahead = "miss"
                    handle = engine.dispatch_decode(positions,
                                                    decode["tokens"])
            nxt = positions.copy()
            nxt[slots] += 1
        else:
            if self._drop_ahead(engine):
                ahead = "miss"
            nxt = np.zeros((engine.slots,), np.int32)
        firsts = []
        for p in plan["prefills"]:
            with span("prefill", trace=p.get("trace"),
                      bucket=p["bucket"], slot=p["slot"], reused=0):
                firsts.append(engine.dispatch_prefill(
                    p["slot"], p["tokens"], p["length"], p["bucket"]))
            nxt[p["slot"]] = p["length"]
        # (a request at its last row ends there: its slot's write ahead
        # is a dead slot's, and stays inside the cache)
        self._next = np.minimum(nxt, engine.max_seq_len - 1)
        if self._profiler is None or not self._profiler.on_last_step:
            self._dispatch_ahead(engine)
        if handle is not None:
            toks = engine.fetch(handle, "rlt_serve_decode_seconds_total")
            for s in decode["slots"]:
                result["decode"][s] = int(toks[s])
        for p, first in zip(plan["prefills"], firsts):
            result["prefill"][p["slot"]] = int(engine.fetch(
                first, "rlt_serve_prefill_seconds_total"))
        if ahead is not None:
            result["timing"] = {"ahead": ahead}
            reg = _metrics.get_registry()
            if reg is not None:
                reg.counter("rlt_serve_decode_ahead_total").inc(
                    1, result=ahead)
        return result

    def _dispatch_ahead(self, engine) -> None:
        """Queue the decode that the last plan predicted, once."""
        nxt, self._next = self._next, None
        if nxt is not None:
            with span("decode_ahead"):
                self._ahead = (nxt, engine.dispatch_decode(nxt))

    def _drop_ahead(self, engine, wait: bool = False) -> bool:
        """Forget the decode in flight (its outputs are never read);
        ``wait`` for it to have left the device first.  True where
        there was one."""
        flying, self._ahead = self._ahead, None
        if flying is not None and wait:
            engine.fetch(flying[1], "rlt_serve_decode_seconds_total")
        return flying is not None

    # -- KV-page shipping (fleet disaggregation) ---------------------------

    def serve_export_kv(self, slot: int, bucket: int):
        """Device→host donor rows for the router's KV-ship leg.  Runs
        on every rank (the gather is SPMD-replicated); rank 0 alone
        returns the payload, mirroring ``serve_step``."""
        with span("kv_export", slot=slot, bucket=bucket):
            rows = self._engine.export_kv(slot, bucket)
        return rows if self._rank == 0 else None

    def serve_import_kv(self, slot: int, k_rows, v_rows) -> None:
        """Install shipped donor rows (engine ``kv_import_{b}``) —
        dispatched on every rank to keep the SPMD fleet in lockstep."""
        with span("kv_import", slot=slot,
                  bucket=int(k_rows.shape[2])):
            self._engine.import_kv(slot, k_rows, v_rows)

    # -- evidence / teardown -----------------------------------------------

    def serve_stats(self) -> dict:
        return self._engine.stats() if self._engine is not None else {}

    def teardown_serve(self) -> None:
        """Graceful worker exit: flush telemetry, leave the coordination
        service cleanly (the fit path's teardown discipline,
        plugins/xla.py)."""
        if self._profiler is not None:
            self._profiler.stop()   # close a window the drain truncated
        cfg = self._telemetry_cfg
        if cfg is not None and cfg.enabled:
            from ray_lightning_tpu import telemetry
            telemetry.flush_metrics()
            telemetry.disable_metrics()
            telemetry.flush()
            telemetry.disable()
            if self._hb is not None:
                self._hb.stop()
        if self._nproc > 1:
            import jax
            try:
                jax.distributed.shutdown()
            except RuntimeError:
                pass


__all__ = ["ServeWorker"]
