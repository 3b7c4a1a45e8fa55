"""A serving cell: ``Server(module, checkpoint=None).start()`` with the
worker as the only process on the chip, load from ``generator``, and the
counting done around the scheduler's own ``plan`` / ``apply`` calls.

Tokens are counted when the device did the work, not when a request
finishes: a prompt's tokens at the step whose prefill returned, an
answer token at the step that emitted it.  (Counting ~600 tokens at a
request's last step put a window-edge step on top of the sampling noise
that sank the first benchmark.)
"""

from __future__ import annotations

import math
import os
import statistics
import time

from chipbench import check
from chipbench.generator import LoadThread, RequestStream, build_table
from chipbench.train_cell import NoAccelerator

TRACE_STEPS = 40
TRACE_AFTER_S = 1.0


class StepCounter:
    """Wraps one scheduler's ``plan`` and ``apply``: every step's time
    and token counts, and (traced runs) the profiler window that rides
    one plan to the worker."""

    def __init__(self, scheduler, clock=time.monotonic):
        self.steps: list[tuple[float, int, int, int]] = []
        self.kinds: list[list[str]] = []   # programs dispatched, per step
        self.profile = None        # {"id", "steps", "dir", "at"}
        self.profiled_from = None  # index of the first traced step
        self._clock = clock
        self._plan, self._apply = scheduler.plan, scheduler.apply
        scheduler.plan, scheduler.apply = self.plan, self.apply

    def plan(self):
        plan = self._plan()
        prof = self.profile
        if plan is not None and prof is not None \
                and self._clock() >= prof["at"]:
            plan["profile"] = {k: prof[k] for k in ("id", "steps", "dir")}
            self.profile, self.profiled_from = None, len(self.steps)
        return plan

    def apply(self, plan, result):
        self._apply(plan, result)
        prompt = sum(p["length"] for p in plan["prefills"])
        decode = plan.get("decode")
        answer = len(plan["prefills"]) + (
            len(decode["slots"]) if decode is not None else 0)
        live = int(sum(decode["positions"])) if decode is not None else 0
        self.steps.append((self._clock(), prompt, answer, live))
        self.kinds.append((["decode"] if decode is not None else [])
                          + ["prefill"] * len(plan["prefills"]))

    def dispatched_in_trace(self, steps: int) -> list[str]:
        i = self.profiled_from
        return [] if i is None else [
            k for kinds in self.kinds[i:i + steps] for k in kinds]

    def between(self, t0: float, t1: float) -> dict:
        rows = [s for s in self.steps if t0 <= s[0] <= t1]
        return {"steps": len(rows),
                "prompt_tokens": sum(s[1] for s in rows),
                "answer_tokens": sum(s[2] for s in rows),
                "live_tokens_mean": (statistics.fmean(s[3] for s in rows)
                                     if rows else 0.0)}


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile of all the values given."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1,
                       max(0, math.ceil(q * len(ordered)) - 1))]


def used_buckets(mix: dict, context: int) -> list[int]:
    """The rungs of the default ladder that this table's prompts land
    on: the only prefill programs the cell warms."""
    from ray_lightning_tpu.serve.buckets import bucket_for, resolve_buckets
    ladder = resolve_buckets(mix.get("buckets"), context)
    return sorted({bucket_for(p, ladder) for p, _, _ in build_table(mix)})


class ServeSession:
    """One server through its normal entry point, and the load driven at
    it.  ``run`` makes one and drives it once; the chat cell's rate sweep
    (``sweep.py``) drives one several times, so that one set-up serves
    every rate."""

    def __init__(self, cell: dict, seed: int, platform: str, t_process):
        from ray_lightning_tpu.serve import Server

        from chipbench.module import program_seed

        self.cell, self.seed = cell, seed
        model, mix = cell["config"]["model"], cell["traffic"]
        adapter = cell["adapter"]
        self.phases = {"imports_s": time.monotonic() - t_process}
        kw = {"use_tpu": True} if platform == "tpu" \
            else {"platform": platform}
        # the mix's own ``server`` arguments come first: what the harness
        # sets below is not the mix's to change
        self.server = Server(
            adapter.module(model, seed), checkpoint=None,
            **{**mix.get("server", {}),
               "max_batch_slots": int(mix["slots"]),
               "buckets": used_buckets(mix, adapter.context(model)),
               "seed": program_seed(seed),
               "default_root_dir": cell["work"], "telemetry": False,
               "worker_env": {"PYTHONPATH": cell["root"] + os.pathsep
                              + os.environ.get("PYTHONPATH", "")}, **kw})
        t = time.monotonic()
        self.server.start()
        self.phases["server_start_s"] = time.monotonic() - t
        try:
            info = self.server.stats()
            self.phases["worker_setup"] = {
                k: info["workers"][0]["compile_cache"][k]
                for k in ("hits", "misses", "backend_compile_secs")}
            self.device = info["workers"][0]["device"]
            if self.device["platform"] != platform \
                    or self.device["count"] != cell["chips"]:
                raise NoAccelerator(
                    f"the cell needs {cell['chips']} {platform} device(s); "
                    f"the serve worker holds {self.device}")
            self.counter = StepCounter(self.server.scheduler)
        except BaseException:
            self.close()
            raise

    def drive(self, mix: dict, seconds: float, trace: bool) -> dict:
        """Ramp, then a window of ``seconds``; the load stops when the
        window closes.  Returns what was sent and the window's edges."""
        server, counter = self.server, self.counter
        sched = server.scheduler
        closed = mix["kind"] == "serve-closed"
        load = LoadThread(
            RequestStream(mix, self.seed, int(mix["token_ids_below"])),
            lambda tokens, n: server.submit(tokens, max_new_tokens=n),
            backlog=(int(mix["backlog_per_slot"]) * int(mix["slots"])
                     if closed else None),
            queued=lambda: sched.queued_count)
        load.start()
        try:
            time.sleep(float(mix["ramp_s"]))
            t0 = time.monotonic()
            if trace:
                counter.profile = {
                    "id": "chipbench", "steps": TRACE_STEPS,
                    "dir": os.path.join(self.cell["work"], "trace"),
                    "at": t0 + TRACE_AFTER_S}
            time.sleep(max(0.0, t0 + seconds - time.monotonic()))
            t1 = time.monotonic()
        finally:
            load.stop()
            load.join(timeout=30)
        if load.error is not None:
            raise load.error
        return {"sent": list(load.sent), "t0": t0, "t1": t1,
                "queued_at_close": sched.queued_count}

    def close(self) -> None:
        self.server.shutdown(graceful=False)


def finished_in(sent: list, t0: float, t1: float) -> list:
    return [s for s in sent if s.req.done() and s.req.error is None
            and t0 <= s.req.t_done <= t1]


def latencies(sent: list, t0: float, t1: float) -> dict:
    """Over the requests that finished inside the window: the median gap
    between output tokens, and time to first token from when each request
    was DUE (a stall's cost to later requests counts)."""
    done = finished_in(sent, t0, t1)
    tpots = [s.req.tpot_s for s in done if s.req.tpot_s is not None]
    if not tpots:
        return {"finished": len(done)}
    ttfts = [s.req.t_first - s.due for s in done]
    return {"finished": len(done),
            "tpot_p50_ms": 1e3 * statistics.median(tpots),
            "ttft_p50_ms": 1e3 * statistics.median(ttfts),
            "ttft_p90_ms": 1e3 * percentile(ttfts, 0.9)}


def run(cell: dict, seed: int, seconds: float, trace: bool, t_process: float,
        platform: str = "tpu", control: "str | None" = None) -> dict:
    model, mix = cell["config"]["model"], cell["traffic"]
    session = ServeSession(cell, seed, platform, t_process)
    try:
        drove = session.drive(mix, seconds, trace)
        stats = session.server.stats()
    finally:
        session.close()
    counter, phases, device = session.counter, session.phases, session.device
    sent, t0, t1 = drove["sent"], drove["t0"], drove["t1"]
    setup_s = t0 - t_process
    phases["ramp_s"] = float(mix["ramp_s"])

    worker = stats["workers"][0]
    in_window = finished_in(sent, t0, t1)
    late = [s.sent - s.due for s in sent]
    counted = counter.between(t0, t1)
    window_s = t1 - t0
    lat = latencies(sent, t0, t1)
    if mix["kind"] == "serve-closed":
        end_to_end = {"serve_tokens_per_s": (
            counted["prompt_tokens"] + counted["answer_tokens"]) / window_s}
    else:
        end_to_end = {k: v for k, v in lat.items() if k != "finished"}

    # the chip is free now: the reference may use it
    t_check = time.monotonic()
    compared = [(s.req.tokens, list(s.req.generated)) for s in in_window]
    positions = served_positions(cell, seed, compared, control)
    numbers = check.served_numbers(positions)
    phases["check_s"] = time.monotonic() - t_check
    phases["setup_s"] = setup_s
    phases["generator_late_ms_max"] = 1e3 * max(late, default=0.0)
    phases["generator_late_ms_p50"] = 1e3 * (
        statistics.median(late) if late else 0.0)

    red = None
    if trace:
        from chipbench import reduce
        red = reduce.reduce_dir(os.path.join(cell["work"], "trace"))
        red["ms_by_kind"] = reduce.ms_per_run_by_kind(
            red, counter.dispatched_in_trace(TRACE_STEPS))
    mem = worker.get("memory_stats") or {}
    phases["worker_memory_stats"] = mem
    cc = worker["compile_cache"]
    return {
        "device": device,
        "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0)),
        "attempted": len(sent),
        "failed": sum(1 for s in sent if s.req.error is not None),
        "numbers": numbers, "positions": positions,
        "compared_requests": compared, "setup_s": setup_s,
        "phases": phases, "end_to_end": end_to_end,
        "ctx": {
            "kind": mix["kind"], "trace": red, "setup_s": setup_s,
            "compile": {"hits": cc["hits"], "misses": cc["misses"],
                        "backend_compile_s": cc["backend_compile_secs"]},
            "window": {"seconds": window_s, **counted,
                       "finished": len(in_window),
                       "queued_at_close": drove["queued_at_close"]},
            "scheduler": stats["scheduler"], "latencies": lat,
            "requests": [{"queue_wait_s": s.req.queue_wait_s,
                          "ttft_due_s": s.req.t_first - s.due,
                          "tpot_s": s.req.tpot_s} for s in in_window],
            "retraces": sum(worker["retraces"].values()),
            "model": model, "adapter": cell["adapter"], "traffic": mix,
            "chips": cell["chips"],
            "peaks": cell["peaks"].get(device["kind"]),
        },
    }


def served_positions(cell: dict, seed: int, finished: list,
                     control: "str | None" = None) -> dict:
    """Every request the window finished, beside the reference (and, by
    hand, beside the control): ``check.served_positions``'s columns."""
    import jax

    from chipbench.module import init_key

    model, adapter = cell["config"]["model"], cell["adapter"]
    ref = check.load_reference(cell["config"], cell["root"])
    # whatever the family's make_weights returns (arrays, or the key for a
    # reference that makes each layer's weights where it applies them)
    # goes to the reference's forward untouched
    w = jax.jit(lambda key: adapter.make_weights(model, key))(
        init_key("serve", seed))
    return check.served_positions(
        ref, w, model, finished, (control,) if control else (),
        context=adapter.context(model),
        rows_per_block=int(cell["traffic"].get(
            "reference_rows_per_block", check.ROWS_PER_BLOCK)))
