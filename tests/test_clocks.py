"""The always-on phase clocks (telemetry/clocks.py): ``PhaseClock`` on a
fake clock, the serve pump's ``PumpClock`` on scripted steps (every key
its snapshot had keeps its value; the steps split by kind), the three
benchmark readers that read the pump's sums, and the fit loop's clock
after a real fit on the CPU."""

from __future__ import annotations

import gc
import os
import tempfile
import time

import pytest

from ray_lightning_tpu.serve.scheduler import PumpClock
from ray_lightning_tpu.telemetry import clocks

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WALL = 1000.0        # the fake clock's offset to the wall clock


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, seconds: float) -> float:
        self.t += seconds
        return self.t


def _phase_clock():
    fake = FakeClock()
    return fake, clocks.PhaseClock(("a", "b"), clock=fake, wall_offset=WALL)


# -- PhaseClock ------------------------------------------------------------------

def test_phase_clock_sums_seconds_and_counts_intervals():
    fake, clock = _phase_clock()
    t = clock.start()
    t = clock.add("a", t, fake.tick(0.25), step=0)
    t = clock.add("b", t, fake.tick(0.5), step=0)
    t = clock.add("a", t, fake.tick(0.75), step=1)
    fake.tick(1.0)           # charged to nobody
    clock.stop()
    fake.tick(5.0)           # after the stop: nobody's either
    snap = clock.snapshot()
    assert snap["seconds"] == pytest.approx(
        {"a": 1.0, "b": 0.5, "other": 1.0})
    assert snap["n"] == {"a": 2, "b": 1}
    assert snap["wall_s"] == pytest.approx(2.5)
    assert sum(snap["seconds"].values()) == pytest.approx(snap["wall_s"])


def test_phase_clock_names_its_longest_interval_step_and_wall_time():
    fake, clock = _phase_clock()
    t = clock.start()
    t = clock.add("a", t, fake.tick(0.25), step=7)
    t0 = fake.tick(0.125)
    t = clock.add("a", t0, fake.tick(2.0), step=8)     # the longest
    t = clock.add("a", t, fake.tick(0.5), step=9)
    longest = clock.snapshot()["longest"]
    assert longest["a"] == {"seconds": pytest.approx(2.0), "step": 8,
                            "ts": pytest.approx(t0 + WALL)}
    assert longest["b"] is None      # never charged: nothing to name


def test_phase_clock_charges_nothing_before_it_starts():
    fake, clock = _phase_clock()
    assert clock.add("a", fake(), fake.tick(3.0), step=0) == fake()
    assert clock.t_start is None
    snap = clock.snapshot()
    assert snap["seconds"] == {"a": 0.0, "b": 0.0} and "wall_s" not in snap
    t = clock.start()
    assert clock.t_start == t == fake()
    clock.add("a", t, fake.tick(1.0))       # t1 left out: now
    assert clock.snapshot()["seconds"]["a"] == pytest.approx(1.0)


def test_the_real_clocks_ts_is_on_the_span_records_wall_clock():
    clock = clocks.PhaseClock(("a",))
    t = clock.start()
    clock.add("a", t, step=0)
    assert clock.snapshot()["longest"]["a"]["ts"] == pytest.approx(
        time.time(), abs=2.0)


def test_last_is_the_newest_snapshot_of_a_name():
    assert clocks.last("test_clocks_nobody") is None
    clocks.keep("test_clocks_name", {"steps": 1})
    clocks.keep("test_clocks_name", {"steps": 2})
    assert clocks.last("test_clocks_name") == {"steps": 2}


# -- PumpClock --------------------------------------------------------------------

def _plan(*buckets, lengths=None):
    lengths = lengths or [b - 3 for b in buckets]
    return {"prefills": [{"bucket": b, "length": n}
                         for b, n in zip(buckets, lengths)],
            "decode": {"slots": [0]}}


#: one step as the pump drives its clock: seconds of loop, plan, call,
#: wait, apply, then the worker's own seconds and its ahead report
DECODE = (0.001, 0.002, 0.003, 0.050, 0.004, 0.045, "hit")
SCRIPTS = {
    "decode_only": [(_plan(), DECODE)] * 3,
    "one_prefill": [
        (_plan(), DECODE),
        (_plan(512), (0.001, 0.002, 0.003, 0.150, 0.004, 0.140, "hit")),
        (_plan(), (0.001, 0.002, 0.003, 0.090, 0.004, 0.085, "miss")),
        (_plan(256), (0.002, 0.001, 0.003, 0.100, 0.004, 0.095, None))],
    "two_prefills_in_a_plan": [
        (_plan(), DECODE),
        (_plan(512, 256), (0.001, 0.002, 0.003, 0.250, 0.004, 0.24, "hit")),
        (_plan(256, 512, lengths=[200, 400]),
         (0.001, 0.302, 0.003, 0.200, 0.004, 0.19, "hit"))],
}


def _drive(script, idle_s: float = 0.02, profiled=()):
    """What ``Server._pump_step`` does with the clock, step by step, with
    one idle iteration before the last step; the steps numbered in
    ``profiled`` ride a profile window."""
    fake = FakeClock()
    pump = PumpClock(clock=fake, wall_offset=WALL)
    t = pump.start()
    for i, (plan, (loop, plan_s, call, wait, apply, worker, ahead)) \
            in enumerate(script):
        if i == len(script) - 1:
            fake.tick(idle_s)
            t = pump.add("idle", t)
        t_loop = fake.tick(loop)
        pump.add("loop", t, t_loop)
        fake.tick(plan_s)
        t_plan = pump.add("plan", t_loop)
        fake.tick(call)
        t_call = pump.add("call", t_plan)
        fake.tick(wait)
        t_wait = pump.add("wait", t_call)
        pump.worker_s += worker
        pump.ahead_hits += ahead == "hit"
        pump.ahead_misses += ahead == "miss"
        fake.tick(apply)
        t_apply = pump.add("apply", t_wait)
        pump.note_step(plan, (t, t_loop, t_plan, t_call, t_wait, t_apply),
                       profiled=i in profiled)
        t = t_apply
    pump.stop()
    return pump.snapshot()


def _sums(script):
    cols = list(zip(*(seconds for _, seconds in script)))
    return [sum(c) for c in cols[:6]]


@pytest.mark.parametrize("name", sorted(SCRIPTS))
def test_pump_snapshot_keeps_every_key_it_had_with_the_value_it_had(name):
    script = SCRIPTS[name]
    snap = _drive(script)
    loop, plan, call, wait, apply, worker = _sums(script)
    had = {"loop_s": loop, "plan_s": plan, "call_s": call, "wait_s": wait,
           "apply_s": apply, "idle_s": 0.02, "steps": len(script),
           "worker_s": worker,
           "ahead_hits": sum(s[6] == "hit" for _, s in script),
           "ahead_misses": sum(s[6] == "miss" for _, s in script),
           "wall_s": loop + plan + call + wait + apply + 0.02}
    assert {k: snap[k] for k in had} == pytest.approx(had)
    assert set(snap) == set(had) | {"kinds", "longest"}
    # the steps' walls, split by kind, are the five step phases whole
    assert sum(k["wall_s"] for k in snap["kinds"].values()) == \
        pytest.approx(loop + plan + call + wait + apply)
    assert sum(k["n"] for k in snap["kinds"].values()) == len(script)


def test_pump_kinds_of_a_decode_only_run():
    snap = _drive(SCRIPTS["decode_only"])
    assert set(snap["kinds"]) == {"decode"}
    kind = snap["kinds"]["decode"]
    assert kind["n"] == 3 and kind["prompt_tokens"] == 0
    assert kind["wall_s"] == pytest.approx(0.18)
    # three steps alike: the first to reach the length stays the longest
    assert kind["longest"] == {
        "seconds": pytest.approx(0.06), "step": 0, "phase": "wait",
        "ts": pytest.approx(100.0 + WALL)}
    assert snap["longest"] == {**kind["longest"], "kind": "decode"}


def test_pump_kinds_with_one_prefill_a_plan():
    snap = _drive(SCRIPTS["one_prefill"])
    kinds = snap["kinds"]
    assert set(kinds) == {"decode", "prefill_512", "prefill_256"}
    assert (kinds["decode"]["n"], kinds["prefill_512"]["n"],
            kinds["prefill_256"]["n"]) == (2, 1, 1)
    assert kinds["prefill_512"]["prompt_tokens"] == 509
    assert kinds["prefill_256"]["prompt_tokens"] == 253
    assert kinds["prefill_512"]["wall_s"] == pytest.approx(0.16)
    # the decode that waited behind the prefill is the decode kind's
    # longest: step 2, in its wait
    assert kinds["decode"]["longest"] == {
        "seconds": pytest.approx(0.1), "step": 2, "phase": "wait",
        "ts": pytest.approx(100.0 + 0.06 + 0.16 + WALL)}
    assert snap["longest"]["kind"] == "prefill_512"
    assert snap["longest"]["step"] == 1


def test_pump_kinds_with_two_prefills_in_a_plan():
    snap = _drive(SCRIPTS["two_prefills_in_a_plan"])
    kinds = snap["kinds"]
    # the buckets sorted: both plans are one kind
    assert set(kinds) == {"decode", "prefill_256+512"}
    both = kinds["prefill_256+512"]
    assert both["n"] == 2
    assert both["prompt_tokens"] == (509 + 253) + (200 + 400)
    assert both["wall_s"] == pytest.approx(0.26 + 0.51)
    # the longest step spent most of itself planning, and says so
    assert both["longest"]["phase"] == "plan"
    assert both["longest"]["step"] == 2
    assert both["longest"]["seconds"] == pytest.approx(0.51)
    # the idle iteration before it is not in the step's wall, and the
    # step's ``ts`` is where its own ``loop`` began
    assert both["longest"]["ts"] == pytest.approx(
        100.0 + 0.06 + 0.26 + 0.02 + WALL)


def test_pump_steps_under_a_profile_window_are_a_kind_of_their_own():
    """The profiler's start and stop are inside those steps: they are
    counted, whole, and are no decode's and no prefill's."""
    plain = _drive(SCRIPTS["one_prefill"])
    snap = _drive(SCRIPTS["one_prefill"], profiled=(1, 2))
    kinds = snap["kinds"]
    assert set(kinds) == {"decode", "prefill_256", "profiled"}
    assert kinds["profiled"]["n"] == 2
    assert kinds["profiled"]["wall_s"] == pytest.approx(0.16 + 0.1)
    assert kinds["profiled"]["prompt_tokens"] == 509
    assert kinds["profiled"]["longest"]["step"] == 1
    assert kinds["decode"]["n"] == 1
    assert kinds["decode"]["longest"]["seconds"] == pytest.approx(0.06)
    assert snap["longest"]["kind"] == "profiled"
    # every key the snapshot had reads as without the window
    assert {k: v for k, v in snap.items() if k not in ("kinds", "longest")} \
        == pytest.approx({k: v for k, v in plain.items()
                          if k not in ("kinds", "longest")})


@pytest.mark.parametrize("plan, kind", [
    (_plan(), "decode"),
    ({"prefills": [], "decode": None}, "decode"),
    (_plan(1024), "prefill_1024"),
    (_plan(1024, 256, 512), "prefill_256+512+1024"),
    (_plan(512, 512), "prefill_512+512")])
def test_a_steps_kind_is_what_its_plan_carried(plan, kind):
    assert PumpClock.kind_of(plan) == kind


@pytest.mark.parametrize("name, value", [
    # (loop + plan + apply) / steps, in ms
    ("tput_pump_host_ms", 1e3 * (0.005 + 0.007 + 0.016) / 4),
    # (call + wait - worker) / steps, in ms
    ("tput_rpc_ms", 1e3 * (0.012 + 0.390 - 0.365) / 4),
    ("tput_decode_ahead_pct", 100.0 * 2 / 4)])
def test_the_readers_of_the_pumps_sums_read_what_they_read(name, value):
    from chipbench import run
    ctx = {"scheduler": {"pump": _drive(SCRIPTS["one_prefill"])}}
    assert run.read_layer_metric(ROOT, name, ctx) == pytest.approx(value)


# -- the fit loop's clock ------------------------------------------------------------

@pytest.fixture(scope="module")
def fit():
    """One tiny fit on the CPU with a callback that overrides both batch
    hooks and reads its own clock where the loop's starts and stops."""
    from ray_lightning_tpu import Trainer
    from ray_lightning_tpu.core.callbacks import Callback
    from ray_lightning_tpu.models.gpt import GPTLightningModule
    from ray_lightning_tpu.telemetry import scopes, spans

    class Watch(Callback):
        needs_batch = False

        def __init__(self):
            self.t_first = self.t_end = None
            self.live = None

        def on_train_batch_start(self, trainer, module, batch, idx):
            time.sleep(0.002)

        def on_train_batch_end(self, trainer, module, metrics, batch, idx):
            if self.t_first is None:
                # the first step's result is in: the clock just started
                self.t_first = time.monotonic()
                self.live = trainer.loop_stats()
            time.sleep(0.003)

        def teardown(self, trainer, module, stage):
            self.t_end = time.monotonic()

    parses = []
    live = scopes._live
    spans.session_seen()            # whatever an earlier test left
    watch = Watch()
    with pytest.MonkeyPatch.context() as patch, \
            tempfile.TemporaryDirectory() as tmp:
        patch.setattr(scopes, "_live",
                      lambda names: parses.append(names) or live(names))
        trainer = Trainer(
            max_epochs=1, limit_train_batches=12, callbacks=[watch],
            enable_checkpointing=False, num_sanity_val_steps=0,
            limit_val_batches=0, telemetry=False, default_root_dir=tmp)
        trainer.fit(GPTLightningModule("tiny", batch_size=2))
        seen = spans.session_seen()
    stats = trainer.loop_stats()
    # nothing of this fit stays live for the session's other tests (a
    # trainer and its module refer to each other: only a collection
    # frees them, and with them the fit's ``jit_step_fn``)
    del trainer
    gc.collect()
    return {"snap": clocks.last("fit"), "stats": stats,
            "watch": watch, "parses": parses, "session_seen": seen}


def test_fit_leaves_its_loop_clock_as_the_last_fit(fit):
    snap = fit["snap"]
    assert snap == fit["stats"]          # stopped: a later read is the same
    # the first of 12 steps ends where the clock starts
    assert snap["steps"] == 11 and snap["n"]["dispatch"] == 11
    # both hooks of 11 steps, and the first step's on_train_batch_end
    assert snap["n"]["callbacks"] == 23
    assert snap["n"]["device_wait"] >= 1      # the epoch's metrics
    assert snap["seconds"]["callbacks"] >= 11 * 0.002 + 12 * 0.003
    assert all(snap["seconds"][p] >= 0.0 for p in
               ("data_wait", "callbacks", "dispatch", "device_wait"))


def test_fit_loop_phases_add_up_to_the_wall_since_the_first_step(fit):
    snap, watch = fit["snap"], fit["watch"]
    named = sum(snap["seconds"][p] for p in
                ("data_wait", "callbacks", "dispatch", "device_wait"))
    # no phase lies inside another: the rest is not negative
    assert 0.0 <= snap["seconds"]["other"] <= snap["wall_s"]
    assert named + snap["seconds"]["other"] == pytest.approx(
        snap["wall_s"], rel=0.05)
    # and the wall is the one a bystander's clock read
    outside = watch.t_end - watch.t_first
    assert snap["wall_s"] == pytest.approx(outside, rel=0.05, abs=0.01)


def test_fit_loop_clock_names_its_longest_steps(fit):
    snap = fit["snap"]
    # step 0's dispatch lies before the clock; its on_train_batch_end
    # is the first thing after it
    for phase, first in (("dispatch", 1), ("callbacks", 0)):
        longest = snap["longest"][phase]
        assert first <= longest["step"] <= 11, phase
        assert longest["seconds"] <= snap["seconds"][phase]
        assert longest["seconds"] >= snap["seconds"][phase] / snap["n"][phase]
        assert longest["ts"] == pytest.approx(time.time(), abs=600.0)


def test_loop_stats_reads_the_running_clock(fit):
    live = fit["watch"].live
    # asked inside the first step's on_train_batch_end: started, nothing
    # charged yet, no step counted
    assert live["steps"] == 0 and live["n"]["dispatch"] == 0
    assert 0.0 <= live["wall_s"] < fit["snap"]["wall_s"]


def test_a_fit_with_no_profiler_session_parses_no_program(fit):
    assert fit["session_seen"] is False
    assert fit["parses"] == []
