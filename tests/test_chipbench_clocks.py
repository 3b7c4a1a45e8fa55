"""The six per-layer readers of PR 38 (``chipbench/loop_clocks.py``): each
on a snapshot worked out by hand, each None where the program keeps no
such record (a parent commit), and both kinds of cell rehearsed on the
CPU through the real harness, ``run_cell(..., trace=True)``: the train
cell's readers asked after its trainer and its programs are gone."""

from __future__ import annotations

import gc
import io
import json
import os
import sys

import pytest

from chipbench import host_spans, run
from ray_lightning_tpu import telemetry
from ray_lightning_tpu.telemetry import clocks, scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAIN = ("train_dispatch_ms", "train_data_wait_ms",
         "train_dispatch_longest_ms")
SERVE = ("tput_prefill_ms_per_ktoken", "tput_prefill_step_share_pct",
         "tput_step_longest_x")
TINY = {"n_layer": 2, "n_embd": 64, "n_head": 2, "n_positions": 64,
        "n_ctx": 64, "vocab_size": 512}
REHEARSALS = {
    "gpt2s-train-1chip": {"traffic": {
        "global_batch": 4, "steps_per_epoch": 64, "token_ids_below": 500}},
    "gpt2l-serve-doc": {"limits": {"logit_gap": 0.05}, "traffic": {
        "slots": 4, "ramp_s": 1.5, "token_ids_below": 500,
        "buckets": [16, 32, 64],
        "prompt": {"median": 20, "min": 8, "max": 40},
        "answer": {"min": 4, "max": 12}}},
}


def read(name, ctx):
    return run.read_layer_metric(ROOT, name, ctx)


def _longest(seconds):
    return {"seconds": seconds, "step": 3, "ts": 1.0, "phase": "wait"}


# 100 decode-only steps of 50 ms; ten steps with a 512-bucket prefill of
# 200 ms; four with two prefills of 300 ms, one of which stalled for 5 s
KINDS = {
    "decode": {"n": 100, "wall_s": 5.0, "prompt_tokens": 0,
               "longest": _longest(0.06)},
    "prefill_512": {"n": 10, "wall_s": 2.0, "prompt_tokens": 4000,
                    "longest": _longest(0.9)},
    "prefill_256+512": {"n": 4, "wall_s": 1.2, "prompt_tokens": 2400,
                        "longest": _longest(5.0)},
}
# prefills cost (2.0 - 10 x 0.05) + (1.2 - 4 x 0.05) = 2.5 s for 6400
# prompt tokens, of 8.2 s of steps
SERVE_WANT = {
    "tput_prefill_ms_per_ktoken": 2.5e6 / 6400,
    "tput_prefill_step_share_pct": 100 * 2.5 / 8.2,
    # decode 0.06 / 0.05, prefill_512 0.9 / 0.2; four steps are too few
    # to call their mean a mean
    "tput_step_longest_x": 4.5,
}
FIT = {"steps": 400, "wall_s": 42.0,
       "seconds": {"data_wait": 0.1, "callbacks": 0.3, "dispatch": 2.0,
                   "device_wait": 39.0, "other": 0.6},
       # a chunked loop: 200 dispatches of two steps
       "n": {"data_wait": 402, "callbacks": 400, "dispatch": 200,
             "device_wait": 4},
       "longest": {"data_wait": None, "callbacks": None,
                   "dispatch": {"seconds": 0.012, "step": 77, "ts": 9.0},
                   "device_wait": None}}
TRAIN_WANT = {"train_dispatch_ms": 1e3 * 2.0 / 200,
              "train_data_wait_ms": 1e3 * 0.1 / 400,
              "train_dispatch_longest_ms": 12.0}


@pytest.mark.parametrize("name", SERVE)
def test_serve_reader_on_a_hand_worked_snapshot(name):
    ctx = {"scheduler": {"pump": {"steps": 114, "kinds": KINDS}}}
    assert read(name, ctx) == pytest.approx(SERVE_WANT[name])


@pytest.mark.parametrize("name", TRAIN)
def test_train_reader_on_a_hand_worked_snapshot(name, monkeypatch):
    monkeypatch.setitem(clocks._last, "fit", FIT)
    assert read(name, {"kind": "train"}) == pytest.approx(TRAIN_WANT[name])


@pytest.mark.parametrize("name", SERVE)
@pytest.mark.parametrize("scheduler", [
    {},                                               # no pump clock at all
    {"pump": {"steps": 9, "loop_s": 0.1}},            # a parent commit's
    {"pump": {"steps": 0, "kinds": {}}},              # nothing ran
    {"pump": {"steps": 3, "kinds": {                  # no decode-only step
        "prefill_512": KINDS["prefill_512"]}}}])
def test_serve_reader_is_none_without_the_record(name, scheduler):
    got = read(name, {"scheduler": scheduler})
    if name == "tput_step_longest_x" and scheduler.get("pump", {}).get(
            "kinds"):
        assert got == pytest.approx(4.5)     # needs no decode kind
    else:
        assert got is None


@pytest.mark.parametrize("name", TRAIN)
def test_train_reader_is_none_without_the_record(name, monkeypatch):
    monkeypatch.delitem(clocks._last, "fit", raising=False)
    assert read(name, {"kind": "train"}) is None
    # a fit that never reached its first step's result: a clock that
    # never started
    monkeypatch.setitem(clocks._last, "fit", clocks.PhaseClock(
        ("data_wait", "callbacks", "dispatch", "device_wait")).snapshot())
    assert read(name, {"kind": "train"}) is None
    # a parent commit: the program has no such module
    monkeypatch.delattr(telemetry, "clocks")
    monkeypatch.setitem(sys.modules, "ray_lightning_tpu.telemetry.clocks",
                        None)
    assert read(name, {"kind": "train"}) is None


def test_every_new_metric_is_listed_for_its_cells_with_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"] in TRAIN + SERVE}
    assert set(new) == set(TRAIN + SERVE)
    serve_cells = [w["name"] for w in bench["workloads"]
                   if w["name"] != "gpt2s-train-1chip"]
    for name, m in new.items():
        assert m["source"] == "program_counter"
        assert os.path.isfile(os.path.join(
            ROOT, "chipbench", "layer_metrics", name + ".py"))
        if name in TRAIN:
            assert (m["layer"], m["moves"], m["workloads"]) == (
                "train loop", "train_tokens_per_s", ["gpt2s-train-1chip"])
        else:
            assert (m["layer"], m["moves"], m["workloads"]) == (
                "serve loop", "serve_tokens_per_s", serve_cells)


def _rehearse(workload, root, patch=setattr):
    """``run_cell`` traced, at a tiny size, from a root of this test's own
    (links to the repo's files): its work directory, and the newest trace
    the readers look for, are then no other test's (another file of the
    session rehearses a cell at the same time under ``-n 6``)."""
    import functools
    for name in ("BENCHMARK.json", "chipbench", "ray_lightning_tpu"):
        os.symlink(os.path.join(ROOT, name), os.path.join(root, name))
    patch(host_spans, "find_trace",
          functools.partial(host_spans.find_trace, root))
    out = io.StringIO()
    got = run.run_cell(workload, 2 ** 31 + 38, 2.0, True, root=root, out=out,
                       rehearsal={"platform": "cpu", "chips": 1,
                                  "model": TINY, **REHEARSALS[workload]})
    assert json.loads(out.getvalue().splitlines()[-1]) == got["line"]
    assert got["result"]["ctx"]["trace"]["devices"] == 1
    return got


def _train_rehearsal(root: str) -> None:
    """The train cell through ``run_cell(..., trace=True)`` and its
    readers afterwards; raises where one of them fails.  Run in a process
    of its own with ONE CPU device (the cell asks for as many devices as
    JAX sees; the test session has eight)."""
    got = _rehearse("gpt2s-train-1chip", root)
    assert set(TRAIN) <= set(got["line"]["metrics"])
    ctx = got["result"]["ctx"]
    # the trainer is deleted and collected, and whatever this process
    # still holds alive gives no text: only what the program remembered
    # at the end of its stage, a profiler session having lain over it,
    # places the trace's operations now
    del got
    gc.collect()
    assert "jit_step_fn" not in scopes._live(scopes.SCOPES)
    scopes._live = lambda names: {}
    host_spans._cache.clear()
    assert "jit_step_fn" in host_spans.capture(ctx)["tables"]
    for name in ("train_scoped_pct", "train_head_loss_ms",
                 "train_attn_kernel_ms"):
        assert isinstance(read(name, ctx), float), name
    assert 0.0 < read("train_scoped_pct", ctx) <= 100.0
    values = {name: read(name, ctx) for name in TRAIN}
    assert all(isinstance(v, float) and v >= 0.0 for v in values.values())
    assert values["train_dispatch_longest_ms"] >= values["train_dispatch_ms"]
    snap = clocks.last("fit")
    # the fit's first step lies before the clock; 11 more warm the
    # window, 10 are traced after it
    assert snap["steps"] == snap["n"]["dispatch"] \
        == ctx["window"]["steps"] + 11 + 10
    assert abs(sum(snap["seconds"].values()) - snap["wall_s"]) < 1e-6
    print("TRAIN REHEARSAL OK", json.dumps(values))


def test_train_cell_rehearsed_its_readers_asked_after_the_programs_went(
        tmp_path):
    import subprocess
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "RLT_COMPILE_CACHE": "0",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "PYTHONPATH": ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")}
    done = subprocess.run(
        [sys.executable, "-c", "import sys; from tests.test_chipbench_clocks "
         "import _train_rehearsal; _train_rehearsal(sys.argv[1])",
         str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-4000:]
    assert "TRAIN REHEARSAL OK" in done.stdout


def test_serve_cell_rehearsed_reads_its_pumps_kinds(tmp_path, monkeypatch):
    host_spans._cache.clear()
    got = _rehearse("gpt2l-serve-doc", str(tmp_path), monkeypatch.setattr)
    assert set(SERVE) <= set(got["line"]["metrics"])
    ctx = got["result"]["ctx"]
    pump = ctx["scheduler"]["pump"]
    assert "decode" in pump["kinds"]
    assert any(k.startswith("prefill_") for k in pump["kinds"])
    assert sum(k["n"] for k in pump["kinds"].values()) == pump["steps"]
    # (a step in flight when the stats were asked has charged its first
    # phases and is counted under no kind yet)
    assert sum(k["wall_s"] for k in pump["kinds"].values()) == \
        pytest.approx(sum(pump[p + "_s"] for p in
                          ("loop", "plan", "call", "wait", "apply")),
                      rel=0.05)
    assert pump["longest"]["kind"] in pump["kinds"]
    assert pump["longest"]["phase"] in ("loop", "plan", "call", "wait",
                                        "apply")
    values = {name: read(name, ctx) for name in SERVE}
    assert all(isinstance(v, float) for v in values.values())
    # (no sign asserted: at this size, with the profiler inside some
    # decode steps, a step with a prefill is not the longer one)
    assert values["tput_prefill_step_share_pct"] < 100.0
    assert values["tput_step_longest_x"] >= 1.0
    # the readers that read the pump's sums read them as before
    for name in ("tput_pump_host_ms", "tput_rpc_ms",
                 "tput_decode_ahead_pct"):
        assert read(name, ctx) >= 0.0, name
