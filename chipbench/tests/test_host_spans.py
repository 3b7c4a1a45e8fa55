"""``host_spans.py`` and the twelve readers of PR 24 on a synthetic trace
whose numbers are worked out by hand, the three refusals, and a CPU
rehearsal of both cells in which every new reader returns a number or
None and never raises."""

import json
import os
import time

import pytest

from chipbench import host_spans, reduce, run

from conftest import ROOT
from test_harness import _rehearse

NEW = ["tput_pump_host_ms", "tput_rpc_ms", "tput_worker_host_ms",
       "tput_idle_named_pct", "tput_decode_cache_ms",
       "tput_decode_kernel_ms", "train_head_loss_ms",
       "train_attn_kernel_ms", "train_scoped_pct", "start_state_init_s",
       "start_program_load_s", "start_unnamed_s"]
WALL = 1000.0          # the anchor: wall clock = trace clock + 1000 s


def read(name, ctx):
    return run.read_layer_metric(ROOT, name, ctx)


def span(name, ts, dur, id, parent=None, **attrs):
    rec = {"t": "span", "name": name, "ts": ts, "dur": dur, "rank": 0,
           "depth": 0, "id": id, "parent": parent}
    if attrs:
        rec["attrs"] = attrs
    return rec


# -- the serving layout ----------------------------------------------------------
# two steps: a decode run of 100 ms (40 in the cache, 20 in a relayout
# copy of it that the compiler made, 10 in the kernel, 30 in the MLP),
# then a prefill run of 10 ms

def serve_device():
    ops, mods = [], []
    for t in (0.010, 0.130):
        mods.append(("jit_serve_decode(1)", t, 0.100))
        ops += [("fusion.1", t, 0.040), ("copy.7", t + 0.040, 0.020),
                ("flash_decode.3", t + 0.060, 0.010),
                ("fusion.2", t + 0.070, 0.030)]
        mods.append(("jit_serve_prefill_512(2)", t + 0.102, 0.010))
        ops.append(("fusion.9", t + 0.102, 0.010))
    return [{"ops": ops, "modules": mods}]


SERVE_TABLES = {
    "jit_serve_decode": {"fusion.1": "kv_cache", "copy.7": "kv_cache*",
                         "flash_decode.3": "attn", "fusion.2": "mlp",
                         "param.0": None},
    "jit_serve_prefill_512": {"fusion.9": "attn"}}

SERVE_HOST = [
    ("serve_step", 0.008, 0.116, {"step": 5}),
    ("decode", 0.0085, 0.1025, {"step": 5}),
    ("prefill", 0.1112, 0.0123, {"step": 5}),
    ("serve_step", 0.128, 0.116, {"step": 6}),
    ("decode", 0.1285, 0.1025, {"step": 6}),
    ("prefill", 0.2312, 0.0123, {"step": 6})]


def serve_records():
    w = WALL
    pump = [
        span("pump.wait", w + 0.0075, 0.117, 101, step=5),
        span("pump.apply", w + 0.1245, 0.001, 102, step=5),
        # 0.1 ms of the loop's own bookkeeping here: nobody's span
        span("pump.plan", w + 0.1256, 0.0006, 104, 103, step=6),
        span("pump.call", w + 0.1262, 0.0008, 105, 103, step=6),
        span("pump.wait", w + 0.1270, 0.1175, 106, 103, step=6),
        span("pump.step", w + 0.1255, 0.1195, 103, step=6)]
    setup = [
        span("spawn", 900, 1, 2, 1), span("rendezvous", 901, 5, 3, 1),
        span("ship", 906, 0.5, 4, 1),
        span("imports", 907, 3, 7, 6), span("devices", 910, 8, 8, 6),
        span("build", 918, 2, 9, 6), span("weights", 920, 6, 10, 6),
        span("build", 926, 10, 11, 6),
        # the AOT thread overlaps the main thread's time: left out
        span("lower", 927, 20, 13, 12),
        span("aot", 927, 30, 12, 5, program="decode", thread="aot"),
        span("aot_wait", 936, 2, 15, 14),
        span("warm", 938, 10, 16, 14, program="prefill_512"),
        span("warm", 950, 20, 17, 14, program="decode"),
        span("warmup", 936, 40, 14, 6), span("kv_init", 976, 1, 18, 6),
        span("setup_serve", 907, 72, 6, 5, rank=0),
        span("worker_setup", 906.5, 73.5, 5, 1),
        span("server_start", 900, 80, 1)]
    return setup + pump


@pytest.fixture
def serve_ctx(tmp_path, monkeypatch):
    path = str(tmp_path / "serve.trace.json")
    host_spans.write_chrome_trace(
        path, serve_device(), SERVE_HOST,
        anchor=(0.001, int((WALL + 0.001) * 1e9)))
    with open(tmp_path / host_spans.TABLE_FILE, "w") as f:
        json.dump({"scopes": list(host_spans.SCOPES),
                   "programs": SERVE_TABLES}, f)
    host_spans._cache.clear()
    monkeypatch.setattr(host_spans, "find_trace", lambda *a, **k: path)
    monkeypatch.setattr(host_spans, "kept", serve_records)
    red = reduce.reduce_timelines(reduce.load_chrome(path))
    return {"kind": "serve-closed", "trace": red, "scheduler": {"pump": {
        "steps": 200, "loop_s": 0.01, "plan_s": 0.06,
        "call_s": 0.06, "wait_s": 24.3, "apply_s": 0.03, "idle_s": 0.06,
        "worker_s": 24.0, "wall_s": 24.52}}}


def test_the_serve_readers_on_numbers_worked_out_by_hand(serve_ctx):
    ctx = serve_ctx
    cap = host_spans.capture(ctx)
    assert cap["offset_s"] == pytest.approx(WALL, abs=1e-6)
    assert len(cap["host"]) == 6 and cap["tables"] == SERVE_TABLES
    # (0.01 + 0.06 + 0.03) s over 200 steps
    assert read("tput_pump_host_ms", ctx) == pytest.approx(0.5)
    # (0.06 + 24.3 - 24.0) s over 200 steps
    assert read("tput_rpc_ms", ctx) == pytest.approx(1.8)
    # each serve_step: 2 ms before the decode run, 2 between the runs, 2
    # after the prefill run
    assert read("tput_worker_host_ms", ctx) == pytest.approx(6.0)
    # 12 ms of idle (2 + 8 + 2); all of it under a span but the 0.1 ms
    # between pump.apply and pump.plan
    assert reduce.measure(host_spans.idle(cap)) == pytest.approx(0.012)
    assert read("tput_idle_named_pct", ctx) == pytest.approx(
        100 * 11.9 / 12, abs=1e-3)
    # the program's own paths only: the mover the table lists under
    # kv_cache* is the compiler's, and stays out
    assert read("tput_decode_cache_ms", ctx) == pytest.approx(40.0)
    movers = [o for o in host_spans.scoped_ops(cap) if o["inherited"]]
    assert {(o["name"], o["scope"], o["inherited"]) for o in movers} == {
        ("copy.7", None, "kv_cache")}
    assert read("tput_decode_kernel_ms", ctx) == pytest.approx(10.0)
    # 6 s of weights + 1 of kv_init; builds 2 + 10 and the warm-up's 40;
    # 80 s of root less the 68.5 under a leaf span
    assert read("start_state_init_s", ctx) == pytest.approx(7.0)
    assert read("start_program_load_s", ctx) == pytest.approx(52.0)
    assert read("start_unnamed_s", ctx) == pytest.approx(11.5)
    # the order-of-dispatch reading agrees with the reading by name
    kinds = reduce.ms_per_run_by_kind(
        ctx["trace"], ["decode", "prefill", "decode", "prefill"])
    assert kinds["decode"] == pytest.approx(
        reduce.module_ms_per_run(ctx["trace"], "jit_serve_decode"))
    assert kinds["prefill"] == pytest.approx(
        reduce.module_ms_per_run(ctx["trace"], "jit_serve_prefill"))


def test_readers_leave_out_what_the_program_does_not_offer(serve_ctx,
                                                           monkeypatch):
    """A parent commit: no pump counters, no kept records, no tables, no
    rlt/ annotations.  Every reader returns None; none raises."""
    path = host_spans.find_trace()
    host_spans.write_chrome_trace(path, serve_device(), [])
    os.remove(os.path.join(os.path.dirname(path), host_spans.TABLE_FILE))
    host_spans._cache.clear()
    monkeypatch.setattr(host_spans, "kept", lambda: None)
    monkeypatch.setattr(host_spans, "_tables_beside", lambda p: None)
    ctx = dict(serve_ctx, scheduler={})
    assert {name: read(name, ctx) for name in NEW} == dict.fromkeys(NEW)
    # and an untraced run of the change holds no trace to read
    monkeypatch.setattr(host_spans, "kept", serve_records)
    assert read("tput_idle_named_pct", dict(ctx, trace=None)) is None
    assert read("train_scoped_pct", dict(ctx, trace=None)) is None


# -- the training layout -----------------------------------------------------------

def train_device():
    ops, mods = [], []
    for t in (0.0, 0.100):
        mods.append(("jit_step_fn(9)", t, 0.100))
        ops += [("fusion.10", t, 0.020), ("fusion.11", t + 0.020, 0.004),
                ("flash_fwd.1", t + 0.024, 0.005),
                ("flash_bwd_fused.2", t + 0.029, 0.010),
                ("fusion.12", t + 0.039, 0.050), ("copy.5", t + 0.089, 0.006),
                ("fusion.13", t + 0.095, 0.005)]
    return [{"ops": ops, "modules": mods}]


TRAIN_TABLE = {"jit_step_fn": {
    "fusion.10": "lm_head", "fusion.11": "loss", "flash_fwd.1": "attn",
    "flash_bwd_fused.2": "attn", "fusion.12": "mlp", "copy.5": "mlp*",
    "fusion.13": "optimizer"}}


def train_records():
    return [
        span("setup_model", 500, 0.5, 2, 1), span("loaders", 500.5, 1.5, 3, 1),
        span("mesh", 502, 0.5, 4, 1), span("compile", 502.5, 4, 5, 1),
        span("init", 506.5, 8, 6, 1), span("hooks", 514.5, 0.1, 7, 1),
        span("aot_wait", 515, 5, 9, 8), span("step", 520.5, 9, 10, 8, step=0),
        span("device_wait", 529.5, 2.4, 11, 8),
        span("first_step", 515, 16.9, 8, 1), span("fit_setup", 500, 32, 1),
        # the AOT thread's spans are roots of their own
        span("aot", 503, 20, 12, program="train_step", thread="aot")]


@pytest.fixture
def train_ctx(tmp_path, monkeypatch):
    path = str(tmp_path / "train.trace.json")
    host_spans.write_chrome_trace(path, train_device(), [])
    host_spans._cache.clear()
    monkeypatch.setattr(host_spans, "find_trace", lambda *a, **k: path)
    monkeypatch.setattr(host_spans, "kept", train_records)
    # the train cell's trace is this directory's own capture: no table
    # file beside it, the program's tables are asked in process
    monkeypatch.setattr(host_spans, "_tables_beside",
                        lambda p: TRAIN_TABLE)
    red = reduce.reduce_timelines(reduce.load_chrome(path))
    return {"kind": "train", "trace": red}


def test_the_train_readers_on_numbers_worked_out_by_hand(train_ctx):
    ctx = train_ctx
    assert ctx["trace"]["main_module"] == "jit_step_fn(9)"
    assert read("train_head_loss_ms", ctx) == pytest.approx(24.0)
    assert read("train_attn_kernel_ms", ctx) == pytest.approx(15.0)
    assert read("train_scoped_pct", ctx) == pytest.approx(94.0)
    assert read("start_state_init_s", ctx) == pytest.approx(8.0)
    assert read("start_program_load_s", ctx) == pytest.approx(20.9)
    # 32 s of root less 31.0 under a leaf span
    assert read("start_unnamed_s", ctx) == pytest.approx(1.0)


# -- refusals -------------------------------------------------------------------------

def test_no_trace_is_an_error(tmp_path):
    os.makedirs(tmp_path / host_spans.WORK)
    with pytest.raises(FileNotFoundError, match="xplane"):
        host_spans.find_trace(root=str(tmp_path), since=0.0)


def test_a_stale_trace_is_an_error(tmp_path):
    old = tmp_path / host_spans.WORK / "cell" / "trace" / "x.xplane.pb"
    os.makedirs(old.parent)
    old.write_bytes(b"")
    os.utime(old, (time.time() - 3600, time.time() - 3600))
    with pytest.raises(RuntimeError, match="stale trace"):
        host_spans.find_trace(root=str(tmp_path), since=time.time() - 60)
    # the same file, had this process written it, is the run's own
    assert host_spans.find_trace(root=str(tmp_path),
                                 since=time.time() - 7200) == str(old)


def test_an_operation_its_table_does_not_list_is_an_error(train_ctx,
                                                          monkeypatch):
    table = {"jit_step_fn": {k: v for k, v in
                             TRAIN_TABLE["jit_step_fn"].items()
                             if k != "fusion.12"}}
    monkeypatch.setattr(host_spans, "_tables_beside", lambda p: table)
    host_spans._cache.clear()
    with pytest.raises(ValueError, match="fusion.12.*cannot be placed"):
        read("train_scoped_pct", train_ctx)
    # a scope that is not on the list is refused too
    bad = {"jit_step_fn": dict(TRAIN_TABLE["jit_step_fn"], **{
        "fusion.12": "ffn"})}
    monkeypatch.setattr(host_spans, "_tables_beside", lambda p: bad)
    host_spans._cache.clear()
    with pytest.raises(ValueError, match="unknown scope"):
        read("train_head_loss_ms", train_ctx)


def test_a_missing_anchor_is_an_error_for_what_needs_one(serve_ctx):
    path = host_spans.find_trace()
    host_spans.write_chrome_trace(path, serve_device(), SERVE_HOST)
    host_spans._cache.clear()
    cap = host_spans.capture(serve_ctx)
    assert cap["offset_s"] is None
    # the reader leaves the metric out; laying records on the trace's
    # clock without an anchor is refused
    assert read("tput_idle_named_pct", serve_ctx) is None
    with pytest.raises(ValueError, match="anchor"):
        host_spans.record_intervals(cap, serve_records(), ("pump.wait",))


# -- both cells, rehearsed on the CPU ------------------------------------------------

@pytest.mark.parametrize("workload", ["gpt2s-train-1chip",
                                      "gpt2l-serve-doc"])
def test_every_new_reader_returns_a_number_or_none_in_a_rehearsal(workload):
    host_spans._cache.clear()
    got, _ = _rehearse(workload, True)
    ctx = got["result"]["ctx"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]
                  if workload in m["workloads"]}
    # a reader is asked only where BENCHMARK.json lists it
    values = {name: read(name, ctx) for name in NEW if name in listed}
    assert len(values) == (6 if workload == "gpt2s-train-1chip" else 9)
    assert all(v is None or isinstance(v, float) for v in values.values())
    # what the program counts and keeps is there on any backend
    for name in ("start_state_init_s", "start_program_load_s",
                 "start_unnamed_s"):
        assert values[name] is not None and values[name] >= 0.0, name
    if workload == "gpt2l-serve-doc":
        assert values["tput_pump_host_ms"] > 0
        assert values["tput_rpc_ms"] > 0
        assert values["tput_worker_host_ms"] >= 0
        assert 0 <= values["tput_idle_named_pct"] <= 100.0 + 1e-9
    assert set(got["line"]["metrics"]) <= listed
    # the programs that ran were still live when the readers asked (the
    # train cell), or their tables lay beside the worker's trace
    main = "jit_step_fn" if workload == "gpt2s-train-1chip" \
        else "jit_serve_decode"
    assert main in host_spans.capture(ctx)["tables"]


def test_the_process_start_is_the_running_modules_clock(monkeypatch):
    """Under ``python3 -m chipbench.run`` the run's clock is ``__main__``'s
    ``T_PROCESS``; a second copy imported as ``chipbench.run`` starts its
    own at that import, and every trace would read as stale by it."""
    import sys
    import types
    main = types.ModuleType("__main__")
    main.T_PROCESS = time.monotonic() - 300.0
    monkeypatch.setitem(sys.modules, "__main__", main)
    assert time.time() - host_spans.process_started_at() == pytest.approx(
        300.0, abs=1.0)
    del main.T_PROCESS
    assert host_spans.process_started_at() == pytest.approx(
        time.time() - (time.monotonic() - run.T_PROCESS), abs=1.0)
