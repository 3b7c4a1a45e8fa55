"""The harness end to end on the CPU at a tiny size (control flow only: a
rehearsal prints no device metric), its refusals, and a run whose timed
path is broken underneath, which has to come out as not correct."""

import io
import json
import os
import subprocess
import sys

import pytest

from chipbench import run

from conftest import ROOT, TINY

SERVE = {"slots": 4, "ramp_s": 1.5, "token_ids_below": 500,
         "buckets": [16, 32, 64]}
REHEARSALS = {
    "gpt2s-train-1chip": {"traffic": {
        "global_batch": 4, "steps_per_epoch": 64, "token_ids_below": 500}},
    "gpt2l-serve-doc": {"traffic": {
        **SERVE, "prompt": {"median": 20, "min": 8, "max": 40},
        "answer": {"min": 4, "max": 12}}},
    # not in BENCHMARK.json (PERF.md, Open questions): the open-loop path
    # is rehearsed all the same, and brings its entry along
    "gpt2l-serve-chat": {
        "entry": {"name": "gpt2l-serve-chat", "chips": 1,
                  "config": "gpt2-large", "traffic": "chat-steady"},
        "traffic": {
            **SERVE, "rate_rps": 8,
            "prompt": {"median": 16, "min": 8, "max": 40},
            "answer": {"median": 8, "min": 4, "max": 12}}},
}
SERVE_LIMITS = {"logit_gap": 0.05}


def _rehearse(workload, trace, **extra):
    out = io.StringIO()
    rehearsal = {"platform": "cpu", "chips": 1, "model": TINY,
                 **REHEARSALS[workload], **extra}
    if "entry" in rehearsal:
        rehearsal.setdefault("limits", SERVE_LIMITS)
    got = run.run_cell(workload, 2 ** 31 + 7, 2.0, trace, out=out,
                       rehearsal=rehearsal)
    lines = [json.loads(x) for x in out.getvalue().splitlines()]
    assert lines[-1] == got["line"]
    return got, lines


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", sorted(REHEARSALS))
@pytest.mark.parametrize("trace", [False, True])
def test_every_cell_runs_end_to_end_and_names_no_device_metric(workload,
                                                               trace):
    got, lines = _rehearse(workload, trace)
    line = got["line"]
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["rehearsal"] and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert all(m["value"] is None for m in line["metrics"].values())
    assert "busy_s" not in line["device"] and "breakdown" not in line
    bench = _bench()
    kind = "per_layer" if trace else "end_to_end"
    listed = {m["name"] for m in bench[kind]
              if workload in m.get("workloads", [workload])}
    if workload in {w["name"] for w in bench["workloads"]}:
        assert set(line["metrics"]) <= listed and line["metrics"]
        if not trace:
            assert set(line["metrics"]) == listed
    else:
        assert {"tpot_p50_ms", "ttft_p50_ms", "ttft_p90_ms"} <= set(
            got["result"]["end_to_end"])
    assert "phases" in lines[0] and "compared" in lines[1]
    assert all("limit" in row for row in lines[1]["compared"])


def test_a_token_altered_where_it_is_produced_comes_out_not_correct(
        monkeypatch):
    from ray_lightning_tpu.serve.scheduler import Scheduler
    sound, _ = _rehearse("gpt2l-serve-doc", False,
                         limits=SERVE_LIMITS)
    assert sound["line"]["correct"] is True
    # every token of every request the window finished is compared
    assert sound["result"]["numbers"]["tokens_compared"] == sum(
        len(served) for _, served in sound["result"]["compared_requests"])
    apply = Scheduler.apply

    def broken(self, plan, result):
        result["decode"] = {s: (int(t) + 1) % TINY["vocab_size"]
                            for s, t in result["decode"].items()}
        apply(self, plan, result)

    monkeypatch.setattr(Scheduler, "apply", broken)
    got, lines = _rehearse("gpt2l-serve-doc", False,
                           limits=SERVE_LIMITS)
    assert got["line"]["correct"] is False
    assert lines[1]["compared"][0]["value"] > 0.05
    # a token altered in ONE slot only is caught too: all slots are read
    def one_slot(self, plan, result):
        result["decode"] = {
            s: (int(t) + 1) % TINY["vocab_size"] if int(s) == 0 else t
            for s, t in result["decode"].items()}
        apply(self, plan, result)

    monkeypatch.setattr(Scheduler, "apply", one_slot)
    got, _ = _rehearse("gpt2l-serve-doc", False, limits=SERVE_LIMITS)
    assert got["line"]["correct"] is False


def test_without_a_tpu_the_command_exits_non_zero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    cmd = _bench()["command"] + ["--workload", "gpt2s-train-1chip",
                                 "--seed", "1", "--seconds", "1",
                                 "--trace", "0"]
    done = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == run.EXIT_NO_ACCELERATOR
    assert done.stdout.strip() == ""
    assert "needs 1 tpu device" in done.stderr


def test_an_unknown_device_kind_is_an_error_not_a_default():
    peaks = {"TPU v5 lite": {"tflops_bf16": 197.0}}
    assert run.require_peak(peaks, "TPU v5 lite")["tflops_bf16"] == 197.0
    with pytest.raises(SystemExit, match="TPU v9"):
        run.require_peak(peaks, "TPU v9")


def test_every_listed_metric_and_cell_has_its_files():
    bench = _bench()
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "layer_metrics", m["name"] + ".py")), m
    for w in bench["workloads"]:
        assert w["chips"] == 1      # the four-chip cell is not listed yet
        for sub, name in (("traffic", w["traffic"]), ("limits", w["name"])):
            assert os.path.exists(os.path.join(
                ROOT, "chipbench", sub, name + ".json")), (sub, name)


def test_the_four_chip_cell_rehearses_on_four_virtual_devices(tmp_path):
    """ZeRO-1 on a 4-device mesh, the reference spread over the same four
    devices: in a process of its own, because the device count is fixed
    when JAX starts.  The cell is not in BENCHMARK.json yet (PERF.md,
    Open questions), so the rehearsal brings its entry along."""
    script = (
        "import io, json, sys\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "from chipbench import run\n"
        "out = io.StringIO()\n"
        "got = run.run_cell('gpt2l-train-zero1-4chip', 13, 1.5, False, "
        "out=out, rehearsal={'platform': 'cpu', 'chips': 4, "
        "'entry': {'name': 'gpt2l-train-zero1-4chip', 'chips': 4, "
        "'config': 'gpt2-large', 'traffic': 'lm-fixed1024-zero1'}, "
        f"'model': {dict(TINY, n_layer=4)!r}, "
        "'traffic': {'global_batch': 8, 'steps_per_epoch': 64, "
        "'token_ids_below': 500, 'reference_rows_per_block': 4}, "
        "'limits': {'loss_gap': 1e-3, 'grad_norm_gap': 0.05, "
        "'change_norm_gap': 0.5}})\n"
        "print(json.dumps(got['line']))\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "RLT_COMPILE_CACHE": "0",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    done = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["count"] == 4
    assert line["attempted"] > 0 and "setup_s" in line["metrics"]


def test_layer_metric_readers_on_a_scripted_context():
    red = {"steps": 10, "idle_s": 0.037, "exposed_s": 0.05,
           "main_module": "jit_step_fn", "module_s": {"jit_step_fn": 0.5},
           "module_runs": {"jit_step_fn": 10},
           "ms_by_kind": {"decode": 50.0, "prefill": 12.0}}
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "gpt2-large.json")) as f:
        model = json.load(f)["model"]
    peaks = {"tflops_bf16": 197.0, "hbm_gbps": 819.0}
    from chipbench.adapters import gpt2
    train = {"kind": "train", "trace": red, "setup_s": 50.0, "chips": 4,
             "compile": {"hits": 6, "misses": 0, "backend_compile_s": 12.5},
             "window": {"tokens_per_s": 80000.0, "seq_len": 1024},
             "model": model, "adapter": gpt2, "peaks": peaks}
    read = lambda name, ctx: run.read_layer_metric(ROOT, name, ctx)  # noqa
    assert read("start_noncompile_s", train) == 37.5
    assert read("compile_misses", train) == 0
    assert read("train_host_gap_ms", train) == pytest.approx(3.7)
    assert read("train_device_ms", train) == pytest.approx(50.0)
    assert read("train_exposed_comm_ms", train) == pytest.approx(5.0)
    # 80k tokens/s x 4.916 GFLOP over 4 x 197 TFLOP/s
    assert read("train_mfu_pct", train) == pytest.approx(49.91, abs=0.01)
    assert read("tput_decode_device_ms", dict(train, trace=None)) is None
    doc = {"kind": "serve-closed", "trace": red, "model": model,
           "adapter": gpt2, "peaks": peaks, "traffic": {"slots": 32},
           "scheduler": {"batch_occupancy": 0.75},
           "window": {"live_tokens_mean": 20000.0}}
    assert read("tput_batch_occupancy", doc) == 24.0
    assert read("tput_prefill_device_ms", doc) == 12.0
    # (1.548 GB + 20000 x 184320 B) / 819 GB/s = 6.39 ms of a 50 ms step
    assert read("tput_decode_roofline", doc) == pytest.approx(12.78, abs=0.02)
    assert read("tput_decode_device_ms", doc) == 50.0
    chat = dict(doc, kind="serve-open", latencies={"ttft_p90_ms": 7.0},
                requests=[{"queue_wait_s": 0.01}, {"queue_wait_s": 0.03},
                          {"queue_wait_s": 0.02}])
    assert read("lat_queue_wait_p50_ms", chat) == pytest.approx(20.0)
    assert read("lat_queue_wait_p50_ms", dict(chat, requests=[])) is None
    assert read("ttft_p90_ms.obs", chat) == 7.0
    assert read("ttft_p50_ms.obs", chat) is None
    assert read("train_device_ms", dict(train, trace=None)) is None
