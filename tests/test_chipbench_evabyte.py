"""The cell ``evabyte-serve-bytedoc`` rehearsed on the CPU at a tiny size
through the real harness: ``run_cell`` with the cell's own adapter,
reference, traffic file and per-layer readers (control flow only: a
rehearsal names no device metric).  And each reader this cell brings,
on a rehearsed trace or, where a CPU trace holds nothing for it (the
Pallas kernel's name, a published peak), on a synthetic one.
"""

from __future__ import annotations

import io
import json
import os

import pytest

from chipbench import fine_scopes, host_spans, run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "evabyte-serve-bytedoc"
TINY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=2,
            num_key_value_heads=2, intermediate_size=176, num_pred_heads=2,
            window_size=32, chunk_size=4, max_position_embeddings=256,
            max_seq_length=256, served_positions=160, init_std=0.1)
# contexts of 40-124 positions: 1.25 to 4 windows of 32
TRAFFIC = {"slots": 4, "ramp_s": 1.5, "buckets": [48, 112],
           "prompt": {"median": 60, "min": 40, "max": 100},
           "answer": {"min": 8, "max": 24}}
# bf16 flips a near-tie by ~0.02 of a logit spread of 0.8; a wrong token
# lies ~1 below the best
LIMITS = {"logit_gap": 0.1}
NEW = ("tput_eva_attn_ms", "tput_eva_summary_ms", "tput_eva_prefill_attn_ms",
       "tput_eva_attn_roofline", "tput_eva_decode_roofline",
       "tput_cache_rows_per_position")


def _rehearse(trace: bool):
    out = io.StringIO()
    got = run.run_cell(CELL, 2 ** 31 + 7, 3.0, trace, out=out, rehearsal={
        "platform": "cpu", "chips": 1, "model": TINY, "traffic": TRAFFIC,
        "limits": LIMITS})
    lines = [json.loads(x) for x in out.getvalue().splitlines()]
    assert lines[-1] == got["line"]
    return got, lines


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_runs_end_to_end_and_is_correct():
    got, lines = _rehearse(False)
    line = got["line"]
    assert line["correct"] is True and line["rehearsal"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    numbers = got["result"]["numbers"]
    assert numbers["tokens_compared"] == sum(
        len(served) for _, served in got["result"]["compared_requests"]) > 0
    sched = got["result"]["ctx"]["scheduler"]
    # the mechanism: fewer rows read than positions live
    assert 0 < sched["live_rows"] < sched["live_positions"]
    assert lines[1]["compared"][0]["limit"] == LIMITS["logit_gap"]


def test_one_altered_token_is_not_correct(monkeypatch):
    from ray_lightning_tpu.serve.scheduler import Scheduler
    apply = Scheduler.apply

    def one_slot(self, plan, result):
        result["decode"] = {
            s: (int(t) + 1) % 320 if int(s) == 0 else t
            for s, t in result["decode"].items()}
        apply(self, plan, result)

    monkeypatch.setattr(Scheduler, "apply", one_slot)
    got, lines = _rehearse(False)
    assert got["line"]["correct"] is False
    assert lines[1]["compared"][0]["value"] > LIMITS["logit_gap"]


def test_the_fp8_control_fails_the_limits():
    """``python3 -m chipbench.control``'s path: the reference in fp8, put
    in the program's place at the same prompts, reads over a limit that
    the program passes."""
    from chipbench import check
    got = run.run_cell(CELL, 2 ** 31 + 9, 2.0, False, out=io.StringIO(),
                       control="fp8", rehearsal={
                           "platform": "cpu", "chips": 1, "model": TINY,
                           "traffic": TRAFFIC, "limits": LIMITS})
    numbers = got["result"]["numbers"]
    assert got["line"]["correct"] is True
    control = {k[4:]: v for k, v in numbers.items() if k.startswith("fp8_")}
    assert check.verdict(control, LIMITS)[0] is False
    assert control["mean_logit_gap"] > 10 * numbers["mean_logit_gap"]


def test_a_traced_run_reads_what_a_cpu_trace_holds():
    """Every reader listed for the cell runs; on the CPU those that need
    the kernel's name in the trace or a published peak find nothing and
    leave their metric out (``test_readers_of_the_kernel...`` below gives
    them a trace that has both)."""
    got, _ = _rehearse(True)
    line = got["line"]
    assert line["correct"] is True
    listed = {m["name"] for m in _bench()["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert set(NEW) <= listed
    assert set(line["metrics"]) <= listed
    assert {"tput_eva_summary_ms", "tput_eva_prefill_attn_ms",
            "tput_cache_rows_per_position", "tput_decode_cache_ms",
            "tput_decode_device_ms"} <= set(line["metrics"])
    ctx = got["result"]["ctx"]
    for name in ("tput_eva_summary_ms", "tput_eva_prefill_attn_ms"):
        assert run.read_layer_metric(ROOT, name, ctx) > 0
    ratio = run.read_layer_metric(ROOT, "tput_cache_rows_per_position", ctx)
    assert 0.2 < ratio < 0.8
    # the program's own table keeps to the scopes the harness knows
    cap = host_spans.capture(ctx)
    assert {o["scope"] for o in host_spans.scoped_ops(cap)} <= set(
        host_spans.SCOPES) | {None}
    # the decode program writes the cache under kv_cache only, and the
    # compiler added no mover of it
    decode = [o for o in host_spans.scoped_ops(cap)
              if o["program"] == "jit_serve_decode"]
    assert decode and not any(o["inherited"] == "kv_cache" for o in decode)


@pytest.fixture
def synthetic(tmp_path, monkeypatch):
    """A trace of two decode runs and one prefill run, with the scope
    tables a program of this PR writes beside it."""
    decode, prefill = "jit_serve_decode(1)", "jit_serve_prefill_8192(2)"
    ops = [("eva_decode.1", 0.000, 0.002), ("eva_decode.2", 0.002, 0.002),
           ("fusion.1", 0.004, 0.001), ("fusion.2", 0.005, 0.003),
           ("eva_decode.1", 0.010, 0.002), ("eva_decode.2", 0.012, 0.002),
           ("fusion.1", 0.014, 0.001), ("fusion.2", 0.015, 0.003),
           ("fusion.7", 0.020, 0.030), ("fusion.8", 0.050, 0.010)]
    cap = {"devices": [{"modules": [(decode, 0.0, 0.008), (decode, 0.010,
                                                          0.008),
                                    (prefill, 0.020, 0.040)], "ops": ops}],
           "host": [],
           "tables": {"jit_serve_decode": {
                          "eva_decode.1": "attn", "eva_decode.2": "attn",
                          "fusion.1": "attn", "fusion.2": "mlp"},
                      "jit_serve_prefill_8192": {
                          "fusion.7": "attn", "fusion.8": "mlp"}}}
    fine = {"jit_serve_decode": {"eva_decode.1": "eva_attn",
                                 "eva_decode.2": "eva_attn",
                                 "fusion.1": "eva_summary"},
            "jit_serve_prefill_8192": {"fusion.7": "eva_attn"}}
    trace = tmp_path / "trace" / "t.xplane.pb"
    trace.parent.mkdir()
    trace.write_bytes(b"")
    (tmp_path / "trace" / host_spans.TABLE_FILE).write_text(
        json.dumps({"programs": cap["tables"], "fine": fine}))
    monkeypatch.setattr(host_spans, "capture",
                        lambda ctx: cap if ctx.get("trace") else None)
    monkeypatch.setattr(host_spans, "find_trace", lambda: str(trace))
    from chipbench.adapters import evabyte
    with open(os.path.join(ROOT, "chipbench/configs/evabyte-6p5b.json")) as f:
        model = json.load(f)["model"]
    return {"trace": {"ms_by_kind": {"decode": 8.0, "prefill": 40.0}},
            "scheduler": {"live_rows": 50000.0, "live_positions": 280000.0},
            "adapter": evabyte, "model": model,
            "peaks": {"hbm_gbps": 819.0}}


def test_readers_of_the_kernel_and_the_rooflines(synthetic):
    read = lambda name, ctx=synthetic: run.read_layer_metric(ROOT, name, ctx)
    assert read("tput_eva_attn_ms") == pytest.approx(4.0)
    assert read("tput_eva_summary_ms") == pytest.approx(1.0)
    assert read("tput_eva_prefill_attn_ms") == pytest.approx(30.0)
    assert read("tput_cache_rows_per_position") == pytest.approx(50 / 280)
    # 50,000 rows x (K + V) x 4096 x 2 B x 4 layers = 6.55 GB: 8 ms at
    # 819 GB/s, so the kernels' 4 ms would be 200 %: a share over 100
    # shows, it is not clipped
    row_s = 50000 * 2 * 2 * 4096 * 4 / 819e9
    assert read("tput_eva_attn_roofline") == pytest.approx(
        100 * row_s / 4e-3)
    weights_s = synthetic["adapter"].weight_bytes(synthetic["model"]) / 819e9
    assert 1.6e9 < weights_s * 819e9 < 1.7e9
    assert read("tput_eva_decode_roofline") == pytest.approx(
        100 * (row_s + weights_s) / 8e-3)


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_returns_none_only_without_something_to_read(
        synthetic, name):
    """No trace, a parent's scheduler (no ``live_rows``), a parent's table
    file (no ``fine``) or a device without a published peak: None, never
    an exception, never a number."""
    sched_only = name == "tput_cache_rows_per_position"
    untraced = {**synthetic, "trace": None}
    assert (run.read_layer_metric(ROOT, name, untraced) is None) \
        == (not sched_only)
    parent = {**synthetic, "scheduler": {}}
    needs_rows = sched_only or name.endswith("roofline")
    assert (run.read_layer_metric(ROOT, name, parent) is None) == needs_rows
    if name.endswith("roofline"):
        assert run.read_layer_metric(
            ROOT, name, {**synthetic, "peaks": None}) is None
    if name in ("tput_eva_summary_ms", "tput_eva_prefill_attn_ms"):
        path = os.path.join(os.path.dirname(host_spans.find_trace()),
                            host_spans.TABLE_FILE)
        with open(path) as f:
            doc = json.load(f)
        del doc["fine"]
        with open(path, "w") as f:
            json.dump(doc, f)
        assert fine_scopes.tables(host_spans.find_trace()) is None
        assert run.read_layer_metric(ROOT, name, synthetic) is None


def test_the_configuration_file_holds_the_published_sizes():
    with open("/".join([ROOT, "chipbench/configs/evabyte-6p5b.json"])) as f:
        doc = json.load(f)
    entry = next(c for c in _bench()["configs"]
                 if c["name"] == "evabyte-6p5b")
    assert entry["source"] == doc["source"] and entry["file"].endswith(
        "evabyte-6p5b.json")
    assert entry["reduced"] == doc["reduced"] == ["num_hidden_layers"]
    assert doc["published"] == {"num_hidden_layers": 32}
    published = dict(hidden_size=4096, num_attention_heads=32,
                     num_key_value_heads=32, intermediate_size=11008,
                     vocab_size=320, num_pred_heads=8, window_size=2048,
                     chunk_size=16, max_position_embeddings=32768,
                     rope_theta=100000, rms_norm_eps=1e-05,
                     num_hidden_layers=4, attention_class="eva")
    for key, value in published.items():
        # at the top level (what the source's catalog is compared with)
        # and in the group the harness runs
        assert doc[key] == value and doc["model"][key] == value, key
    assert {k: v for k, v in doc["model"].items()
            if k != "served_positions"} == {
        k: doc[k] for k in doc["model"] if k != "served_positions"}
    assert {"pooling_scale", "mu_on_key", "rotary_before_pooling", "windows",
            "head_layout", "phi_mu_init", "compute_dtype", "decoding",
            "token_ids_below"} <= set(doc["assumed"])
    assert "eight-stage pipeline" in doc["deployment"]
    with open(os.path.join(ROOT, "chipbench/traffic",
                           "bytedoc-saturated.json")) as f:
        mix = json.load(f)
    assert mix["slots"] == 32 and mix["buckets"] == [8192, 10240, 12288]
    assert doc["model"]["served_positions"] >= mix["prompt"]["max"] \
        + mix["answer"]["max"]
