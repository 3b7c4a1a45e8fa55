"""The ZAYA1 family through the harness, beside ``test_xing_family.py``:
the ``zaya`` adapter, its reference and the cell's limits file load by the
names ``BENCHMARK.json`` and the configuration give; the cost functions
against counts by hand; the fp8 control fails the tiny limits where the
sound program passes them; and ``run_cell`` rehearses
``zaya1-serve-reason`` on the CPU at a tiny size through the same
``Server``, over rows and a tail, with the cell's own readers."""

import io
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, run

from conftest import ROOT

WORKLOAD = "zaya1-serve-reason"
TINY = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_experts=4, moe_intermediate_size=32, router_hidden_size=16,
            rope_parameters={"hybrid": {"rope_theta": 10000}},
            max_position_embeddings=64, served_positions=64)
TRAFFIC = {"slots": 4, "ramp_s": 1.5, "token_ids_below": 256,
           "buckets": [16, 32], "server": {"max_new_tokens": 32},
           "prompt": {"median": 12, "min": 6, "max": 30},
           "answer": {"min": 8, "max": 24}}
LIMITS = {"logit_gap": 0.2, "mean_logit_gap": 0.01}
NEW = ("tput_cca_mix_ms", "tput_cca_proj_ms", "tput_head_decode_ms",
       "tput_head_decode_roofline", "tput_moe_skip_pct")


def test_the_cells_files_load_by_name():
    cell = run.load_cell(ROOT, WORKLOAD, None)
    config, adapter = cell["config"], cell["adapter"]
    assert adapter.__name__ == "chipbench.adapters.zaya"
    ref = check.load_reference(config, ROOT)
    assert ref.PRECISIONS == ("float32", "bfloat16", "fp8") \
        and callable(ref.forward)
    assert set(check.load_limits(ROOT, WORKLOAD)) \
        == {"logit_gap", "mean_logit_gap"}
    model = config["model"]
    assert adapter.context(model) == 3328
    # what the check hands the reference: every tensor made once, 5.2 GB
    held = jax.eval_shape(lambda k: adapter.make_weights(model, k),
                          jax.random.PRNGKey(0))
    assert 5.23e9 < sum(a.size * a.dtype.itemsize
                        for a in held.values()) < 5.27e9
    assert held["gate_w"].shape == (10, 16, 2048, 2048) \
        and held["gate_w"].dtype == jnp.bfloat16 \
        and held["router_w1"].dtype == jnp.float32 \
        and held["wte"].shape == (262272, 2048)
    bench = cell["bench"]
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types"]
    # every key of the catalog's config at the top level and under model
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")
    for key, value in row["config"].items():
        assert config[key] == model[key]
        assert (config[key] == value) != (key in entry["reduced"]), key
    assert config["num_hidden_layers"] == 10 \
        and config["layer_types"] == ["hybrid"] * 10
    assert cell["traffic"]["slots"] == 128 and cell["chips"] == 1
    listed = {m["name"] for m in bench["per_layer"]
              if WORKLOAD in m.get("workloads", ())}
    assert set(NEW) <= listed and len(listed) >= 30
    # a reader that needs a prefill program INSIDE the 40 traced steps is
    # not this cell's: a prompt arrives every ~12 steps here and a seed's
    # 40 steps can hold none (the driver's seed 1168227746 did not)
    assert not listed & {
        "tput_prefill_device_ms", "tput_moe_prefill_roofline",
        "tput_moe_prefill_route_ms", "tput_moe_prefill_gmm_calls",
        "tput_window_prefill_attn_ms", "tput_window_prefill_roofline"}
    cfg = adapter.config_of(model)
    assert cfg.tail_width == 2688 and cfg.num_hidden_layers == 10 \
        and cfg.rope_theta == 5000000 and cfg.rotary_dim == 64 \
        and cfg.block_size == 3328
    # every (A) reading is under ``assumed`` with its source
    for key in ("value_shift", "convolutions", "qk_mean",
                "norm_and_temperature", "rotary", "router", "skip_output",
                "residual", "residual_dtype", "served_positions"):
        assert len(config["assumed"][key]) > 40, key


def test_costs_against_counts_by_hand():
    cell = run.load_cell(ROOT, WORKLOAD, None)
    model, adapter = cell["config"]["model"], cell["adapter"]
    d = 2048
    attn = d * 1024 + d * 256 + 2 * d * 128 + 1024 * d \
        + 10 * 2 * 128 * 128 + 3 * 1280
    assert 5.56e6 < attn < 5.58e6          # the issue's 5.57M
    router = d * 256 + 1 + 256 + 2 * 256 * 256 + 257 * 17
    assert 0.65e6 < router < 0.67e6        # the issue's 0.66M
    layer = 2 * (attn + 2 * d) + 4 * (router + 2 + 8 * d)
    head = 2 * (d + d * 262272)
    assert adapter.weight_bytes(model) == 10 * layer + head
    # ~1.21 GB: the head 1.07, attention 0.11, the routers 0.03
    assert 1.2e9 < adapter.weight_bytes(model) < 1.23e9
    # every expert of ten layers hit, 128 pairs a layer: 4.03 GB
    assert adapter.expert_bytes(model, 10 * 16, 10 * 128) \
        == 2 * (160 * 3 * d * d + 1280 * 2 * d)
    assert 4.02e9 < adapter.expert_bytes(model, 160, 1280) < 4.05e9
    assert adapter.expert_flops(model, 1000) == 2.0 * 3 * d * d * 1000
    # 1,024 B a position a layer
    assert adapter.decode_row_bytes(model, 128 * 1400) \
        == 1024 * 10 * 128 * 1400
    # the table once: 1.07 GB
    assert adapter.head_bytes(model) == 2 * 262272 * d
    assert adapter.routed_sublayers(model) == 10
    # the parameters of the cut: 2.61 B, 5.2 GB in bfloat16
    total = 10 * (attn + 2 * d + router + 2 + 8 * d + 16 * 3 * d * d) \
        + d + d * 262272
    assert 2.60e9 < total < 2.63e9


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_the_fp8_control_fails_the_tiny_limits_and_bfloat16_does_not(seed):
    """The reference put in the program's place, at the tiny size: in
    bfloat16 (what the configuration states) its greedy tokens lie within
    limits set between the two readings of three seeds of the float32
    reference's best; in fp8 they fail both."""
    from chipbench import zaya_reference as ref
    limits = dict(LOW)
    # (matrices at 0.1, not 0.02: at a width of 64 and three layers a
    # token's own embedding outweighs what the layers add, the tied head
    # hands every position its own token by a wide margin and no precision
    # flips one; at 0.1 the layers outweigh it, as at the published widths)
    tiny = {**run.load_cell(ROOT, WORKLOAD, None)["config"]["model"], **TINY,
            "init_std": 0.1}
    key = jax.random.PRNGKey(seed)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, 256, (16, 60)), jnp.int32)
    exact = ref.forward(key, tokens, tiny)
    best = jnp.max(exact, axis=-1)
    verdicts = {}
    for precision in ("bfloat16", "fp8"):
        first = jnp.argmax(ref.forward(key, tokens, tiny, precision),
                           axis=-1)
        gap = np.asarray(best - jnp.take_along_axis(
            exact, first[..., None], axis=-1)[..., 0])
        numbers = check.served_numbers({"gap": gap.reshape(-1)})
        print(seed, precision, numbers)
        verdicts[precision] = check.verdict(numbers, limits)
    assert verdicts["bfloat16"][0] is True
    assert not any(row["ok"] for row in verdicts["fp8"][1])
    with pytest.raises(ValueError, match="precision"):
        ref.forward(key, tokens, tiny, "int4")


#: between the three seeds' readings over 960 tokens: bfloat16's largest
#: 0.0406 / 4.29e-5, fp8's smallest 0.290 / 3.29e-3
LOW = {"logit_gap": 0.11, "mean_logit_gap": 4e-4}


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_rehearses_on_the_cpu(trace):
    out = io.StringIO()
    got = run.run_cell(
        WORKLOAD, 2 ** 31 + 41, 2.0, trace, out=out,
        rehearsal={"platform": "cpu", "chips": 1, "model": TINY,
                   "traffic": TRAFFIC, "limits": LIMITS})
    line = got["line"]
    assert line["correct"] is True and line["rehearsal"] \
        and line["failed"] == 0
    ctx = got["result"]["ctx"]
    sched = ctx["scheduler"]
    counted = sched["device_counters"]
    assert counted["decode_runs"] >= sched["decode_steps"] > 0
    # one choice a token a layer; some choose no expert
    routed = counted["decode_runs"] * TRAFFIC["slots"] * 3
    assert 0 < counted["decode_moe_pairs"] <= routed
    assert counted["decode_moe_rows"] == routed
    skip = run.read_layer_metric(ROOT, "tput_moe_skip_pct", ctx)
    assert skip == pytest.approx(
        100 * (1 - counted["decode_moe_pairs"] / routed)) and 0 <= skip < 100
    # a row a position in every layer
    assert sched["live_rows"] == sched["live_positions"]
    assert sched["pump"]["ahead_hits"] > 0.9 * sched["pump"]["steps"]
    if trace:
        # the CPU's trace has no kernels: the scoped readers read, the
        # kernels' read nothing and are left out of the line
        assert {"tput_cca_mix_ms", "tput_cca_proj_ms", "tput_head_decode_ms",
                "tput_moe_skip_pct", "tput_moe_ms",
                "tput_decode_ahead_pct"} <= set(line["metrics"])
        for name in ("tput_cca_mix_ms", "tput_cca_proj_ms",
                     "tput_head_decode_ms"):
            assert run.read_layer_metric(ROOT, name, ctx) > 0
        # a share of the peak needs the device's peak: none on the CPU
        assert run.read_layer_metric(ROOT, "tput_head_decode_roofline",
                                     ctx) is None
        peaked = {**ctx, "peaks": {"hbm_gbps": 819.0, "tflops_bf16": 197.0}}
        assert 0 < run.read_layer_metric(
            ROOT, "tput_head_decode_roofline", peaked)
        assert run.read_layer_metric(ROOT, "tput_gqa_decode_roofline",
                                     peaked) is None
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert [json.loads(x) for x in out.getvalue().splitlines()]
    assert os.path.isdir(os.path.join(ROOT, ".chipbench_work", WORKLOAD))


def test_a_run_without_a_trace_or_of_another_family_reads_nothing():
    cell = run.load_cell(ROOT, WORKLOAD, None)
    ctx = {"scheduler": {}, "trace": None, "peaks": None,
           "adapter": cell["adapter"], "model": cell["config"]["model"],
           "traffic": cell["traffic"]}
    for name in NEW:
        assert run.read_layer_metric(ROOT, name, ctx) is None
    # a family without the two cost functions: the counter is there, the
    # reader has nothing to price it with
    other = run.load_cell(ROOT, "xing4-serve-doc8k", None)
    ctx = {**ctx, "adapter": other["adapter"], "scheduler": {
        "device_counters": {"decode_runs": 5, "decode_moe_pairs": 9}}}
    assert run.read_layer_metric(ROOT, "tput_moe_skip_pct", ctx) is None
