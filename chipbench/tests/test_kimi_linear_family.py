"""The Kimi Linear family through the harness, beside
``test_zaya_family.py``: the ``kimi_linear`` adapter, its reference and the
cell's limits file load by the names ``BENCHMARK.json`` and the
configuration give; the cost functions against counts by hand; the fp8
control fails the tiny limits where bfloat16 passes them; and ``run_cell``
rehearses ``kimi-linear-serve-turns4k`` on the CPU at a tiny size through
the same ``Server``, over latent rows, matrices, rings and stamps, with
the cell's own readers."""

import io
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, run

from conftest import ROOT

WORKLOAD = "kimi-linear-serve-turns4k"
TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=5,
    linear_attn_config=dict(kda_layers=[1, 2, 3, 5], full_attn_layers=[4],
                            num_heads=4, head_dim=8,
                            short_conv_kernel_size=4),
    num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, num_experts=4,
    num_experts_published=8, num_experts_per_token=2, model_max_length=256,
    served_positions=256)
TRAFFIC = {"slots": 4, "ramp_s": 1.5, "token_ids_below": 256,
           "buckets": [64, 128], "server": {"max_new_tokens": 32},
           "prompt": {"median": 70, "min": 40, "max": 120},
           "answer": {"min": 8, "max": 24}, "reference_rows_per_block": 4}
LIMITS = {"logit_gap": 0.2, "mean_logit_gap": 0.01}
NEW = ("tput_kda_state_ms", "tput_kda_state_roofline", "tput_kda_proj_ms",
       "tput_kda_prefill_ms", "tput_kda_prefill_roofline",
       "tput_hybrid_decode_roofline")


def test_the_cells_files_load_by_name():
    cell = run.load_cell(ROOT, WORKLOAD, None)
    config, adapter = cell["config"], cell["adapter"]
    assert adapter.__name__ == "chipbench.adapters.kimi_linear"
    ref = check.load_reference(config, ROOT)
    assert ref.PRECISIONS == ("float32", "bfloat16", "fp8") \
        and callable(ref.forward)
    assert set(check.load_limits(ROOT, WORKLOAD)) \
        == {"logit_gap", "mean_logit_gap"}
    model = config["model"]
    assert adapter.context(model) == 5248
    # what the check hands the reference: every tensor made once, 4.7 GB
    held = jax.eval_shape(lambda k: adapter.make_weights(model, k),
                          jax.random.PRNGKey(0))
    size = sum(a.size * a.dtype.itemsize for a in held.values())
    assert 4.74e9 < size < 4.78e9
    assert held["gate_w"].shape == (8, 32, 2304, 1024) \
        and held["gate_w"].dtype == jnp.bfloat16 \
        and held["kda_q_w"].shape == (7, 2304, 4096) \
        and held["mla_q_w"].shape == (2, 2304, 32 * 192) \
        and held["router_w"].shape == (8, 2304, 256) \
        and held["router_w"].dtype == jnp.float32 \
        and held["a2_w"].dtype == jnp.float32 \
        and held["head_w"].shape == (2304, 20480)
    bench = cell["bench"]
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "linear_attn_config", "num_experts",
        "vocab_size"]
    assert "6 pairs" in entry["why"] and "deployed 48" in entry["why"]
    # every key of the catalog's config at the top level and under model
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    for key, value in row["config"].items():
        assert config[key] == model[key]
        assert (config[key] == value) != (key in entry["reduced"]), key
        if key in entry["reduced"]:
            assert config["published"][key] == value
    # no width changes inside the one nested group that is listed
    lin, was = config["linear_attn_config"], \
        row["config"]["linear_attn_config"]
    assert {k: lin[k] for k in ("num_heads", "head_dim",
                                "short_conv_kernel_size")} \
        == {k: was[k] for k in ("num_heads", "head_dim",
                                "short_conv_kernel_size")}
    assert lin["kda_layers"] == [1, 2, 3, 5, 6, 7, 9] \
        and lin["full_attn_layers"] == [4, 8]
    assert config["num_hidden_layers"] == 9 and model["num_experts"] == 32 \
        and model["num_experts_published"] == 256 \
        and model["vocab_size"] == 20480
    mix = cell["traffic"]
    assert mix["slots"] == 192 and cell["chips"] == 1 \
        and mix["buckets"] == [2560, 3328, 4096] \
        and mix["backlog_per_slot"] == 2
    listed = {m["name"] for m in bench["per_layer"]
              if WORKLOAD in m.get("workloads", ())}
    assert set(NEW) <= listed and len(listed) == 40
    # the head is scoped ``lm_head`` and the adapter prices its table
    assert {"tput_head_decode_ms", "tput_head_decode_roofline"} <= listed
    # its bytes would leave the state out
    assert "tput_cmd_decode_roofline" not in listed
    cfg = adapter.config_of(model)
    assert cfg.kda_width == 4096 and cfg.num_hidden_layers == 9 \
        and cfg.row_width == 640 and cfg.block_size == 5248 \
        and cfg.published_experts == 256 \
        and [cfg.index_of(i) for i in (0, 3, 4, 7, 8)] == [
            (True, 0), (False, 0), (True, 3), (False, 1), (True, 6)]
    # every (A) reading is under ``assumed`` with its source
    for key in ("sources", "short_conv", "qk_norm_and_scale", "decay",
                "beta", "state_update", "output_gate", "mla", "experts",
                "serve_state", "served_positions"):
        assert len(config["assumed"][key]) > 40, key


def test_costs_against_counts_by_hand():
    cell = run.load_cell(ROOT, WORKLOAD, None)
    model, adapter = cell["config"]["model"], cell["adapter"]
    d, P, K = 2304, 4096, 128
    kda_low = 4 * d * P + 3 * P * 4 + d * K + K * P + P + K
    kda_high = d * K + K * P + 32 + P + d * 32
    assert 39.4e6 < kda_low + kda_high < 39.6e6      # the issue's 39.5M
    mla = d * 32 * 192 + d * 576 + 512 + 512 * 32 * 256 + 4096 * d
    assert 29.0e6 < mla < 29.2e6                     # the issue's 29.1M
    shared, router = 3 * d * 1024, d * 256 + 256
    want = 7 * (2 * kda_low + 4 * kda_high) + 2 * 2 * mla + 9 * 2 * 2 * d \
        + 2 * 3 * d * 9216 + 8 * (2 * shared + 4 * router) \
        + 2 * (d + d * 20480)
    assert adapter.weight_bytes(model) == want
    # ~1.0 GB: what a step reads whatever it routes
    assert 1.0e9 < want < 1.05e9
    # all 32 experts of eight layers hit, 192 pairs a layer: 3.63 GB
    assert adapter.expert_bytes(model, 8 * 32, 8 * 192) \
        == 2 * (256 * 3 * d * 1024 + 1536 * 2 * d)
    assert 3.62e9 < adapter.expert_bytes(model, 256, 1536) < 3.64e9
    assert adapter.expert_flops(model, 1000) == 2.0 * 3 * d * 1024 * 1000
    # the held rows of the untied table once, 94 MB
    assert adapter.head_bytes(model) == 2 * d * 20480
    # the parameters of the cut: 2.37 B
    total = 7 * (kda_low + kda_high) + 2 * mla + 9 * 2 * d \
        + 3 * d * 9216 + 8 * (32 * 3 * d * 1024 + shared + router) \
        + d + 2 * d * 20480
    assert 2.36e9 < total < 2.38e9
    # 1,152 B a position in each of the two layers that keep rows:
    # live_rows is the mean over all nine
    rows = 192 * 3600 * 2 / 9
    assert adapter.decode_row_bytes(model, rows) \
        == pytest.approx(1152 * 2 * 192 * 3600)
    # the state: a matrix read and written, three ring rows read and one
    # written, a stamp either way; 5.9 GB at 192 slots (3.0 each way)
    a_slot = 7 * 4 * (2 * 4096 * 128 + 4 * 12288 + 2)
    assert adapter.state_bytes(model, 192) == 192 * a_slot
    assert 5.85e9 < adapter.state_bytes(model, 192) < 5.95e9
    # the chunkwise form: C^2 (3 K + 2 V) + 6 C K V a chunk a head
    a_chunk = 64 * 64 * 5 * 128 + 6 * 64 * 128 * 128
    assert a_chunk == 8912896
    assert adapter.kda_prefill_flops(model, 3072) == a_chunk * 32 * 48 * 7
    assert adapter.kda_prefill_flops(model, 3073) == a_chunk * 32 * 49 * 7
    # two layers' triangles of scores
    assert adapter.prefill_attn_flops(model, 3072) \
        == 2.0 * (192 + 128) * 32 * 2 * (3072 * 3073 // 2)


#: between the three seeds' readings over 960 tokens (printed by the test
#: below): bfloat16's largest 0.462 / 1.47e-3, fp8's smallest 1.264 / 0.0971
LOW = {"logit_gap": 0.8, "mean_logit_gap": 0.012}


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_the_fp8_control_fails_the_tiny_limits_and_bfloat16_does_not(seed):
    """The reference put in the program's place, at the tiny size: in
    bfloat16 (what the configuration states) its greedy tokens lie within
    limits set between the two readings of three seeds of the float32
    reference's best; in fp8 they fail both."""
    from chipbench import kimi_linear_reference as ref
    # (matrices at 0.1, not 0.02: at a width of 64 and five layers a
    # token's own embedding outweighs what the layers add; at 0.1 the
    # layers outweigh it, as at the published widths)
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
        verdicts[precision] = check.verdict(numbers, dict(LOW))
    assert verdicts["bfloat16"][0] is True
    assert not any(row["ok"] for row in verdicts["fp8"][1])
    with pytest.raises(ValueError, match="precision"):
        ref.forward(key, tokens, tiny, "int4")


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_rehearses_on_the_cpu(trace):
    out = io.StringIO()
    got = run.run_cell(
        WORKLOAD, 2 ** 31 + 43, 2.0, trace, out=out,
        rehearsal={"platform": "cpu", "chips": 1, "model": TINY,
                   "traffic": TRAFFIC, "limits": LIMITS})
    line = got["line"]
    assert line["correct"] is True and line["rehearsal"] \
        and line["failed"] == 0
    ctx = got["result"]["ctx"]
    sched = ctx["scheduler"]
    counted = sched["device_counters"]
    assert counted["decode_runs"] >= sched["decode_steps"] > 0
    # four expert layers, 2 of 8 a token, half of the experts held
    routed = counted["decode_runs"] * TRAFFIC["slots"] * 4 * 2
    assert 0 < counted["decode_moe_pairs"] < routed
    assert counted["decode_moe_rows"] == routed
    # a row a position in ONE layer of five
    assert sched["live_rows"] == pytest.approx(sched["live_positions"] / 5)
    assert sched["pump"]["ahead_hits"] > 0.9 * sched["pump"]["steps"]
    assert run.read_layer_metric(ROOT, "tput_cache_rows_per_position",
                                 ctx) == pytest.approx(0.2)
    if trace:
        # the CPU's trace has no kernels: the scoped readers read, the
        # kernels' read nothing and are left out of the line
        assert {"tput_kda_state_ms", "tput_kda_proj_ms",
                "tput_kda_prefill_ms", "tput_moe_ms", "tput_mla_proj_ms",
                "tput_head_decode_ms",
                "tput_decode_ahead_pct", "tput_cache_rows_per_position",
                "tput_prefill_device_ms"} <= set(line["metrics"])
        for name in ("tput_kda_state_ms", "tput_kda_proj_ms",
                     "tput_kda_prefill_ms"):
            assert run.read_layer_metric(ROOT, name, ctx) > 0
        # a share of the peak needs the device's peak: none on the CPU
        for name in ("tput_kda_state_roofline", "tput_kda_prefill_roofline",
                     "tput_hybrid_decode_roofline"):
            assert run.read_layer_metric(ROOT, name, ctx) is None
        peaked = {**ctx, "peaks": {"hbm_gbps": 819.0, "tflops_bf16": 197.0}}
        for name in ("tput_kda_state_roofline", "tput_kda_prefill_roofline",
                     "tput_hybrid_decode_roofline"):
            assert 0 < run.read_layer_metric(ROOT, name, peaked), name
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
    # a family without ``state_bytes``: the whole run's share with the
    # state has nothing to price the state with
    other = run.load_cell(ROOT, "xing4-serve-doc8k", None)
    ctx = {**ctx, "adapter": other["adapter"],
           "trace": {"ms_by_kind": {"decode": 20.0}, "steps": 40},
           "peaks": {"hbm_gbps": 819.0, "tflops_bf16": 197.0},
           "scheduler": {"live_rows": 9.0, "batch_occupancy": 1.0,
                         "device_counters": {
                             "decode_runs": 5, "decode_moe_pairs": 9,
                             "decode_moe_experts_hit": 3}}}
    assert run.read_layer_metric(ROOT, "tput_hybrid_decode_roofline",
                                 ctx) is None
