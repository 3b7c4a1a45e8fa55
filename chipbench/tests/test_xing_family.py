"""The Xing4.0 family through the harness, beside ``test_adapter.py`` and
``test_command_family.py``: the ``xing`` adapter, its reference and the
cell's limits file load by the names ``BENCHMARK.json`` and the
configuration give; the cost functions against counts by hand; the fp8
control fails the tiny limits where the sound program passes them; and
``run_cell`` rehearses ``xing4-serve-doc8k`` on the CPU at a tiny size
through the same ``Server``, over a state of ONE array, with the cell's own
readers."""

import io
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, run

from conftest import ROOT

WORKLOAD = "xing4-serve-doc8k"
TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=3,
            first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
            rope_scaling=dict(type="yarn", factor=4,
                              original_max_position_embeddings=16,
                              beta_fast=32, beta_slow=1, mscale=1,
                              mscale_all_dim=1),
            max_position_embeddings=64, served_positions=64)
TRAFFIC = {"slots": 4, "ramp_s": 1.5, "token_ids_below": 256,
           "buckets": [16, 32, 48], "server": {"max_new_tokens": 16},
           "prompt": {"median": 20, "min": 10, "max": 40},
           "answer": {"min": 4, "max": 12}}
LIMITS = {"logit_gap": 0.2, "mean_logit_gap": 0.01}
NEW = ("tput_mla_decode_ms", "tput_mla_decode_roofline",
       "tput_mla_proj_ms", "tput_mhc_ms", "tput_mhc_prefill_ms",
       "tput_mhc_prefill_stream_pct")
# the prefill's attention is jax's splash attention, as Command's: the
# readers in place read its kernels by name and ask the adapter
IN_PLACE = ("tput_window_prefill_attn_ms", "tput_window_prefill_roofline")


def test_the_cells_files_load_by_name():
    cell = run.load_cell(ROOT, WORKLOAD, None)
    config, adapter = cell["config"], cell["adapter"]
    assert adapter.__name__ == "chipbench.adapters.xing"
    ref = check.load_reference(config, ROOT)
    assert ref.PRECISIONS == ("float32", "bfloat16", "fp8") \
        and callable(ref.forward)
    assert set(check.load_limits(ROOT, WORKLOAD)) \
        == {"logit_gap", "mean_logit_gap"}
    model = config["model"]
    assert adapter.context(model) == 10240
    # what the check hands the reference: every tensor but the table made
    # once, 7.16 GB, and the key for the table
    held = jax.eval_shape(lambda k: adapter.make_weights(model, k),
                          jax.random.PRNGKey(0))
    assert 7.15e9 < sum(a.size * a.dtype.itemsize
                        for a in held.values()) < 7.18e9
    assert held["gate_w"].shape == (4, 64, 3584, 1024) \
        and held["gate_w"].dtype == jnp.bfloat16 \
        and held["router_w"].dtype == jnp.float32 and "wte" not in held
    bench = cell["bench"]
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace",
        "num_nextn_predict_layers"]
    for key in entry["reduced"]:
        assert config[key] == model[key] != config["published"][key]
    # every key of the catalog's config at the top level and under model
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    for key, value in row["config"].items():
        assert config[key] == model[key]
        assert (config[key] == value) != (key in entry["reduced"]), key
    assert cell["traffic"]["slots"] == 64 and cell["chips"] == 1
    listed = {m["name"] for m in bench["per_layer"]
              if WORKLOAD in m.get("workloads", ())}
    assert set(NEW + IN_PLACE) <= listed and len(listed) == 31
    cfg = adapter.config_of(model)
    assert cfg.row_width == 640 and cfg.num_hidden_layers == 5 \
        and abs(cfg.softmax_scale - 0.14468) < 1e-5


def test_costs_against_counts_by_hand():
    cell = run.load_cell(ROOT, WORKLOAD, None)
    model, adapter = cell["config"]["model"], cell["adapter"]
    d, H = 3584, 32
    attn = d * 768 + 768 * H * 192 + d * 576 + 512 * H * 256 + H * 128 * d \
        + 768 + 512
    assert attn == 28_411_136      # the issue's 28.4M
    hyper = 2 * (4 * d * 24 + 24 + 3)
    every = 2 * (attn + 2 * d) + 4 * hyper
    dense = 2 * 3 * d * 9216
    moe = 2 * 3 * d * 1024 + 4 * (d * 64 + 64)
    head = 2 * (d + d * 131072)
    assert adapter.weight_bytes(model) \
        == 5 * every + dense + 4 * moe + head
    # ~1.53 GB: the head 0.94, the dense MLP 0.20, attention 0.28
    assert 1.5e9 < adapter.weight_bytes(model) < 1.56e9
    # every expert of four layers hit, 256 pairs a layer: 5.64 GB
    assert adapter.expert_bytes(model, 4 * 64, 4 * 256) \
        == 2 * (256 * 3 * d * 1024 + 1024 * 2 * d)
    assert adapter.expert_flops(model, 1000) == 2.0 * 3 * d * 1024 * 1000
    # 1,152 B a row a layer
    assert adapter.decode_row_bytes(model, 64 * 8300) \
        == 1152 * 5 * 64 * 8300
    assert adapter.prefill_attn_flops(model, 8192) \
        == 2.0 * 320 * 32 * 5 * (8192 * 8193 // 2)
    # ten sublayers x three passes over [tokens, 4, 3584] float32
    assert adapter.stream_bytes(model, 8000) == 10 * 3 * 4 * 4 * d * 8000
    # the parameters of the cut: 4.05 B, 8.10 GB in bfloat16
    z = adapter.ref.sizes(model)
    total = 5 * (attn + 2 * d + hyper) + 3 * d * 9216 \
        + 4 * (65 * 3 * d * 1024 + d * 64 + 64) + d + 2 * d * 131072
    assert z["L"] == 5 and 4.04e9 < total < 4.06e9


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_the_fp8_control_fails_the_tiny_limits_and_bfloat16_does_not(seed):
    """The reference put in the program's place, at the tiny size (its
    logits spread by 0.16): in bfloat16 (what the configuration states)
    its greedy tokens lie within limits set between the two readings of
    three seeds (bfloat16's largest 0.0038 / 2.0e-5, fp8's smallest 0.0132
    / 3.0e-4) of the float32 reference's best; in fp8 they pass both."""
    from chipbench import xing_reference as ref
    limits = {"logit_gap": 0.007, "mean_logit_gap": 8e-5}
    tiny = {**run.load_cell(ROOT, WORKLOAD, None)["config"]["model"], **TINY}
    key = jax.random.PRNGKey(seed)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, 256, (4, 48)), jnp.int32)
    exact = ref.forward(key, tokens, tiny)
    best = jnp.max(exact, axis=-1)
    verdicts = {}
    for precision in ("bfloat16", "fp8"):
        first = jnp.argmax(ref.forward(key, tokens, tiny, precision),
                           axis=-1)
        gap = np.asarray(best - jnp.take_along_axis(
            exact, first[..., None], axis=-1)[..., 0])
        verdicts[precision] = check.verdict(
            check.served_numbers({"gap": gap.reshape(-1)}), limits)
    assert verdicts["bfloat16"][0] is True
    assert not any(row["ok"] for row in verdicts["fp8"][1])
    with pytest.raises(ValueError, match="precision"):
        ref.forward(key, tokens, tiny, "int4")


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_rehearses_on_the_cpu(trace):
    out = io.StringIO()
    got = run.run_cell(
        WORKLOAD, 2 ** 31 + 29, 2.0, trace, out=out,
        rehearsal={"platform": "cpu", "chips": 1, "model": TINY,
                   "traffic": TRAFFIC, "limits": LIMITS})
    line = got["line"]
    assert line["correct"] is True and line["rehearsal"] \
        and line["failed"] == 0
    ctx = got["result"]["ctx"]
    sched = ctx["scheduler"]
    counted = sched["device_counters"]
    assert counted["decode_runs"] >= sched["decode_steps"] > 0
    # every expert held: every pair a slot routes is computed, two expert
    # layers, 2 a token
    assert counted["decode_moe_pairs"] \
        == counted["decode_runs"] * TRAFFIC["slots"] * 2 * 2
    # a row a position in every layer
    assert sched["live_rows"] == sched["live_positions"]
    assert sched["pump"]["ahead_hits"] > 0.9 * sched["pump"]["steps"]
    if trace:
        # the CPU's trace has no kernels: the scoped readers read, the
        # kernels' read nothing and are left out of the line
        assert {"tput_mla_proj_ms", "tput_mhc_ms", "tput_mhc_prefill_ms",
                "tput_moe_ms", "tput_moe_prefill_rows_per_pair",
                "tput_decode_ahead_pct"} <= set(line["metrics"])
        assert run.read_layer_metric(ROOT, "tput_mhc_ms", ctx) > 0
        assert run.read_layer_metric(ROOT, "tput_mhc_prefill_ms", ctx) > 0
        assert run.read_layer_metric(ROOT, "tput_mla_decode_ms", ctx) is None
        assert run.read_layer_metric(ROOT, "tput_mla_decode_roofline",
                                     ctx) is None
        # a share of the peak needs the device's peak: none on the CPU
        assert run.read_layer_metric(ROOT, "tput_mhc_prefill_stream_pct",
                                     ctx) is None
        peaked = {**ctx, "peaks": {"hbm_gbps": 819.0, "tflops_bf16": 197.0}}
        assert 0 < run.read_layer_metric(
            ROOT, "tput_mhc_prefill_stream_pct", peaked) < 100
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert [json.loads(x) for x in out.getvalue().splitlines()]
    assert os.path.isdir(os.path.join(ROOT, ".chipbench_work", WORKLOAD))


def test_a_run_without_a_trace_reads_nothing():
    cell = run.load_cell(ROOT, WORKLOAD, None)
    ctx = {"scheduler": {}, "trace": None, "peaks": None,
           "adapter": cell["adapter"], "model": cell["config"]["model"],
           "traffic": cell["traffic"]}
    for name in NEW + IN_PLACE:
        assert run.read_layer_metric(ROOT, name, ctx) is None
