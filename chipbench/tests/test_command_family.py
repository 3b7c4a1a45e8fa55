"""The Command A+ family through the harness, beside ``test_adapter.py``
(whose ``family2`` proves that a family takes new files only): the
``command`` adapter, its reference and the cell's limits file load by the
names ``BENCHMARK.json`` and the configuration give, and ``run_cell``
rehearses ``command-a-plus-serve-doc`` on the CPU at a tiny size through
the same ``Server``, with the cache by layer kind, the device's
accumulator and the cell's own readers."""

import io
import json
import os

import pytest

from chipbench import check, run

from conftest import ROOT

WORKLOAD = "command-a-plus-serve-doc"
TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=48,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=2, head_dim=16, num_experts=4,
            num_experts_published=16, num_experts_per_tok=4,
            num_shared_experts=2, sliding_window=8,
            max_position_embeddings=64, served_positions=56)
TRAFFIC = {"slots": 4, "ramp_s": 1.5, "token_ids_below": 256,
           "buckets": [16, 32, 48], "server": {"max_new_tokens": 16},
           "prompt": {"median": 20, "min": 10, "max": 40},
           "answer": {"min": 4, "max": 12}}


def test_the_cells_files_load_by_name():
    cell = run.load_cell(ROOT, WORKLOAD, None)
    config, adapter = cell["config"], cell["adapter"]
    assert adapter.__name__ == "chipbench.adapters.command"
    ref = check.load_reference(config, ROOT)
    assert "fp8" in ref.PRECISIONS and callable(ref.forward)
    limits = check.load_limits(ROOT, WORKLOAD)
    assert set(limits) == {"logit_gap", "mean_logit_gap"}
    model = config["model"]
    assert adapter.context(model) == 8960
    assert adapter.make_weights(model, "the key") == "the key"
    bench = cell["bench"]
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"] \
        == ["num_hidden_layers", "num_experts", "vocab_size"]
    for key in entry["reduced"]:
        assert config[key] == model[key] != config["published"][key]
    # every published key at the top level and again under ``model``
    top = {k: v for k, v in config.items() if k in model}
    assert top == {k: model[k] for k in top} and len(top) == 38
    # 9.47 GB of weights beside 2.78 GB of cache at 32 slots
    assert 3.0e9 < adapter.weight_bytes(model) < 3.1e9
    assert adapter.expert_bytes(model, 16 * 4, 0) == 2 * 64 * 3 * 4096 ** 2
    assert adapter.prefill_attn_flops(model, 4096) \
        == 4 * 2.0 * 2 * 128 * 128 * (4096 * 4097 // 2)


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_rehearses_on_the_cpu(trace):
    out = io.StringIO()
    got = run.run_cell(
        WORKLOAD, 2 ** 31 + 29, 2.0, trace, out=out,
        rehearsal={"platform": "cpu", "chips": 1, "model": TINY,
                   "traffic": TRAFFIC,
                   "limits": {"logit_gap": 0.2, "mean_logit_gap": 0.01}})
    line = got["line"]
    assert line["correct"] is True and line["rehearsal"] \
        and line["failed"] == 0
    sched = got["result"]["ctx"]["scheduler"]
    counted = sched["device_counters"]
    assert counted["decode_runs"] >= sched["decode_steps"] > 0
    assert counted["prefill_runs"] > 0 \
        and counted["decode_moe_pairs"] >= counted["decode_moe_experts_hit"]
    # a ring of 8 rows in three layers of four
    assert sched["live_rows"] < sched["live_positions"]
    if trace:
        assert {"tput_moe_ms", "tput_moe_route_ms",
                "tput_moe_pairs_per_expert", "tput_cache_rows_per_position",
                "tput_decode_ahead_pct"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert [json.loads(x) for x in out.getvalue().splitlines()]
    assert os.path.isdir(os.path.join(ROOT, ".chipbench_work", WORKLOAD))
