"""The two readers PR 34 added for ``command-a-plus-serve-doc``, at the
tiny size on the CPU: ``tput_moe_prefill_route_ms`` (the prefill
programs' device time under the finer scope ``moe_route``) and
``tput_moe_prefill_rows_per_pair`` (the device accumulator's
``prefill_moe_rows / prefill_moe_pairs``), through the same rehearsal as
``test_command_family.py``; and what each reads on a commit that has
nothing for it."""

import io

from chipbench import run

from conftest import ROOT
from test_command_family import TINY, TRAFFIC, WORKLOAD

NAMES = ("tput_moe_prefill_route_ms", "tput_moe_prefill_rows_per_pair")


def test_both_are_the_cells_and_read_in_a_traced_rehearsal():
    bench = run.load_cell(ROOT, WORKLOAD, None)["bench"]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        assert entries[name]["workloads"] == [WORKLOAD]
        assert entries[name]["layer"] == "serve programs"
        assert entries[name]["moves"] == "serve_tokens_per_s"
    got = run.run_cell(
        WORKLOAD, 2 ** 31 + 31, 2.0, True, out=io.StringIO(),
        rehearsal={"platform": "cpu", "chips": 1, "model": TINY,
                   "traffic": TRAFFIC,
                   "limits": {"logit_gap": 0.2, "mean_logit_gap": 0.01}})
    # (a rehearsal's line names the metrics it read and gives no value)
    assert set(NAMES) <= set(got["line"]["metrics"])
    ctx = got["result"]["ctx"]
    counted = ctx["scheduler"]["device_counters"]
    # a tiny prompt's 4 x 48 sorted rows at most are one piece: every
    # row of the bucket goes through the products, four layers a prefill
    assert counted["prefill_moe_rows"] % (4 * 4 * 16) == 0
    assert counted["decode_moe_rows"] \
        == counted["decode_runs"] * 4 * 4 * TRAFFIC["slots"]
    assert run.read_layer_metric(ROOT, NAMES[1], ctx) \
        == counted["prefill_moe_rows"] / counted["prefill_moe_pairs"] > 1
    route = run.read_layer_metric(ROOT, NAMES[0], ctx)
    # the decode program's reader of the same scope reads another program
    assert 0 < route != run.read_layer_metric(ROOT, "tput_moe_route_ms", ctx)


def test_a_commit_without_the_counter_or_the_trace_reads_nothing():
    old = {"prefill_runs": 3, "prefill_moe_pairs": 21000,
           "prefill_moe_experts_hit": 192}
    for sched in ({}, {"device_counters": old},
                  {"device_counters": {**old, "prefill_moe_pairs": 0,
                                       "prefill_moe_rows": 0}}):
        ctx = {"scheduler": sched, "trace": None}
        for name in NAMES:
            assert run.read_layer_metric(ROOT, name, ctx) is None
    full = {"scheduler": {"device_counters": {**old,
                                              "prefill_moe_rows": 172032}},
            "trace": None}
    assert run.read_layer_metric(ROOT, NAMES[1], full) == 172032 / 21000
