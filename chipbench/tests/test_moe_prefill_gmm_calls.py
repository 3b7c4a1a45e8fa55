"""The reader PR 37 added, ``tput_moe_prefill_gmm_calls``: its entry, a
synthetic capture whose count is worked out by hand (the trace's own
``gmm*`` events inside prefill runs over those runs), and what it reads
where there is no such event or no trace."""

import pytest

from chipbench import host_spans, run

from conftest import ROOT

NAME = "tput_moe_prefill_gmm_calls"
CELLS = ["command-a-plus-serve-doc", "xing4-serve-doc8k"]


def device(pieces=(2, 3)):
    """A decode run with three grouped products, then prefill runs of
    two buckets: a layer in one pass (three events) and, around a
    ``while`` event, ``pieces`` trips of three each."""
    ops, mods = [], []
    mods.append(("jit_serve_decode(1)", 0.010, 0.012))
    ops += [("gmm.%d" % i, 0.011 + 0.002 * i, 0.001) for i in range(3)]
    t = 0.030
    for bucket, trips in zip((6144, 8192), pieces):
        mods.append((f"jit_serve_prefill_{bucket}({bucket})", t, 0.200))
        ops += [("fusion.7", t, 0.010), ("while.3", t + 0.010, 0.150)]
        ops += [("gmm.%d" % (4 + i), t + 0.161 + 0.002 * i, 0.001)
                for i in range(3)]
        for trip in range(trips):
            ops += [("gmm.%d" % (7 + i), t + 0.011 + 0.01 * trip
                     + 0.002 * i, 0.001) for i in range(3)]
        t += 0.250
    return [{"ops": ops, "modules": mods}]


@pytest.fixture
def traced(tmp_path, monkeypatch):
    def ctx_of(devices):
        path = str(tmp_path / "serve.trace.json")
        host_spans.write_chrome_trace(path, devices, [])
        host_spans._cache.clear()
        monkeypatch.setattr(host_spans, "find_trace", lambda *a, **k: path)
        monkeypatch.setattr(host_spans, "_tables_beside", lambda p: None)
        return {"trace": {"window_s": 1.0}, "scheduler": {}}
    return ctx_of


def test_the_entry_is_the_two_expert_cells():
    bench = run.load_cell(ROOT, CELLS[1], None)["bench"]
    entry = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert entry == {"name": NAME, "unit": "calls", "better": "lower",
                     "source": "device_trace", "layer": "serve programs",
                     "moves": "serve_tokens_per_s", "workloads": CELLS}
    assert bench["per_layer"][-1]["name"] == NAME


def test_events_by_name_inside_prefill_runs_over_those_runs(traced):
    # (3 + 2 x 3) + (3 + 3 x 3) events in two prefill runs; the decode
    # run's three are another program's
    assert run.read_layer_metric(ROOT, NAME, traced(device())) \
        == pytest.approx((9 + 12) / 2)
    # one pass in every layer of both runs: three a run here
    assert run.read_layer_metric(ROOT, NAME, traced(device((0, 0)))) == 3
    # two devices: the mean over the runs of both
    both = device() + device((0, 0))
    assert run.read_layer_metric(ROOT, NAME, traced(both)) \
        == pytest.approx((9 + 12 + 3 + 3) / 4)


def test_no_such_kernel_or_no_trace_reads_nothing(traced):
    plain = [{"modules": [("jit_serve_prefill_512(2)", 0.010, 0.020)],
              "ops": [("fusion.9", 0.010, 0.010),
                      ("ragged-dot.1", 0.020, 0.005)]}]
    assert run.read_layer_metric(ROOT, NAME, traced(plain)) is None
    only_decode = [{"modules": [("jit_serve_decode(1)", 0.010, 0.020)],
                    "ops": [("gmm.1", 0.011, 0.005)]}]
    assert run.read_layer_metric(ROOT, NAME, traced(only_decode)) is None
    assert run.read_layer_metric(ROOT, NAME, {"trace": None,
                                              "scheduler": {}}) is None
