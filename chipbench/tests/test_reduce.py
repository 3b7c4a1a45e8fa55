"""The copied trace reducer on a small synthetic trace of the TPU layout."""

import os

import pytest

from chipbench import reduce


@pytest.fixture
def timelines(tmp_path):
    # two devices, two steps each of 10 ms, 2 ms of host gap between them;
    # per step: 4 ms fusion, a 3 ms all-reduce that starts 1 ms before the
    # fusion ends (2 ms exposed), 3 ms fusion
    def device(shift):
        ops, mods = [], []
        for k in range(2):
            t = shift + k * 0.012
            mods.append(("jit_step_fn", t, 0.010))
            ops += [("fusion.1", t, 0.004), ("all-reduce.7", t + 0.003, 0.003),
                    ("fusion.2", t + 0.007, 0.003)]
        return {"ops": ops, "modules": mods}
    path = os.path.join(tmp_path, "t.trace.json")
    reduce.write_chrome_trace(path, [device(0.0), device(0.0)])
    return reduce.load_chrome(path)


def test_busy_idle_host_gap_and_exposed_collective(timelines):
    red = reduce.reduce_timelines(timelines)
    assert red["devices"] == 2 and red["steps"] == 2
    assert red["window_s"] == pytest.approx(0.022)
    assert red["busy_s"] == pytest.approx(0.018)
    assert red["idle_s"] == pytest.approx(0.004)
    assert red["compute_s"] == pytest.approx(0.014)
    assert red["collective_s"] == pytest.approx(0.006)
    assert red["exposed_s"] == pytest.approx(0.004)
    # the identity the reducer was copied for
    assert red["window_s"] == pytest.approx(
        red["compute_s"] + red["exposed_s"] + red["idle_s"])
    assert red["main_module"] == "jit_step_fn"
    assert reduce.module_ms_per_run(red, "step_fn") == pytest.approx(10.0)
    assert reduce.module_ms_per_run(red, "absent") is None
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(0.008)]
    gaps = dict(red["idle_gaps"])
    assert gaps["before:jit_step_fn"] == pytest.approx(0.002)
    assert gaps["inside:jit_step_fn"] == pytest.approx(0.002)


def test_programs_of_one_name_are_told_apart_by_dispatch_order(timelines):
    red = reduce.reduce_timelines(timelines)
    assert reduce.ms_per_run_by_kind(red, ["decode", "prefill"]) == {
        "decode": pytest.approx(10.0), "prefill": pytest.approx(10.0)}
    assert reduce.ms_per_run_by_kind(red, ["decode"]) == {}


def test_a_trace_without_device_operations_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        reduce.load(str(tmp_path))


def test_interval_algebra():
    assert reduce.union([(3, 4), (0, 2), (1, 2.5)]) == [[0, 2.5], [3, 4]]
    assert reduce.subtract([[0, 10]], [[2, 3], [5, 11]]) == [[0, 2], [3, 5]]
    assert reduce.measure([[0, 2.5], [3, 4]]) == 3.5
