"""``tput_decode_ahead_pct``: the share of steps whose decode was queued
ahead, read from the pump's two counters; nothing where a program (a
parent commit's) counts none."""

import pytest

from chipbench import run

from conftest import ROOT


def _read(scheduler):
    return run.read_layer_metric(ROOT, "tput_decode_ahead_pct",
                                 {"scheduler": scheduler})


@pytest.mark.parametrize("pump,want", [
    ({"steps": 200, "ahead_hits": 197, "ahead_misses": 3}, 98.5),
    ({"steps": 40, "ahead_hits": 0, "ahead_misses": 1}, 0.0),
    ({"steps": 200, "worker_s": 1.0}, None),     # a parent: no such keys
    ({"steps": 0, "ahead_hits": 0, "ahead_misses": 0}, None),
    (None, None),
], ids=["hits", "none_hit", "keys_absent", "no_steps", "no_pump"])
def test_the_share_comes_from_the_pumps_counters(pump, want):
    got = _read({} if pump is None else {"pump": pump})
    assert got == (None if want is None else pytest.approx(want))
