import collections
import json
import os

import pytest

from chipbench import generator

from conftest import ROOT

MIXES = ["doc-saturated", "chat-steady"]


def _mix(name):
    with open(os.path.join(ROOT, "chipbench", "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_sends_the_same_multiset_in_another_order(name):
    mix = _mix(name)
    n = mix["rows"]
    seen = []
    for seed in (0, 1, 2 ** 31 + 5):
        stream = generator.RequestStream(mix, seed, 1000)
        rows = []
        for _ in range(2 * n):           # two periods of the table
            tokens, answer, gap = stream.next()
            rows.append((len(tokens), answer, gap))
        assert collections.Counter(rows[:n]) == collections.Counter(rows[n:])
        seen.append(rows[:n])
    assert all(collections.Counter(s) == collections.Counter(seen[0])
               for s in seen)
    assert seen[0] != seen[1] != seen[2]


@pytest.mark.parametrize("name", MIXES)
def test_table_keeps_to_the_file(name):
    mix = _mix(name)
    table = generator.build_table(mix)
    assert len(table) == mix["rows"]
    for prompt, answer, gap in table:
        assert mix["prompt"]["min"] <= prompt <= mix["prompt"]["max"]
        assert mix["answer"]["min"] <= answer <= mix["answer"]["max"]
        assert prompt + answer <= 1024 + 1
    if mix.get("rate_rps"):
        period = sum(g for _, _, g in table)
        assert period == pytest.approx(mix["rows"] / mix["rate_rps"])


def test_strata_even_out_every_block_of_requests():
    mix = _mix("doc-saturated")
    table = generator.build_table(mix)
    order = generator.seeded_order(table, 3, 8)
    assert sorted(order) == list(range(len(table)))
    mean = sum(p + a for p, a, _ in table) / len(table)
    for b in range(0, len(order), 8):
        block = [sum(table[i][:2]) for i in order[b:b + 8]]
        assert abs(sum(block) / 8 - mean) < 0.08 * mean


def test_same_seed_same_tokens():
    mix = _mix("chat-steady")
    a = generator.RequestStream(mix, 9, 500).next()[0]
    b = generator.RequestStream(mix, 9, 500).next()[0]
    assert (a == b).all() and a.max() < 500


def test_open_loop_times_from_when_a_request_was_due():
    """A submit that stalls makes later requests late, not later-due."""
    now = [0.0]
    mix = {"rows": 4, "prompt": {"dist": "constant", "value": 3},
           "answer": {"dist": "constant", "value": 2},
           "gap": {"dist": "constant", "value": 1.0}, "rate_rps": 2.0}
    load = generator.LoadThread(
        generator.RequestStream(mix, 0, 10), None,
        clock=lambda: now[0],
        sleep=lambda s: now.__setitem__(0, now[0] + s))

    def submit(tokens, answer_len):
        now[0] += 0.8                     # a stalled server
        if len(load.sent) == 3:
            load.stop()
        return object()

    load.submit = submit
    load.run()
    assert load.error is None
    assert [round(s.due, 6) for s in load.sent] == [0.5, 1.0, 1.5, 2.0]
    assert load.sent[-1].sent - load.sent[-1].due > 1.0


def test_lm_rows_follow_the_seed():
    x, y = generator.lm_rows(6, 32, 100, 4)
    x2, _ = generator.lm_rows(6, 32, 100, 4)
    x3, _ = generator.lm_rows(6, 32, 100, 5)
    assert x.shape == y.shape == (6, 32) and (x == x2).all()
    assert (x[:, 1:] == y[:, :-1]).all() and (x != x3).any()
    assert len({tuple(r) for r in x}) == 6
