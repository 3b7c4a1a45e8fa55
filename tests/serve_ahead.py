"""Scripted traffic for the worker's decode-ahead order (serve/worker.py
``_run_ahead``), shared by tests/test_serve.py (GPT-2: a row per
position) and tests/test_evabyte.py (a window and chunk summaries).

``drive`` runs a real ``Scheduler`` and a real ``ServeWorker`` over an
engine twice alike but for the order on the host: once with the engine's
``runs_ahead`` held false (every program waited for before the next is
queued, the order before decode-ahead) and once as the engine is.  The
two have to agree on every token and on every cache row a live slot can
read, step by step.
"""

from __future__ import annotations

import contextlib
from unittest import mock

import numpy as np

from ray_lightning_tpu.serve.engine import ServeEngine
from ray_lightning_tpu.serve.scheduler import Scheduler
from ray_lightning_tpu.serve.worker import ServeWorker

#: name -> (scheduler arguments, waves).  A wave is a list of ``(which
#: prompt, max_new_tokens)``; it is submitted whole and stepped until
#: nothing is left to plan, so the next wave finds the server idle.
#: ``S`` is the engine's slot count, filled in by ``scenario``.
SCENARIOS = {
    # more requests than slots, answers of unequal length: a slot is
    # freed while the others decode and admitted into the step after
    "freed_slot": ({}, [[(0, 3), (1, 6), (2, 4), (3, 7), (4, 5), (5, 3)]]),
    # a request that ends at its first token, beside longer ones
    "max_new_1": ({}, [[(0, 1), (1, 5), (2, 1), (3, 1), (4, 4)]]),
    # the scheduler ends a request at a token the model emits (the
    # driver picks one from a run without it)
    "eos": ({"eos_token": "probe"}, [[(0, 8), (1, 8), (2, 8), (3, 8)]]),
    "two_prefills": ({"max_prefills_per_step": 2},
                     [[(0, 4), (1, 6), (2, 3), (3, 5), (4, 4), (5, 2)]]),
    # every slot ends on one step with a request still queued: the next
    # step is a prefill alone, and the decode in flight is dropped
    "no_decode": ({}, [[("S", 4), (5, 3)]]),
    "idle_gap": ({}, [[(0, 3), (1, 5)], [(2, 4), (3, 2)], [(4, 3)]]),
}


def scenario(name: str, slots: int):
    kwargs, waves = SCENARIOS[name]
    out = []
    for wave in waves:
        reqs = []
        for which, n in wave:
            reqs += [(i, n) for i in range(slots)] if which == "S" \
                else [(which, n)]
        out.append(reqs)
    return dict(kwargs), out


def worker_on(eng) -> ServeWorker:
    """A worker as ``setup_serve`` leaves it, around an engine (or a
    stand-in for one) made here."""
    worker = ServeWorker()
    worker._engine, worker._rank = eng, 0
    return worker


def _kinds(state) -> list:
    """The cache's arrays a kind (serve/kvcache.py): the one array, or a
    tuple's (an accumulator behind them is no rows)."""
    if isinstance(state, tuple):
        return [np.asarray(a) for a in state if a.ndim == 4]
    return [np.asarray(state)]


def _live(arrays: list, slot: int, at) -> np.ndarray:
    """A slot's live rows of every kind, flat: ``at`` is the rows of the
    one kind, or a list of them a kind."""
    at = at if isinstance(at, (list, tuple)) else [at]
    # (no array at all: the values' side of a state of one array)
    return np.concatenate([np.zeros(0)] + [a[:, slot, rows].reshape(-1)
                                           for a, rows in zip(arrays, at)])


def drive(eng: ServeEngine, prompts, waves, live_rows, *, ahead: bool,
          **sched_kw) -> dict:
    """One run from a zeroed cache.  ``live_rows(position)`` lists the
    cache rows of a slot that a decode at ``position`` may read and that
    were written before it (the rows to compare; a list of them a kind
    where the cache holds more than one kind of layer).  Returns ``{"tokens":
    [per request], "ahead": [per step], "decoded": [per step: did the
    plan decode], "rows": {(step, slot): (k rows, v rows)}}``."""
    eng._k, eng._v = eng._kv_init()
    sched = Scheduler(buckets=eng.buckets, slots=eng.slots,
                      max_seq_len=eng.max_seq_len, **sched_kw)
    worker = worker_on(eng)
    held = contextlib.nullcontext() if ahead else \
        mock.patch.object(ServeEngine, "runs_ahead", False)
    reqs, aheads, decoded, rows = [], [], [], {}
    with held:
        for wave in waves:
            reqs += [sched.submit(prompts[i], max_new_tokens=n)
                     for i, n in wave]
            for _ in range(400):
                plan = sched.plan()
                if plan is None:
                    break
                result = worker.serve_step(plan)
                sched.apply(plan, result)
                aheads.append(result["timing"].get("ahead"))
                decoded.append(plan["decode"] is not None)
                k, v = _kinds(eng._k), _kinds(eng._v)
                for slot, r in sched._by_slot.items():
                    at = live_rows(r.pos)
                    rows[len(aheads) - 1, slot] = (_live(k, slot, at),
                                                   _live(v, slot, at))
            assert sched.idle()
    assert all(r.done() for r in reqs)
    return {"tokens": [r.result(1).tolist() for r in reqs],
            "ahead": aheads, "decoded": decoded, "rows": rows}


def check_equal_and_counted(eng: ServeEngine, prompts, name: str,
                            live_rows) -> dict:
    """The scenario through both orders: equal tokens, equal live rows,
    every step's hit or miss as the order predicts it, no retrace."""
    kwargs, waves = scenario(name, eng.slots)
    if kwargs.get("eos_token") == "probe":
        plain = drive(eng, prompts, waves, live_rows, ahead=False)
        # a token some request emits after its first: it ends there
        kwargs["eos_token"] = next(t[2] for t in plain["tokens"]
                                   if len(t) > 3)
    traced = dict(eng.trace_counts)
    want = drive(eng, prompts, waves, live_rows, ahead=False, **kwargs)
    got = drive(eng, prompts, waves, live_rows, ahead=True, **kwargs)
    assert got["tokens"] == want["tokens"]
    assert got["decoded"] == want["decoded"]
    assert set(got["rows"]) == set(want["rows"]) and got["rows"]
    for key, (k, v) in want["rows"].items():
        np.testing.assert_array_equal(got["rows"][key][0], k, str(key))
        np.testing.assert_array_equal(got["rows"][key][1], v, str(key))
    # the blocking order counts nothing; ahead, every step after the
    # first finds a decode in flight: the plan's own (a hit), or one to
    # drop where the plan decodes nothing (a miss)
    assert set(want["ahead"]) == {None}
    assert got["ahead"] == [None] + [
        "hit" if d else "miss" for d in got["decoded"][1:]]
    assert eng.trace_counts == traced
    return got
