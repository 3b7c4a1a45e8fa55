"""Tokens are counted at the step that did the work."""

from chipbench.serve_cell import StepCounter, percentile


class FakeScheduler:
    """Plays back a script of plans; ``apply`` records what it was given."""

    def __init__(self, plans):
        self.plans, self.applied = list(plans), []

    def plan(self):
        return self.plans.pop(0) if self.plans else None

    def apply(self, plan, result):
        self.applied.append(plan)


def _plan(prefill_lengths=(), decode_slots=(), positions=()):
    decode = None
    if decode_slots:
        decode = {"slots": list(decode_slots), "positions": list(positions)}
    return {"prefills": [{"length": n} for n in prefill_lengths],
            "decode": decode}


def test_prompt_tokens_count_at_the_prefill_step_answers_at_each_emit():
    now = [0.0]
    sched = FakeScheduler([
        _plan(prefill_lengths=[300]),                        # t=1
        _plan(prefill_lengths=[500], decode_slots=[0],
              positions=[300, 0]),                           # t=2
        _plan(decode_slots=[0, 1], positions=[301, 500]),    # t=3
        _plan(decode_slots=[0, 1], positions=[302, 501]),    # t=4
    ])
    counter = StepCounter(sched, clock=lambda: now[0])
    while True:
        now[0] += 1.0
        plan = sched.plan()
        if plan is None:
            break
        sched.apply(plan, {})
    assert len(sched.applied) == 4
    # a window over steps 2 and 3 only: the 300-token prompt of step 1
    # and the tokens of step 4 are outside it, whenever their requests end
    got = counter.between(1.5, 3.5)
    assert got["steps"] == 2
    assert got["prompt_tokens"] == 500
    assert got["answer_tokens"] == (1 + 1) + 2
    assert got["live_tokens_mean"] == (300 + 801) / 2
    assert counter.kinds == [["prefill"], ["decode", "prefill"],
                             ["decode"], ["decode"]]


def test_the_profile_rides_one_plan_and_marks_the_traced_steps():
    now = [0.0]
    sched = FakeScheduler([_plan(decode_slots=[0], positions=[5])
                           for _ in range(3)])
    counter = StepCounter(sched, clock=lambda: now[0])
    counter.profile = {"id": "x", "steps": 2, "dir": "/d", "at": 1.0}
    first = sched.plan()
    sched.apply(first, {})
    now[0] = 2.0
    second = sched.plan()
    sched.apply(second, {})
    third = sched.plan()
    sched.apply(third, {})
    assert "profile" not in first and "profile" not in third
    assert second["profile"] == {"id": "x", "steps": 2, "dir": "/d"}
    assert counter.dispatched_in_trace(2) == ["decode", "decode"]


def test_percentile_is_nearest_rank_over_all_values():
    values = list(range(1, 101))
    assert percentile(values, 0.9) == 90
    assert percentile(values, 0.5) == 50
    assert percentile([7.0], 0.9) == 7.0


def test_the_check_reads_every_served_token_of_every_finished_request():
    """A scripted reference whose best token at a position is the token
    before it plus one: requests that count upwards have gap 0 at every
    served token, and one wrong token shows at its own request."""
    import types

    import jax.numpy as jnp
    import numpy as np

    from chipbench import check

    model = {"vocab_size": 50}

    def forward(w, tokens, model, precision="float32"):
        want = (tokens + (1 if precision == "float32" else 2)) % 50
        return -jnp.abs(jnp.arange(50)[None, None, :]
                        - want[..., None]).astype(jnp.float32)

    ref = types.SimpleNamespace(forward=forward)
    finished = [([(i + j) % 50 for j in range(3 + i % 5)],
                 [(i + 3 + i % 5 + j) % 50 for j in range(2 + i % 4)])
                for i in range(19)]           # three blocks, the last short
    got = check.served_positions(ref, {}, model, finished,
                                 also=("bfloat16",), context=32)
    served = sum(len(a) for _, a in finished)
    assert got["gap"].shape == got["gap_bfloat16"].shape == (served,)
    assert np.array_equal(np.bincount(got["request"].astype(int)),
                          [len(a) for _, a in finished])
    assert float(got["gap"].max()) == 0.0
    assert float(got["gap_bfloat16"].min()) == 1.0   # its best is one off
    numbers = check.served_numbers(got)
    assert numbers["tokens_compared"] == served
    assert numbers["logit_gap"] == 0.0 and numbers["not_best_share"] == 0.0
    assert numbers["bfloat16_mean_logit_gap"] == 1.0
    finished[11][1][1] = (finished[11][1][1] + 7) % 50
    bad = check.served_positions(ref, {}, model, finished, context=32)
    hit = np.nonzero(bad["gap"])[0]
    # the altered token, and the one that follows from it
    assert set(bad["request"][hit].astype(int)) == {11} and len(hit) == 2
    assert check.served_numbers(bad)["logit_gap"] == 7.0
    assert check.served_numbers(check.served_positions(
        ref, {}, model, [], context=32)) == {}
