"""The program's always-on loop clocks: what the six readers added in
PR 38 read.

Two records, both made by the program under test whether or not a
profiler session is open, and both kept after the work they describe is
gone:

- **the fit loop's phase clock** (``ray_lightning_tpu/telemetry/
  clocks.py``): ``Trainer`` charges a ``PhaseClock`` with the clock reads
  it makes anyway around ``data_wait``, ``callbacks``, ``dispatch`` (the
  body of the ``step`` span) and ``device_wait``, from its first step's
  result to the end of the stage, and keeps the snapshot as
  ``clocks.last("fit")``: the train cell's ``ctx`` holds no trainer;
- **the serve pump's steps by kind** (``Scheduler.stats()["pump"]``, so
  ``ctx["scheduler"]["pump"]``): ``kinds`` has, per kind of step
  (``decode``: the plan carried no prefill; ``prefill_<bucket>``, several
  buckets joined by ``+``), the steps ``n``, their ``wall_s``, their
  prefills' ``prompt_tokens`` and the ``longest`` step.  The worker
  fetches a plan's own prefills' first tokens before it returns, so a
  prefill's time lies in its own step's wall: a kind's wall less ``n``
  times a ``decode`` step's mean is what its prefills cost.  The sums
  are over every step the server ran, the ramp's first fill of the
  slots included, but for the steps of the traced window, which are a
  kind of their own (``profiled``: the profiler's start and stop are
  inside them) that the readers leave out.

On a parent commit that keeps no such record every function here
returns None, and the reader leaves its metric out.
"""

from __future__ import annotations

#: the kind of the traced window's own steps: the profiler starts and
#: stops inside them, so no reader judges a prefill or a stall by them
PROFILED = "profiled"


def fit_clock() -> "dict | None":
    """The snapshot of the newest finished fit's loop clock in this
    process; None where the program keeps none or the loop never
    reached its first step's result."""
    try:
        from ray_lightning_tpu.telemetry import clocks
    except ImportError:      # a parent commit: the program keeps none
        return None
    snap = clocks.last("fit")
    return snap if snap and "wall_s" in snap else None


def pump_kinds(ctx: dict) -> "dict | None":
    """The pump's steps by kind, the traced window's own left out; None
    where the program keeps none or no step ran."""
    pump = (ctx.get("scheduler") or {}).get("pump") or {}
    kinds = {name: k for name, k in (pump.get("kinds") or {}).items()
             if name != PROFILED}
    return kinds or None


def prefill_seconds(kinds: dict) -> "tuple | None":
    """``(seconds, prompt tokens, steps)`` over the kinds whose plan
    carried a prefill, the seconds less a ``decode`` step's mean a step;
    None without both a decode kind and a prefill kind."""
    decode = kinds.get("decode")
    with_prefill = [k for name, k in kinds.items() if name != "decode"]
    if not decode or not decode["n"] or not with_prefill:
        return None
    mean = decode["wall_s"] / decode["n"]
    return (sum(k["wall_s"] - k["n"] * mean for k in with_prefill),
            sum(k["prompt_tokens"] for k in with_prefill),
            sum(k["n"] for k in with_prefill))
