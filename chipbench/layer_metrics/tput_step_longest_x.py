"""How far the pump's worst step lies from its kind's mean: the largest,
over the kinds of step with at least eight steps, of the kind's longest
step over its mean (``Scheduler.stats()["pump"]["kinds"]``).  1.1-1.5 in
a sound run; a stall of seconds inside one step reads tens (on a prefill
step) to hundreds (on a decode step).  ``pump["longest"]`` names the
step, its kind, its largest phase and its wall-clock ``ts``.  The traced
window's own steps are a kind apart and left out."""

MIN_STEPS = 8


def read(ctx: dict):
    from chipbench import loop_clocks
    kinds = loop_clocks.pump_kinds(ctx)
    if not kinds:
        return None
    ratios = [k["longest"]["seconds"] * k["n"] / k["wall_s"]
              for k in kinds.values() if k["n"] >= MIN_STEPS and k["wall_s"]]
    return max(ratios) if ratios else None
