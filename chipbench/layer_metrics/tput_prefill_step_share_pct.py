"""Share of the pump's step time that prefills take: over the steps
whose plan carried a prefill, their wall time less a decode-only step's
mean a step, over all steps' wall time (``Scheduler.stats()["pump"]
["kinds"]``).  Over EVERY step the server ran, the ramp's first fill of
the slots included (a prefill a step until the slots are full), so it
reads a little above the measured window's own share; the traced
window's steps (the kind ``profiled``: the profiler's start and stop are
inside them) are left out above and below."""


def read(ctx: dict):
    from chipbench import loop_clocks
    kinds = loop_clocks.pump_kinds(ctx)
    got = kinds and loop_clocks.prefill_seconds(kinds)
    whole = kinds and sum(k["wall_s"] for k in kinds.values())
    if not got or not whole:
        return None
    return 100.0 * got[0] / whole
