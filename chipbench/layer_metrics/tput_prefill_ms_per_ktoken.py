"""Pump milliseconds a thousand prompt tokens cost: over the steps whose
plan carried a prefill, their wall time less a decode-only step's mean a
step, over their prefills' prompt tokens (``Scheduler.stats()["pump"]
["kinds"]``, always on, every step the server ran): steady whatever
buckets a traced sample catches."""


def read(ctx: dict):
    from chipbench import loop_clocks
    kinds = loop_clocks.pump_kinds(ctx)
    got = kinds and loop_clocks.prefill_seconds(kinds)
    if not got or not got[1]:
        return None
    return 1e6 * got[0] / got[1]
