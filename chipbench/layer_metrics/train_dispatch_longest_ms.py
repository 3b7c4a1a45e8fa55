"""Milliseconds of the longest single dispatch of the train step after
the fit's first (``longest["dispatch"]`` of ``clocks.last("fit")``): a
sound run reads about one step's time, a run whose dispatch the runtime
held reads the stall (ROADMAP S6: 1,840 ms once).  The snapshot also
names the step and its wall-clock ``ts``."""


def read(ctx: dict):
    from chipbench import loop_clocks
    snap = loop_clocks.fit_clock()
    longest = snap and snap["longest"].get("dispatch")
    if not longest:
        return None
    return 1e3 * longest["seconds"]
