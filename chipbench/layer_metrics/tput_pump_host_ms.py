"""Driver milliseconds per serve step outside the worker call: the
pump's ``plan`` and ``apply`` and its own bookkeeping between two steps
(``Scheduler.stats()["pump"]``: always-on clock reads in
``Server._pump_step``, summed over every step the server ran)."""


def read(ctx: dict):
    pump = ctx["scheduler"].get("pump")
    if not pump or not pump["steps"]:
        return None
    return 1e3 * (pump["loop_s"] + pump["plan_s"] + pump["apply_s"]) \
        / pump["steps"]
