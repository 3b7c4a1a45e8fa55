"""Share of the serve steps whose decode the worker had queued on the
device before the plan for it arrived (``ahead_hits`` over ``steps`` of
``Scheduler.stats()["pump"]``: the worker reports hit or miss in each
step result and the pump sums them); None on a program that counts
none."""


def read(ctx: dict):
    pump = ctx["scheduler"].get("pump")
    if not pump or not pump.get("steps") or "ahead_hits" not in pump:
        return None
    return 100.0 * pump["ahead_hits"] / pump["steps"]
