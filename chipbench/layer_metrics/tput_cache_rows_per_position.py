"""Cache rows a decode step reads per context position live
(``Scheduler.stats()``: ``live_rows / live_positions``, both means over
decode steps summed over the occupied slots): 1 for a cache that holds a
row per position, what the window-and-summary cache saves otherwise."""


def read(ctx: dict):
    sched = ctx["scheduler"]
    if not sched.get("live_positions"):
        return None
    return sched["live_rows"] / sched["live_positions"]
