"""Milliseconds per serve step that the worker call costs beyond the
worker's own work: the pump's ``call`` + ``wait`` seconds minus the
``serve_step`` seconds the worker reports in each step result
(``Scheduler.stats()["pump"]``)."""


def read(ctx: dict):
    pump = ctx["scheduler"].get("pump")
    if not pump or not pump["steps"]:
        return None
    return 1e3 * (pump["call_s"] + pump["wait_s"] - pump["worker_s"]) \
        / pump["steps"]
