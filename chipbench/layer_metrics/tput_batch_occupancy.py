"""Mean slots in use per decode step (Scheduler.stats batch_occupancy times
the slot count)."""


def read(ctx: dict):
    return ctx["scheduler"]["batch_occupancy"] * int(ctx["traffic"]["slots"])
