"""Milliseconds per step in which no operation ran on the device (traced
steps)."""


def read(ctx: dict):
    red = ctx["trace"]
    if not red or not red["steps"]:
        return None
    return 1e3 * red["idle_s"] / red["steps"]
