"""Milliseconds per step of collective time with no compute under it (0 on
one chip)."""


def read(ctx: dict):
    red = ctx["trace"]
    if not red or not red["steps"]:
        return None
    return 1e3 * red["exposed_s"] / red["steps"]
