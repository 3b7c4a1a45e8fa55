"""Programs compiled instead of read from the persistent cache; 0 on every
run after a checkout's first."""


def read(ctx: dict):
    return ctx["compile"]["misses"]
