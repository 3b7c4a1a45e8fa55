"""Seconds XLA spent compiling in the process that holds the chip
(jax.monitoring, through the program's compile_cache record)."""


def read(ctx: dict):
    return ctx["compile"]["backend_compile_s"]
