"""Set-up that is not XLA compilation: worker start, weight init, warm-up
dispatches, the ramp."""


def read(ctx: dict):
    return ctx["setup_s"] - ctx["compile"]["backend_compile_s"]
