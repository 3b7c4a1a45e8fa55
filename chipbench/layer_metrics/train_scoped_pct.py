"""Share of the step program's device time in operations whose own path
lies under one of the fixed scopes (``host_spans.SCOPES``); the rest is
operations the model's named scopes do not reach, or that the compiler
made (a mover listed under an inherited scope is among the rest)."""


def read(ctx: dict):
    from chipbench import host_spans
    red = ctx["trace"]
    cap = host_spans.capture(ctx)
    if cap is None or not red["main_module"]:
        return None
    program = host_spans.program_of(red["main_module"])
    scoped = host_spans.device_ms_per_run(
        cap, program, lambda op: op["scope"] is not None)
    whole = host_spans.device_ms_per_run(cap, program, lambda op: True)
    if scoped is None or not whole:
        return None
    return 100.0 * scoped / whole
