"""Device milliseconds per run of the step program (the trace's dominant
module)."""


def read(ctx: dict):
    red = ctx["trace"]
    if not red or not red["main_module"]:
        return None
    from chipbench import reduce
    return reduce.module_ms_per_run(red, red["main_module"])
