"""Device milliseconds per train step in operations scoped ``lm_head``
or ``loss``, forward and backward (the trace's operations placed by the
program's scope table)."""


def read(ctx: dict):
    from chipbench import host_spans
    red = ctx["trace"]
    cap = host_spans.capture(ctx)
    if cap is None or not red["main_module"]:
        return None
    return host_spans.device_ms_per_run(
        cap, host_spans.program_of(red["main_module"]),
        lambda op: op["scope"] in ("lm_head", "loss"))
