"""Device milliseconds per train step in the flash-attention Pallas
kernels, forward and backward (``flash_fwd``, ``flash_bwd_*`` by the
kernels' name in the trace)."""


def read(ctx: dict):
    from chipbench import host_spans
    red = ctx["trace"]
    cap = host_spans.capture(ctx)
    if cap is None or not red["main_module"]:
        return None
    return host_spans.device_ms_per_run(
        cap, host_spans.program_of(red["main_module"]),
        lambda op: op["name"].startswith(("flash_fwd", "flash_bwd")))
