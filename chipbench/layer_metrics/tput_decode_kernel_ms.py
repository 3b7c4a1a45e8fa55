"""Device milliseconds per run of the decode program in the
``flash_decode`` Pallas kernels (by the kernels' name in the trace)."""


def read(ctx: dict):
    from chipbench import host_spans
    cap = host_spans.capture(ctx)
    if cap is None:
        return None
    return host_spans.device_ms_per_run(
        cap, "jit_serve_decode",
        lambda op: op["name"].startswith("flash_decode"))
