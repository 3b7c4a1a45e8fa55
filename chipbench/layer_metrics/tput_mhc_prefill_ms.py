"""Device milliseconds per run of a prefill program in the
hyper-connections: the operations under the finer scopes ``mhc_mix`` and
``mhc_apply`` (``tput_mhc_ms`` reads the decode program under the same):
four float32 streams of every token of the bucket, read and written
around each of the ten sublayers."""

SCOPES = ("mhc_mix", "mhc_apply")


def read(ctx: dict):
    from chipbench import fine_scopes
    parts = [fine_scopes.device_ms_per_run(ctx, "jit_serve_prefill", name)
             for name in SCOPES]
    if all(p is None for p in parts):
        return None
    return sum(p or 0.0 for p in parts)
