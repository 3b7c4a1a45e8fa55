"""Device milliseconds per run of the decode program under the finer
scope ``eva_summary``: every layer's re-pooling of the chunk that holds
the current position (its rows read back from the cache, the pooled row
written)."""


def read(ctx: dict):
    from chipbench import fine_scopes
    return fine_scopes.device_ms_per_run(ctx, "jit_serve_decode",
                                         "eva_summary")
