"""Device milliseconds per run of the decode program in the ``eva_decode``
Pallas kernels (by the kernels' name in the trace): one query a slot
against the exact rows and the summary rows it may see."""


def read(ctx: dict):
    from chipbench import fine_scopes
    return fine_scopes.kernel_ms_per_run(ctx, "jit_serve_decode",
                                         "eva_decode")
