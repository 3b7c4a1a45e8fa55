"""Device milliseconds per run of the decode program in the latent decode
attention kernels (``mla_decode*`` by the kernels' name in the trace):
every layer's one query a slot against the rows of the latent cache as
they lie, the value a row's first 512 lanes."""


def read(ctx: dict):
    from chipbench import fine_scopes
    return fine_scopes.kernel_ms_per_run(ctx, "jit_serve_decode",
                                         "mla_decode")
