"""Device milliseconds per run of a prefill program in the attention
kernels (jax's splash attention, ``splash_*`` by the kernels' name in the
trace): every layer's causal attention over the prompt, under the band
of the window in a sliding layer, with a K/V head shared by its group of
query heads."""


def read(ctx: dict):
    from chipbench import fine_scopes
    return fine_scopes.kernel_ms_per_run(ctx, "jit_serve_prefill", "splash")
