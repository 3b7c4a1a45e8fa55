"""Device milliseconds per run of a prefill program under the finer scope
``eva_attn``: every layer's window-by-window attention over the prompt
(exact causal rows of the window and the summaries of earlier windows in
one softmax)."""


def read(ctx: dict):
    from chipbench import fine_scopes
    return fine_scopes.device_ms_per_run(ctx, "jit_serve_prefill",
                                         "eva_attn")
