"""Device milliseconds per run of a prefill program under the finer scope
``moe_route``: every expert layer's float32 router, sigmoid and top-k, the
sort of the prompt's token-expert pairs by expert, the gather of the rows
the grouped products take and the way from their output to the tokens'
float32 rows (weights, sum per token): what of a prompt's expert layers
is not a product.  ``tput_moe_route_ms`` reads the decode program under
the same scope."""


def read(ctx: dict):
    from chipbench import fine_scopes
    return fine_scopes.device_ms_per_run(ctx, "jit_serve_prefill",
                                         "moe_route")
