"""Device milliseconds per run of the decode program under the finer scope
``moe_route``: the float32 router, sigmoid and top-k, the sort of the
token-expert pairs by expert, the gather of their rows and the way back
(inverse permutation, weights, sum per token): what of an expert layer is
not a product."""


def read(ctx: dict):
    from chipbench import fine_scopes
    return fine_scopes.device_ms_per_run(ctx, "jit_serve_decode",
                                         "moe_route")
