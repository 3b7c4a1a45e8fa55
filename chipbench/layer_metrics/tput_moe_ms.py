"""Device milliseconds per run of the decode program in the expert layers:
the operations whose own path lies under the finer scopes ``moe_route``
(router, top-k, sort, gather, the way back), ``moe_experts`` (the grouped
products over the held experts) and ``moe_shared`` (the shared experts'
gated product), every layer."""

SCOPES = ("moe_route", "moe_experts", "moe_shared")


def read(ctx: dict):
    from chipbench import fine_scopes
    parts = [fine_scopes.device_ms_per_run(ctx, "jit_serve_decode", name)
             for name in SCOPES]
    if all(p is None for p in parts):
        return None
    return sum(p or 0.0 for p in parts)
