"""Device milliseconds per run of the decode program under the finer scope
``mla_proj``: what of latent attention is not its kernel, every layer:
the down- and up-projections of the query, the latent and its norm, rotary
on the rotated parts, the absorbed query ``q_nope W_uk^T`` and the way
back through ``W_uv``.  (The output projection and the cache row's write
lie under ``attn`` / ``kv_cache``.)"""


def read(ctx: dict):
    from chipbench import fine_scopes
    return fine_scopes.device_ms_per_run(ctx, "jit_serve_decode", "mla_proj")
