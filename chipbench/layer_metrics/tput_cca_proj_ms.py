"""Device milliseconds per run of the decode program under the finer scope
``cca_proj``: compressed convolutional attention's projections, every
layer: ``W_q``, ``W_k``, ``W_v1``, ``W_v2`` in, ``W_o`` out, and the
partial rotary on queries and keys.  A program without the scope (a parent
commit) reads nothing."""


def read(ctx: dict):
    from chipbench import fine_scopes
    return fine_scopes.device_ms_per_run(ctx, "jit_serve_decode",
                                         "cca_proj") or None
