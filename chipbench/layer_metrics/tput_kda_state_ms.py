"""Device milliseconds per run of the decode program under the finer scope
``kda_state``: what of Kimi Delta Attention reads or writes a slot's
matrix state, every KDA layer: the decay, the delta update, the read-out
and the stamps (``ops/kda.py kda_step``).  A program without the scope (a
parent commit) reads nothing."""


def read(ctx: dict):
    from chipbench import fine_scopes
    return fine_scopes.device_ms_per_run(ctx, "jit_serve_decode",
                                         "kda_state") or None
