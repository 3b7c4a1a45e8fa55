"""Device milliseconds per prefill under the finer scope ``kda_state``:
the chunkwise delta rule of every KDA layer (the decayed products inside
the chunks, the triangular systems, the scan that carries the state
between chunks) and the state's write at the slot, the mean over the
prefill programs' runs in the trace.  A program without the scope (a
parent commit) reads nothing."""


def read(ctx: dict):
    from chipbench import fine_scopes
    return fine_scopes.device_ms_per_run(ctx, "jit_serve_prefill",
                                         "kda_state") or None
