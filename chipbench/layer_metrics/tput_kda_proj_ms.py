"""Device milliseconds per run of the decode program under the finer scope
``kda_proj``: what of Kimi Delta Attention is not its state, every KDA
layer: the projections, the three short convolutions with the ring's read
and write, both L2 norms, the decay's and beta's float32 products, the
output's norm and gate.  A program without the scope (a parent commit)
reads nothing."""


def read(ctx: dict):
    from chipbench import fine_scopes
    return fine_scopes.device_ms_per_run(ctx, "jit_serve_decode",
                                         "kda_proj") or None
