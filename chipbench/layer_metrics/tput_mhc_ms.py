"""Device milliseconds per run of the decode program in the
hyper-connections: the operations whose own path lies under the finer
scopes ``mhc_mix`` (a sublayer's coefficients: the streams' norm, the
``n d x n (n + 2)`` product, sigmoids, the Sinkhorn iterations) and
``mhc_apply`` (``H_pre X``, ``H_res X + H_post^T y``), every sublayer."""

SCOPES = ("mhc_mix", "mhc_apply")


def read(ctx: dict):
    from chipbench import fine_scopes
    parts = [fine_scopes.device_ms_per_run(ctx, "jit_serve_decode", name)
             for name in SCOPES]
    if all(p is None for p in parts):
        return None
    return sum(p or 0.0 for p in parts)
