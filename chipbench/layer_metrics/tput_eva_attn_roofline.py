"""Share of the bytes roofline the decode attention kernel reaches: least
time = the keys and values of the cache rows the occupied slots may see
(the family's ``decode_row_bytes`` of ``Scheduler.stats()['live_rows']``)
over the published HBM bandwidth, divided by the kernels' device time a
decode run (``tput_eva_attn_ms``).  Bytes-bound: one query a slot."""


def read(ctx: dict):
    from chipbench import fine_scopes
    rows = ctx["scheduler"].get("live_rows")
    price = getattr(ctx["adapter"], "decode_row_bytes", None)
    ms = fine_scopes.kernel_ms_per_run(ctx, "jit_serve_decode", "eva_decode")
    if not ms or not rows or price is None or not ctx["peaks"]:
        return None
    least_s = price(ctx["model"], rows) / (ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
