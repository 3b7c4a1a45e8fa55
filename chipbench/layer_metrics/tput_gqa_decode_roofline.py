"""Share of the bytes roofline the grouped decode attention kernel
reaches: least time = the keys and values of the cache rows the occupied
slots see, by layer kind (the family's ``decode_row_bytes`` of
``Scheduler.stats()['live_rows']``: a ring's rows in a sliding layer, a
row a position in a full one), over the published HBM bandwidth, divided
by the ``gqa_decode`` kernels' device time a decode run.  Visible rows
only are counted and whole blocks are read, so it cannot pass 100 %."""


def read(ctx: dict):
    from chipbench import fine_scopes
    rows = ctx["scheduler"].get("live_rows")
    price = getattr(ctx["adapter"], "decode_row_bytes", None)
    ms = fine_scopes.kernel_ms_per_run(ctx, "jit_serve_decode", "gqa_decode")
    if not ms or not rows or price is None or not ctx["peaks"]:
        return None
    least_s = price(ctx["model"], rows) / (ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
