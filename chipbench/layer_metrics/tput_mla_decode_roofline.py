"""Share of the bytes roofline the latent decode attention kernel
reaches: least time = the ONE latent row (the family's
``decode_row_bytes`` of ``Scheduler.stats()['live_rows']``: 1,152 B a
position a layer at the published widths, whatever lanes a row is padded
to in memory, so padding shows as a lower share) of every position the
occupied slots see, over the published HBM bandwidth, divided by the
``mla_decode`` kernels' device time a decode run.  Visible rows only are
counted and whole blocks are read, so it cannot pass 100 %."""


def read(ctx: dict):
    from chipbench import fine_scopes
    rows = ctx["scheduler"].get("live_rows")
    price = getattr(ctx["adapter"], "decode_row_bytes", None)
    ms = fine_scopes.kernel_ms_per_run(ctx, "jit_serve_decode", "mla_decode")
    if not ms or not rows or price is None or not ctx["peaks"]:
        return None
    least_s = price(ctx["model"], rows) / (ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
