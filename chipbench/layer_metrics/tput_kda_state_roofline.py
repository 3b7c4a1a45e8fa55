"""Share of the bytes roofline the decode program's KDA state arithmetic
reaches: least time = the family's ``state_bytes`` of the slots occupied
(``Scheduler.stats()['batch_occupancy']`` times the slots: a layer's
float32 matrix a slot read once and written once, its convolution inputs
and stamp beside it) over the published HBM bandwidth, divided by the
decode program's device time under the finer scope ``kda_state``.  One
read and one write of the occupied slots only (the program moves every
slot's), so no implementation can pass 100 %."""


def read(ctx: dict):
    from chipbench import fine_scopes
    price = getattr(ctx["adapter"], "state_bytes", None)
    ms = fine_scopes.device_ms_per_run(ctx, "jit_serve_decode", "kda_state")
    occupied = ctx["scheduler"].get("batch_occupancy", 0.0) \
        * int(ctx["traffic"]["slots"])
    if not ms or price is None or not occupied or not ctx["peaks"]:
        return None
    least_s = price(ctx["model"], occupied) / (ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
