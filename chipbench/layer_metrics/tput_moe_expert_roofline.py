"""Share of the bytes roofline the decode program's grouped products
reach: least time = the family's ``expert_bytes`` (the three matrices of
every expert HIT, summed over the layers, once, and a row in and out a
pair; means a decode run from the device's accumulator) over the
published HBM bandwidth, divided by the decode program's device time
under the finer scope ``moe_experts``.  Only hit experts are counted, so
a call that skips the others cannot pass 100 %."""


def read(ctx: dict):
    from chipbench import fine_scopes
    c = ctx["scheduler"].get("device_counters")
    price = getattr(ctx["adapter"], "expert_bytes", None)
    ms = fine_scopes.device_ms_per_run(ctx, "jit_serve_decode",
                                       "moe_experts")
    if not ms or not c or not c.get("decode_runs") or price is None \
            or not ctx["peaks"]:
        return None
    runs = c["decode_runs"]
    least_s = price(ctx["model"], c["decode_moe_experts_hit"] / runs,
                    c["decode_moe_pairs"] / runs) / (
                        ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
