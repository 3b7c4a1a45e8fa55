"""Share of the MXU peak the prefill programs' grouped products reach:
the family's ``expert_flops`` of the pairs a prefill computes here (the
mean over the prefills that ran, from the device's accumulator; a
bucket's padding routes nowhere and is not in it) over the published
bf16 peak, divided by the prefill programs' device time under the finer
scope ``moe_experts``."""


def read(ctx: dict):
    from chipbench import fine_scopes
    c = ctx["scheduler"].get("device_counters")
    price = getattr(ctx["adapter"], "expert_flops", None)
    ms = fine_scopes.device_ms_per_run(ctx, "jit_serve_prefill",
                                       "moe_experts")
    if not ms or not c or not c.get("prefill_runs") or price is None \
            or not ctx["peaks"]:
        return None
    least_s = price(ctx["model"],
                    c["prefill_moe_pairs"] / c["prefill_runs"]) / (
                        ctx["peaks"]["tflops_bf16"] * 1e12)
    return 100.0 * least_s / (ms * 1e-3)
