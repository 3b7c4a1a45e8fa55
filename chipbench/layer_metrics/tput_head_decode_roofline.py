"""Share of the bytes roofline the head of a decode run reaches: least
time = the family's ``head_bytes`` (the tied table's vocabulary x width
once in bf16; the logits are not counted, so a program that writes and
re-reads them reads lower and none can pass 100 %) over the published HBM
bandwidth, divided by the decode program's device time in operations
scoped ``lm_head`` or ``sample`` (the product and the arg-max over its
output, wherever the compiler put the reduction).  A family without
``head_bytes`` reads nothing."""


def read(ctx: dict):
    from chipbench import host_spans
    price = getattr(ctx["adapter"], "head_bytes", None)
    cap = host_spans.capture(ctx)
    if price is None or cap is None or not ctx["peaks"]:
        return None
    ms = host_spans.device_ms_per_run(
        cap, "jit_serve_decode",
        lambda op: op["scope"] in ("lm_head", "sample"))
    if not ms:
        return None
    least_s = price(ctx["model"]) / (ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
