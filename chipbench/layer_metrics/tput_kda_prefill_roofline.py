"""Share of the MXU peak the prefill programs' chunkwise delta rule
reaches: the family's ``kda_prefill_flops`` (the chunkwise form's
operations at chunks of 64, counted from the algorithm) over the
published bf16 peak, divided by the prefill programs' device time under
the finer scope ``kda_state``.  Bucket by bucket, as
``tput_window_prefill_roofline``: the operations are the mean over the
traffic table's prompts that land on a bucket, the time that of the
bucket's own program, both weighted by the runs of it the trace holds.
The arithmetic is float32 at ``highest`` (six bf16 passes a product) and
the scan between chunks is sequential, so the share is small."""


def read(ctx: dict):
    from chipbench import fine_scopes, host_spans
    from chipbench.generator import build_table
    from ray_lightning_tpu.serve.buckets import bucket_for, resolve_buckets
    price = getattr(ctx["adapter"], "kda_prefill_flops", None)
    cap = host_spans.capture(ctx)
    if cap is None or price is None or not ctx["peaks"]:
        return None
    model, mix = ctx["model"], ctx["traffic"]
    ladder = resolve_buckets(mix.get("buckets"),
                             ctx["adapter"].context(model))
    by_bucket: dict = {}
    for prompt, _, _ in build_table(mix):
        by_bucket.setdefault(bucket_for(prompt, ladder), []).append(
            price(model, prompt))
    flops = seconds = 0.0
    for bucket, costs in by_bucket.items():
        program = f"jit_serve_prefill_{bucket}"
        runs = host_spans.runs_of(cap, program)
        ms = fine_scopes.device_ms_per_run(ctx, program, "kda_state")
        if runs and ms:
            flops += runs * sum(costs) / len(costs)
            seconds += runs * ms * 1e-3
    if not seconds:
        return None
    return 100.0 * flops / (ctx["peaks"]["tflops_bf16"] * 1e12) / seconds
