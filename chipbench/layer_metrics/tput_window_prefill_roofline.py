"""Share of the MXU peak the prefill attention kernels reach: the
family's ``prefill_attn_flops`` (q.k and p.v over the VISIBLE scores of a
prompt: the band in a sliding layer, the triangle in a full one, not the
padded square) over the published bf16 peak, divided by the ``splash_*``
kernels' device time.  Bucket by bucket: the operations are the mean over
the traffic table's prompts that land on a bucket, the time that of the
bucket's own program (``jit_serve_prefill_<bucket>``), both weighted by
the runs of it the trace holds."""


def read(ctx: dict):
    from chipbench import fine_scopes, host_spans
    from chipbench.generator import build_table
    from ray_lightning_tpu.serve.buckets import bucket_for, resolve_buckets
    price = getattr(ctx["adapter"], "prefill_attn_flops", None)
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
        ms = fine_scopes.kernel_ms_per_run(ctx, program, "splash")
        if runs and ms:
            flops += runs * sum(costs) / len(costs)
            seconds += runs * ms * 1e-3
    if not seconds:
        return None
    return 100.0 * flops / (ctx["peaks"]["tflops_bf16"] * 1e12) / seconds
