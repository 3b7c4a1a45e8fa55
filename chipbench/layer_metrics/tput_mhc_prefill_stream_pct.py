"""Share of the prefill programs' hyper-connection time that the streams'
least traffic explains: the family's ``stream_bytes`` (each sublayer reads
the valid tokens' streams twice and writes them once, in the type they
are kept in) over the published HBM bandwidth, divided by the device time
under ``mhc_mix`` + ``mhc_apply``.  Bucket by bucket: the tokens are the
mean over the traffic table's prompts that land on a bucket, the time
that of the bucket's own program, both weighted by the runs of it the
trace holds.  A fused sublayer moves no less, so it cannot pass 100 %;
what is missing from 100 is what such a kernel is worth."""

SCOPES = ("mhc_mix", "mhc_apply")


def read(ctx: dict):
    from chipbench import fine_scopes, host_spans
    from chipbench.generator import build_table
    from ray_lightning_tpu.serve.buckets import bucket_for, resolve_buckets
    price = getattr(ctx["adapter"], "stream_bytes", None)
    cap = host_spans.capture(ctx)
    if cap is None or price is None or not ctx["peaks"]:
        return None
    model, mix = ctx["model"], ctx["traffic"]
    ladder = resolve_buckets(mix.get("buckets"),
                             ctx["adapter"].context(model))
    by_bucket: dict = {}
    for prompt, _, _ in build_table(mix):
        by_bucket.setdefault(bucket_for(prompt, ladder), []).append(prompt)
    moved = seconds = 0.0
    for bucket, prompts in by_bucket.items():
        program = f"jit_serve_prefill_{bucket}"
        runs = host_spans.runs_of(cap, program)
        parts = [fine_scopes.device_ms_per_run(ctx, program, name)
                 for name in SCOPES]
        if runs and any(parts):
            moved += runs * price(model, sum(prompts) / len(prompts))
            seconds += runs * sum(p or 0.0 for p in parts) * 1e-3
    if not seconds:
        return None
    return 100.0 * moved / (ctx["peaks"]["hbm_gbps"] * 1e9) / seconds
