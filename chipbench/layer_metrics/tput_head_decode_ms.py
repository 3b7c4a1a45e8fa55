"""Device milliseconds per run of the decode program in operations whose
own path is scoped ``lm_head``: the tied table read as the head (vocabulary
x width, once a run) with the arg-max the compiler folds into the
product's epilogue.  The float32 logits of every slot are never written
whole (the described compile of the decode program holds 27 MB of
temporaries, the logits would be 134 MB), which is why the adapter's
``head_bytes`` counts the table alone."""


def read(ctx: dict):
    from chipbench import host_spans
    cap = host_spans.capture(ctx)
    if cap is None:
        return None
    return host_spans.device_ms_per_run(
        cap, "jit_serve_decode", lambda op: op["scope"] == "lm_head") or None
