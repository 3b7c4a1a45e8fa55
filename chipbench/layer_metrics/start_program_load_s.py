"""Set-up seconds that make the programs runnable: ``compile`` +
``first_step`` in ``Trainer.fit`` (trace, then the wait for the AOT
thread, the first dispatch's load and the first run), ``build`` +
``warmup`` in the serve worker; backend compile seconds are inside
these (the program's kept set-up spans)."""


def read(ctx: dict):
    from chipbench import host_spans
    records = host_spans.kept()
    if records is None:
        return None
    return host_spans.setup_seconds(
        records, ("compile", "first_step", "build", "warmup"))
