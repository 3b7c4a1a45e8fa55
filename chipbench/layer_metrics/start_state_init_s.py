"""Set-up seconds that make the state: ``init`` in ``Trainer.fit``
(parameters and optimizer state), ``weights`` + ``kv_init`` in the serve
worker (the program's kept set-up spans)."""


def read(ctx: dict):
    from chipbench import host_spans
    records = host_spans.kept()
    if records is None:
        return None
    return host_spans.setup_seconds(records, ("init", "weights", "kv_init"))
