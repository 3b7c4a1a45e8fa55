"""Seconds of the root set-up span (``fit`` entry to the end of the
first step; ``Server.start`` entry to return, the worker's spans merged
in) that lie under no leaf span: set-up nobody has named yet."""


def read(ctx: dict):
    from chipbench import host_spans
    records = host_spans.kept()
    if records is None:
        return None
    return host_spans.setup_unnamed_seconds(records)
