"""Device milliseconds per decode program run in the worker's trace."""


def read(ctx: dict):
    if not ctx["trace"]:
        return None
    return ctx["trace"]["ms_by_kind"].get("decode")
