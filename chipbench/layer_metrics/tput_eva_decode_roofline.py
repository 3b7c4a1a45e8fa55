"""Share of the bytes roofline one decode step reaches when its cache
bytes follow ROWS, not positions: least time = the weights once (the
family's ``weight_bytes``) plus the keys and values of the cache rows the
occupied slots may see (``decode_row_bytes`` of ``Scheduler.stats()
['live_rows']``) over the published HBM bandwidth, divided by the decode
program's device time."""


def read(ctx: dict):
    red = ctx["trace"]
    decode_ms = red["ms_by_kind"].get("decode") if red else None
    rows = ctx["scheduler"].get("live_rows")
    adapter = ctx["adapter"]
    if not decode_ms or not rows or not ctx["peaks"] \
            or not hasattr(adapter, "decode_row_bytes"):
        return None
    least_s = (adapter.weight_bytes(ctx["model"])
               + adapter.decode_row_bytes(ctx["model"], rows)) / (
                   ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (decode_ms * 1e-3)
