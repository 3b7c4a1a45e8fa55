"""Share of the bytes roofline one decode step reaches when its bytes
follow what it routes and the ROWS it reads: least time = the weights
every step reads (the family's ``weight_bytes``: attention, shared
experts, routers, the held rows of the tied table) plus the experts HIT
and their pairs' rows (``expert_bytes``, means a run from the device's
accumulator) plus the keys and values of the visible cache rows
(``decode_row_bytes`` of ``Scheduler.stats()['live_rows']``), each once,
over the published HBM bandwidth, divided by the decode program's device
time."""


def read(ctx: dict):
    red = ctx["trace"]
    decode_ms = red["ms_by_kind"].get("decode") if red else None
    rows = ctx["scheduler"].get("live_rows")
    c = ctx["scheduler"].get("device_counters")
    adapter = ctx["adapter"]
    if not decode_ms or not rows or not ctx["peaks"] or not c \
            or not c.get("decode_runs") \
            or not hasattr(adapter, "expert_bytes"):
        return None
    runs = c["decode_runs"]
    least_s = (adapter.weight_bytes(ctx["model"])
               + adapter.expert_bytes(
                   ctx["model"], c["decode_moe_experts_hit"] / runs,
                   c["decode_moe_pairs"] / runs)
               + adapter.decode_row_bytes(ctx["model"], rows)) / (
                   ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (decode_ms * 1e-3)
