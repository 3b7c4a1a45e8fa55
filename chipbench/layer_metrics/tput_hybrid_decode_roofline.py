"""Share of the bytes roofline one decode run reaches in a family that
keeps a matrix state beside its rows: least time = the weights every step
reads (the family's ``weight_bytes``) plus the experts HIT and their
pairs' rows (``expert_bytes``, means a run from the device's accumulator)
plus the visible cache rows (``decode_row_bytes`` of ``Scheduler.stats()
['live_rows']``) plus the occupied slots' state read and written
(``state_bytes`` of ``batch_occupancy`` times the slots), each once, over
the published HBM bandwidth, divided by the decode program's device time.
``tput_cmd_decode_roofline`` with the state: a family without
``state_bytes`` reads nothing here."""


def read(ctx: dict):
    red = ctx["trace"]
    decode_ms = red["ms_by_kind"].get("decode") if red else None
    sched = ctx["scheduler"]
    rows, c = sched.get("live_rows"), sched.get("device_counters")
    adapter = ctx["adapter"]
    occupied = sched.get("batch_occupancy", 0.0) \
        * int(ctx["traffic"]["slots"])
    if not decode_ms or not rows or not occupied or not ctx["peaks"] \
            or not c or not c.get("decode_runs") \
            or not hasattr(adapter, "state_bytes"):
        return None
    runs = c["decode_runs"]
    least_s = (adapter.weight_bytes(ctx["model"])
               + adapter.expert_bytes(
                   ctx["model"], c["decode_moe_experts_hit"] / runs,
                   c["decode_moe_pairs"] / runs)
               + adapter.decode_row_bytes(ctx["model"], rows)
               + adapter.state_bytes(ctx["model"], occupied)) / (
                   ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (decode_ms * 1e-3)
