"""Token-expert pairs a held expert that was hit computes in one decode
run: ``decode_moe_pairs / decode_moe_experts_hit`` of the accumulator the
model's steps keep on the device (``Scheduler.stats()
['device_counters']``, read with the stats and never inside a step; a
decode the worker queued ahead and dropped still ran and still counts).
About 2 on this chip's share; the deployment's experts would see about 16
(eight chips' slots route to each)."""


def read(ctx: dict):
    c = ctx["scheduler"].get("device_counters")
    if not c or not c.get("decode_moe_experts_hit"):
        return None
    return c["decode_moe_pairs"] / c["decode_moe_experts_hit"]
