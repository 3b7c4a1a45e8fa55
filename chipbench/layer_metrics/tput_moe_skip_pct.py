"""Share of the decode runs' tokens that chose NO expert: 1 less
``decode_moe_pairs`` over the tokens routed, of the accumulator the
model's steps keep on the device (``Scheduler.stats()
['device_counters']``).  A decode run routes every slot in every expert
sublayer (the family's ``routed_sublayers``), one choice a token, so the
tokens routed are ``decode_runs x slots x sublayers`` and the pairs
computed are those that chose an expert.  A family whose router has no
such output (no ``routed_sublayers``) reads nothing."""


def read(ctx: dict):
    c = ctx["scheduler"].get("device_counters")
    sublayers = getattr(ctx["adapter"], "routed_sublayers", None)
    if not c or not c.get("decode_runs") or sublayers is None:
        return None
    routed = c["decode_runs"] * int(ctx["traffic"]["slots"]) \
        * sublayers(ctx["model"])
    return 100.0 * (1.0 - c["decode_moe_pairs"] / routed)
