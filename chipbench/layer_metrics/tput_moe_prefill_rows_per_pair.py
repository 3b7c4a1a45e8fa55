"""Rows a prefill pushes through the grouped products for each
token-expert pair it computes: ``prefill_moe_rows / prefill_moe_pairs``
of the accumulator the model's steps keep on the device
(``Scheduler.stats()['device_counters']``, read with the stats and never
inside a step).  A layer that moves every one of a prompt's ``T x k``
sorted rows reads the published experts over the held ones (8 on this
chip's 16 of 128); one that moves the pairs in pieces reads the pieces
that ran times a piece's rows over the pairs.  Nothing on a commit whose
accumulator does not count the rows."""


def read(ctx: dict):
    c = ctx["scheduler"].get("device_counters")
    if not c or not c.get("prefill_moe_pairs") \
            or c.get("prefill_moe_rows") is None:
        return None
    return c["prefill_moe_rows"] / c["prefill_moe_pairs"]
