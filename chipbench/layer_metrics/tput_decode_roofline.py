"""Share of the bytes roofline one decode step reaches: least time = the
bytes a step cannot avoid moving (the family's ``decode_step_bytes``:
resident weights + the live cache rows of the occupied slots) over the
published HBM bandwidth, divided by the decode program's device time.
Bytes-bound."""


def read(ctx: dict):
    red = ctx["trace"]
    decode_ms = red["ms_by_kind"].get("decode") if red else None
    if not decode_ms or not ctx["peaks"]:
        return None
    least_s = ctx["adapter"].decode_step_bytes(
        ctx["model"], ctx["window"]["live_tokens_mean"]) / (
            ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (decode_ms * 1e-3)
