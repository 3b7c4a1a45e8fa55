"""Share of the bytes roofline one decode step reaches: least time = (bf16
weights + live K/V rows of the occupied slots, chipbench/bytes.py) over
the published HBM bandwidth, divided by the decode program's device
time.  Bytes-bound."""


def read(ctx: dict):
    from chipbench import bytes as traffic_bytes
    return traffic_bytes.decode_roofline_pct(ctx)
