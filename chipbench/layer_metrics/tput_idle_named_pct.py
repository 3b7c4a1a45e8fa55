"""Share of the traced window's device idle time that a span explains:
it lies under a worker ``rlt/`` span (trace clock), or, the clock anchor
applied, under the pump's ``pump.plan`` / ``pump.apply`` / ``pump.call``
or under ``pump.wait`` (whose part outside the worker's ``serve_step``
is the RPC).  The rest is idle that tracing does not explain."""

PUMP = ("pump.plan", "pump.apply", "pump.call", "pump.wait")


def read(ctx: dict):
    from chipbench import host_spans, reduce
    cap = host_spans.capture(ctx)
    records = host_spans.kept()
    if cap is None or records is None or not cap["host"] \
            or cap["offset_s"] is None:
        return None
    gaps = host_spans.idle(cap)
    if not reduce.measure(gaps):
        return None
    named = reduce.union([
        *host_spans.host_intervals(cap, None),
        *host_spans.record_intervals(cap, records, PUMP)])
    return 100.0 * reduce.measure(host_spans.intersect(gaps, named)) \
        / reduce.measure(gaps)
