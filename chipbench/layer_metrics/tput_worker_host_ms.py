"""Milliseconds per traced serve step that the worker spends in
``serve_step`` with no operation on the device: the ``rlt/serve_step``
spans of the trace's host plane minus the device's busy time."""


def read(ctx: dict):
    from chipbench import host_spans, reduce
    cap = host_spans.capture(ctx)
    if cap is None:
        return None
    steps = [e for e in cap["host"] if e["name"] == "serve_step"]
    if not steps:
        return None
    spans = host_spans.host_intervals(cap, ("serve_step",))
    return 1e3 * reduce.measure(
        reduce.subtract(spans, host_spans.busy(cap))) / len(steps)
