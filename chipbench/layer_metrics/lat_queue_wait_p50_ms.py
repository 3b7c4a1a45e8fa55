"""Median submit-to-admission wait of the requests that finished in the
window (ServeRequest.queue_wait_s)."""


def read(ctx: dict):
    import statistics
    waits = [r["queue_wait_s"] for r in ctx.get("requests", [])
             if r["queue_wait_s"] is not None]
    if not waits:
        return None
    return 1e3 * statistics.median(waits)
