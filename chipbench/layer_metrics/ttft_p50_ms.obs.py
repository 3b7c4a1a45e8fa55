"""The median time to first token, from when each request was due, over
the requests that finished in the window.  Observed beside
``ttft_p90_ms.obs``, for the same reason not judged."""


def read(ctx: dict):
    return ctx["latencies"].get("ttft_p50_ms")
