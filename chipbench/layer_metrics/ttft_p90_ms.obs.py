"""The 90th percentile of time to first token, from when each request
was due, over the requests that finished in the window.  Observed, not
judged: at 0.8 of the knee the 24 slots fill now and then, and whether
they do in a given window depends on the order the seed gives the table
(270 ms on three seeds, 1.3-1.7 s on three others: PERF.md section 2)."""


def read(ctx: dict):
    return ctx["latencies"].get("ttft_p90_ms")
