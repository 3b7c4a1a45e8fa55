"""Device milliseconds per run of the decode program in operations
whose own path is scoped ``kv_cache``: every layer's write into, slice
out of and stack back into the K/V cache (the trace's operations placed
by the program's scope table).  The relayout copies of the cache that
the compiler makes have no path: the table lists them as ``kv_cache*``,
``host_spans`` gives that as ``inherited``, and they are not in here."""


def read(ctx: dict):
    from chipbench import host_spans
    cap = host_spans.capture(ctx)
    if cap is None:
        return None
    return host_spans.device_ms_per_run(
        cap, "jit_serve_decode", lambda op: op["scope"] == "kv_cache")
