"""Host milliseconds per dispatch of the train step: the body of the
fit loop's ``step`` span (``source.run_one`` / ``run_chunk``), summed by
the program's always-on loop clock from the fit's first step's result to
its end, over the dispatches (``clocks.last("fit")``: ``seconds`` /
``n`` of ``dispatch``).  Where the host runs ahead of the device this is
where the runtime holds it back, so it reads near the step's device time,
not the cost of the call alone."""


def read(ctx: dict):
    from chipbench import loop_clocks
    snap = loop_clocks.fit_clock()
    if snap is None or not snap["n"].get("dispatch"):
        return None
    return 1e3 * snap["seconds"]["dispatch"] / snap["n"]["dispatch"]
