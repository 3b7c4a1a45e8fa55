"""Host milliseconds per train step that the fit loop waited for the
loader (the ``data_wait`` span's body, summed by the program's always-on
loop clock from the fit's first step's result to its end, over the steps
it ran: ``clocks.last("fit")``)."""


def read(ctx: dict):
    from chipbench import loop_clocks
    snap = loop_clocks.fit_clock()
    if snap is None or not snap["steps"]:
        return None
    return 1e3 * snap["seconds"]["data_wait"] / snap["steps"]
