"""Grouped-product kernel calls per run of a prefill program: the
trace's own events named ``gmm*`` (megablox' ``pallas_call``, by the
kernel's name, as ``fine_scopes.kernel_ms_per_run`` picks a kernel's
events) that start inside a run of ``jit_serve_prefill*``, over those
runs.  An expert layer that takes a prompt's sorted rows in one pass
makes three a layer; one that cuts them into pieces makes three a piece
that ran, each trip of its loop an event of its own.  None without a
trace, and where no such kernel ran (another platform, another
family)."""

PROGRAM = "jit_serve_prefill"


def read(ctx: dict):
    from chipbench import host_spans
    cap = host_spans.capture(ctx)
    if cap is None:
        return None
    runs = calls = 0
    for tl in cap["devices"]:
        spans = [(s, s + d) for name, s, d in tl["modules"]
                 if host_spans.program_of(name).startswith(PROGRAM)]
        runs += len(spans)
        calls += sum(1 for name, s, _ in tl["ops"]
                     if name.startswith("gmm")
                     and any(lo <= s < hi for lo, hi in spans))
    return calls / runs if calls else None
