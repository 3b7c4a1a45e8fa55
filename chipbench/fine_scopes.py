"""Device time under a FINER scope than ``host_spans.SCOPES`` lists.

The program's scope table places every device operation under one of a
short fixed list of scopes, and ``host_spans.scoped_ops`` refuses a name
it does not know.  A family that wants a part of a layer apart (EvaByte:
the pooling of a chunk, ``eva_summary``, and the attention, ``eva_attn``)
keeps the table as it is and writes a second one beside it, under
``"fine"`` in ``op_scopes.json``: program -> operation -> finer name
(``ray_lightning_tpu/telemetry/scopes.py``).  This reads it.  A program
that writes none (a parent commit) makes every reader here return None,
and its metric is left out.
"""

from __future__ import annotations

import json
import os

from chipbench import host_spans


def tables(cap_path: str) -> "dict | None":
    """``{program: {operation: finer name}}`` from the ``op_scopes.json``
    beside the trace (or above it, up to the work directory)."""
    d = os.path.dirname(os.path.abspath(cap_path))
    while True:
        cand = os.path.join(d, host_spans.TABLE_FILE)
        if os.path.exists(cand):
            with open(cand) as f:
                return json.load(f).get("fine")
        up = os.path.dirname(d)
        if os.path.basename(d) == host_spans.WORK or up == d:
            return None
        d = up


def device_ms_per_run(ctx: dict, program_prefix: str,
                      name: str) -> "float | None":
    """Device milliseconds per run of the programs ``program_prefix*`` in
    the operations whose own path lies under the finer scope ``name``."""
    cap = host_spans.capture(ctx)
    if cap is None:
        return None
    fine = tables(host_spans.find_trace())
    if not fine:
        return None
    return host_spans.device_ms_per_run(
        cap, program_prefix,
        lambda op: fine.get(op["program"], {}).get(op["name"]) == name)


def kernel_ms_per_run(ctx: dict, program_prefix: str,
                      kernel: str) -> "float | None":
    """Device milliseconds per run of the programs ``program_prefix*`` in
    the operations named ``kernel*`` (a Pallas kernel's ``name=``); None
    without a trace, or where no such operation ran."""
    cap = host_spans.capture(ctx)
    if cap is None:
        return None
    return host_spans.device_ms_per_run(
        cap, program_prefix,
        lambda op: op["name"].startswith(kernel)) or None

