"""Which part of the model a device operation belongs to.

A profiler trace names device operations ``fusion.2031``, ``copy.795``:
a TPU trace's operation events carry no scope (read on the v5e, PR 24:
their stats are ``device_offset_ps``, ``device_duration_ps`` and a time
scale).  The compiled program's own text does: every instruction and
fusion has ``metadata={op_name="jit(step_fn)/.../h3/attn/kv_cache/
scatter"}``, the ``jax.named_scope`` / flax module path it was traced
under.  ``tables()`` reads, when asked and not before, the text of
every executable live in this process (the ones that loaded and ran,
whatever tree compiled them into the cache) into a table from operation
name to scope per program; each profiler window the program captures
gets them beside it (``op_scopes.json``, telemetry/tracing.py
``WorkerProfiler``, utils/profiling.py).  No window, no parse.  An
executable lives as long as its jitted function does, which is nobody's
contract with a reader: whoever had a session open over its programs
calls ``remember()`` before it lets go of them, and ``tables()`` gives
what was remembered under what is live.

The scopes are a short fixed list.  ``models/gpt.py``, ``core/steps.py``,
``ops/attention.py`` and ``ops/losses.py`` enter them with
``jax.named_scope`` (or rely on a flax module of that name: ``attn``,
``mlp``).  An operation's scope is the INNERMOST listed name on its
path, so the cache write inside a block's attention is ``kv_cache``,
not ``attn``.  A fusion carries the path of its root instruction: that
is the compiler's choice, and the table's.

An instruction the compiler made itself has no path.  If it only moves
a value (a layout copy, a bitcast, a tuple element) the table gives it
the scope of the value it moves, through chains of such movers, MARKED
as inherited: ``"kv_cache*"``.  That is a reading aid, not a placement:
the per-layer relayout copies of the K/V cache in the decode program
(72 of them, ~30 ms of its 110 on the v5e, PR 24) are the compiler's,
their producer is one of two neighbours, and a reader that adds up a
scope's time leaves them out or reports them apart.  ``None`` means no
listed name was found either way: unscoped, and counted as such.

No jax import: worker_main touches this package before jax exists.
"""

from __future__ import annotations

import json
import os
import re
import sys
from typing import Optional

#: every device operation of the train step and of the serve programs
#: should fall under one of these
SCOPES = ("embed", "attn", "mlp", "ln", "lm_head", "loss", "optimizer",
          "kv_cache", "sample")

#: finer names inside a scope, for the parts of a layer that a reader
#: wants apart (ops/eva_attention.py: a chunk's pooling, the attention;
#: ops/moe.py: an expert layer's routing, products and shared experts;
#: models/xing.py: the hyper-connections and latent attention's
#: projections).
#: They are NOT scopes of the table above: readers of that table know
#: its short list and refuse another name, so a second table, ``"fine"``
#: in the file, places the operations that lie under one of these
FINE_SCOPES = ("eva_summary", "eva_attn",
               # ops/moe.py dropless_experts, models/command.py: what is
               # not a product, the grouped products, the shared experts
               "moe_route", "moe_experts", "moe_shared",
               # models/xing.py: a sublayer's hyper-connection
               # coefficients (the streams' norm, the n d x n (n + 2)
               # product, sigmoids, Sinkhorn), the streams mixed with
               # them, and what of latent attention is not its kernel
               "mhc_mix", "mhc_apply", "mla_proj",
               # models/zaya.py: what of compressed convolutional
               # attention is neither a projection nor the kernel (the q-k
               # mean, both convolutions, the norm and temperature, the
               # shifted value, the tail's read and write), and its
               # projections with rotary
               "cca_mix", "cca_proj",
               # models/kimi_linear.py: what of Kimi Delta Attention reads
               # or writes a slot's matrix state (the decay, the delta
               # update and read-out of a step; a prompt's chunkwise scan
               # and the state's write), and everything else of it
               # (projections, short convolutions and their ring, norms,
               # gates)
               "kda_state", "kda_proj")

#: the file of tables written beside a captured trace
TABLE_FILE = "op_scopes.json"
#: suffix of a scope that a pathless mover took from the value it moves
INHERITED = "*"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ", re.M)
#: instructions that only move a value: made by the compiler without a
#: path (a layout copy, a tuple element), they are listed under the
#: scope of the value they move, marked ``INHERITED``
_MOVES = re.compile(r" (?:copy|copy-start|copy-done|bitcast|transpose|"
                    r"reshape|get-tuple-element)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)


def scope_of(op_name: str, names=SCOPES) -> Optional[str]:
    """The innermost listed scope on an ``op_name`` path such as
    ``jit(step_fn)/transpose(jvp(GPT))/h0/attn/qkv/dot_general``."""
    for word in reversed(_WORD.findall(op_name)):
        if word in names:
            return word
    return None


def table_from_text(hlo_text: str, names=SCOPES) -> "tuple[str, dict]":
    """``(module name, {instruction name: scope, scope + "*" or None})``
    from a compiled program's text.  Every instruction of every
    computation is listed, with or without metadata, so that a reader
    can tell "the compiler gave this one no path" (None, or an inherited
    scope) from "this operation is not of this program" (absent)."""
    m = _MODULE.search(hlo_text)
    module = m.group(1) if m else ""
    table: dict = {}
    moved_from: dict = {}     # pathless mover -> the value it moves
    for line in hlo_text.splitlines():
        inst = _INSTRUCTION.match(line)
        if inst is None:
            continue
        name = inst.group(1)
        path = _OP_NAME.search(line)
        table[name] = scope_of(path.group(1), names) if path else None
        if path is None:
            mover = _MOVES.search(line, inst.end())
            # operands come first, and no type holds a ``%``
            source = mover and _OPERAND.search(line, mover.end())
            if source:
                moved_from[name] = source.group(1)
    inherited = {}
    for name in moved_from:
        # a chain of movers ends at an instruction with a path (or at a
        # parameter, which has none: unscoped)
        source, hops = moved_from[name], 0
        while source in moved_from and hops < 16:
            source, hops = moved_from[source], hops + 1
        if table.get(source) is not None:
            inherited[name] = table[source] + INHERITED
    table.update(inherited)
    return module, table


def _live(names) -> "dict[str, dict[str, Optional[str]]]":
    """``{program: table}`` of every executable live in this process,
    read now.  Two live programs of one name (a step retraced for
    another shape) share a table; an operation name they place
    differently is left out, so a reader that meets it fails."""
    jax = sys.modules.get("jax")
    out: dict = {}
    clash: set = set()
    try:
        live = jax.devices()[0].client.live_executables()
    except Exception:   # noqa: BLE001 - no jax, no backend, no such call
        return out
    for exe in live:
        try:
            texts = [m.to_string() for m in exe.hlo_modules()]
        except Exception:   # noqa: BLE001 - a backend without text
            continue
        for text in texts:
            module, table = table_from_text(text, names)
            seen = out.setdefault(module, table)
            clash.update((module, op) for op, scope in table.items()
                         if seen.setdefault(op, scope) != scope)
    for module, op in clash:
        del out[module][op]
    return out


#: what ``remember()`` read, per list of names and program: a program's
#: table is replaced whole by a later reading, never added to, so the
#: store is bounded by the number of program names
_remembered: "dict[tuple, dict[str, dict]]" = {}


def remember() -> None:
    """Read the live executables now, under both lists of names, and
    keep their tables: an executable goes when the last reference to
    its jitted function does (a trainer let go of, an engine rebuilt),
    and a reader that asks afterwards still finds what ran.  Whoever
    had a profiler session open over its programs calls this before it
    lets go of them (``Trainer`` at the end of a stage; ``write_tables``
    for a window the program captured itself): no session, no parse."""
    for names in (SCOPES, FINE_SCOPES):
        _remembered.setdefault(tuple(names), {}).update(_live(names))


def tables(names=SCOPES) -> "dict[str, dict[str, Optional[str]]]":
    """``{program: table}`` (``names``: the list a path is searched for,
    ``SCOPES`` or ``FINE_SCOPES``): the tables ``remember()`` kept,
    overlaid by those of every executable live in this process, read
    now.  A live program's table wins whole over a remembered one of
    its name.  Never raises: the tables are evidence, not a
    dependency."""
    return {**_remembered.get(tuple(names), {}), **_live(names)}


def write_tables(trace_dir: str) -> Optional[str]:
    """Write this process's tables beside a trace it captured, and keep
    them (``remember()``: one reading serves both, and what was just
    remembered IS ``tables()``)."""
    remember()
    snap = _remembered[tuple(SCOPES)]
    if not snap:
        return None
    path = os.path.join(trace_dir, TABLE_FILE)
    fine = {program: placed
            for program, table in _remembered[tuple(FINE_SCOPES)].items()
            if (placed := {op: s for op, s in table.items() if s})}
    with open(path, "w") as f:
        json.dump({"scopes": list(SCOPES), "programs": snap,
                   "fine_scopes": list(FINE_SCOPES), "fine": fine}, f)
    return path


__all__ = ["SCOPES", "FINE_SCOPES", "TABLE_FILE", "INHERITED", "scope_of",
           "table_from_text", "remember", "tables", "write_tables"]
