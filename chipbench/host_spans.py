"""The program's own spans beside the device's operations: what the
per-layer readers added in PR 24 read.

Three sources, all made by the program under test and none by this
directory:

- **the host plane of the run's profiler trace**: every span site of the
  program enters a ``jax.profiler.TraceAnnotation`` named ``rlt/<name>``
  (``ray_lightning_tpu/telemetry/spans.py``), so a trace holds the spans
  of the captured process on the clock of its device operations;
- **the clock anchor**: ``rlt/clock`` carries the captured process's
  ``time.time_ns()`` as its ``wall_ns`` stat; ``offset_s = wall_ns / 1e9 -
  start_s`` maps trace time to the wall clock of span records' ``ts``, so
  the pump's records (made in THIS process, which no trace captures)
  can be laid beside the worker's spans and the device's operations;
- **the keep windows** of this process (``spans.kept(name)``): the
  set-up spans of ``Trainer.fit`` / ``Server.start`` (the worker's own
  merged in by the program) and the pump's spans of the traced steps;
- and, for the device plane, **the scope table**: a TPU trace names
  operations ``fusion.N`` and carries no scope, so the program writes
  ``op_scopes.json`` beside each trace it captures (operation name ->
  one of ``SCOPES``, such a scope marked ``*``, or null, per program)
  and reads the same tables in process (``telemetry/scopes.py``) for a
  trace this directory captured itself (the train cell).  A scope
  marked ``*`` was not on the operation's own path: the compiler made
  the operation to move a value (a layout copy, a tuple element) and
  the table names the scope of what it moves.  Here that is
  ``op["inherited"]``, never ``op["scope"]``: a reader that adds up a
  scope's time counts what the program's own paths place, and the
  movers stay with the unscoped.

Use from a reader (``layer_metrics/<name>.py``)::

    from chipbench import host_spans
    cap = host_spans.capture(ctx)      # None: this run has no trace
    ops = host_spans.scoped_ops(cap)   # None: the program left no table
    recs = host_spans.kept()           # None: the program keeps none

``capture`` finds the run's own trace (``run.py`` wipes
``.chipbench_work/<cell>/`` at the start of every run, so the newest
``*.xplane.pb`` under ``.chipbench_work/`` written after this process
began is this run's; none, or only older ones, is an error) and parses
it once per process.  A parent commit that has no such spans, counters
or tables makes every function here return None, and the reader leaves
its metric out; what IS there and cannot be read is an error: a stale
trace, an operation that its program's table does not list.

The interval algebra is ``reduce.py``'s, imported.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import time

from chipbench import reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREFIX = "rlt/"
ANCHOR = PREFIX + "clock"
SCOPES = ("embed", "attn", "mlp", "ln", "lm_head", "loss", "optimizer",
          "kv_cache", "sample")
TABLE_FILE = "op_scopes.json"
WORK = ".chipbench_work"
#: the program's keep windows a reader may ask for
WINDOWS = ("fit_setup", "server_start", "pump")

_cache: dict = {}


# -- finding and loading the run's trace ---------------------------------------

def process_started_at() -> float:
    """Wall-clock time at which this run's ``run.py`` was loaded: as the
    process's ``__main__`` under ``python3 -m chipbench.run`` (importing
    ``chipbench.run`` there would load a second copy, whose clock starts
    at that import), as ``chipbench.run`` under a test runner."""
    import sys
    t = getattr(sys.modules.get("__main__"), "T_PROCESS", None)
    if t is None:
        from chipbench import run
        t = run.T_PROCESS
    return time.time() - (time.monotonic() - t)


def find_trace(root: str = ROOT, since: "float | None" = None) -> str:
    paths = glob.glob(os.path.join(root, WORK, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {root}/{WORK}")
    newest = max(paths, key=os.path.getmtime)
    since = process_started_at() if since is None else since
    if os.path.getmtime(newest) < since:
        raise RuntimeError(
            f"stale trace: the newest one, {newest}, was written before "
            f"this process began; this run captured none")
    return newest


def _host_events_xplane(path: str) -> list[dict]:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append({"name": e.name[len(PREFIX):],
                                "start_s": e.start_ns * 1e-9,
                                "dur_s": e.duration_ns * 1e-9,
                                "thread": line.name,
                                "stats": dict(e.stats)})
    return out


def _host_events_chrome(path: str) -> list[dict]:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    hosts = {e["pid"] for e in events if e.get("ph") == "M"
             and e.get("name") == "process_name"
             and e["args"]["name"].startswith("/host:")}
    return [{"name": e["name"][len(PREFIX):], "start_s": e["ts"] * 1e-6,
             "dur_s": e.get("dur", 0) * 1e-6, "thread": str(e.get("tid")),
             "stats": dict(e.get("args", {}))}
            for e in events if e.get("ph") == "X" and e["pid"] in hosts
            and e["name"].startswith(PREFIX)]


def load(path: str) -> dict:
    """``{"path", "host": [events], "offset_s", "devices": [timelines],
    "tables": {program: {op: scope}} or None}``.  ``devices`` are
    ``reduce``'s timelines; ``offset_s`` is None without an anchor."""
    if path.endswith(".xplane.pb"):
        host, devices = _host_events_xplane(path), reduce.load_xplane(path)
    else:
        host, devices = _host_events_chrome(path), reduce.load_chrome(path)
    anchors = [e for e in host if PREFIX + e["name"] == ANCHOR]
    offset = None
    if anchors:
        # the program emits another when it was held up while making one
        a = max(anchors, key=lambda e: e["start_s"])
        offset = float(a["stats"]["wall_ns"]) * 1e-9 - a["start_s"]
    return {"path": path, "offset_s": offset, "devices": devices,
            "host": [e for e in host if PREFIX + e["name"] != ANCHOR],
            "tables": _tables_beside(path)}


def _tables_beside(path: str) -> "dict | None":
    """``op_scopes.json`` in the trace's directory or one above it, up
    to the work directory; else the program's tables in this process."""
    d = os.path.dirname(os.path.abspath(path))
    while True:
        cand = os.path.join(d, TABLE_FILE)
        if os.path.exists(cand):
            with open(cand) as f:
                return json.load(f)["programs"]
        up = os.path.dirname(d)
        if os.path.basename(d) == WORK or up == d:
            break
        d = up
    try:
        from ray_lightning_tpu.telemetry import scopes
    except ImportError:      # a parent commit: the program keeps none
        return None
    return scopes.tables() or None


def capture(ctx: dict) -> "dict | None":
    """This run's trace, loaded once per process; None when the run was
    not traced (``ctx["trace"]`` is None)."""
    if not ctx.get("trace"):
        return None
    path = find_trace()
    if _cache.get("path") != path:
        _cache.clear()
        _cache.update(path=path, capture=load(path))
    return _cache["capture"]


# -- device operations with their program and scope ----------------------------

def program_of(module_name: str) -> str:
    """``jit_serve_decode(4675318136194592487)`` -> ``jit_serve_decode``."""
    return module_name.split("(", 1)[0]


def scoped_ops(cap: dict) -> "list[dict] | None":
    """Every device operation as ``{"name", "program", "run", "scope",
    "inherited", "start_s", "dur_s"}``: ``run`` numbers the executions
    of programs in time order per device, ``scope`` is one of ``SCOPES``
    or None (unscoped), ``inherited`` the scope of the value a
    compiler-made mover moves (module docstring) or None.  None when
    the program left no table at all.  An operation inside a program
    run whose table does not list it cannot be placed: that is an
    error, never a guess."""
    tables = cap["tables"]
    if not tables:
        return None
    if "scoped_ops" in cap:       # several readers ask: place them once
        return cap["scoped_ops"]
    out = []
    for tl in cap["devices"]:
        mods = sorted(tl["modules"], key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, s, d in tl["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][1] + mods[i][2] + 1e-9:
                continue     # outside every program run: nothing to place
            program = program_of(mods[i][0])
            table = tables.get(program)
            scope = inherited = None
            if table is None:
                pass                  # a program the tables do not cover
            elif name not in table:
                raise ValueError(
                    f"operation {name!r} of {program} is in the trace "
                    f"but not in the program's scope table: it cannot "
                    f"be placed")
            else:
                scope = table[name]
                if scope is not None and scope.endswith("*"):
                    scope, inherited = None, scope[:-1]
                if (scope or inherited) not in SCOPES + (None,):
                    raise ValueError(f"unknown scope {table[name]!r} for "
                                     f"{name!r} of {program}")
            out.append({"name": name, "program": program, "run": i,
                        "covered": table is not None, "scope": scope,
                        "inherited": inherited,
                        "start_s": s, "dur_s": d})
    cap["scoped_ops"] = out
    return out


def runs_of(cap: dict, prefix: str) -> int:
    """Executions of the programs whose name starts with ``prefix``,
    averaged over the devices in the trace."""
    n = sum(1 for tl in cap["devices"] for m in tl["modules"]
            if program_of(m[0]).startswith(prefix))
    return n / max(1, len(cap["devices"]))


def device_ms_per_run(cap: dict, program_prefix: str, keep) -> "float | None":
    """Device milliseconds per run of the programs named
    ``program_prefix*`` in the operations ``keep(op)`` selects; None
    when no such program ran or it has no table."""
    ops = scoped_ops(cap)
    runs = runs_of(cap, program_prefix)
    if ops is None or not runs:
        return None
    mine = [o for o in ops if o["program"].startswith(program_prefix)]
    if not mine or not all(o["covered"] for o in mine):
        return None
    n = max(1, len(cap["devices"]))
    return 1e3 * sum(o["dur_s"] for o in mine if keep(o)) / n / runs


def busy(cap: dict) -> list:
    """Merged intervals in which the first device ran an operation."""
    return reduce.union((s, s + d) for _, s, d in cap["devices"][0]["ops"])


def idle(cap: dict) -> list:
    b = busy(cap)
    return reduce.subtract([[b[0][0], b[-1][1]]], b) if b else []


def intersect(a: list, b: list) -> list:
    """a and b, both merged: a minus (a minus b)."""
    return reduce.subtract(a, reduce.subtract(a, b))


# -- the program's records ------------------------------------------------------

def kept() -> "list[dict] | None":
    """The span records of the program's keep windows in this process
    (set-up, and the pump's spans of the traced steps); None on a
    commit whose program keeps none."""
    try:
        from ray_lightning_tpu.telemetry import spans
        return [r for name in WINDOWS for r in spans.kept(name)]
    except (ImportError, AttributeError):
        return None


def host_intervals(cap: dict, names) -> list:
    """Merged trace-clock intervals of the captured process's spans
    with these names (all ``rlt/`` spans when ``names`` is None)."""
    return reduce.union(
        (e["start_s"], e["start_s"] + e["dur_s"]) for e in cap["host"]
        if names is None or e["name"] in names)


def record_intervals(cap: dict, records: list, names) -> list:
    """Merged intervals of span records (wall clock) with these names,
    moved onto the trace's clock by the anchor."""
    off = cap["offset_s"]
    if off is None:
        raise ValueError(f"no {ANCHOR} anchor in {cap['path']}")
    return reduce.union((r["ts"] - off, r["ts"] - off + r["dur"])
                        for r in records if r["name"] in names)


def setup_tree(records: list, root_names=("fit_setup", "server_start")):
    """``(root, descendants)`` of the newest set-up root among the
    records.  Spans of another thread that overlap the main thread's time
    (attr ``thread``) and everything under them are left out."""
    roots = [r for r in records if r["name"] in root_names]
    if not roots:
        return None, []
    root = roots[-1]
    children: dict = {}
    for r in records:
        children.setdefault(r.get("parent"), []).append(r)
    out, todo = [], [root]
    while todo:
        for c in children.get(todo.pop()["id"], []):
            if "thread" in (c.get("attrs") or {}):
                continue
            out.append(c)
            todo.append(c)
    return root, out


def setup_seconds(records: list, names) -> "float | None":
    """Seconds of the set-up root's descendants with these names
    (merged: nested spans of one name count once)."""
    root, desc = setup_tree(records)
    if root is None:
        return None
    return reduce.measure(reduce.union(
        (r["ts"], r["ts"] + r["dur"]) for r in desc if r["name"] in names))


def setup_unnamed_seconds(records: list) -> "float | None":
    """The root's seconds that lie under no leaf span: a span that has
    children counts through them, so its own self time is unnamed too."""
    root, desc = setup_tree(records)
    if root is None:
        return None
    parents = {r.get("parent") for r in desc}
    leaves = reduce.union((r["ts"], r["ts"] + r["dur"]) for r in desc
                          if r["id"] not in parents)
    whole = [[root["ts"], root["ts"] + root["dur"]]]
    return reduce.measure(reduce.subtract(whole, leaves))


# -- a synthetic trace for tests --------------------------------------------------

def write_chrome_trace(path: str, devices: list[dict],
                       host_events: list[tuple],
                       anchor: "tuple | None" = None) -> None:
    """``reduce.write_chrome_trace``'s layout plus a host process:
    ``host_events`` is ``[(name, start_s, dur_s, stats)]`` (names without
    the ``rlt/`` prefix), ``anchor`` ``(start_s, wall_ns)``."""
    reduce.write_chrome_trace(path, devices)
    with open(path) as f:
        doc = json.load(f)
    pid = len(devices) + 1
    events = doc["traceEvents"]
    events.append({"ph": "M", "pid": pid, "name": "process_name",
                   "args": {"name": "/host:CPU"}})
    events.append({"ph": "M", "pid": pid, "tid": 1, "name": "thread_name",
                   "args": {"name": "python3"}})
    if anchor is not None:
        events.append({"ph": "X", "pid": pid, "tid": 1, "name": ANCHOR,
                       "ts": anchor[0] * 1e6, "dur": 1.0,
                       "args": {"wall_ns": anchor[1]}})
    events += [{"ph": "X", "pid": pid, "tid": 1, "name": PREFIX + name,
                "ts": s * 1e6, "dur": d * 1e6, "args": dict(stats)}
               for name, s, d, stats in host_events]
    with open(path, "w") as f:
        json.dump(doc, f)
