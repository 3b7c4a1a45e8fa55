"""From a profiler trace to numbers: device busy and idle time, time per
program, host gap and exposed collective time per step, the operations
that took most time and the longest idle gaps.

The interval algebra is a copy of ``ray_lightning_tpu/telemetry/
anatomy.py`` (sound on the train cell: it reproduced 49.35 ms to the
digit), kept here so that no later PR can change how a number is
reduced.  What is not copied: its ``parse_anatomy_or_none``, which
swallowed a failed parse.  A trace that cannot be read is an error.

Identity, per device: window = compute + exposed collective + idle,
because exposed = |collective minus compute| and idle = window minus
|collective union compute|.

Input is the profiler's ``.xplane.pb`` (read with
``jax.profiler.ProfileData``) or, for tests, a Chrome-trace JSON of the
same layout.  A TPU trace has one plane per chip (``/device:TPU:k``)
with the lines ``XLA Modules`` (one event per program execution) and
``XLA Ops``.  A CPU trace has no device plane: its operations are the
host-thread events that carry an ``hlo_op`` stat, and each run of a
program is the span of its operations; the CPU rehearsal reads those so
that the same code path is exercised before chip time is spent.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "all-to-all", "collective-permute")


def locate(trace_dir: str, suffix: str = ".xplane.pb") -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*" + suffix), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *{suffix} under {trace_dir}")
    return paths[-1]


# -- loaders: both give [{"device", "ops": [(name, start_s, dur_s)],
#                         "modules": [(name, start_s, dur_s)]}] ------------

def short_name(name: str) -> str:
    """``%fusion.145 = bf16[...] fusion(...)`` (a TPU trace names an
    operation by its whole HLO line) -> ``fusion.145``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: str) -> list[dict]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            tl = {"device": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                tl[key] = [(short_name(e.name), e.start_ns * 1e-9,
                            e.duration_ns * 1e-9)
                           for e in line.events if e.duration_ns > 0]
            if tl["ops"]:
                out.append(tl)
    if out:
        return out
    # CPU layout: XLA's thread pool spreads one device's operations over
    # several host threads, so all of them make one timeline
    ops, runs = [], {}
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_op" not in stats or e.duration_ns <= 0:
                    continue
                s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                ops.append((e.name, s, d))
                key = (stats.get("hlo_module", ""), stats.get("run_id"))
                lo, hi = runs.get(key, (s, s + d))
                runs[key] = (min(lo, s), max(hi, s + d))
    if ops:
        out.append({"device": "/host:CPU", "ops": ops, "modules": [
            (k[0], lo, hi - lo) for k, (lo, hi) in runs.items()]})
    return out


def load_chrome(path: str) -> list[dict]:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    out: dict = {}
    for e in events:
        if e.get("ph") != "X" or not e.get("dur"):
            continue
        pname = procs.get(e["pid"], "")
        key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
            threads.get((e["pid"], e["tid"])))
        if not pname.startswith("/device:") or key is None:
            continue
        tl = out.setdefault(e["pid"], {"device": pname, "ops": [],
                                       "modules": []})
        tl[key].append((e["name"], e["ts"] * 1e-6, e["dur"] * 1e-6))
    return [tl for tl in out.values() if tl["ops"]]


def load(trace_dir: str) -> list[dict]:
    timelines = load_xplane(locate(trace_dir))
    if not timelines:
        raise ValueError(f"no device operation in the trace under "
                         f"{trace_dir}")
    return timelines


# -- interval algebra ---------------------------------------------------------

def union(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def measure(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a, b) -> list:
    """a minus b, both merged."""
    out, bi = [], 0
    for s, e in a:
        cur = s
        while bi < len(b) and b[bi][1] <= cur:
            bi += 1
        j = bi
        while j < len(b) and b[j][0] < e:
            if b[j][0] > cur:
                out.append([cur, b[j][0]])
            cur = max(cur, b[j][1])
            j += 1
        if cur < e:
            out.append([cur, e])
    return out


def is_collective(name: str) -> bool:
    n = name.lower()
    return any(c in n for c in COLLECTIVES)


# -- the reduction --------------------------------------------------------------

def reduce_timelines(timelines: list[dict], top: int = 10) -> dict:
    """All numbers are per device (averaged over the devices in the
    trace); the window is the extent of all device operations."""
    lo = min(s for tl in timelines for _, s, _ in tl["ops"])
    hi = max(s + d for tl in timelines for _, s, d in tl["ops"])
    window = hi - lo
    n = len(timelines)
    busy = compute = exposed = collective = 0.0
    op_time: dict = {}
    module_time: dict = {}
    module_runs: dict = {}
    gaps: dict = {}
    for tl in timelines:
        comp = union((s, s + d) for name, s, d in tl["ops"]
                     if not is_collective(name))
        coll = union((s, s + d) for name, s, d in tl["ops"]
                     if is_collective(name))
        both = union([*comp, *coll])
        busy += measure(both)
        compute += measure(comp)
        collective += sum(d for name, _, d in tl["ops"]
                          if is_collective(name))
        exposed += measure(subtract(coll, comp))
        for name, _, d in tl["ops"]:
            op_time[name] = op_time.get(name, 0.0) + d
        mods = sorted(tl["modules"], key=lambda m: m[1])
        for name, _, d in mods:
            module_time[name] = module_time.get(name, 0.0) + d
            module_runs[name] = module_runs.get(name, 0) + 1
        # an idle gap inside one run of a program is the program's own
        # bubble; one between runs is named by the program that follows:
        # the host was getting that dispatch ready
        starts = [m[1] for m in mods]
        for (_, e0), (s1, _) in zip(both, both[1:]):
            i = bisect.bisect_right(starts, e0) - 1
            if i >= 0 and mods[i][1] + mods[i][2] >= s1:
                label = f"inside:{mods[i][0]}"
            elif i + 1 < len(mods):
                label = f"before:{mods[i + 1][0]}"
            else:
                label = "after:last"
            gaps[label] = gaps.get(label, 0.0) + (s1 - e0)
    main = max(module_runs, key=lambda k: module_time[k]) \
        if module_runs else None
    steps = module_runs[main] / n if main else 0

    def per_device(d):
        return {k: v / n for k, v in d.items()}

    def ranked(d):
        return [[k, v] for k, v in sorted(per_device(d).items(),
                                          key=lambda kv: -kv[1])[:top]]

    return {
        "devices": n, "window_s": window, "busy_s": busy / n,
        "compute_s": compute / n, "collective_s": collective / n,
        "exposed_s": exposed / n, "idle_s": window - busy / n,
        "main_module": main, "steps": steps,
        "module_s": per_device(module_time),
        "module_runs": {k: v / n for k, v in module_runs.items()},
        "device_ops": ranked(op_time), "idle_gaps": ranked(gaps),
        # the first device's program runs in time order, for callers that
        # know what they dispatched (every serve program is `jit_wrapped`)
        "module_sequence": [[m[0], m[2]] for m in sorted(
            timelines[0]["modules"], key=lambda m: m[1])],
    }


def reduce_dir(trace_dir: str) -> dict:
    return reduce_timelines(load(trace_dir))


def module_ms_per_run(red: dict, pattern: str):
    """Mean device milliseconds per execution of the programs whose name
    contains ``pattern``; None when none ran in the window."""
    names = [k for k in red["module_s"] if pattern in k]
    runs = sum(red["module_runs"][k] for k in names)
    if not runs:
        return None
    return 1e3 * sum(red["module_s"][k] for k in names) / runs


def ms_per_run_by_kind(red: dict, dispatched: list[str]) -> dict:
    """Mean device milliseconds per run of each kind of program, for a
    caller that recorded the kinds it dispatched during the traced
    window, in order.  The serve engine's programs all carry one name in
    the trace, so the order of dispatch is what tells them apart; if the
    trace holds another number of runs than were dispatched, nothing can
    be told and the result is empty."""
    seq = red["module_sequence"]
    if not seq or len(seq) != len(dispatched):
        return {}
    total: dict = {}
    for kind, (_, dur) in zip(dispatched, seq):
        t, n = total.get(kind, (0.0, 0))
        total[kind] = (t + dur, n + 1)
    return {k: 1e3 * t / n for k, (t, n) in total.items()}


def write_chrome_trace(path: str, devices: list[dict]) -> None:
    """A small Chrome trace of the TPU layout, for tests: ``devices`` is
    ``[{"ops": [(name, start_s, dur_s)], "modules": [...]}]``."""
    events = []
    for pid, dev in enumerate(devices, start=1):
        events.append({"ph": "M", "pid": pid, "name": "process_name",
                       "args": {"name": f"/device:TPU:{pid - 1}"}})
        for tid, (track, key) in enumerate(
                (("XLA Modules", "modules"), ("XLA Ops", "ops")), start=1):
            events.append({"ph": "M", "pid": pid, "tid": tid,
                           "name": "thread_name", "args": {"name": track}})
            events += [{"ph": "X", "pid": pid, "tid": tid, "name": name,
                        "ts": s * 1e6, "dur": d * 1e6}
                       for name, s, d in dev.get(key, [])]
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
