"""Span/counter recording — the worker-side half of run telemetry.

The reference's only perf surface is a single epoch-timer callback
(SURVEY.md §5); this module gives every process one span API the hot
loop can afford:

- ``span("step")`` / ``span("compile")`` / ``span("data_wait")`` —
  context managers timing host-side phases.  A record carries an ``id``,
  its ``parent`` (the span open on THIS thread when it was entered; the
  stack is per thread, so a background thread's spans never corrupt the
  loop's nesting), its ``depth``, and its attrs; a child inherits its
  parent's ``step`` attr, so everything under a serve step's
  ``serve_step`` or a train ``step`` names the step it belongs to.
- ``counter(name, value)`` — point-in-time scalars (throughput, HBM).

Three places a span can land, all from the one call site:

1. **The profiler's host plane, always.**  Every site enters a
   ``jax.profiler.TraceAnnotation`` named ``rlt/<name>`` with the span's
   scalar attrs, recorder or no recorder.  With no profiler session open
   that is one static ``is_enabled()`` call; with one open (the benchmark's,
   ``POST /debug/profile``, ``JaxProfilerCallback``, an anatomy window)
   the program's spans lie in the trace beside the device's operations,
   on the trace's own clock.  ``clock_anchor()`` emits ``rlt/clock``
   whose ``wall_ns`` stat is this process's ``time.time_ns()``: the map
   from the trace's clock to the wall clock that records' ``ts`` use.
2. **The recorder**, when ``enable()`` installed one: a bounded ring
   (full buffers drop the OLDEST records, a counter reports how many)
   flushed in batches to a ``sink`` callable (the worker→driver queue
   under distributed plugins, the aggregator directly in-process);
   flushing never raises into the training loop.
3. **A keep window**, while one is open: ``with keep(name) as records``
   gives a bounded list that takes every span closed inside it, whatever
   the telemetry flag says, and that a reader in the same process finds
   afterwards under its name (``kept(name)``: the newest window of that
   name, so nothing grows).  Set-up (``Trainer._run_stage`` up to its
   first step, ``Server.start``, the serve worker's ``setup_serve``) and
   the pump's steps under an on-demand profile window (its own thread's
   spans only) are kept: dozens of spans, none in a loop.  ``adopt()``
   merges another process's window into one of this process's.

With the recorder off, no window open and no session, a span site costs
one ``TraceAnnotation.is_enabled()`` call (less than an annotation's
enter/exit) and allocates nothing.

No jax/numpy imports here: worker_main starts heartbeats through this
package before any heavy import happens.  The annotation class is taken
from ``sys.modules`` once jax is there, and never imported from here.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import logging
import sys
import threading
import time
from typing import Any, Callable, Optional

_log = logging.getLogger(__name__)

#: every span's profiler annotation is ``rlt/<name>``
ANNOTATION_PREFIX = "rlt/"
#: the clock anchor's annotation and the stat holding ``time.time_ns()``
CLOCK_ANCHOR = ANNOTATION_PREFIX + "clock"
CLOCK_STAT = "wall_ns"
#: records a keep window holds before it drops its oldest
KEPT_CAPACITY = 4096

_ids = itertools.count(1)
_tls = threading.local()
_annotation_cls = None


def _annotation():
    """``jax.profiler.TraceAnnotation`` once jax is imported, else None.
    Never imports jax (module docstring)."""
    global _annotation_cls
    if _annotation_cls is None:
        jax = sys.modules.get("jax")
        try:
            _annotation_cls = jax.profiler.TraceAnnotation
        except AttributeError:   # jax absent, or still mid-import
            return None
    return _annotation_cls


#: a span site found a profiler session open since the flag was last read
_session_seen = False


def session_seen() -> bool:
    """Whether any span site of this process has found a profiler
    session open since this was last asked; asking clears it.  The
    sites ask ``is_enabled()`` anyway, so whoever owns the programs a
    session may have captured (``Trainer`` at the end of a stage) learns
    for one boolean whether their scope tables are worth keeping
    (telemetry/scopes.py ``remember``)."""
    global _session_seen
    seen, _session_seen = _session_seen, False
    return seen


def _annotate(name: str, attrs: Optional[dict]):
    """The span's profiler annotation, or None when no profiler session
    is open (one static call to find out) or jax is not imported."""
    global _session_seen
    ann = _annotation_cls or _annotation()
    if ann is None or not ann.is_enabled():
        return None
    _session_seen = True
    if not attrs:
        return ann(ANNOTATION_PREFIX + name)
    if "traces" in attrs:
        # stats are text in the trace, and the decode span's slot→trace
        # map is the recorder's business
        attrs = {k: v for k, v in attrs.items() if k != "traces"}
    return ann(ANNOTATION_PREFIX + name, **attrs)


def _stack() -> list:
    try:
        return _tls.stack
    except AttributeError:
        _tls.stack = stack = []
        return stack


class _NoopSpan:
    """Returned by ``span()`` when nothing records and no profiler
    session is open."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("name", "attrs", "t0", "id", "parent", "_ann")

    def __init__(self, name: str, attrs: Optional[dict]):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        stack = _stack()
        self.id = next(_ids)
        self.parent = None
        if stack:
            top = stack[-1]
            self.parent = top.id
            step = top.attrs.get("step") if top.attrs else None
            if step is not None and "step" not in (self.attrs or ()):
                self.attrs = {**(self.attrs or {}), "step": step}
        stack.append(self)
        rec = _recorder
        if rec is not None:
            rec.last_span = self.name
        self._ann = _annotate(self.name, self.attrs)
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        rec = _recorder
        windows = _windows
        if rec is None and not windows:
            return False     # disabled mid-span: drop silently
        record = {
            "t": "span",
            "name": self.name,
            "ts": self.t0 + _WALL_OFFSET,
            "dur": t1 - self.t0,
            "rank": rec.rank if rec is not None else 0,
            "depth": len(stack),
            "id": self.id,
            "parent": self.parent,
        }
        if self.attrs:
            record["attrs"] = self.attrs
        for records, thread in windows:
            if thread is None or thread == threading.get_ident():
                records.append(record)
        if rec is not None:
            rec.add(record)
        return False


def _wall_offset() -> float:
    """wall minus monotonic, from the tightest of a few bracketed reads:
    a process descheduled between two bare reads would carry the error
    in every record's ``ts`` for its whole life."""
    best = None
    for _ in range(5):
        m0 = time.monotonic()
        wall = time.time()
        m1 = time.monotonic()
        if best is None or m1 - m0 < best[0]:
            best = (m1 - m0, wall - (m0 + m1) / 2)
    return best[1]


#: monotonic→wall offset, captured once per process: records carry
#: wall-clock timestamps so the driver can merge ranks (and a reader the
#: pump's and the worker's spans) onto one timeline: same-host skew is
#: zero; cross-host skew is NTP-bounded
_WALL_OFFSET = _wall_offset()


class _Recorder:
    """Process-wide ring buffer + sink.  The lock covers buffer swaps
    only; the training loop's common case is one append under it."""

    def __init__(self, rank: int, sink: Optional[Callable],
                 capacity: int, flush_every: Optional[int]):
        self.rank = rank
        self.sink = sink
        self.capacity = max(1, int(capacity))
        self.flush_every = flush_every
        self.records: list[dict] = []
        self.dropped = 0
        self.lock = threading.Lock()
        self.last_span: Optional[str] = None
        self._sink_failed = False
    def add(self, record: dict) -> None:
        batch = None
        with self.lock:
            if len(self.records) >= self.capacity:
                self.records.pop(0)
                self.dropped += 1
            self.records.append(record)
            if self.sink is not None and self.flush_every \
                    and len(self.records) >= self.flush_every:
                batch, self.records = self.records, []
        if batch:
            self._emit(batch)

    def flush(self) -> None:
        with self.lock:
            batch, self.records = self.records, []
        if batch and self.sink is not None:
            self._emit(batch)
        elif batch:
            # no sink: flushing without a consumer would lose records —
            # put them back for drain()
            with self.lock:
                self.records = batch + self.records

    def drain(self) -> list[dict]:
        with self.lock:
            batch, self.records = self.records, []
        return batch

    def _emit(self, batch: list[dict]) -> None:
        try:
            self.sink(batch)
        except Exception:
            # telemetry must never kill training; warn once per recorder
            if not self._sink_failed:
                self._sink_failed = True
                _log.warning("telemetry sink failed; further records "
                             "will be dropped silently", exc_info=True)


_recorder: Optional[_Recorder] = None


def enable(rank: int = 0, sink: Optional[Callable] = None,
           capacity: int = 65536, flush_every: Optional[int] = 256) -> None:
    """Install a process-wide recorder.  ``sink(batch_of_records)`` is
    called with full batches (and on ``flush()``); with no sink the
    records accumulate in the ring buffer for ``drain()``."""
    global _recorder
    _recorder = _Recorder(rank, sink, capacity, flush_every)


def disable() -> None:
    global _recorder
    _recorder = None


def enabled() -> bool:
    return _recorder is not None


def span(name: str, **attrs: Any):
    """Time a host-side phase.  With nothing recording this is the
    profiler annotation alone, or a no-op singleton when no profiler
    session is open either: no record, no span object."""
    if _recorder is None and not _windows:
        return _annotate(name, attrs) or _NOOP
    return _Span(name, attrs or None)


def counter(name: str, value: float, **attrs: Any) -> None:
    """Record a point-in-time scalar (no-op when disabled)."""
    rec = _recorder
    if rec is None:
        return
    record = {
        "t": "counter",
        "name": name,
        "ts": time.monotonic() + _WALL_OFFSET,
        "value": float(value),
        "rank": rec.rank,
    }
    if attrs:
        record["attrs"] = attrs
    rec.add(record)


def flush() -> None:
    rec = _recorder
    if rec is not None:
        rec.flush()


def drain() -> list[dict]:
    """Return and clear buffered records (sink-less recorders)."""
    rec = _recorder
    return rec.drain() if rec is not None else []


def dropped() -> int:
    rec = _recorder
    return rec.dropped if rec is not None else 0


def last_span() -> Optional[str]:
    """Most recently ENTERED span name — heartbeats carry this so the
    driver watchdog can say what a dead worker was doing."""
    rec = _recorder
    return rec.last_span if rec is not None else None


# -- keep windows -------------------------------------------------------------

#: open windows as ``(records, thread or None)``; replaced, never changed
#: in place, so a span's exit reads it without the lock
_windows: tuple = ()
#: the newest window of each name, for a reader in the same process
_kept: "dict[str, collections.deque]" = {}
_keep_lock = threading.Lock()


@contextlib.contextmanager
def keep(name: str, own_thread: bool = False):
    """Open a window and yield its records: every span of this process
    (of this thread only, with ``own_thread``) that closes inside it is
    appended, recorder or no recorder, oldest dropped past
    ``KEPT_CAPACITY``.  Windows nest; ``kept(name)`` finds the newest
    one of a name afterwards."""
    global _windows
    records: collections.deque = collections.deque(maxlen=KEPT_CAPACITY)
    window = (records, threading.get_ident() if own_thread else None)
    with _keep_lock:
        _kept[name] = records
        _windows = _windows + (window,)
    try:
        yield records
    finally:
        with _keep_lock:
            _windows = tuple(w for w in _windows if w is not window)


def kept(name: str) -> list[dict]:
    """The records of the newest ``keep(name)`` window, open or closed;
    empty when there was none."""
    return list(_kept.get(name, ()))


def adopt(into, records: list, parent: Optional[int] = None,
          rank: Optional[int] = None) -> None:
    """Merge another process's window (a serve worker's set-up, returned
    with ``setup_serve``'s result) into ``into``, a window of this
    process: ids are renumbered here, their own nesting kept, and their
    roots hung under ``parent``.  ``ts`` needs no change: both
    processes read one host's wall clock."""
    new_id = {r["id"]: next(_ids) for r in records if "id" in r}
    for r in records:
        r = dict(r)
        if "id" in r:
            r["id"] = new_id[r["id"]]
            r["parent"] = new_id.get(r.get("parent"), parent)
        if rank is not None:
            r["rank"] = rank
        into.append(r)


def clock_anchor() -> None:
    """Emit ``rlt/clock`` with this process's ``time.time_ns()`` as its
    ``wall_ns`` stat.  Called once per profiler window, right after the
    session opens: a trace counts from its own zero, and this is what
    maps it to the wall clock of records' ``ts``.  The stat is read
    before the annotation starts; if the thread was held up in between,
    another is emitted, so a reader takes the window's LAST anchor."""
    ann = _annotation_cls or _annotation()
    if ann is None:
        return
    for _ in range(5):
        t0 = time.time_ns()
        with ann(CLOCK_ANCHOR, **{CLOCK_STAT: t0}):
            pass
        if time.time_ns() - t0 < 200_000:
            break
