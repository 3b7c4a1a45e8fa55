"""Phase clocks: where a loop's wall time went, always on.

A span says how long one phase of one step took, to whoever records
spans or has a profiler session open; with neither (a plain run, the
benchmark's untraced window) it says nothing.  A ``PhaseClock`` is what
stays: the loop hands it the clock reads it makes anyway at each
boundary of a step, and the clock keeps per phase the ``seconds``, the
number of intervals ``n`` and the ``longest`` one: its seconds, the
step it belonged to and when it began, ``ts``, on the wall clock that
span records use (telemetry/spans.py), so a longest step that fell
inside a profiler window is found in the trace through the
``rlt/clock`` anchor.  A handful of adds and one compare an interval; no
record, no list, nothing that grows.

Two loops own one each: the serve pump (serve/scheduler.py
``PumpClock``, a subclass that also splits its steps by kind) and
``Trainer`` (one a stage, from the first step's result on;
``Trainer.loop_stats()``).  A finished stage's snapshot is kept under
its name, ``last("fit")``: the newest of a name, as ``spans.kept`` does
for windows, for a reader that holds no trainer.

No jax import (telemetry/__init__.py).
"""

from __future__ import annotations

import time
from typing import Optional

from ray_lightning_tpu.telemetry import spans


class PhaseClock:
    """Sums of wall time per phase between ``start()`` and ``stop()``.
    Before ``start()`` an ``add`` charges nothing: a loop whose clock
    begins at its first step's result calls ``add`` from its first
    iteration on.  ``other`` is not a phase to charge: the snapshot
    gives under that name what of ``wall_s`` no phase was charged with."""

    def __init__(self, phases: tuple, clock=time.monotonic,
                 wall_offset: Optional[float] = None):
        self._clock = clock
        # monotonic -> wall, the same offset as span records' ``ts``
        self._wall = spans._WALL_OFFSET if wall_offset is None \
            else wall_offset
        phases = tuple(phases)
        #: the loop's steps since ``start()``; the owner counts them
        self.steps = 0
        self.seconds = dict.fromkeys(phases, 0.0)
        self.n = dict.fromkeys(phases, 0)
        #: per phase ``(seconds, step, t0)`` of its longest interval
        self._longest = dict.fromkeys(phases, (0.0, None, None))
        self._t_start: Optional[float] = None
        self._t_stop: Optional[float] = None

    def start(self, t: Optional[float] = None) -> float:
        self._t_start = self._clock() if t is None else t
        self._t_stop = None
        return self._t_start

    def stop(self) -> None:
        if self._t_start is not None and self._t_stop is None:
            self._t_stop = self._clock()

    @property
    def t_start(self) -> Optional[float]:
        """The clock's own reading at ``start()``; None before it."""
        return self._t_start

    def now(self) -> float:
        return self._clock()

    def add(self, phase: str, t0: float, t1: Optional[float] = None,
            step: Optional[int] = None) -> float:
        """Charge ``phase`` with the time from ``t0`` to ``t1`` (now when
        left out); returns ``t1``."""
        if t1 is None:
            t1 = self._clock()
        if self._t_start is None:
            return t1
        dt = t1 - t0
        self.seconds[phase] += dt
        self.n[phase] += 1
        if dt > self._longest[phase][0]:
            self._longest[phase] = (dt, step, t0)
        return t1

    def _longest_doc(self, entry: tuple) -> Optional[dict]:
        seconds, step, t0 = entry
        if t0 is None:
            return None
        return {"seconds": seconds, "step": step, "ts": t0 + self._wall}

    def wall_s(self) -> Optional[float]:
        if self._t_start is None:
            return None
        return (self._t_stop if self._t_stop is not None
                else self._clock()) - self._t_start

    def snapshot(self) -> dict:
        """``{"steps", "seconds", "n", "longest": {phase: {"seconds",
        "step", "ts"} or None}}`` and, once started, ``wall_s`` with the
        uncharged rest of it as ``seconds["other"]``."""
        out = {"steps": self.steps,
               "seconds": dict(self.seconds), "n": dict(self.n),
               "longest": {p: self._longest_doc(e)
                           for p, e in self._longest.items()}}
        wall = self.wall_s()
        if wall is not None:
            out["wall_s"] = wall
            out["seconds"]["other"] = wall - sum(self.seconds.values())
        return out


# -- the newest snapshot of each name ------------------------------------------

_last: "dict[str, dict]" = {}


def keep(name: str, snapshot: dict) -> None:
    """Keep ``snapshot`` as the newest of ``name`` (replaces the one
    before: bounded by the number of names)."""
    _last[name] = snapshot


def last(name: str) -> Optional[dict]:
    """The newest snapshot kept under ``name``; None when there is none."""
    return _last.get(name)


__all__ = ["PhaseClock", "keep", "last"]
