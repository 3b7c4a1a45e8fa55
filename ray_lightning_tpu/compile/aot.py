"""AOT precompilation overlapped with fit setup.

The trainer knows every step program's exact input avals the moment
``_build_compiled`` finishes (``jax.eval_shape`` of the init fn gives
the state; the peeked example batch gives the batch), yet without this
module XLA compilation only starts at the FIRST DISPATCH — serialized
after state init, the rendezvous, the sanity check and the
device-resident dataset upload.  :class:`AotPrecompiler` moves it off
the critical path: one background thread runs
``jitted.lower(*abstract_args).compile()`` for each submitted program
while the fit does that other work.

The compiled artifact reaches the first dispatch THROUGH THE
PERSISTENT CACHE, not through memory: jax's ``lower().compile()``
executables are invisible to the jit dispatch path (measured — the
dispatch re-invokes XLA even on the same jit object), but with the
persistent cache active the background compile writes the cache entry
and the dispatch-time compile collapses to a ~ms disk retrieval.
Without an active cache, precompiling would genuinely DOUBLE compile
work (measured +50% on the CPU test suite), so :meth:`resolve`
disables itself unless :func:`compile.cache.active_dir` is set —
AOT overlap is a feature of the cached configuration, by construction.

Failure is always soft: a program whose predicted avals turn out wrong
(exotic loader, mispredicted global batch) logs and falls back to the
normal lazy compile at dispatch — precompilation is an overlap
optimization, never a correctness dependency.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from dataclasses import dataclass
from typing import Any, Optional

from ray_lightning_tpu.telemetry import span

_log = logging.getLogger(__name__)

#: kill switch: RLT_AOT_PRECOMPILE=0 restores compile-at-first-dispatch
ENV_AOT = "RLT_AOT_PRECOMPILE"


class AotPrecompiler:
    """Sequentially compiles submitted programs on one daemon thread.

    One thread, not a pool: concurrent XLA compiles fight over the same
    cores the main thread's init compile is using, and the programs of
    one fit share most of their compilation anyway.  ``barrier()``
    blocks until everything submitted so far is done — the trainer calls
    it right before the first train dispatch so a lazy dispatch-time
    compile never races the background one for the same program.
    """

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.results: dict[str, Any] = {}   # name -> seconds | exception
        self._queue: list[tuple[str, Any, tuple, Any]] = []
        self._pending = 0
        self._cond = threading.Condition()
        self._thread: Optional[threading.Thread] = None

    @classmethod
    def resolve(cls) -> "AotPrecompiler":
        """Enabled only when the persistent cache is active (module
        docstring: without it, background compiles are pure double
        work) and ``RLT_AOT_PRECOMPILE`` doesn't opt out."""
        from ray_lightning_tpu.compile import cache as _cache
        enabled = (os.environ.get(ENV_AOT, "").strip() != "0"
                   and _cache.active_dir() is not None)
        return cls(enabled=enabled)

    def submit(self, name: str, jitted, abstract_args: tuple) -> None:
        """Queue ``jitted.lower(*abstract_args).compile()`` under
        ``name``.  No-op when disabled."""
        if not self.enabled:
            return
        # the submitter's mesh rides along: it is thread-local
        # (parallel/mesh.py), and mesh-aware ops traced on the compile
        # thread (auto_attention's sharded flash kernel, ring attention)
        # must see what the dispatching thread would have seen.  jax
        # caches the TRACE per avals, so a program traced here without
        # the mesh is also the program the fit then runs — on a
        # multi-chip mesh that silently swapped the Pallas kernel for
        # the XLA dot path.
        from ray_lightning_tpu.parallel.mesh import get_current_mesh
        with self._cond:
            self._queue.append((name, jitted, abstract_args,
                                get_current_mesh()))
            self._pending += 1
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name="rlt-aot-precompile")
                self._thread.start()

    def _run(self) -> None:
        from ray_lightning_tpu.parallel.mesh import set_current_mesh
        while True:
            with self._cond:
                if not self._queue:
                    return
                name, jitted, args, mesh = self._queue.pop(0)
            t0 = time.monotonic()
            try:
                set_current_mesh(mesh)
                # this thread's own span stack (telemetry/spans.py): its
                # spans are roots beside the main thread's, and say
                # which program's trace or load a set-up waited for.
                # ``thread`` marks them as overlapping the main thread's
                # time, for readers that add set-up up
                with span("aot", program=name, thread="aot"):
                    with span("lower"):
                        lowered = jitted.lower(*args)
                    with span("backend_compile"):
                        lowered.compile()
                dt = time.monotonic() - t0
                self.results[name] = dt
            except Exception as e:   # noqa: BLE001 - soft fallback
                self.results[name] = e
                _log.info(
                    "AOT precompile of %s failed (%s: %s); the program "
                    "will compile lazily at first dispatch", name,
                    type(e).__name__, e)
            finally:
                with self._cond:
                    self._pending -= 1
                    self._cond.notify_all()

    def barrier(self, timeout: Optional[float] = None) -> dict[str, Any]:
        """Wait for every submitted compile; returns the results map.
        Instant once drained (the per-epoch engine calls it every
        epoch; only the first can wait)."""
        with self._cond:
            self._cond.wait_for(lambda: self._pending == 0,
                                timeout=timeout)
        return dict(self.results)

    def succeeded(self, name: str) -> bool:
        return isinstance(self.results.get(name), float)


# -- batched AOT scoring (planner verify stage) ----------------------------

@dataclass
class ScoredCompile:
    """What one AOT candidate compile yields for plan ranking: measured
    compile seconds, the backend's real per-device memory analysis, and
    the audited HLO collective wire bytes (comm/audit.py model)."""

    name: str
    seconds: float = 0.0
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    alias_bytes: int = 0
    wire_bytes: int = 0
    #: per-link split of wire_bytes (replica-group classification with
    #: the candidate's ici group size) — both 0 for flat candidates
    wire_bytes_dcn: int = 0
    wire_bytes_ici: int = 0
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None

    @property
    def peak_bytes(self) -> int:
        """Per-device residency of one step dispatch: live arguments +
        outputs + XLA temp workspace, minus the aliased (donated)
        buffers counted on both sides."""
        return max(0, self.argument_bytes + self.output_bytes
                   + self.temp_bytes - self.alias_bytes)

    def to_dict(self) -> dict:
        return {
            "compile_seconds": round(self.seconds, 6),
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "alias_bytes": self.alias_bytes,
            "peak_bytes": self.peak_bytes,
            "wire_bytes": self.wire_bytes,
            "wire_bytes_dcn": self.wire_bytes_dcn,
            "wire_bytes_ici": self.wire_bytes_ici,
            "error": self.error,
        }


def compile_scored(programs: "list[tuple[str, Any, tuple, int]]",
                   max_workers: int = 4) -> "dict[str, ScoredCompile]":
    """AOT-compile candidate programs concurrently and score each.

    ``programs`` entries are ``(name, jitted, abstract_args,
    axis_size)`` or ``(..., axis_size, ici_size)`` — ``axis_size``
    scales reduce-scatter results back to input bytes in the wire
    audit; a non-zero ``ici_size`` (hierarchical comm candidates)
    additionally splits the audited bytes by link tier over each
    collective's replica groups.  Unlike :class:`AotPrecompiler`
    (one thread — its compiles overlap the main thread's init compile),
    these run BEFORE any other compilation exists, so a small pool is
    pure win; with the persistent cache active every artifact lands on
    disk and the winner's first real dispatch collapses to a cache
    retrieval.  Failure is per-program soft: a candidate whose compile
    raises scores as an error entry instead of sinking the whole plan.
    """
    import concurrent.futures

    from ray_lightning_tpu.comm.audit import (total_wire_bytes,
                                              wire_bytes_by_link)

    def one(entry) -> ScoredCompile:
        name, jitted, args, axis_size = entry[:4]
        ici_size = entry[4] if len(entry) > 4 else 0
        t0 = time.monotonic()
        try:
            compiled = jitted.lower(*args).compile()
        except Exception as e:   # noqa: BLE001 - per-candidate soft fail
            return ScoredCompile(name=name,
                                 seconds=time.monotonic() - t0,
                                 error=f"{type(e).__name__}: {e}")
        out = ScoredCompile(name=name, seconds=time.monotonic() - t0)
        try:
            mem = compiled.memory_analysis()
            out.argument_bytes = int(
                getattr(mem, "argument_size_in_bytes", 0) or 0)
            out.output_bytes = int(
                getattr(mem, "output_size_in_bytes", 0) or 0)
            out.temp_bytes = int(
                getattr(mem, "temp_size_in_bytes", 0) or 0)
            out.alias_bytes = int(
                getattr(mem, "alias_size_in_bytes", 0) or 0)
        except Exception:   # noqa: BLE001 - backend without the API
            _log.debug("memory_analysis unavailable for %s", name,
                       exc_info=True)
        try:
            text = compiled.as_text()
            out.wire_bytes = total_wire_bytes(text, axis_size=axis_size)
            if ici_size > 1:
                link = wire_bytes_by_link(text, ici_size,
                                          axis_size=axis_size)
                out.wire_bytes_dcn = link["dcn"]
                out.wire_bytes_ici = link["ici"]
        except Exception:   # noqa: BLE001 - text dump unavailable
            _log.debug("HLO wire audit unavailable for %s", name,
                       exc_info=True)
        return out

    if not programs:
        return {}
    workers = max(1, min(max_workers, len(programs)))
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=workers,
            thread_name_prefix="rlt-plan-aot") as pool:
        return {s.name: s for s in pool.map(one, programs)}


# -- abstract-aval helpers -------------------------------------------------

def global_batch_abstract(host_batch, process_count: int):
    """Abstract avals of the batch the train step will actually see.

    Single-process: the host (numpy) batch goes straight into the jitted
    step, so its own shapes/dtypes are the avals.  Multi-process: the
    dispatch wraps each leaf in ``make_array_from_process_local_data``,
    whose global array concatenates the per-process shards along dim 0 —
    global leading dim = local × process count (the same arithmetic the
    mesh ``batch_hint`` uses).  Pass the batch AFTER ``_host_cast`` so
    bf16 input casting is reflected in the dtypes.
    """
    import jax
    import numpy as np

    def leaf(x):
        a = np.asarray(x)
        shape = a.shape
        if process_count > 1 and a.ndim > 0:
            shape = (shape[0] * process_count,) + shape[1:]
        return jax.ShapeDtypeStruct(shape, a.dtype)

    return jax.tree_util.tree_map(leaf, host_batch)


def stack_abstract(abstract_batch, k: int):
    """Avals of ``k`` stacked batches (the ``steps_per_execution``
    chunk program's input: one leading scan dimension)."""
    import jax

    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((k,) + tuple(s.shape), s.dtype),
        abstract_batch)


__all__ = [
    "AotPrecompiler",
    "ENV_AOT",
    "ScoredCompile",
    "compile_scored",
    "global_batch_abstract",
    "stack_abstract",
]
