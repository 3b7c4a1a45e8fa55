"""Persistent XLA compilation-cache management.

Every actor, every Ray Tune trial and every fault-recovery restart of
this framework dispatches byte-identical SPMD programs — and, without
this module, re-pays full XLA compilation for each of them.  JAX ships a
persistent compilation cache keyed by the serialized HLO + compile
options; what it does NOT ship is lifecycle management: who picks the
directory, how workers of a cluster run share (or seed) it, how tune
trials point at one cache, and how hits/misses become observable.  That
is this module:

- :class:`CompileCacheConfig` — picklable settings carried on the
  Trainer (like ``TelemetryConfig``), resolved from the ``compile_cache=``
  argument and the ``RLT_COMPILE_CACHE*`` env knobs.  The cache is ON by
  default.
- One directory, placed from outside: where ``JAX_COMPILATION_CACHE_DIR``
  is set the cache lives THERE and nothing in this package points jax
  anywhere else (workers inherit the variable); unset, it lives at the
  fixed in-checkout :data:`DEFAULT_DIR`.  jax keys the entries by
  program, jax version, platform and device kind, so one directory is
  safe to share between runs, tune experiments and device kinds; what
  must not happen is a path that moves (time, pid, temp name) — it
  never hits.
- :func:`activate` — enables JAX's persistent cache at that directory.
- Cache accounting: listeners on JAX's monitoring events count cache
  hits / misses and accumulate real backend-compile seconds; the
  metrics plane (telemetry/metrics.py) exposes them as
  ``rlt_compile_cache_hits_total`` / ``rlt_compile_cache_misses_total``
  / ``rlt_compile_seconds_total``, and bench rounds read
  :func:`status_word` for the JSON line's ``compile_cache`` field.

Nothing here imports jax at module load (worker_main touches sibling
packages before jax exists); jax is imported inside the functions that
need a live backend.
"""

from __future__ import annotations

import logging
import os
import threading
from dataclasses import dataclass, field, replace
from typing import Any, Optional

_log = logging.getLogger(__name__)

#: jax's own variable: when set it is THE cache directory — it outranks
#: every argument and knob below and is never overridden in code
ENV_JAX_DIR = "JAX_COMPILATION_CACHE_DIR"

#: the one fixed cache directory otherwise: inside the checkout (next to
#: the package, git-ignored), so a second run from the same tree hits
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: the user-facing env knobs (README "Compilation cache"; validated by
#: compile/selfcheck.py so docs and code can't drift)
ENV_ENABLE = "RLT_COMPILE_CACHE"            # 0 | 1 | /path/to/root
ENV_DIR = "RLT_COMPILE_CACHE_DIR"           # explicit root directory
ENV_MIN_ENTRY = "RLT_COMPILE_CACHE_MIN_ENTRY_BYTES"
ENV_MIN_COMPILE = "RLT_COMPILE_CACHE_MIN_COMPILE_SECS"
ENV_KNOBS = (ENV_ENABLE, ENV_DIR, ENV_MIN_ENTRY, ENV_MIN_COMPILE)


@dataclass
class CompileCacheConfig:
    """Picklable compile-cache settings carried on the Trainer (the
    trainer ships to workers, so the config rides along for free)."""

    enabled: bool = True
    #: explicit cache directory; outranked by ``JAX_COMPILATION_CACHE_DIR``
    #: (see :attr:`root`).  None = :data:`DEFAULT_DIR`.
    dir: Optional[str] = None
    #: persist entries at least this large (bytes; 0 = everything —
    #: jax's own default of 0 kept, the floor exists for shared NFS
    #: roots where tiny entries cost more in metadata than they save)
    min_entry_bytes: int = 0
    #: persist only compiles at least this slow (seconds; 0 = every
    #: compile — deliberately below jax's 1.0 default so short CPU-test
    #: programs and small eval steps warm-start too; raise it on shared
    #: roots if churn becomes a problem)
    min_compile_secs: float = 0.0

    @classmethod
    def resolve(cls, value: Any) -> "CompileCacheConfig":
        """Trainer's ``compile_cache=`` argument → a config.

        ``None`` defers to the environment; ``True``/``False`` force;
        a string is an explicit cache directory; a dict supplies field
        overrides (enabled unless it says otherwise).  For ``None``:
        ``RLT_COMPILE_CACHE=0`` turns the cache off, anything else
        leaves it on, with ``RLT_COMPILE_CACHE_DIR`` (or
        ``RLT_COMPILE_CACHE=/path``) as the explicit directory.
        Whatever directory is resolved here, a set
        ``JAX_COMPILATION_CACHE_DIR`` outranks it (:attr:`root`).
        """
        if isinstance(value, cls):
            return value
        if isinstance(value, bool):
            return (cls()._with_env_knobs() if value
                    else cls(enabled=False))
        if isinstance(value, str):
            return cls(enabled=True, dir=value)._with_env_knobs()
        if isinstance(value, dict):
            cfg = dict(value)
            cfg.setdefault("enabled", True)
            return cls(**cfg)
        if value is not None:
            raise TypeError(
                f"compile_cache must be None/bool/str/dict/"
                f"CompileCacheConfig; got {type(value).__name__}")
        enable = os.environ.get(ENV_ENABLE, "").strip()
        if enable == "0":
            return cls(enabled=False)
        env_dir = os.environ.get(ENV_DIR, "").strip() or None
        if enable not in ("", "1") and env_dir is None:
            env_dir = enable          # RLT_COMPILE_CACHE=/path/to/dir
        return cls(dir=env_dir)._with_env_knobs()

    def _with_env_knobs(self) -> "CompileCacheConfig":
        out = self
        raw = os.environ.get(ENV_MIN_ENTRY, "").strip()
        if raw:
            try:
                out = replace(out, min_entry_bytes=int(raw))
            except ValueError:
                _log.warning("%s=%r is not an integer; ignored",
                             ENV_MIN_ENTRY, raw)
        raw = os.environ.get(ENV_MIN_COMPILE, "").strip()
        if raw:
            try:
                out = replace(out, min_compile_secs=float(raw))
            except ValueError:
                _log.warning("%s=%r is not a number; ignored",
                             ENV_MIN_COMPILE, raw)
        return out

    @property
    def root(self) -> str:
        """THE cache directory: ``JAX_COMPILATION_CACHE_DIR`` where the
        environment sets it (read at use, so a worker that inherits the
        variable agrees with its driver), else the explicit ``dir``,
        else the fixed in-checkout :data:`DEFAULT_DIR`."""
        return (os.environ.get(ENV_JAX_DIR, "").strip()
                or self.dir or DEFAULT_DIR)

    def worker_env(self) -> dict[str, str]:
        """Env replicating this config in a spawned worker — belt and
        braces alongside the pickled trainer (covers worker-side code
        that consults the env before the payload arrives).
        ``JAX_COMPILATION_CACHE_DIR`` itself is inherited, not set."""
        if not self.enabled:
            return {ENV_ENABLE: "0"}
        env = {
            ENV_ENABLE: "1",
            ENV_MIN_ENTRY: str(self.min_entry_bytes),
            ENV_MIN_COMPILE: str(self.min_compile_secs),
        }
        if self.dir:
            env[ENV_DIR] = self.dir
        return env


# -- activation -----------------------------------------------------------

_active_dir: Optional[str] = None
_activate_lock = threading.Lock()


def activate(config: CompileCacheConfig) -> Optional[str]:
    """Turn JAX's persistent compilation cache on at ``config.root``
    (idempotent).  ``root`` is ``JAX_COMPILATION_CACHE_DIR`` whenever
    that is set, so the one ``jax_compilation_cache_dir`` update below
    can only restate the variable, never override it.  Returns the
    active directory, or None when disabled."""
    global _active_dir
    if config is None or not config.enabled:
        return None
    import jax
    with _activate_lock:
        target = config.root
        os.makedirs(target, exist_ok=True)
        if _active_dir != target:
            # unconditionally drop jax's memoized cache state: jax
            # latches "cache unused" at the first compile of a process,
            # so activating AFTER any compile has happened (tests, a
            # warmup jit, a prior experiment) would otherwise be ignored
            _reset_jax_cache()
            jax.config.update("jax_enable_compilation_cache", True)
            jax.config.update("jax_compilation_cache_dir", target)
            _active_dir = target
            _log.info("persistent XLA compilation cache at %s", target)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          int(config.min_entry_bytes))
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(config.min_compile_secs))
        _install_listeners()
        return target


def deactivate() -> None:
    """Switch the persistent cache off again (tests use this so one
    module's cache never leaks into the next).  The directory setting
    is left alone — only :func:`activate` ever writes it."""
    global _active_dir
    with _activate_lock:
        if _active_dir is None:
            return
        import jax
        _reset_jax_cache()
        jax.config.update("jax_enable_compilation_cache", False)
        _active_dir = None


def active_dir() -> Optional[str]:
    return _active_dir


def _reset_jax_cache() -> None:
    """Drop jax's live cache handle so the next compile re-reads the
    (changed) cache config."""
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


# -- accounting -----------------------------------------------------------

@dataclass
class CacheStats:
    """Cumulative compile/cache accounting for this process."""

    hits: int = 0
    requests: int = 0
    backend_compile_secs: float = 0.0
    #: compile seconds a cache hit avoided (as recorded with the entry)
    saved_secs: float = 0.0
    retrieval_secs: float = 0.0
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False)

    @property
    def misses(self) -> int:
        return max(0, self.requests - self.hits)

    def snapshot(self) -> "CacheStats":
        with self._lock:
            return CacheStats(hits=self.hits, requests=self.requests,
                              backend_compile_secs=self.backend_compile_secs,
                              saved_secs=self.saved_secs,
                              retrieval_secs=self.retrieval_secs)


_stats = CacheStats()
_listeners_installed = False

_EV_HIT = "/jax/compilation_cache/cache_hits"
_EV_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_EV_COMPILE_SECS = "/jax/core/compile/backend_compile_duration"
_EV_SAVED_SECS = "/jax/compilation_cache/compile_time_saved_sec"
_EV_RETRIEVAL_SECS = "/jax/compilation_cache/cache_retrieval_time_sec"


def _on_event(event: str, **_kw: Any) -> None:
    if event == _EV_HIT:
        with _stats._lock:
            _stats.hits += 1
    elif event == _EV_REQUEST:
        with _stats._lock:
            _stats.requests += 1


def _on_duration(event: str, duration: float, **_kw: Any) -> None:
    if event == _EV_COMPILE_SECS:
        with _stats._lock:
            _stats.backend_compile_secs += duration
    elif event == _EV_SAVED_SECS:
        with _stats._lock:
            _stats.saved_secs += duration
    elif event == _EV_RETRIEVAL_SECS:
        with _stats._lock:
            _stats.retrieval_secs += duration


def _install_listeners() -> None:
    """Register jax monitoring listeners once per process."""
    global _listeners_installed
    if _listeners_installed:
        return
    from jax import monitoring
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _listeners_installed = True


def stats() -> CacheStats:
    """Consistent snapshot of this process's compile/cache counters."""
    return _stats.snapshot()


def reset_stats() -> None:
    with _stats._lock:
        _stats.hits = 0
        _stats.requests = 0
        _stats.backend_compile_secs = 0.0
        _stats.saved_secs = 0.0
        _stats.retrieval_secs = 0.0


def status_word() -> str:
    """One word for the bench JSON line: ``hit`` (the persistent cache
    served at least one program this process), ``miss`` (active but
    everything compiled fresh), ``off`` (no cache active)."""
    if _active_dir is None:
        return "off"
    s = stats()
    if s.hits > 0:
        return "hit"
    return "miss"


def publish_metrics(registry) -> None:
    """Mirror the cumulative stats into the metrics plane (called from
    ``MetricsRegistry.snapshot`` when this module is loaded)."""
    s = stats()
    registry.gauge("rlt_compile_cache_hits_total").set(s.hits)
    registry.gauge("rlt_compile_cache_misses_total").set(s.misses)
    registry.gauge("rlt_compile_seconds_total").set(
        round(s.backend_compile_secs, 6))


# -- startup overlap bookkeeping ------------------------------------------

def note_first_step(seconds: float) -> None:
    """Record time-to-first-step into the metrics plane (the trainer
    calls this once per fit; chip_smoke.py reads the trainer
    attribute)."""
    from ray_lightning_tpu.telemetry import metrics as _metrics
    reg = _metrics.get_registry()
    if reg is not None:
        reg.gauge("rlt_time_to_first_step_seconds").set(round(seconds, 6))


__all__ = [
    "CompileCacheConfig",
    "DEFAULT_DIR",
    "ENV_JAX_DIR",
    "ENV_KNOBS",
    "activate",
    "deactivate",
    "active_dir",
    "stats",
    "reset_stats",
    "status_word",
    "publish_metrics",
    "note_first_step",
    "CacheStats",
]
