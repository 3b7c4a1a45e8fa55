"""Compile plane: persistent XLA compilation cache + AOT precompile.

Makes compilation a cached, overlapped, shared resource instead of a
per-process tax (the biggest framework-controlled wall-clock cost once
steady-state step time sits at raw-JAX parity):

- ``cache.py`` — lifecycle of JAX's persistent compilation cache:
  config/env resolution, the one cache directory
  (``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``), hit/miss
  and compile-seconds accounting surfaced through the metrics plane.
- ``aot.py`` — background lower+compile of the step programs from their
  ``eval_shape`` avals, overlapped with state init, the rendezvous and
  the device-resident dataset upload.
- ``shipping.py`` — cache-dir seeding for cluster backends without a
  shared filesystem.

Wired through ``core/trainer.py`` (activation + AOT submission +
time-to-first-step), ``core/loop_engine.py`` (cached-step programs
submit when their shapes become known), ``plugins/xla.py`` (worker env
+ seeding); tune trials share the same directory as everything else.
"""

from ray_lightning_tpu.compile.cache import (  # noqa: F401
    CacheStats,
    CompileCacheConfig,
    DEFAULT_DIR,
    activate,
    active_dir,
    deactivate,
    note_first_step,
    publish_metrics,
    reset_stats,
    stats,
    status_word,
)
from ray_lightning_tpu.compile.aot import (  # noqa: F401
    AotPrecompiler,
    global_batch_abstract,
    stack_abstract,
)

__all__ = [
    "CacheStats",
    "CompileCacheConfig",
    "DEFAULT_DIR",
    "activate",
    "active_dir",
    "deactivate",
    "note_first_step",
    "publish_metrics",
    "reset_stats",
    "stats",
    "status_word",
    "AotPrecompiler",
    "global_batch_abstract",
    "stack_abstract",
]
