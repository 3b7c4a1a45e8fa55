"""Compile-plane self-check (format.sh --check / tests).

Validates, without initializing any jax backend, that the compile
plane's user-facing surface is internally consistent: env-knob parsing
round-trips through ``worker_env``, the pack/unpack seeding path
round-trips bytes, and the metric names the plane publishes are
registered in the metrics plane's lint surface (so ``/metrics`` can
never emit an unscrapable compile series).  Exits nonzero on any
violation — same contract as the metrics-name lint it runs beside.
"""

from __future__ import annotations

import os
import tempfile

#: metric names compile/cache.py publishes (publish_metrics +
#: note_first_step); must all be declared in telemetry.metrics
#: CORE_METRICS so the name lint covers them
PUBLISHED_METRICS = (
    "rlt_compile_cache_hits_total",
    "rlt_compile_cache_misses_total",
    "rlt_compile_seconds_total",
    "rlt_time_to_first_step_seconds",
)


def run_selfcheck() -> list[str]:
    """Returns the list of violations (empty = clean)."""
    from ray_lightning_tpu.compile import cache, shipping
    from ray_lightning_tpu.telemetry import metrics as tmetrics

    problems: list[str] = []

    # 1. every published metric is in CORE_METRICS and Prometheus-clean
    for name in PUBLISHED_METRICS:
        if name not in tmetrics.CORE_METRICS:
            problems.append(
                f"compile plane publishes {name!r} but it is missing "
                f"from telemetry.metrics.CORE_METRICS")
        try:
            tmetrics.validate_metric_name(name)
        except ValueError as e:
            problems.append(str(e))

    # 2. env-knob round-trip: a config built from env reproduces itself
    #    through worker_env (what the plugin ships to workers); the
    #    default is ON at the fixed in-checkout directory; and a set
    #    JAX_COMPILATION_CACHE_DIR outranks every knob without ever
    #    being restated to workers (they inherit it)
    names = cache.ENV_KNOBS + (cache.ENV_JAX_DIR,)
    saved = {k: os.environ.get(k) for k in names}
    try:
        for k in names:
            os.environ.pop(k, None)
        cfg0 = cache.CompileCacheConfig.resolve(None)
        if not (cfg0.enabled and cfg0.root == cache.DEFAULT_DIR):
            problems.append(f"default is not ON at DEFAULT_DIR: {cfg0}")
        os.environ[cache.ENV_ENABLE] = "1"
        os.environ[cache.ENV_DIR] = "/explicit/cache"
        os.environ[cache.ENV_MIN_ENTRY] = "1024"
        os.environ[cache.ENV_MIN_COMPILE] = "0.25"
        cfg = cache.CompileCacheConfig.resolve(None)
        if not (cfg.enabled and cfg.root == "/explicit/cache"
                and cfg.min_entry_bytes == 1024
                and cfg.min_compile_secs == 0.25):
            problems.append(f"env resolution broken: {cfg}")
        env = cfg.worker_env()
        for k in cache.ENV_KNOBS:
            os.environ.pop(k, None)
        os.environ.update(env)
        cfg2 = cache.CompileCacheConfig.resolve(None)
        if cfg2 != cfg:
            problems.append(
                f"worker_env round-trip drifted: {cfg} -> {cfg2}")
        os.environ[cache.ENV_JAX_DIR] = "/placed/from/outside"
        if cfg2.root != "/placed/from/outside":
            problems.append(
                f"{cache.ENV_JAX_DIR} did not outrank {cache.ENV_DIR}")
        if "/placed/from/outside" in cfg2.worker_env().values():
            problems.append(
                f"worker_env restates {cache.ENV_JAX_DIR}; workers "
                f"inherit it")
        os.environ[cache.ENV_ENABLE] = "0"
        off = cache.CompileCacheConfig.resolve(None)
        if off.enabled:
            problems.append(f"{cache.ENV_ENABLE}=0 failed to disable")
        if off.worker_env().get(cache.ENV_ENABLE) != "0":
            problems.append("an off config does not switch its workers "
                            "off (the default is ON)")
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    # 3. pack/unpack round-trip (the worker seeding path)
    with tempfile.TemporaryDirectory(prefix="rlt_selfcheck_") as d:
        src = os.path.join(d, "src")
        os.makedirs(os.path.join(src, "sub"))
        with open(os.path.join(src, "sub", "entry"), "wb") as f:
            f.write(b"x" * 128)
        blob = shipping.pack_cache_dir(src)
        if blob is None:
            problems.append("pack_cache_dir returned None for a "
                            "populated dir")
        else:
            dst = os.path.join(d, "dst")
            n = shipping.unpack_cache_dir(blob, dst)
            target = os.path.join(dst, "sub", "entry")
            if n != 1 or not os.path.isfile(target) \
                    or os.path.getsize(target) != 128:
                problems.append("pack/unpack round-trip corrupted the "
                                "cache entry")

    return problems


def _main(argv: list[str]) -> int:
    problems = run_selfcheck()
    for p in problems:
        print(f"compile selfcheck: {p}")
    if not problems:
        print("compile selfcheck: env knobs, metric names and cache "
              "seeding consistent")
    return 1 if problems else 0


if __name__ == "__main__":   # pragma: no cover - exercised via format.sh
    import sys
    sys.exit(_main(sys.argv[1:]))
