"""Compile-plane tests: persistent XLA compilation cache + AOT
precompile (ray_lightning_tpu/compile/).

The load-bearing assertion is the cold→warm A/B across real process
boundaries: two subprocess fits sharing one cache dir, where the warm
one records cache hits, spends a fraction of the cold one's
backend-compile seconds, and reaches its first step faster — the
multiplied-by-trial-count cost the compile plane exists to remove.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from ray_lightning_tpu import Trainer
from ray_lightning_tpu import tune
from ray_lightning_tpu.compile import cache as cc
from ray_lightning_tpu.compile import shipping
from ray_lightning_tpu.compile.aot import (
    AotPrecompiler,
    global_batch_abstract,
    stack_abstract,
)
from ray_lightning_tpu.core.trainer import _cache_bytes_estimate
from ray_lightning_tpu.models import BoringModel


@pytest.fixture(autouse=True)
def _isolate_cache_state():
    """Each test starts from a clean compile-plane state and leaves no
    active cache dir behind for unrelated tests."""
    cc.reset_stats()
    yield
    cc.deactivate()


# ---------------------------------------------------------------------------
# config / env resolution
# ---------------------------------------------------------------------------

def _clear_env(monkeypatch):
    for k in cc.ENV_KNOBS + (cc.ENV_JAX_DIR,):
        monkeypatch.delenv(k, raising=False)


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_config_default_on_at_fixed_checkout_dir(monkeypatch):
    """Unset environment: the cache is ON at ONE fixed path inside the
    checkout — not under $HOME, not a temp, pid or time-stamped name."""
    _clear_env(monkeypatch)
    cfg = cc.CompileCacheConfig.resolve(None)
    assert cfg.enabled
    assert cfg.root == cc.DEFAULT_DIR == os.path.join(_REPO_ROOT,
                                                      ".jax_cache")
    assert cc.CompileCacheConfig.resolve(None).root == cfg.root


def test_config_env_enable_forms(monkeypatch, tmp_path):
    _clear_env(monkeypatch)
    monkeypatch.setenv(cc.ENV_ENABLE, "1")
    cfg = cc.CompileCacheConfig.resolve(None)
    assert cfg.enabled and cfg.root == cc.DEFAULT_DIR

    monkeypatch.setenv(cc.ENV_ENABLE, str(tmp_path / "root"))
    cfg = cc.CompileCacheConfig.resolve(None)
    assert cfg.enabled and cfg.root == str(tmp_path / "root")

    monkeypatch.setenv(cc.ENV_ENABLE, "0")
    monkeypatch.setenv(cc.ENV_DIR, str(tmp_path))
    assert not cc.CompileCacheConfig.resolve(None).enabled  # 0 kills all


def test_config_env_dir_and_knobs(monkeypatch, tmp_path):
    _clear_env(monkeypatch)
    monkeypatch.setenv(cc.ENV_DIR, str(tmp_path))
    monkeypatch.setenv(cc.ENV_MIN_ENTRY, "2048")
    monkeypatch.setenv(cc.ENV_MIN_COMPILE, "0.5")
    cfg = cc.CompileCacheConfig.resolve(None)
    assert cfg.enabled and cfg.root == str(tmp_path)
    assert cfg.min_entry_bytes == 2048
    assert cfg.min_compile_secs == 0.5


def test_config_explicit_arg_forms(monkeypatch, tmp_path):
    _clear_env(monkeypatch)
    assert not cc.CompileCacheConfig.resolve(False).enabled
    assert cc.CompileCacheConfig.resolve(True).enabled
    cfg = cc.CompileCacheConfig.resolve(str(tmp_path))
    assert cfg.enabled and cfg.root == str(tmp_path)
    cfg = cc.CompileCacheConfig.resolve(
        {"dir": str(tmp_path), "min_entry_bytes": 7})
    assert cfg.enabled and cfg.min_entry_bytes == 7
    with pytest.raises(TypeError):
        cc.CompileCacheConfig.resolve(3.14)


def test_worker_env_round_trip(monkeypatch, tmp_path):
    _clear_env(monkeypatch)
    cfg = cc.CompileCacheConfig(enabled=True, dir=str(tmp_path),
                                min_entry_bytes=64, min_compile_secs=0.1)
    for k, v in cfg.worker_env().items():
        monkeypatch.setenv(k, v)
    assert cc.CompileCacheConfig.resolve(None) == cfg
    # the default is ON, so an off driver must say so to its workers
    _clear_env(monkeypatch)
    for k, v in cc.CompileCacheConfig(enabled=False).worker_env().items():
        monkeypatch.setenv(k, v)
    assert not cc.CompileCacheConfig.resolve(None).enabled


@pytest.mark.parametrize("how", ["arg", "rlt_dir", "rlt_enable_path",
                                 "default"])
def test_jax_env_dir_outranks_everything(monkeypatch, tmp_path, how):
    """``JAX_COMPILATION_CACHE_DIR`` set: the cache lives THERE — no
    argument, knob or default re-points it, the worker env never
    restates it (workers inherit the variable), and activation hands
    jax exactly that directory."""
    _clear_env(monkeypatch)
    outside = str(tmp_path / "placed_from_outside")
    other = str(tmp_path / "other")
    monkeypatch.setenv(cc.ENV_JAX_DIR, outside)
    if how == "arg":
        cfg = cc.CompileCacheConfig.resolve(other)
    elif how == "rlt_dir":
        monkeypatch.setenv(cc.ENV_DIR, other)
        cfg = cc.CompileCacheConfig.resolve(None)
    elif how == "rlt_enable_path":
        monkeypatch.setenv(cc.ENV_ENABLE, other)
        cfg = cc.CompileCacheConfig.resolve(None)
    else:
        cfg = cc.CompileCacheConfig.resolve(None)
    assert cfg.enabled and cfg.root == outside
    assert outside not in cfg.worker_env().values()
    assert cc.activate(cfg) == outside == cc.active_dir()
    assert jax.config.jax_compilation_cache_dir == outside
    assert not os.path.exists(other)
    # switching off leaves the directory setting alone
    cc.deactivate()
    assert jax.config.jax_compilation_cache_dir == outside
    assert not jax.config.jax_enable_compilation_cache


# ---------------------------------------------------------------------------
# cache seeding (shipping)
# ---------------------------------------------------------------------------

def test_pack_unpack_round_trip(tmp_path):
    src = tmp_path / "src"
    (src / "sub").mkdir(parents=True)
    (src / "a").write_bytes(b"alpha")
    (src / "sub" / "b").write_bytes(b"beta" * 100)
    blob = shipping.pack_cache_dir(str(src))
    assert blob is not None
    dst = tmp_path / "dst"
    assert shipping.unpack_cache_dir(blob, str(dst)) == 2
    assert (dst / "a").read_bytes() == b"alpha"
    assert (dst / "sub" / "b").read_bytes() == b"beta" * 100
    # additive: an existing (newer) entry is never overwritten
    (dst / "a").write_bytes(b"newer")
    assert shipping.unpack_cache_dir(blob, str(dst)) == 0
    assert (dst / "a").read_bytes() == b"newer"


def test_pack_empty_and_missing(tmp_path):
    assert shipping.pack_cache_dir(str(tmp_path / "nope")) is None
    empty = tmp_path / "empty"
    empty.mkdir()
    assert shipping.pack_cache_dir(str(empty)) is None


def test_pack_cap_keeps_newest(tmp_path):
    src = tmp_path / "src"
    src.mkdir()
    (src / "old").write_bytes(b"x" * 600)
    os.utime(src / "old", (1, 1))
    (src / "new").write_bytes(b"y" * 600)
    blob = shipping.pack_cache_dir(str(src), max_bytes=1000)
    dst = tmp_path / "dst"
    shipping.unpack_cache_dir(blob, str(dst))
    assert (dst / "new").exists() and not (dst / "old").exists()


# ---------------------------------------------------------------------------
# AOT precompiler
# ---------------------------------------------------------------------------

def test_aot_precompile_and_dispatch():
    jitted = jax.jit(lambda x: x * 2 + 1)
    pre = AotPrecompiler()
    pre.submit("double", jitted,
               (jax.ShapeDtypeStruct((4,), np.float32),))
    results = pre.barrier(timeout=60)
    assert pre.succeeded("double"), results
    out = jitted(np.ones((4,), np.float32))
    np.testing.assert_allclose(np.asarray(out), np.full((4,), 3.0))


def test_aot_thread_traces_under_the_submitters_mesh():
    """The current mesh is thread-local, and jax caches a program's
    trace per avals: a step traced on the precompile thread WITHOUT the
    mesh is the step the fit then runs.  (Found on four chips: the
    sharded fits had silently lost the flash kernel.)"""
    from ray_lightning_tpu.parallel.mesh import (build_device_mesh,
                                                 get_current_mesh,
                                                 set_current_mesh)
    mesh = build_device_mesh(("data",), {"data": 2}, jax.devices()[:2])
    seen = []

    def fn(x):
        seen.append(get_current_mesh())
        return x + 1

    set_current_mesh(mesh)
    try:
        pre = AotPrecompiler()
        pre.submit("meshy", jax.jit(fn),
                   (jax.ShapeDtypeStruct((4,), np.float32),))
        pre.barrier(timeout=60)
    finally:
        set_current_mesh(None)
    assert pre.succeeded("meshy"), pre.results
    assert seen == [mesh]


def test_aot_failure_is_soft():
    pre = AotPrecompiler()
    pre.submit("bad", jax.jit(lambda x: x), ("not-an-aval",))
    results = pre.barrier(timeout=60)
    assert not pre.succeeded("bad")
    assert isinstance(results["bad"], Exception)


def test_aot_disabled_noop(monkeypatch):
    monkeypatch.setenv("RLT_AOT_PRECOMPILE", "0")
    pre = AotPrecompiler.resolve()
    assert not pre.enabled
    pre.submit("x", None, ())        # must not touch the dead jitted
    assert pre.barrier(timeout=1) == {}


def test_abstract_helpers():
    batch = {"x": np.zeros((4, 3), np.float32),
             "n": np.int32(7)}
    ab = global_batch_abstract(batch, process_count=1)
    assert ab["x"].shape == (4, 3) and ab["n"].shape == ()
    ab2 = global_batch_abstract(batch, process_count=4)
    assert ab2["x"].shape == (16, 3)      # dim 0 scales; scalars don't
    assert ab2["n"].shape == ()
    st = stack_abstract(ab, 5)
    assert st["x"].shape == (5, 4, 3) and st["x"].dtype == np.float32


# ---------------------------------------------------------------------------
# trainer integration (in-process)
# ---------------------------------------------------------------------------

def _fit(tmp_path, cache_dir, **kw):
    trainer = Trainer(max_steps=3, enable_checkpointing=False,
                      num_sanity_val_steps=0, limit_val_batches=0,
                      default_root_dir=str(tmp_path),
                      compile_cache=str(cache_dir), **kw)
    trainer.fit(BoringModel())
    return trainer


def test_fit_records_first_step_and_precompiles(tmp_path):
    trainer = _fit(tmp_path, tmp_path / "cache")
    assert trainer.time_to_first_step is not None
    assert trainer.time_to_first_step > 0
    assert trainer._precompiler.succeeded("train_step"), \
        trainer._precompiler.results
    ns = cc.active_dir()
    assert ns == str(tmp_path / "cache")    # the directory itself
    assert os.listdir(ns)           # entries persisted
    assert cc.stats().requests > 0


def test_second_fit_hits_cache_in_process(tmp_path):
    _fit(tmp_path, tmp_path / "cache")
    before = cc.stats()
    t2 = _fit(tmp_path, tmp_path / "cache")
    after = cc.stats()
    # a fresh Trainer builds fresh jit objects: same programs, new
    # requests — served from the persistent cache, not recompiled
    assert after.hits > before.hits
    assert t2.time_to_first_step is not None


def test_chunked_fit_precompiles_multi_step(tmp_path):
    trainer = Trainer(max_steps=4, steps_per_execution=2,
                      enable_checkpointing=False, num_sanity_val_steps=0,
                      limit_val_batches=0, default_root_dir=str(tmp_path),
                      compile_cache=str(tmp_path / "cache"))
    trainer.fit(BoringModel(batch_size=8))
    assert trainer.global_step == 4
    assert trainer._precompiler.succeeded("multi_step"), \
        trainer._precompiler.results


def test_cached_dataset_fit_precompiles_cached_steps(tmp_path):
    trainer = Trainer(max_steps=4, steps_per_execution=2,
                      cache_train_dataset=True,
                      enable_checkpointing=False, num_sanity_val_steps=0,
                      limit_val_batches=0, default_root_dir=str(tmp_path),
                      compile_cache=str(tmp_path / "cache"))
    trainer.fit(BoringModel(batch_size=8))
    assert trainer.global_step == 4
    res = trainer._precompiler.results
    assert trainer._precompiler.succeeded("cached_single"), res
    assert trainer._precompiler.succeeded("cached_multi"), res


def test_metrics_plane_exports_compile_counters(tmp_path):
    from ray_lightning_tpu.telemetry import metrics as tmetrics
    reg = tmetrics.enable_metrics(pump=False)
    try:
        _fit(tmp_path, tmp_path / "cache")
        names = {m["name"] for m in reg.snapshot()}
    finally:
        tmetrics.disable_metrics()
    assert {"rlt_compile_cache_hits_total",
            "rlt_compile_cache_misses_total",
            "rlt_compile_seconds_total"} <= names


# ---------------------------------------------------------------------------
# cold → warm across process boundaries (the acceptance A/B)
# ---------------------------------------------------------------------------

_CHILD = """\
import json, sys
from ray_lightning_tpu import Trainer
from ray_lightning_tpu.compile import cache as cc
from ray_lightning_tpu.models import BoringModel

batch = int(sys.argv[1])
trainer = Trainer(max_steps=3, enable_checkpointing=False,
                  num_sanity_val_steps=0, limit_val_batches=0)
trainer.fit(BoringModel(dataset_length=32, batch_size=batch))
s = cc.stats()
print(json.dumps({"ttfs": trainer.time_to_first_step, "hits": s.hits,
                  "misses": s.misses,
                  "compile_secs": s.backend_compile_secs}))
"""


def _run_child(tmp_path, cache_dir, batch=2):
    script = tmp_path / "child.py"
    script.write_text(_CHILD)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
           # placed from outside, the way a chip run places it; the
           # conftest opt-out must not reach this child
           "JAX_COMPILATION_CACHE_DIR": str(cache_dir),
           "RLT_COMPILE_CACHE": "1",
           "PYTHONPATH": repo_root + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    out = subprocess.run(
        [sys.executable, str(script), str(batch)],
        capture_output=True, text=True, cwd=str(tmp_path), timeout=300,
        env=env)
    assert out.returncode == 0, f"child failed:\n{out.stdout}\n{out.stderr}"
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("{")][-1]
    return json.loads(line)


def test_cold_then_warm_across_processes(tmp_path):
    """Same process tree torn down between fits, cache dir retained:
    the warm process must record cache hits, spend a fraction of the
    cold one's XLA compile seconds, and start stepping sooner; a shape
    change must miss (fresh programs compile)."""
    cache_dir = tmp_path / "cache"
    cold = _run_child(tmp_path, cache_dir)
    # fresh dir: every program misses (a stray in-process hit can come
    # from byte-identical duplicate programs within the cold run itself)
    assert cold["misses"] > 0, cold

    warm = _run_child(tmp_path, cache_dir)
    assert warm["hits"] > cold["hits"], (cold, warm)
    assert warm["compile_secs"] < cold["compile_secs"] * 0.5, (cold, warm)
    assert warm["ttfs"] < cold["ttfs"], (cold, warm)

    reshaped = _run_child(tmp_path, cache_dir, batch=4)
    assert reshaped["misses"] > 0, reshaped


# ---------------------------------------------------------------------------
# tune: shared cache across trials and restarts
# ---------------------------------------------------------------------------

def _tune_trainable(config, checkpoint_dir=None):
    trainer = Trainer(max_steps=2, enable_checkpointing=False,
                      num_sanity_val_steps=0, limit_val_batches=0,
                      default_root_dir=tune.get_trial_dir())
    trainer.fit(BoringModel())
    tune.report(loss=float(trainer.callback_metrics.get("loss", 0.0)))


@pytest.fixture
def placed_cache(monkeypatch, tmp_path):
    """The cache placed from outside, as a chip run places it (and the
    conftest opt-out lifted)."""
    d = tmp_path / "placed_cache"
    monkeypatch.setenv(cc.ENV_JAX_DIR, str(d))
    monkeypatch.setenv(cc.ENV_ENABLE, "1")
    return d


def test_tune_trials_share_compile_cache(tmp_path, seed, placed_cache):
    before = cc.stats()
    analysis = tune.run(_tune_trainable, config={}, num_samples=2,
                        metric="loss", mode="min",
                        local_dir=str(tmp_path), name="cc_exp")
    assert all(t.status == "TERMINATED" for t in analysis.trials)
    after = cc.stats()
    # trial 1 rebuilt every jit object; its programs came off trial 0's
    # persistent cache instead of recompiling
    assert after.hits > before.hits
    # experiments share THE cache directory: nothing per-experiment
    # (a directory named after the experiment never hits again)
    assert os.listdir(placed_cache)
    assert not os.path.exists(os.path.join(str(tmp_path), "cc_exp",
                                           "compile_cache"))


def test_tune_restart_resumes_warm(tmp_path, seed, placed_cache):
    attempts = []

    def flaky(config, checkpoint_dir=None):
        _tune_trainable(config, checkpoint_dir)
        attempts.append(cc.stats().hits)
        if len(attempts) == 1:
            raise RuntimeError("boom after first fit")

    analysis = tune.run(flaky, config={}, num_samples=1, max_failures=1,
                        metric="loss", mode="min",
                        local_dir=str(tmp_path), name="cc_restart")
    assert analysis.trials[0].status == "TERMINATED"
    assert len(attempts) == 2
    # the retry's fit hit the cache the crashed attempt populated
    assert attempts[1] > attempts[0]


def test_tune_cache_optout(tmp_path, monkeypatch, seed, placed_cache):
    monkeypatch.setenv("RLT_COMPILE_CACHE", "0")
    tune.run(_tune_trainable, config={}, num_samples=1,
             metric="loss", mode="min",
             local_dir=str(tmp_path), name="cc_off")
    assert not os.path.exists(placed_cache)


# ---------------------------------------------------------------------------
# satellites: advisor r5 fixes
# ---------------------------------------------------------------------------

class _Loader:
    def __init__(self, n, shuffle=False):
        self._n = n
        self.shuffle = shuffle

    def __len__(self):
        return self._n


def test_cache_bytes_estimate_ignores_limit_and_doubles_shuffle():
    batch = {"x": np.zeros((4, 8), np.float32)}     # 128 bytes
    # the flat upload covers the FULL dataset: limit_train_batches must
    # not shrink the debit (the old signature took and applied a limit)
    assert _cache_bytes_estimate(_Loader(10), batch) == 10 * 128
    # shuffling keeps flat + repacked resident: double
    assert _cache_bytes_estimate(_Loader(10, shuffle=True), batch) \
        == 2 * 10 * 128
    # length-less loaders stay un-estimable (caller donates)
    assert _cache_bytes_estimate(iter(()), batch) is None


def test_slots_callback_batch_hook_plan():
    """A callback instance without a __dict__ (all-slots hierarchy)
    must not crash the hook plan (advisor r5 low: ``vars(cb)`` raised
    TypeError for it; ``Callback`` subclasses always inherit a __dict__,
    so the duck-typed case is exactly where this bites)."""

    class SlotsCb:
        __slots__ = ()

        def on_train_batch_end(self, trainer, module, metrics, batch,
                               batch_idx):
            pass

    cb = SlotsCb()
    with pytest.raises(TypeError):
        vars(cb)                     # the shape the old probe choked on
    trainer = Trainer(enable_checkpointing=False)
    trainer.callbacks = [cb]
    invoke, materialize = trainer._batch_hook_plan()
    assert invoke                    # override detected
    assert materialize               # conservative default: batch needed


def test_slots_callback_respects_class_needs_batch_flag():
    """The slots-safe probe still honors a class-level needs_batch=False
    declared alongside the overriding hook."""

    class SlotsCb:
        __slots__ = ()
        needs_batch = False

        def on_train_batch_end(self, trainer, module, metrics, batch,
                               batch_idx):
            pass

    trainer = Trainer(enable_checkpointing=False)
    trainer.callbacks = [SlotsCb()]
    invoke, materialize = trainer._batch_hook_plan()
    assert invoke and not materialize
