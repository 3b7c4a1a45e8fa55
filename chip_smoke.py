#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

Run from the repo root on a machine with a TPU::

    python3 chip_smoke.py            # one chip: train, then serve
    python3 chip_smoke.py --chips 4  # one host, four chips: the sharded
                                     # fits and what they are compared to

One chip (what the driver runs).  Two phases, each in a fresh child
process, one after the other, because a chip belongs to one process at a
time and THIS process never initialises a JAX backend:

- ``train``: ``Trainer(...).fit(GPTLightningModule("gpt2-small",
  batch_size=8))`` for a handful of steps in the child.  Passes when the
  losses are finite and falling, the compiled step program contains the
  Pallas flash kernel, and the checkpoint the fit wrote reads back equal
  to the trained weights.
- ``serve``: ``Server(module, checkpoint=<that checkpoint>, use_tpu=True,
  max_batch_slots=8).start()`` — the child stays off JAX, the server's
  own worker is the only process on the chip — answers prompts of
  different lengths; then a second server forced to the dense einsum
  answers the same prompts.  Passes when the tokens are equal, the
  decode program lowered the Pallas decode kernel, and nothing retraced
  after warm-up.

``--chips 4`` (run by hand; the driver never passes it) runs only the
multi-chip paths and their reference, each leg in its own child: the
one-chip fit (process scoped to chip 0), the same fit over a 4-device
mesh under ``zero1`` and under ``fsdp``, and the actor path
(``RayXlaShardedPlugin(num_workers=4, use_tpu=True,
devices_per_worker=1)``, each worker scoped to its chip).  Passes when
the losses agree with the one-chip fit, every device holds its shard of
the state, and the step programs contain the expected collectives.

Every phase prints one JSON line; the LAST line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
with the device as JAX reported it in the process that held the chip.
Any failed phase, a platform other than ``tpu``, or a kernel that was
requested but not lowered exits non-zero and prints no such line.  No
CPU run, no smaller model, no interpret mode: there is no option for
them (tests/test_chip_compile.py rehearses the phases on the CPU by
patching the constants below from inside the test).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))

# -- what the smoke runs ----------------------------------------------------
MODEL = "gpt2-small"        # 12 x 768, 12 heads, T=1024, vocab 50304
BATCH = 8
SEED = 0
TRAIN_STEPS = 24
PLATFORM = "tpu"            # what the process that holds the chip must see
KERNEL_MARKER = "tpu_custom_call"   # a Mosaic kernel in compiled HLO text
SERVER_KW = {"use_tpu": True}
DECODE_ENV: dict = {}       # default server: decode impl "auto"
DECODE_KERNEL = "flash_decode"      # ...which must lower this kernel
SLOTS = 8
PROMPT_LENS = (7, 48, 300, 900)     # four different prefill buckets
NEW_TOKENS = 16
MESH_STEPS = 12             # --chips 4 legs
#: losses of the sharded legs vs the one-chip fit, relative — the bar
#: ``__graft_entry__.dryrun_multichip`` holds its equality legs to
LOSS_RTOL = 5e-3
PHASE_TIMEOUT_S = {"train": 700, "serve": 800, "one_chip": 500,
                   "zero1": 600, "fsdp": 600, "actors": 400}
EXIT_NO_ACCELERATOR = 2


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


class NoAccelerator(SmokeFailure):
    """The process that should hold the chip does not see one."""


def _check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- helpers that run in the process holding the chip -----------------------

def _claim_device() -> dict:
    """Initialise JAX here and report the device; refuse anything but
    the accelerator."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != PLATFORM:
        raise NoAccelerator(
            f"JAX found no {PLATFORM} device: platform={dev.platform!r} "
            f"kind={dev.device_kind!r} count={len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def _memory_stats() -> list:
    """``memory_stats()`` of every device, as the runtime reports it."""
    import jax
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
    out = []
    for d in jax.devices():
        ms = d.memory_stats()
        out.append(None if ms is None
                   else {k: int(ms[k]) for k in keep if k in ms})
    return out


def _cache_report() -> dict:
    from ray_lightning_tpu.compile import cache as compile_cache
    s = compile_cache.stats()
    return {"dir": compile_cache.active_dir(), "hits": s.hits,
            "misses": s.misses,
            "backend_compile_secs": round(s.backend_compile_secs, 2)}


def _step_text(trainer) -> str:
    """Compiled HLO text of the fit's train step (through the persistent
    cache: the program was compiled for the fit already)."""
    return trainer._train_step.lower(
        trainer._abstract_state, trainer._abstract_batch).compile().as_text()


def _model_flops_per_token(cfg) -> float:
    """6 FLOPs per parameter and token for the matmuls (embedding table
    counted once, as the tied head) plus causal attention's
    6 * n_layer * T * n_embd — what forward and backward require, no
    recompute."""
    n = 12 * cfg.n_layer * cfg.n_embd ** 2 + cfg.vocab_size * cfg.n_embd
    return 6.0 * n + 6.0 * cfg.n_layer * cfg.block_size * cfg.n_embd


def _fit(workdir: str, name: str, steps: int, *, batch_size: int = BATCH,
         **trainer_kw):
    """One seeded fit through ``Trainer.fit``; returns (trainer, module,
    losses, warm seconds per step).  The device is kept busy: the losses
    stay on it until the fit is over, and the host waits for it twice
    only — half way (where the warm window opens) and at the last step
    (where it closes)."""
    import jax

    from ray_lightning_tpu import Trainer
    from ray_lightning_tpu.core.callbacks import Callback
    from ray_lightning_tpu.models.gpt import GPTLightningModule

    class Record(Callback):
        needs_batch = False

        def __init__(self):
            self.losses, self.marks = [], {}

        def on_train_batch_end(self, trainer, module, metrics, batch, idx):
            self.losses.append(metrics["loss"])
            if len(self.losses) in (steps // 2, steps):
                jax.block_until_ready(metrics["loss"])
                self.marks[len(self.losses)] = time.monotonic()

    rec = Record()
    module = GPTLightningModule(MODEL, batch_size=batch_size,
                                dataset_size=batch_size * steps)
    trainer = Trainer(max_steps=steps, max_epochs=1, callbacks=[rec],
                      num_sanity_val_steps=0, limit_val_batches=0,
                      log_every_n_steps=10 ** 9, seed=SEED,
                      default_root_dir=os.path.join(workdir, name),
                      **trainer_kw)
    trainer.fit(module)
    warm = ((rec.marks[steps] - rec.marks[steps // 2])
            / (steps - steps // 2))
    return trainer, module, [float(x) for x in rec.losses], warm


def _check_losses(losses, steps: int) -> None:
    import numpy as np
    _check(len(losses) == steps, f"ran {len(losses)} steps, wanted {steps}")
    _check(bool(np.all(np.isfinite(losses))), f"loss not finite: {losses}")
    k = max(1, steps // 4)
    _check(float(np.mean(losses[-k:])) < float(np.mean(losses[:k])),
           f"loss is not falling: {losses}")


# -- one chip: train ---------------------------------------------------------

def phase_train(workdir: str) -> dict:
    device = _claim_device()
    import jax
    import numpy as np

    from ray_lightning_tpu import Trainer, native
    from ray_lightning_tpu.telemetry.goodput import device_peak

    trainer, module, losses, step_s = _fit(workdir, "train", TRAIN_STEPS)
    _check_losses(losses, TRAIN_STEPS)
    _check(KERNEL_MARKER in _step_text(trainer),
           f"the flash-attention kernel was requested (attention_impl="
           f"{module.config.attention_impl!r}) but the compiled train "
           f"step holds no {KERNEL_MARKER}")

    # the checkpoint the fit wrote, read back
    ckpt = trainer.checkpoint_callback.best_model_path
    _check(ckpt and os.path.exists(ckpt), f"no checkpoint written: {ckpt!r}")
    loaded = Trainer.load_checkpoint_dict(ckpt)
    _check(int(loaded["global_step"]) == TRAIN_STEPS,
           f"checkpoint at step {loaded['global_step']}, not {TRAIN_STEPS}")
    trained = jax.tree_util.tree_leaves(module._trained_variables["params"])
    stored = jax.tree_util.tree_leaves(loaded["state"]["params"])
    _check(len(trained) == len(stored) and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(trained, stored)),
        "checkpoint params differ from the trained weights")

    cfg = module.config
    tokens_per_s = BATCH * cfg.block_size / step_s
    peak = device_peak(device["kind"])   # unknown kind: an error
    return {
        "phase": "train", "device": device, "model": MODEL,
        "steps": TRAIN_STEPS, "loss_first": losses[0],
        "loss_last": losses[-1], "losses": [round(x, 4) for x in losses],
        "time_to_first_step_s": round(trainer.time_to_first_step, 2),
        "step_s_warm": round(step_s, 5),   # mean of the second half
        "tokens_per_s": round(tokens_per_s, 1),
        "mfu": round(tokens_per_s * _model_flops_per_token(cfg)
                     / (peak["tflops_bf16"] * 1e12), 4),
        "peak_tflops_bf16": peak["tflops_bf16"],
        "attention_kernel": KERNEL_MARKER,
        "prefetcher": "native" if native.native_available() else "python",
        "checkpoint": ckpt,
        "checkpoint_bytes": os.path.getsize(ckpt),
        "compile_cache": _cache_report(),
        "memory_stats": _memory_stats(),
    }


# -- one chip: serve ---------------------------------------------------------

def _prompts():
    import numpy as np

    from ray_lightning_tpu.models.gpt import CONFIGS
    rng = np.random.default_rng(SEED)
    vocab = CONFIGS[MODEL].vocab_size
    return [rng.integers(0, vocab, size=n).astype(np.int32)
            for n in PROMPT_LENS]


def _serve_once(workdir: str, name: str, ckpt: str, worker_env: dict):
    """Start a server through its normal entry point, answer the
    prompts (queued together, then one blocking ``generate``), return
    (tokens per prompt, server stats)."""
    from ray_lightning_tpu.models.gpt import GPTLightningModule
    from ray_lightning_tpu.serve import Server

    prompts = _prompts()
    t0 = time.monotonic()
    with Server(GPTLightningModule(MODEL), checkpoint=ckpt,
                max_batch_slots=SLOTS, max_new_tokens=NEW_TOKENS, seed=SEED,
                default_root_dir=os.path.join(workdir, name),
                telemetry=False, worker_env=worker_env,
                **SERVER_KW) as server:
        ready_s = time.monotonic() - t0
        reqs = [server.submit(p) for p in prompts[:-1]]
        outs = [r.result(timeout=300) for r in reqs]
        outs.append(server.generate(prompts[-1], timeout=300))
        stats = server.stats()
    stats["ready_s"] = round(ready_s, 2)
    return [[int(t) for t in o] for o in outs], stats


def phase_serve(workdir: str) -> dict:
    from ray_lightning_tpu.models.gpt import CONFIGS

    with open(os.path.join(workdir, "train.json")) as f:
        ckpt = json.load(f)["checkpoint"]
    outs, stats = _serve_once(workdir, "serve", ckpt, dict(DECODE_ENV))
    ref, ref_stats = _serve_once(workdir, "serve_dense", ckpt,
                                 {"RLT_DECODE_IMPL": "dense"})

    worker = stats["workers"][0]
    device = worker["device"]
    _check(device["platform"] == PLATFORM,
           f"the serve worker ran on {device}, not on {PLATFORM}")
    vocab = CONFIGS[MODEL].vocab_size
    for n, o in zip(PROMPT_LENS, outs):
        _check(len(o) == NEW_TOKENS and all(0 <= t < vocab for t in o),
               f"prompt of {n} tokens: bad answer {o}")
    _check(worker["decode_kernel"] == DECODE_KERNEL,
           f"decode kernel {DECODE_KERNEL!r} was requested but the decode "
           f"program lowered {worker['decode_kernel']!r}")
    _check(ref_stats["workers"][0]["decode_kernel"] == "dense",
           f"the reference server lowered "
           f"{ref_stats['workers'][0]['decode_kernel']!r}, not dense")
    _check(outs == ref,
           f"tokens differ from the dense-einsum engine on the same "
           f"weights:\n kernel {outs}\n dense  {ref}")
    retraces = sum(worker["retraces"].values())
    _check(retraces == 0, f"retraced after warm-up: {worker['retraces']}")
    return {
        "phase": "serve", "device": device, "model": MODEL,
        "requests": len(outs), "prompt_lens": list(PROMPT_LENS),
        "new_tokens": NEW_TOKENS, "tokens_equal_dense": True,
        "decode_kernel": worker["decode_kernel"],
        "retraces_after_warmup": retraces,
        "programs": worker["programs"],
        "buckets": stats["setup"][0]["buckets"],
        "kv_shape": stats["setup"][0]["kv_shape"],
        "ready_s": stats["ready_s"], "ready_s_dense": ref_stats["ready_s"],
        "compile_cache": worker["compile_cache"],
        "compile_cache_dense": ref_stats["workers"][0]["compile_cache"],
        "memory_stats": worker["memory_stats"],
        "answers": outs,
    }


# -- four chips --------------------------------------------------------------

def _state_bytes_per_device(state) -> dict:
    """Bytes of the TrainState each device holds (its shards)."""
    import jax
    held: dict = {}
    for leaf in jax.tree_util.tree_leaves(state):
        for s in leaf.addressable_shards:
            held[s.device.id] = held.get(s.device.id, 0) + s.data.nbytes
    return held


def _count_collectives(text: str) -> dict:
    """Collective ops in compiled HLO text.  The TPU compiler fuses a
    reduce-scatter into a computation it names ``all-reduce-scatter``."""
    import re
    out = {op: len(re.findall(rf" {op}(?:-start)?\(", text))
           for op in ("all-reduce", "reduce-scatter", "all-gather")}
    out["reduce-scatter"] += len(
        re.findall(r"^%all-reduce-scatter[.\d]* \(", text, re.M))
    return out


def _fit_record(name: str, trainer, losses, step_s: float,
                device: dict) -> dict:
    import jax
    text = _step_text(trainer)
    full = sum(leaf.nbytes
               for leaf in jax.tree_util.tree_leaves(trainer.state))
    return {
        "phase": name, "device": device, "model": MODEL,
        "steps": len(losses), "losses": [round(x, 5) for x in losses],
        # the fit's own figure (the epoch mean), what the actor leg can
        # report too: the number the legs are compared on
        "loss": float(trainer.callback_metrics["loss"]),
        "step_s_warm": round(step_s, 5),   # mean of the second half
        "mesh": {k: int(v) for k, v in trainer._mesh.shape.items()},
        "collectives": _count_collectives(text),
        "flash_kernel": KERNEL_MARKER in text,
        "state_bytes_full": int(full),
        "state_bytes_per_device": _state_bytes_per_device(trainer.state),
        "time_to_first_step_s": round(trainer.time_to_first_step, 2),
        "compile_cache": _cache_report(),
        "memory_stats": _memory_stats(),
    }


def phase_one_chip(workdir: str) -> dict:
    """The comparison: the same fit on ONE chip of the host.  The process
    scopes itself to chip 0 the way the actor plugin scopes a worker."""
    from ray_lightning_tpu.cluster.protocol import find_free_port
    from ray_lightning_tpu.utils.tpu_topology import partition_env
    if PLATFORM == "tpu":
        os.environ.update(partition_env(1, 0, "127.0.0.1",
                                        [find_free_port()]))
    device = _claim_device()
    _check(device["count"] == 1 or PLATFORM != "tpu",
           f"scoped to chip 0 but JAX sees {device['count']} devices")
    trainer, _, losses, step_s = _fit(workdir, "one_chip", MESH_STEPS,
                                      enable_checkpointing=False)
    _check_losses(losses, MESH_STEPS)
    return _fit_record("one_chip", trainer, losses, step_s, device)


def _phase_mesh(workdir: str, strategy: str) -> dict:
    device = _claim_device()
    _check(device["count"] == 4 or PLATFORM != "tpu",
           f"--chips 4 needs four devices in one process; JAX sees "
           f"{device['count']}")
    trainer, _, losses, step_s = _fit(workdir, strategy, MESH_STEPS,
                                      strategy=strategy,
                                      enable_checkpointing=False)
    _check_losses(losses, MESH_STEPS)
    rec = _fit_record(strategy, trainer, losses, step_s, device)
    n = trainer._mesh.devices.size
    held = rec["state_bytes_per_device"]
    _check(len(held) == n, f"state lives on {len(held)} of {n} devices")
    _check(max(held.values()) <= 1.05 * min(held.values()),
           f"state is not spread evenly (something piled up): {held}")
    _check(max(held.values()) < 0.9 * rec["state_bytes_full"],
           f"no device holds a shard — each holds the whole state: {held}")
    peaks = [m["peak_bytes_in_use"] for m in rec["memory_stats"] if m]
    _check(not peaks or max(peaks) <= 1.25 * min(peaks),
           f"device memory is uneven: {rec['memory_stats']}")
    c = rec["collectives"]
    _check(c["all-gather"] > 0 and c["reduce-scatter"] + c["all-reduce"] > 0,
           f"{strategy}: the step program lacks its collectives: {c}")
    _check(rec["flash_kernel"] or PLATFORM != "tpu",
           f"{strategy}: no {KERNEL_MARKER} in the sharded step program")
    return rec


def phase_zero1(workdir: str) -> dict:
    return _phase_mesh(workdir, "zero1")


def phase_fsdp(workdir: str) -> dict:
    return _phase_mesh(workdir, "fsdp")


def phase_actors(workdir: str) -> dict:
    """Four one-chip worker processes under the actor plugin.  This
    process is their driver and stays off JAX (the plugin checks)."""
    from ray_lightning_tpu import RayXlaShardedPlugin, Trainer
    from ray_lightning_tpu.core.callbacks import Callback
    from ray_lightning_tpu.models.gpt import GPTLightningModule

    class Report(Callback):
        """Rank 0's view, sent back through ``callback_metrics``."""
        needs_batch = False

        def on_train_end(self, trainer, module):
            import jax
            dev = jax.local_devices()[0]
            ms = dev.memory_stats() or {}
            trainer.callback_metrics.update({
                "chip_platform": dev.platform,
                "chip_kind": dev.device_kind,
                "chip_local_devices": jax.local_device_count(),
                "chip_global_devices": jax.device_count(),
                "chip_peak_bytes": int(ms.get("peak_bytes_in_use", 0)),
            })

    plugin_kw = ({"use_tpu": True} if PLATFORM == "tpu"
                 else {"platform": PLATFORM})
    plugin = RayXlaShardedPlugin(num_workers=4, devices_per_worker=1,
                                 **plugin_kw)
    module = GPTLightningModule(MODEL, batch_size=BATCH // 4,
                                dataset_size=BATCH * MESH_STEPS)
    trainer = Trainer(plugins=[plugin], max_steps=MESH_STEPS, max_epochs=1,
                      callbacks=[Report()], enable_checkpointing=False,
                      num_sanity_val_steps=0, limit_val_batches=0,
                      log_every_n_steps=1, seed=SEED,
                      default_root_dir=os.path.join(workdir, "actors"))
    trainer.fit(module)
    m = trainer.callback_metrics
    device = {"platform": m["chip_platform"], "kind": m["chip_kind"],
              "count": int(m["chip_global_devices"])}
    _check(device["platform"] == PLATFORM,
           f"the workers ran on {device}, not on {PLATFORM}")
    _check(int(m["chip_local_devices"]) == 1 and device["count"] == 4,
           f"wanted 4 workers x 1 chip; rank 0 saw "
           f"{m['chip_local_devices']} local of {device['count']} devices")
    _check(trainer.global_step == MESH_STEPS,
           f"ran {trainer.global_step} steps, wanted {MESH_STEPS}")
    _check(module._trained_variables is not None,
           "trained weights did not return to the driver")
    return {"phase": "actors", "device": device, "model": MODEL,
            "workers": 4, "steps": int(trainer.global_step),
            "loss": float(m["loss"]),
            "rank0_peak_bytes": int(m["chip_peak_bytes"]),
            "time_to_first_step_s": trainer.time_to_first_step}


PHASES = {"train": phase_train, "serve": phase_serve,
          "one_chip": phase_one_chip, "zero1": phase_zero1,
          "fsdp": phase_fsdp, "actors": phase_actors}
PLAN = {1: ("train", "serve"),
        4: ("one_chip", "zero1", "fsdp", "actors")}


# -- child and parent --------------------------------------------------------

def run_phase(name: str, workdir: str) -> int:
    """Child entry: run one phase, leave its record in the workdir and
    as the last line of stdout."""
    try:
        rec = PHASES[name](workdir)
        rec["ok"] = True
        rc = 0
    except Exception as e:   # noqa: BLE001 - boundary: report and fail
        traceback.print_exc()
        rec = {"phase": name, "ok": False,
               "error": f"{type(e).__name__}: {e}"}
        rc = EXIT_NO_ACCELERATOR if isinstance(e, NoAccelerator) else 1
    with open(os.path.join(workdir, f"{name}.json"), "w") as f:
        json.dump(rec, f)
    print(json.dumps(rec), flush=True)
    return rc


def run_child(name: str, workdir: str) -> tuple:
    """Run one phase in a fresh process (its own process group, so that
    a timeout or a failure takes its workers down with it).  Returns
    (exit code, record or None)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", name,
         "--workdir", workdir],
        cwd=REPO, env=env, start_new_session=True)
    try:
        rc = proc.wait(timeout=PHASE_TIMEOUT_S[name])
    except subprocess.TimeoutExpired:
        rc = 124
        print(f"chip_smoke: phase {name} still running after "
              f"{PHASE_TIMEOUT_S[name]} s — killed", flush=True)
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)   # stragglers, if any
        except ProcessLookupError:
            pass
        proc.wait()
    rec = None
    path = os.path.join(workdir, f"{name}.json")
    if os.path.exists(path):
        with open(path) as f:
            rec = json.load(f)
        rec["phase_wall_s"] = round(time.monotonic() - t0, 1)
    return rc, rec


def _compare_four_chip(recs: dict) -> None:
    ref = recs["one_chip"]["loss"]
    tol = LOSS_RTOL * max(1.0, abs(ref))
    for name in ("zero1", "fsdp", "actors"):
        got = recs[name]["loss"]
        _check(abs(got - ref) <= tol,
               f"{name} loss {got:.6f} differs from the one-chip fit's "
               f"{ref:.6f} by more than {tol:.4f} on the same seed and "
               f"batch")
    print(json.dumps({
        "phase": "compare", "ok": True, "tolerance": tol,
        "loss": {n: recs[n]["loss"] for n in recs}}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(PLAN), default=1,
                    help="1 (default): train then serve on one chip; "
                    "4: the sharded fits on one four-chip host")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help=argparse.SUPPRESS)     # set by run_child only
    ap.add_argument("--workdir", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return run_phase(args.phase, args.workdir)

    workdir = os.path.join(REPO, "rlt_logs", f"chip_smoke_{args.chips}")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    recs: dict = {}
    try:
        for name in PLAN[args.chips]:
            rc, rec = run_child(name, workdir)
            if rc != 0 or rec is None or not rec.get("ok"):
                print(f"chip_smoke: phase {name} FAILED (exit {rc})"
                      + (f": {rec['error']}" if rec and "error" in rec
                         else ""), flush=True)
                return rc or 1
            recs[name] = rec
        devices = [r["device"] for r in recs.values()
                   if r["device"]["count"] == args.chips]
        _check(devices and all(d["platform"] == PLATFORM for d in devices),
               f"no phase reported {args.chips} {PLATFORM} device(s): "
               f"{[r['device'] for r in recs.values()]}")
        if args.chips == 4:
            _compare_four_chip(recs)
        summary = os.path.join(REPO, "chiprun_out")
        os.makedirs(summary, exist_ok=True)
        with open(os.path.join(summary,
                               f"chip_smoke_{args.chips}.json"), "w") as f:
            json.dump(recs, f, indent=1)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", flush=True)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)  # the checkpoint is GBs
    print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
