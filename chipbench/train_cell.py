"""A training cell: ``Trainer.fit`` in this process, one object from the
seed through its first three steps (which the check reads), a warm-up,
and the measured window — never two objects built alike.

The window's clock is the host's, read twice: after a device sync at the
step that opens the window and after one at the step that closes it.  No
loss is fetched in between (a per-step fetch costs ~30 ms here).
"""

from __future__ import annotations

import gc
import os
import time

from chipbench import check
from chipbench.module import leaves

CHECK_STEPS = 3
WARM_STEPS = 12          # the window opens after this many steps
TRACE_STEPS = 10


class NoAccelerator(RuntimeError):
    """JAX does not see the chips the cell asks for."""


def claim_devices(chips: int, platform: str) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != platform or len(devs) != chips:
        raise NoAccelerator(
            f"the cell needs {chips} {platform} device(s); JAX sees "
            f"{len(devs)} x {devs[0].platform} ({devs[0].device_kind})")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def stall_readout(stamps: list, cpu0) -> dict:
    """Where a window that reads low lost its time: the host's clock at
    every step (the host runs ahead of the device until the queue is
    full, then keeps its pace), the longest gaps between two steps and
    when they fell, and how busy this process and the machine kept the
    host's cores.  Printed with the phases on every run, judged by none."""
    import statistics
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    if not gaps:
        return {}
    median = statistics.median(gaps)
    longest = sorted(range(len(gaps)), key=gaps.__getitem__)[-3:][::-1]
    cpu1 = os.times()
    window = stamps[-1] - stamps[0]
    return {
        "step_gap_ms_p50": 1e3 * median,
        "step_gaps_over_twice_p50": sum(g > 2 * median for g in gaps),
        "step_gaps_longest_ms_at_s": [
            [1e3 * gaps[i], stamps[i] - stamps[0]] for i in longest],
        "window_cpu_cores_used": (
            cpu1.user + cpu1.system + cpu1.children_user
            + cpu1.children_system - cpu0.user - cpu0.system
            - cpu0.children_user - cpu0.children_system) / window,
        "host_load_avg_1min": os.getloadavg()[0],
        "host_cores": os.cpu_count()}


def find_field(state, name: str):
    """The first ``.name`` in a nest of optimizer-state tuples."""
    if hasattr(state, name):
        return getattr(state, name)
    if isinstance(state, (tuple, list)):
        for s in state:
            found = find_field(s, name)
            if found is not None:
                return found
    return None


def _norms(tree) -> dict:
    import jax.numpy as jnp
    return {k: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for k, a in leaves(tree).items()}


def run(cell: dict, seed: int, seconds: float, trace: bool, t_process: float,
        platform: str = "tpu") -> dict:
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu import Trainer
    from ray_lightning_tpu.compile import cache as compile_cache
    from ray_lightning_tpu.core.callbacks import Callback

    from chipbench.module import program_seed

    phases = {"imports_s": time.monotonic() - t_process}
    device = claim_devices(cell["chips"], platform)
    phases["devices_s"] = time.monotonic() - t_process
    model, job = cell["config"]["model"], cell["traffic"]
    b1 = float(job["optimizer"]["b1"])
    module = cell["adapter"].module(model, seed, job)
    trace_dir = os.path.join(cell["work"], "trace")

    grad_norms = jax.jit(lambda mu: {
        k: v / (1.0 - b1) for k, v in _norms(mu).items()})
    snapshot = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))
    change_norms = jax.jit(lambda now, before: _norms(
        jax.tree_util.tree_map(jnp.subtract, now, before)))

    class Window(Callback):
        needs_batch = False

        def __init__(self):
            self.n = 0
            self.losses, self.program = [], {}
            self.t0 = self.t1 = None
            self.steps = 0
            self.peak = None
            self.trace_left = None
            self.master0 = None
            self.stamps = []      # host clock at every step of the window

        def on_fit_start(self, trainer, module):
            phases["fit_start_s"] = time.monotonic() - t_process

        def on_train_start(self, trainer, module):
            phases["train_start_s"] = time.monotonic() - t_process

        def on_train_batch_start(self, trainer, module, batch, idx):
            if self.n == 0 and self.master0 is None:
                phases["first_batch_s"] = time.monotonic() - t_process
                # the master parameters before the first step: a copy of
                # the program's own state (the step donates the original)
                self.master0 = snapshot(
                    find_field(trainer.state.opt_state, "master"))

        def on_train_batch_end(self, trainer, module, metrics, batch, idx):
            self.n += 1
            n = self.n
            if n <= CHECK_STEPS:
                self.losses.append(metrics["loss"])
            if n == 1:
                phases["first_step_s"] = time.monotonic() - t_process
                self.program["grad_norms"] = grad_norms(
                    find_field(trainer.state.opt_state, "mu"))
            if n == CHECK_STEPS:
                self.program["change_norms"] = change_norms(
                    find_field(trainer.state.opt_state, "master"),
                    self.master0)
                self.master0 = None
            if n == WARM_STEPS:
                jax.block_until_ready(metrics["loss"])
                self.t0 = time.monotonic()
                self.compiled0 = compile_cache.stats()
                self.cpu0 = os.times()
            elif self.t0 is not None and self.t1 is None \
                    and time.monotonic() >= self.t0 + seconds:
                jax.block_until_ready(metrics["loss"])
                self.t1 = time.monotonic()
                self.steps = n - WARM_STEPS
                phases.update(stall_readout(
                    [self.t0] + self.stamps + [self.t1], self.cpu0))
                # nothing may compile inside the window: say if it did
                done = compile_cache.stats()
                phases["window_compile_misses"] = (
                    done.misses - self.compiled0.misses)
                phases["window_backend_compile_s"] = (
                    done.backend_compile_secs
                    - self.compiled0.backend_compile_secs)
                self.peak = _peak_bytes()
                phases["memory_stats"] = jax.devices()[0].memory_stats()
                if trace:
                    jax.profiler.start_trace(trace_dir)
                    self.trace_left = TRACE_STEPS
                else:
                    trainer.should_stop = True
            elif self.t1 is None and self.t0 is not None:
                self.stamps.append(time.monotonic())
            elif self.trace_left is not None:
                self.trace_left -= 1
                if self.trace_left == 0:
                    jax.block_until_ready(metrics["loss"])
                    jax.profiler.stop_trace()
                    trainer.should_stop = True

    def _peak_bytes():
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.devices())

    win = Window()
    trainer = Trainer(
        max_epochs=10 ** 6, callbacks=[win], enable_checkpointing=False,
        num_sanity_val_steps=0, limit_val_batches=0,
        log_every_n_steps=10 ** 9, seed=program_seed(seed),
        strategy=job.get("strategy"), default_root_dir=cell["work"],
        telemetry=False)
    trainer.fit(module)
    if win.t1 is None:
        raise RuntimeError("the fit ended before the window closed")

    cache_stats = compile_cache.stats()
    rows = module.train_rows()
    losses = [float(x) for x in win.losses]
    program = {"losses": losses,
               "grad_norms": {k: float(v) for k, v in
                              win.program["grad_norms"].items()},
               "change_norms": {k: float(v) for k, v in
                                win.program["change_norms"].items()}}
    seq_len = int(rows[0].shape[1])
    tokens = win.steps * int(job["global_batch"]) * seq_len
    window_s = win.t1 - win.t0
    setup_s = win.t0 - t_process

    # the program's state goes before the reference's comes
    trainer.state = None
    module._trained_variables = None
    del trainer
    gc.collect()
    t_check = time.monotonic()
    numbers = reference_numbers(cell, seed, rows, program)
    phases["check_s"] = time.monotonic() - t_check
    phases["rows_s"] = getattr(module, "bench_rows_s", None)
    phases["setup_s"] = setup_s

    red = None
    if trace:
        from chipbench import reduce
        red = reduce.reduce_dir(trace_dir)
    return {
        "device": device, "memory_peak_bytes": win.peak,
        "attempted": win.steps,
        "failed": 0 if all(x == x for x in losses) else win.steps,
        "numbers": numbers, "setup_s": setup_s, "phases": phases,
        "end_to_end": {"train_tokens_per_s": tokens / window_s},
        "ctx": {
            "kind": "train", "trace": red, "setup_s": setup_s,
            "compile": {"hits": cache_stats.hits,
                        "misses": cache_stats.misses,
                        "backend_compile_s":
                            cache_stats.backend_compile_secs},
            "window": {"seconds": window_s, "steps": win.steps,
                       "tokens": tokens, "seq_len": seq_len,
                       "tokens_per_s": tokens / window_s},
            "model": model, "adapter": cell["adapter"], "traffic": job,
            "chips": cell["chips"],
            "peaks": cell["peaks"].get(device["kind"]),
        },
    }


def reference_numbers(cell: dict, seed: int, rows, program: dict,
                      precision: str = "float32") -> dict:
    """The reference's three steps beside the program's (or, with a lower
    ``precision``, beside the float32 reference's: the control)."""
    import jax

    from chipbench.module import init_key

    model, job = cell["config"]["model"], cell["traffic"]
    adapter = cell["adapter"]
    to_program_tree = adapter.to_program_tree
    ref = check.load_reference(cell["config"], cell["root"])
    make = lambda key: adapter.make_weights(model, key)   # noqa: E731
    place = None
    if len(jax.devices()) > 1:
        # no one chip holds this model's float32 parameters, gradients
        # and moments: every tensor is split on its leading axis (layers,
        # vocabulary rows, positions) over the cell's chips
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(jax.devices(), ("chips",))
        sharding = jax.tree_util.tree_map(
            lambda a: NamedSharding(mesh, P("chips")),
            jax.eval_shape(make, init_key("train", seed)))
        place = lambda tree: jax.lax.with_sharding_constraint(  # noqa: E731
            tree, sharding)
        make = jax.jit(make, out_shardings=sharding)
    else:
        make = jax.jit(make)
    w = make(init_key("train", seed))
    got = check.train_reference(ref, w, model, job, rows, precision, place,
                                axes=adapter.leaf_norm_axes)
    reference = {
        "losses": got["losses"],
        "grad_norms": {k: float(v) for k, v in leaves(
            to_program_tree(got["grad_norms"])).items()},
        "change_norms": {k: float(v) for k, v in leaves(
            to_program_tree(got["change_norms"])).items()}}
    if program is None:
        return reference
    return check.train_numbers(program, reference)
