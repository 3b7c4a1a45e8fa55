"""Platform/env helpers shared by the plugins and driver entry points."""

from __future__ import annotations

import os
import sys

_FORCE_FLAG = "xla_force_host_platform_device_count"


def host_device_count_flags(n: int, base_flags: str | None = None) -> str:
    """XLA_FLAGS value with exactly one ``--{_FORCE_FLAG}={n}``.

    Strips any inherited copy of the flag (e.g. from a test harness)
    first, so the virtual-device count is deterministic.
    """
    base = (os.environ.get("XLA_FLAGS", "")
            if base_flags is None else base_flags)
    flags = [f for f in base.split() if _FORCE_FLAG not in f]
    flags.append(f"--{_FORCE_FLAG}={n}")
    return " ".join(flags).strip()


def require_chip_free(what: str, remedy: str) -> None:
    """Raise at once when THIS process already holds the accelerator
    that ``what``'s worker processes are about to ask for.

    A TPU chip belongs to one process at a time: once a driver has
    initialised a JAX backend on it (an in-process ``Trainer.fit``, a
    stray ``jax.devices()``), a child that needs the same chip fails or
    hangs inside libtpu — and the caller would only learn of it at the
    end of a setup timeout.  A driver that never touched JAX, or whose
    backend is the CPU, passes.  Never initialises a backend itself."""
    if "jax" not in sys.modules:
        return
    # jax 0.9.0 has no public "has a backend been initialised?" probe
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return
    raise RuntimeError(
        f"{what} starts worker processes that need the {dev.platform} "
        f"device, but this process (pid {os.getpid()}) has already "
        f"initialised JAX on it ({len(jax.devices())} x "
        f"{dev.device_kind}) and a chip belongs to one process at a "
        f"time: the workers would fail or hang in device init.  "
        f"{remedy}")
