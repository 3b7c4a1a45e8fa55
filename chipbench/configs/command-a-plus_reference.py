"""Plain reference for the `command-a-plus` configuration: the float32
`jax.numpy` Command A+ of `chipbench/command_reference.py`, at the sizes of
`command-a-plus.json` beside this file."""

from chipbench.command_reference import (  # noqa: F401
    PRECISIONS, forward, loss)
