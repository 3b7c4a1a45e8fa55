"""Plain reference for the `kimi-linear-48b-a3b` configuration: the float32
`jax.numpy` Kimi Linear of `chipbench/kimi_linear_reference.py`, at the
sizes of `kimi-linear-48b-a3b.json` beside this file."""

from chipbench.kimi_linear_reference import (  # noqa: F401
    PRECISIONS, forward, loss)
