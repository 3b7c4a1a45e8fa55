"""Plain reference for the `evabyte-6p5b` configuration: the float32
`jax.numpy` EvaByte of `chipbench/evabyte_reference.py`, at the sizes of
`evabyte-6p5b.json` beside this file."""

from chipbench.evabyte_reference import (  # noqa: F401
    PRECISIONS, forward, forward_heads, loss)
