"""Plain reference for the `zaya1-8b` configuration: the float32
`jax.numpy` ZAYA1 of `chipbench/zaya_reference.py`, at the sizes of
`zaya1-8b.json` beside this file."""

from chipbench.zaya_reference import (  # noqa: F401
    PRECISIONS, forward, loss)
