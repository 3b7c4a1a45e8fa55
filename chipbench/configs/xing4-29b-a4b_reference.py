"""Plain reference for the `xing4-29b-a4b` configuration: the float32
`jax.numpy` Xing4.0 of `chipbench/xing_reference.py`, at the sizes of
`xing4-29b-a4b.json` beside this file."""

from chipbench.xing_reference import (  # noqa: F401
    PRECISIONS, forward, loss)
