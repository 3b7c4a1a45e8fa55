"""Plain reference for the `gpt2-large` configuration: the float32
`jax.numpy` GPT-2 of `chipbench/gpt2_reference.py`, at the sizes of
`gpt2-large.json` beside this file.  A configuration whose mathematics
differs brings a reference of its own here instead."""

from chipbench.gpt2_reference import PRECISIONS, forward, loss  # noqa: F401
