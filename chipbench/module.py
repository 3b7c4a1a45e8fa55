"""What the harness knows about the program under test and not about any
model family: the key the program inits with, the seed it takes, and the
names of a parameter tree's leaves.  (What belongs to a family, its
module class, weights and parameter tree, is the family's adapter:
``chipbench/adapters/``.)
"""

from __future__ import annotations


def init_key(kind: str, seed: int):
    """The key that the program hands to ``init_params`` when it is given
    ``seed=program_seed(seed)``: ``Server`` passes ``PRNGKey(seed)``
    (serve/engine.py), ``Trainer`` the first half of its split
    (core/steps.py ``build_init_fn``).  If the program changes this, the
    comparison with the reference fails loudly rather than quietly."""
    import jax
    key = jax.random.PRNGKey(program_seed(seed))
    return jax.random.split(key)[0] if kind == "train" else key


def program_seed(seed: int) -> int:
    """The driver's seeds pass 2**31; the program takes them unsigned."""
    return int(seed) % 2 ** 32


def leaves(tree) -> dict:
    """``{"h0/attn/qkv/kernel": leaf}`` for any pytree of dicts and
    named tuples."""
    import jax
    return {"/".join(str(getattr(k, "key", getattr(k, "name", k)))
                     for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
