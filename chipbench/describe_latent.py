"""``describe_state.py`` for a family whose serve state is ONE array a
kind (``KVCacheSpec.paired`` False: a latent row holds key and value at
once): compile a serving cell's programs for a described (not attached)
v5e and print what the compiler says each needs.  By hand:

    JAX_PLATFORMS=cpu python3 -m chipbench.describe_latent \
        --config xing4-29b-a4b --slots 64 --buckets 7168,9216 --init 1

``describe_state.py`` hands ``KVCacheSpec.from_capture`` the first block
of each layer's captured tuple and so sizes a keys' and a values' array;
this hands it the captured tuples themselves, where a tuple of one block
says that there is no values' array.  Everything else is
``describe_state``'s.
"""

from __future__ import annotations

import sys

from chipbench import describe_state


def main(argv=None) -> int:
    from ray_lightning_tpu.core import steps
    captured = steps.kv_layer_pairs
    # describe_state unpacks ``for k, _ in kv_layer_pairs(...)``: give it
    # each layer's whole tuple as the ``k``
    steps.kv_layer_pairs = lambda tree: [(entry, None)
                                         for entry in captured(tree)]
    try:
        return describe_state.main(argv)
    finally:
        steps.kv_layer_pairs = captured


if __name__ == "__main__":
    sys.exit(main())
