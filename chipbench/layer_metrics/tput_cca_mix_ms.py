"""Device milliseconds per run of the decode program under the finer scope
``cca_mix``: what of compressed convolutional attention is neither a
projection nor the kernel, every layer: the q-k mean, both convolutions
(the depthwise taps and the ten heads' 256 x 128 products), the per-head
L2 norm and temperature, the shifted value, and the read and write of the
slots' tails.  A program without the scope (a parent commit) reads
nothing."""


def read(ctx: dict):
    from chipbench import fine_scopes
    return fine_scopes.device_ms_per_run(ctx, "jit_serve_decode",
                                         "cca_mix") or None
