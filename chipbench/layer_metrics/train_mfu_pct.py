"""Model FLOP/s utilisation end to end: the window's tokens/s times the
FLOPs a token requires (chipbench/flops.py, recomputation not counted)
over chips times the published bf16 peak."""


def read(ctx: dict):
    if not ctx["peaks"]:
        return None
    from chipbench import flops
    per_token = flops.train_flops_per_token(
        ctx["model"], int(ctx["model"]["n_positions"]))
    return 100.0 * ctx["window"]["tokens_per_s"] * per_token / (
        ctx["chips"] * ctx["peaks"]["tflops_bf16"] * 1e12)
