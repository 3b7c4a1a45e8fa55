"""Model FLOP/s utilisation end to end: the window's tokens/s times the
FLOPs a token requires (the family's ``train_flops_per_token`` at the
rows' length, recomputation not counted) over chips times the published
bf16 peak."""


def read(ctx: dict):
    if not ctx["peaks"]:
        return None
    per_token = ctx["adapter"].train_flops_per_token(
        ctx["model"], ctx["window"]["seq_len"])
    return 100.0 * ctx["window"]["tokens_per_s"] * per_token / (
        ctx["chips"] * ctx["peaks"]["tflops_bf16"] * 1e12)
