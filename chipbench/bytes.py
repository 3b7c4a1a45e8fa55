"""Bytes a decode step cannot avoid moving, from the configuration's
sizes alone.  Decode is bytes-bound: every weight and every live cache
row is read once per token and almost nothing is reused."""

from __future__ import annotations

BF16 = 2


def weight_bytes(model: dict) -> int:
    """Every parameter in bf16: matmul weights, biases, LayerNorms, both
    embedding tables."""
    L, d = int(model["n_layer"]), int(model["n_embd"])
    per_block = 12 * d * d + 13 * d
    return BF16 * (L * per_block + int(model["vocab_size"]) * d
                   + int(model["n_positions"]) * d + 2 * d)


def kv_bytes_per_token(model: dict) -> int:
    """K and V rows of one position over all layers, bf16."""
    return 2 * BF16 * int(model["n_layer"]) * int(model["n_embd"])


def decode_step_bytes(model: dict, live_tokens: float) -> float:
    """Least traffic of one decode step over the occupied slots: the
    weights once, plus the K/V rows of every live context position."""
    return weight_bytes(model) + kv_bytes_per_token(model) * live_tokens


def decode_roofline_pct(ctx: dict):
    """The share of the bytes roofline that the traced decode steps of a
    serving cell reached (a layer-metric reader's ``ctx``), or None."""
    red = ctx["trace"]
    decode_ms = red["ms_by_kind"].get("decode") if red else None
    if not decode_ms or not ctx["peaks"]:
        return None
    least_s = decode_step_bytes(
        ctx["model"], ctx["window"]["live_tokens_mean"]) / (
            ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (decode_ms * 1e-3)
