"""Operations a GPT-2 training step requires, from the configuration's
sizes alone (forward and backward, recomputation not counted)."""

from __future__ import annotations


def matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix product once per token:
    12 d^2 per block (QKV 3, projection 1, MLP 8) plus the tied
    embedding table, counted once, as the output head."""
    d = int(model["n_embd"])
    return 12 * int(model["n_layer"]) * d * d + int(model["vocab_size"]) * d


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """6 FLOPs per matmul parameter (2 forward, 4 backward) plus causal
    attention's score and value products: 12 L T d for the full square,
    half of it under the causal mask, so 6 L T d."""
    return 6.0 * matmul_params(model) + 6.0 * int(model["n_layer"]) \
        * int(seq_len) * int(model["n_embd"])
