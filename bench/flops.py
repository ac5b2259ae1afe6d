"""Model operations and bytes, counted from the configuration's shapes.

Counts are of the work the algorithm needs, not what a lowering does: a
product (M,K)x(K,N) is 2*M*K*N operations; causal attention counts half
of the score square (the lower triangle with its diagonal, taken as S*S/2);
nothing recomputed under rematerialisation counts; a training step is the
forward pass and twice it again for the backward pass.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Weights that enter a product once per token: the blocks' projections
    and MLP, and the output head (the tied embedding)."""
    D, H, Hkv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    attn = D * H * hd + 2 * D * Hkv * hd + H * hd * D
    mlp = 3 * D * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (attn + mlp) \
        + D * cfg["vocab_size"]


def attention_core(cfg: dict, rows: int, seq: int) -> float:
    """Scores and the weighted sum of values for causal self-attention of
    `rows` sequences of `seq` tokens, over all layers."""
    H, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return cfg["num_hidden_layers"] * 2 * 2 * rows * H * hd * seq * seq / 2


def forward(cfg: dict, rows: int, seq: int) -> float:
    return 2 * matmul_params(cfg) * rows * seq \
        + attention_core(cfg, rows, seq)


def train_step(cfg: dict, rows: int, seq: int) -> float:
    """One optimizer step over a batch of `rows` x `seq` tokens."""
    return 3 * forward(cfg, rows, seq)


def n_params(cfg: dict) -> int:
    D, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    per = D * H * hd + 2 * D * Hkv * hd + H * hd * D \
        + 3 * D * cfg["intermediate_size"] + 2 * D
    if cfg["qkv_bias"]:
        per += (H + 2 * Hkv) * hd
    head = 0 if cfg["tie_word_embeddings"] else D * cfg["vocab_size"]
    return L * per + D * cfg["vocab_size"] + head + D
