"""Operations a dense decoder needs per token, from its published shapes.

Forward: two operations per weight of every matrix product (the four
attention projections, the three SwiGLU matrices, the output head; the
embedding is a lookup), plus the causal attention products: QK^T and PV
over the (seq_len + 1) / 2 keys a token sees on average, 2 x 2 x heads x
head_dim operations per key.  Training is three forward passes' worth
(the backward pass costs two), with nothing counted for
rematerialization.
"""
from __future__ import annotations


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    f = cfg["intermediate_size"]
    L = cfg["num_hidden_layers"]
    V = cfg["vocab_size"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    matmul = L * per_layer + d * V
    attn = L * 2 * 2 * h * hd * (seq_len + 1) / 2
    return 2.0 * matmul + attn


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 3.0 * forward_flops_per_token(cfg, seq_len)
