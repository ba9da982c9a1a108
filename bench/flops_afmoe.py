"""Operations of one chip's share of an AFMoE decoder, from its published
shapes and the rows its held experts received.

Forward, per unique token: two operations per weight of every matrix
product a token passes through on this chip (the four attention
projections and the attention gate; the dense SwiGLU on the leading
layers; on the expert layers the router over every routed expert and the
shared expert; the output head; the embedding is a lookup), plus the
attention products, QK^T and PV, 2 x 2 x heads x head_dim operations per
key a query sees: on a sliding layer the keys within the window, on a
global one every earlier key.  The held experts' SwiGLUs count the rows
they received, as the program's counters report them, per unique token.
Training is three forward passes' worth, with nothing counted for
rematerialization or for the replicated coded rows.

The grouped matmul's work (``gmm_work``) counts what its calls do in a
train step: per expert layer, the three expert products forward, again
in the rematerialized forward, and each one's two backward products.
"""
from __future__ import annotations

#: grouped-matmul calls per expert product in a train step: forward,
#: rematerialized forward, gradient of the input, gradient of the weights
GMM_CALLS_PER_PRODUCT = 4


def keys_seen(seq_len: int, window: int) -> float:
    """Mean over positions of the keys a causal query sees (window 0:
    all earlier ones)."""
    w = window or seq_len
    full = min(w, seq_len)
    # positions 0..w-1 see i + 1 keys, the rest see w
    return (full * (full + 1) / 2 + (seq_len - full) * full) / seq_len


def forward_flops_per_token(cfg: dict, seq_len: int,
                            held_rows_per_token: float) -> float:
    d = cfg["hidden_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    nd = cfg["num_dense_layers"]
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    ne = len(kinds) - nd
    attn = 2 * d * h * hd + 2 * d * kv * hd + h * hd * d
    dense_mlp = 3 * d * f
    expert_fixed = d * cfg["num_experts_routed"] + \
        3 * d * fe * cfg["num_shared_experts"]
    matmul = len(kinds) * attn + nd * dense_mlp + ne * expert_fixed + \
        d * cfg["vocab_size"] + held_rows_per_token * 3 * d * fe
    scores = sum(4 * h * hd * keys_seen(
        seq_len, cfg["sliding_window"] if k == "sliding_attention" else 0)
        for k in kinds)
    return 2.0 * matmul + scores


def train_flops_per_token(cfg: dict, seq_len: int,
                          held_rows_per_token: float) -> float:
    return 3.0 * forward_flops_per_token(cfg, seq_len, held_rows_per_token)


def gmm_work(cfg: dict, rows_per_layer) -> tuple:
    """(operations, bytes) of the grouped matmul's calls in one train step,
    given the rows each expert layer's held experts received in it.  A
    call multiplies the rows by one of the three expert matrices (or
    their transposes) of every held expert: 2 x rows x d x fe operations,
    and in bfloat16 the rows in, the rows out, and each held expert's
    matrix once."""
    d, fe, held = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["num_experts"]
    ops = byts = 0.0
    for rows in rows_per_layer:
        calls = 3 * GMM_CALLS_PER_PRODUCT
        ops += calls * 2.0 * rows * d * fe
        byts += calls * 2.0 * (rows * (d + fe) + held * d * fe)
    return ops, byts
