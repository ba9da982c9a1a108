"""trinity-mini [moe]: Arcee Trinity Mini (afmoe), 26B total, 3B active.
32L d_model=2048 32H (kv=4) head_dim=128; the first 2 layers dense
(d_ff=6144), then 128 sigmoid-routed experts of width 1024, top-8, plus
one shared expert; sliding window 2048 on three layers of four, every
4th layer global; gated attention, sandwich norms, sqrt(d) embedding
scale.  vocab=200192, untied  [hf:arcee-ai/Trinity-Mini config.json;
the gate, the norms, RoPE on sliding layers only and the embedding scale
from transformers' modeling_afmoe.py]."""
from .base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="trinity-mini",
    family="moe",
    num_layers=32,
    d_model=2048,
    num_heads=32,
    num_kv_heads=4,
    d_ff=6144,
    vocab_size=200192,
    head_dim=128,
    qk_norm=True,
    num_experts=128,
    experts_per_token=8,
    experts_held=128,
    expert_d_ff=1024,
    num_shared_experts=1,
    route_scale=2.826,
    num_dense_layers=2,
    layer_types=tuple("global" if (i + 1) % 4 == 0 else "sliding"
                      for i in range(32)),
    attn_window=2048,
    block="afmoe",
    rope_theta=1e4,
    norm_eps=1e-5,
))
