"""Family dispatch: one uniform model API over every assigned architecture.

    init_params / param_shapes / partition_specs
    forward / forward_aux / loss_fn
    init_cache / cache_shapes / cache_specs / decode_step

``transformer`` serves dense, MoE, encoder-only and embedding-input (vlm /
audio) families; ``ssm_lm`` serves pure-SSM (mamba2) and hybrid (zamba2).
"""
from __future__ import annotations

from ..configs.base import ModelConfig
from . import ssm_lm, transformer

_SSM_FAMILIES = ("ssm", "hybrid")


def model_module(cfg: ModelConfig):
    return ssm_lm if cfg.family in _SSM_FAMILIES else transformer


def init_params(cfg, key):
    return model_module(cfg).init_params(cfg, key)


def param_shapes(cfg):
    return model_module(cfg).param_shapes(cfg)


def partition_specs(cfg, fsdp: str = "data", tp: str = "model"):
    return model_module(cfg).partition_specs(cfg, fsdp, tp)


def forward(cfg, params, tokens, positions=None):
    return model_module(cfg).forward(cfg, params, tokens, positions)


def forward_aux(cfg, params, tokens, positions=None):
    """(logits, aux): aux holds the model's counters (the expert-share
    layer's rows per held expert and its selections), empty where it
    has none."""
    mod = model_module(cfg)
    if hasattr(mod, "forward_aux"):
        return mod.forward_aux(cfg, params, tokens, positions)
    return mod.forward(cfg, params, tokens, positions), {}


def attn_tiles(cfg, seq_len):
    """(visited, total) attention score tiles of one full-sequence
    forward, summed over the model's attention layers."""
    return model_module(cfg).attn_tiles(cfg, seq_len)


def loss_fn(cfg, params, tokens, labels):
    return model_module(cfg).loss_fn(cfg, params, tokens, labels)


def init_cache(cfg, batch, max_len, dtype="bfloat16"):
    return model_module(cfg).init_cache(cfg, batch, max_len, dtype)


def cache_shapes(cfg, batch, max_len, dtype="bfloat16"):
    return model_module(cfg).cache_shapes(cfg, batch, max_len, dtype)


def cache_specs(cfg, fsdp: str = "data", tp: str = "model"):
    return model_module(cfg).cache_specs(cfg, fsdp, tp)


def decode_step(cfg, params, cache, tokens, pos):
    return model_module(cfg).decode_step(cfg, params, cache, tokens, pos)


def padded_vocab(cfg):
    return transformer.padded_vocab(cfg)
