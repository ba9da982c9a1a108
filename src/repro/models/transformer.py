"""Decoder/encoder transformer LM family (dense + MoE) as pure pytrees.

Covers the assigned archs: deepseek-7b, llama3-405b, qwen3-0.6b (qk_norm),
yi-9b, dbrx-132b (MoE), qwen3-moe-235b-a22b (MoE), hubert-xlarge
(encoder-only, embedding inputs), internvl2-76b (embedding inputs), and
trinity-mini (afmoe: leading dense layers, then dropless expert-share
layers; sliding and global layers; gated attention, sandwich norms).

Layer parameters are stacked along a leading ``num_layers`` axis and the
forward pass is a single ``lax.scan`` over layers, so the lowered HLO is
O(1) in depth (essential for the 126-layer dry-run) and activation
rematerialization is one ``jax.checkpoint`` on the scan body.  A model
with ``num_dense_layers`` scans those first, as a stack of their own
(``params["dense_layers"]``).  Where ``layer_types`` gives each layer a
kind, its window and RoPE flag ride along the scan as per-layer data, so
sliding and global layers still run as one scan.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from . import layers as L
from .moe import expert_share_ffn, moe_ffn

Params = Dict[str, Any]


def padded_vocab(cfg: ModelConfig) -> int:
    """Vocab rounded up to a multiple of 128 (MXU + model-axis sharding)."""
    return (cfg.vocab_size + 127) // 128 * 128


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# --------------------------------------------------------------------------
# Parameter construction
# --------------------------------------------------------------------------

def _layer_shapes(cfg: ModelConfig, nl: Optional[int] = None,
                  experts: Optional[bool] = None
                  ) -> Dict[str, Tuple[int, ...]]:
    """Stacked shapes of ``nl`` layers (default: the scanned stack), with
    expert MLPs or, ``experts=False``, the dense SwiGLU of width d_ff."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv, f = cfg.num_heads, cfg.num_kv_heads, cfg.d_ff
    nl = cfg.num_layers - cfg.num_dense_layers if nl is None else nl
    experts = bool(cfg.num_experts) if experts is None else experts
    shapes = {
        "attn_norm": (nl, d),
        "wq": (nl, d, h, hd),
        "wk": (nl, d, kv, hd),
        "wv": (nl, d, kv, hd),
        "wo": (nl, h, hd, d),
        "mlp_norm": (nl, d),
    }
    if cfg.qk_norm:
        shapes["q_norm"] = (nl, hd)
        shapes["k_norm"] = (nl, hd)
    if cfg.block == "afmoe":
        shapes["w_attn_gate"] = (nl, d, h, hd)
        shapes["post_attn_norm"] = (nl, d)
        shapes["post_mlp_norm"] = (nl, d)
    if experts and cfg.experts_held:
        e, held, fe = cfg.num_experts, cfg.experts_held, cfg.expert_d_ff
        shapes.update(router=(nl, d, e), expert_bias=(nl, e),
                      w_gate=(nl, held, d, fe), w_up=(nl, held, d, fe),
                      w_down=(nl, held, fe, d))
        if cfg.num_shared_experts:
            fs = fe * cfg.num_shared_experts
            shapes.update(shared_gate=(nl, d, fs), shared_up=(nl, d, fs),
                          shared_down=(nl, fs, d))
    elif experts:
        e = cfg.num_experts
        shapes.update(
            router=(nl, d, e),
            w_gate=(nl, e, d, f),
            w_up=(nl, e, d, f),
            w_down=(nl, e, f, d),
        )
    else:
        shapes.update(w_gate=(nl, d, f), w_up=(nl, d, f), w_down=(nl, f, d))
    return shapes


def param_shapes(cfg: ModelConfig) -> Params:
    """ShapeDtypeStruct pytree (for the allocation-free dry-run)."""
    dt = _dt(cfg)
    v = padded_vocab(cfg)
    tree: Params = {"layers": {k: jax.ShapeDtypeStruct(s, dt)
                               for k, s in _layer_shapes(cfg).items()}}
    if cfg.num_dense_layers:
        tree["dense_layers"] = {
            k: jax.ShapeDtypeStruct(s, dt) for k, s in _layer_shapes(
                cfg, cfg.num_dense_layers, experts=False).items()}
    if not cfg.embedding_inputs:
        tree["embed"] = jax.ShapeDtypeStruct((v, cfg.d_model), dt)
    tree["final_norm"] = jax.ShapeDtypeStruct((cfg.d_model,), dt)
    tree["lm_head"] = jax.ShapeDtypeStruct((cfg.d_model, v), dt)
    return tree


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Materialized parameters (smoke tests / examples; full configs use
    param_shapes + dry-run only)."""
    dt = _dt(cfg)
    if cfg.num_dense_layers:
        key, dkey = jax.random.split(key)
    shapes = _layer_shapes(cfg)
    keys = jax.random.split(key, len(shapes) + 2)
    tree: Params = {"layers": _init_layers(shapes, keys, dt)}
    if cfg.num_dense_layers:
        dshapes = _layer_shapes(cfg, cfg.num_dense_layers, experts=False)
        tree["dense_layers"] = _init_layers(
            dshapes, jax.random.split(dkey, len(dshapes)), dt)
    v = padded_vocab(cfg)
    if not cfg.embedding_inputs:
        tree["embed"] = L.embed_init(keys[-2], (v, cfg.d_model), dt)
    tree["final_norm"] = jnp.ones((cfg.d_model,), dt)
    tree["lm_head"] = L.dense_init(keys[-1], (cfg.d_model, v), cfg.d_model, dt)
    return tree


def _init_layers(shapes, keys, dt) -> Params:
    layer_p = {}
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if "norm" in name:
            layer_p[name] = jnp.ones(shape, dt)
        elif name == "expert_bias":          # selection-only, starts at 0
            layer_p[name] = jnp.zeros(shape, dt)
        else:
            fan_in = shape[-2] if len(shape) > 2 else shape[-1]
            if name == "wo":
                fan_in = shape[1] * shape[2]
            if name in ("wq", "wk", "wv", "w_attn_gate"):
                fan_in = shape[1]
            layer_p[name] = L.dense_init(k, shape, fan_in, dt)
    return layer_p


def partition_specs(cfg: ModelConfig, fsdp: str = "data", tp: str = "model") -> Params:
    """PartitionSpec pytree congruent with param_shapes.

    TP shards the head / ffn / expert / vocab axes over ``tp`` where
    divisible by the axis size (checked at mesh-apply time by GSPMD; we use
    static divisibility by 16 here, the production model-axis size); FSDP
    shards a complementary axis over ``data``.  KV projections whose head
    count does not divide the tp axis stay replicated over tp (standard
    GQA practice) but remain FSDP-sharded.
    """
    def head_spec(nheads):
        return tp if nheads % 16 == 0 else None

    specs_l = {
        "attn_norm": P(None, None),
        "wq": P(None, fsdp, head_spec(cfg.num_heads), None),
        "wk": P(None, fsdp, head_spec(cfg.num_kv_heads), None),
        "wv": P(None, fsdp, head_spec(cfg.num_kv_heads), None),
        "wo": P(None, head_spec(cfg.num_heads), None, fsdp),
        "mlp_norm": P(None, None),
    }
    if cfg.qk_norm:
        specs_l["q_norm"] = P(None, None)
        specs_l["k_norm"] = P(None, None)
    if cfg.block == "afmoe":
        specs_l["w_attn_gate"] = specs_l["wq"]
        specs_l["post_attn_norm"] = specs_l["post_mlp_norm"] = P(None, None)
    dense_l = dict(specs_l, w_gate=P(None, fsdp, tp), w_up=P(None, fsdp, tp),
                   w_down=P(None, tp, fsdp))
    if cfg.experts_held:
        # the held experts are this chip's share already: FSDP over d
        specs_l.update(
            router=P(None, fsdp, None), expert_bias=P(None, None),
            w_gate=P(None, None, fsdp, None), w_up=P(None, None, fsdp, None),
            w_down=P(None, None, None, fsdp))
        if cfg.num_shared_experts:
            specs_l.update(shared_gate=P(None, fsdp, tp),
                           shared_up=P(None, fsdp, tp),
                           shared_down=P(None, tp, fsdp))
    elif cfg.num_experts:
        ep = tp if cfg.num_experts % 16 == 0 else None
        ffn_tp = None if ep else tp
        specs_l.update(
            router=P(None, fsdp, None),
            w_gate=P(None, ep, fsdp, ffn_tp),
            w_up=P(None, ep, fsdp, ffn_tp),
            w_down=P(None, ep, ffn_tp, fsdp),
        )
    else:
        specs_l.update(
            w_gate=P(None, fsdp, tp), w_up=P(None, fsdp, tp),
            w_down=P(None, tp, fsdp),
        )
    tree: Params = {"layers": specs_l}
    if cfg.num_dense_layers:
        tree["dense_layers"] = dense_l
    if not cfg.embedding_inputs:
        tree["embed"] = P(tp, fsdp)
    tree["final_norm"] = P(None)
    tree["lm_head"] = P(fsdp, tp)
    return tree


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _attn_block(cfg: ModelConfig, lp: Params, x: jax.Array,
                positions: jax.Array,
                cache_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                cache_pos: Optional[jax.Array] = None,
                window=0, rope: Optional[jax.Array] = None):
    """One attention sub-block.  Returns (out, new_kv) where new_kv is the
    updated (k_cache, v_cache) when a cache is provided, else None.
    ``rope`` (a per-layer int32 0/1) zeroes the positions of a layer that
    uses no position encoding: rotation by angle 0 is the identity."""
    dtype = jnp.dtype(cfg.compute_dtype)
    h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhe->bshe", h, lp["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhe->bshe", h, lp["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhe->bshe", h, lp["wv"].astype(dtype))
    if cfg.qk_norm:
        q = L.rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, lp["k_norm"], cfg.norm_eps)
    if rope is not None:
        positions = positions * rope
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)

    new_kv = None
    if cache_kv is not None:
        kc, vc = cache_kv
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                          (0, cache_pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                          (0, cache_pos, 0, 0))
        new_kv = (kc, vc)
        kv_len = cache_pos + k.shape[1]
        out = L.attention(q, kc, vc, causal=True, q_offset=cache_pos,
                          block_kv=cfg.flash_block_kv, kv_len=kv_len,
                          window=window)
    else:
        out = L.attention(q, k, v, causal=cfg.causal, q_offset=0,
                          block_kv=cfg.flash_block_kv, window=window)
    if cfg.block == "afmoe":
        out = out * jax.nn.sigmoid(jnp.einsum(
            "bsd,dhe->bshe", h, lp["w_attn_gate"].astype(dtype)))
    out = jnp.einsum("bshe,hed->bsd", out, lp["wo"].astype(dtype))
    return out, new_kv


def _ffn_block(cfg: ModelConfig, lp: Params, x: jax.Array,
               experts: Optional[bool] = None):
    """(out, counters): the expert-share layer's ``expert_rows`` (the
    assignments each held expert received) and ``expert_choices`` (the
    experts each token selected); None for the other MLPs."""
    h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    experts = bool(cfg.num_experts) if experts is None else experts
    if experts and cfg.experts_held:
        shared = (lp["shared_gate"], lp["shared_up"], lp["shared_down"]) \
            if cfg.num_shared_experts else None
        y, rows, choices = expert_share_ffn(
            h, lp["router"], lp["expert_bias"], lp["w_gate"], lp["w_up"],
            lp["w_down"], shared, top_k=cfg.experts_per_token,
            first=cfg.expert_first, route_scale=cfg.route_scale)
        return y, {"expert_rows": rows, "expert_choices": choices}
    if experts:
        return moe_ffn(h, lp["router"], lp["w_gate"], lp["w_up"],
                       lp["w_down"], top_k=cfg.experts_per_token,
                       capacity_factor=cfg.capacity_factor), None
    return L.swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), None


def _block(cfg: ModelConfig, lp: Params, x: jax.Array, positions: jax.Array,
           window=0, rope: Optional[jax.Array] = None,
           experts: Optional[bool] = None):
    """One layer on the full sequence: (x, counters)."""
    a, _ = _attn_block(cfg, lp, x, positions, window=window, rope=rope)
    if cfg.block == "afmoe":
        a = L.rms_norm(a, lp["post_attn_norm"], cfg.norm_eps)
    x = L.constrain(x + a, "batch", None, None)
    f, counters = _ffn_block(cfg, lp, x, experts)
    if cfg.block == "afmoe":
        f = L.rms_norm(f, lp["post_mlp_norm"], cfg.norm_eps)
    return L.constrain(x + f, "batch", None, None), counters


def _windows(cfg: ModelConfig, lo: int, hi: int):
    return [cfg.attn_window if k == "sliding" else 0
            for k in cfg.layer_types[lo:hi]]


def layer_kinds(cfg: ModelConfig, lo: int, hi: int):
    """(window, rope) int32 (hi - lo,) of layers lo..hi-1: a "sliding"
    layer attends within ``attn_window`` with RoPE, a "global" one over
    every earlier position with no position encoding."""
    kinds = cfg.layer_types[lo:hi]
    return (jnp.asarray(_windows(cfg, lo, hi), jnp.int32),
            jnp.asarray([int(k == "sliding") for k in kinds], jnp.int32))


def attn_tiles(cfg: ModelConfig, seq_len: int):
    """(visited, total) attention score tiles of one full-sequence
    forward over ``seq_len`` tokens, summed over the layers
    (``layers.flash_tiles``)."""
    windows = (_windows(cfg, 0, cfg.num_layers) if cfg.layer_types
               else [0] * cfg.num_layers)
    tiles = [L.flash_tiles(seq_len, cfg.flash_block_kv, w, cfg.causal)
             for w in windows]
    return sum(t[0] for t in tiles), sum(t[1] for t in tiles)


def _embed(cfg: ModelConfig, params: Params, tokens: jax.Array) -> jax.Array:
    dtype = jnp.dtype(cfg.compute_dtype)
    if cfg.embedding_inputs:
        return tokens.astype(dtype)          # already (B, S, d) embeddings
    x = params["embed"].astype(dtype)[tokens]
    if cfg.block == "afmoe":
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
    return x


def _unembed(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    dtype = jnp.dtype(cfg.compute_dtype)
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(dtype))


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array,
            positions: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence forward -> logits (B, S, V_pad).  Train / prefill path."""
    return forward_aux(cfg, params, tokens, positions)[0]


def forward_aux(cfg: ModelConfig, params: Params, tokens: jax.Array,
                positions: Optional[jax.Array] = None):
    """(logits, aux).  For a model with the expert-share layer, by expert
    layer: ``aux["expert_rows"]`` (layers, held) int32, the assignments
    each held expert received, and ``aux["expert_choices"]`` (layers, B,
    S, k) int32, the experts each token selected; otherwise aux is
    empty."""
    x = L.constrain(_embed(cfg, params, tokens), "batch", None, None)
    b, s = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def stack(x, layers, lo, hi, experts=None):
        if cfg.layer_types:
            def layer(x, inp):
                lp, window, rope = inp
                return _block(cfg, lp, x, positions, window, rope, experts)
            xs = (layers,) + layer_kinds(cfg, lo, hi)
        else:
            def layer(x, lp):
                return _block(cfg, lp, x, positions, experts=experts)
            xs = layers
        if cfg.remat == "full":
            layer = jax.checkpoint(layer)
        elif cfg.remat == "dots":
            # save matmul outputs, recompute the cheap elementwise chains:
            # trades a little residency for removing the recompute HBM
            # traffic
            layer = jax.checkpoint(
                layer,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        return jax.lax.scan(layer, x, xs)

    nd = cfg.num_dense_layers
    if nd:
        x, _ = stack(x, params["dense_layers"], 0, nd, experts=False)
    x, counters = stack(x, params["layers"], nd, cfg.num_layers)
    aux = counters or {}
    return L.constrain(_unembed(cfg, params, x), "batch", None, "model"), aux


def loss_fn(cfg: ModelConfig, params: Params, tokens: jax.Array,
            labels: jax.Array) -> jax.Array:
    logits = forward(cfg, params, tokens)
    # padded vocab tail never appears in labels; mask not needed
    return L.cross_entropy_loss(logits, labels)


# --------------------------------------------------------------------------
# KV-cache serving path
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype: str = "bfloat16") -> Tuple[jax.Array, jax.Array]:
    """Stacked KV cache (L, B, S_max, KV, hd) pair."""
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads,
             cfg.resolved_head_dim)
    z = jnp.zeros(shape, jnp.dtype(dtype))
    return (z, z)


def cache_shapes(cfg: ModelConfig, batch: int, max_len: int,
                 dtype: str = "bfloat16"):
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads,
             cfg.resolved_head_dim)
    sds = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    return (sds, sds)


def cache_specs(cfg: ModelConfig, fsdp: str = "data", tp: str = "model"):
    """KV-cache sharding (L, B, S, KV, hd).

    KV heads shard over ``tp`` when divisible; otherwise (GQA with few KV
    heads) the HEAD_DIM axis is tp-sharded instead -- RoPE is applied before
    the cache write, so head_dim becomes a pure contraction axis and GSPMD
    turns the q.k score into a psum (one small collective per decode step
    on the dense single-token path), keeping the multi-GB cache sharded
    rather than replicated 16-way.
    """
    if cfg.num_kv_heads % 16 == 0:
        spec = P(None, fsdp, None, tp, None)
    else:
        spec = P(None, fsdp, None, None, tp)
    return (spec, spec)


def decode_step(cfg: ModelConfig, params: Params,
                cache: Tuple[jax.Array, jax.Array],
                tokens: jax.Array, pos: jax.Array):
    """One autoregressive step: tokens (B, 1) (or (B, 1, d) embeddings),
    ``pos`` scalar int32 position. Returns (logits (B, 1, V), new_cache).
    Not for a model with a leading dense stack or per-layer kinds."""
    if cfg.num_dense_layers or cfg.layer_types:
        raise NotImplementedError(
            f"{cfg.name}: decode through the cache is not implemented for "
            "leading dense layers or per-layer attention kinds")
    x = _embed(cfg, params, tokens)
    b = x.shape[0]
    positions = jnp.broadcast_to(pos.astype(jnp.int32), (b, 1))

    def layer(carry, inputs):
        x = carry
        lp, kc, vc = inputs
        a, new_kv = _attn_block(cfg, lp, x, positions, cache_kv=(kc, vc),
                                cache_pos=pos)
        x = x + a
        x = x + _ffn_block(cfg, lp, x)[0]
        return x, new_kv

    x, new_cache = jax.lax.scan(layer, x, (params["layers"],) + tuple(cache))
    return _unembed(cfg, params, x), new_cache
