"""Mamba2 LM (pure SSM) and Zamba2-style hybrid (SSM + shared attention).

The hybrid applies ONE shared attention+MLP block (a single parameter copy,
as in Zamba2) after every ``cfg.attn_every``-th mamba layer; each invocation
site keeps its own KV cache.  For long contexts the shared block uses
sliding-window attention (``cfg.attn_window``) with a ring-buffer cache, so
decode state is O(window), giving the sub-quadratic long_500k path.

Layer params are stacked; the forward is a scan per period (period =
``attn_every`` mamba layers + one shared-attention call), so HLO depth is
O(num_layers / attn_every) for the hybrid and O(1) for pure SSM.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from . import layers as L
from . import mamba2 as M
from .transformer import padded_vocab

Params = Dict[str, Any]


def _dt(cfg):
    return jnp.dtype(cfg.param_dtype)


def _n_attn_sites(cfg: ModelConfig) -> int:
    if cfg.attn_every <= 0:
        return 0
    return cfg.num_layers // cfg.attn_every


def attn_tiles(cfg: ModelConfig, seq_len: int):
    """(visited, total) attention score tiles of one full-sequence
    forward: the shared block's calls (``layers.flash_tiles``)."""
    visited, total = L.flash_tiles(seq_len, cfg.flash_block_kv,
                                   cfg.attn_window)
    n = _n_attn_sites(cfg)
    return n * visited, n * total


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def _shared_attn_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv, f = cfg.num_heads, cfg.num_kv_heads, cfg.d_ff
    return {
        "attn_norm": (d,), "wq": (d, h, hd), "wk": (d, kv, hd),
        "wv": (d, kv, hd), "wo": (h, hd, d), "mlp_norm": (d,),
        "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
    }


def param_shapes(cfg: ModelConfig) -> Params:
    dt = _dt(cfg)
    v = padded_vocab(cfg)
    tree: Params = {
        "embed": jax.ShapeDtypeStruct((v, cfg.d_model), dt),
        "layers": {k: jax.ShapeDtypeStruct(s, dt)
                   for k, s in M.layer_shapes(cfg, cfg.num_layers).items()},
        "final_norm": jax.ShapeDtypeStruct((cfg.d_model,), dt),
        "lm_head": jax.ShapeDtypeStruct((cfg.d_model, v), dt),
    }
    if _n_attn_sites(cfg):
        tree["shared_attn"] = {k: jax.ShapeDtypeStruct(s, dt)
                               for k, s in _shared_attn_shapes(cfg).items()}
    return tree


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    dt = _dt(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    v = padded_vocab(cfg)
    tree: Params = {
        "embed": L.embed_init(k1, (v, cfg.d_model), dt),
        "layers": M.init_layer_params(cfg, cfg.num_layers, k2),
        "final_norm": jnp.ones((cfg.d_model,), dt),
        "lm_head": L.dense_init(k3, (cfg.d_model, v), cfg.d_model, dt),
    }
    if _n_attn_sites(cfg):
        shapes = _shared_attn_shapes(cfg)
        keys = jax.random.split(k4, len(shapes))
        sa = {}
        for (name, shape), kk in zip(sorted(shapes.items()), keys):
            if "norm" in name:
                sa[name] = jnp.ones(shape, dt)
            else:
                sa[name] = L.dense_init(kk, shape, shape[0], dt)
        tree["shared_attn"] = sa
    return tree


def partition_specs(cfg: ModelConfig, fsdp: str = "data", tp: str = "model") -> Params:
    tree: Params = {
        "embed": P(tp, fsdp),
        "layers": M.layer_specs(cfg, fsdp, tp),
        "final_norm": P(None),
        "lm_head": P(fsdp, tp),
    }
    if _n_attn_sites(cfg):
        head = tp if cfg.num_heads % 16 == 0 else None
        kvh = tp if cfg.num_kv_heads % 16 == 0 else None
        tree["shared_attn"] = {
            "attn_norm": P(None), "wq": P(fsdp, head, None),
            "wk": P(fsdp, kvh, None), "wv": P(fsdp, kvh, None),
            "wo": P(head, None, fsdp), "mlp_norm": P(None),
            "w_gate": P(fsdp, tp), "w_up": P(fsdp, tp), "w_down": P(tp, fsdp),
        }
    return tree


# --------------------------------------------------------------------------
# Shared attention block (single param copy)
# --------------------------------------------------------------------------

def _shared_attn(cfg: ModelConfig, sp: Params, x: jax.Array,
                 positions: jax.Array,
                 cache_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                 cache_pos: Optional[jax.Array] = None):
    dtype = jnp.dtype(cfg.compute_dtype)
    h = L.rms_norm(x, sp["attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhe->bshe", h, sp["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhe->bshe", h, sp["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhe->bshe", h, sp["wv"].astype(dtype))
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    new_kv = None
    if cache_kv is not None:
        # ring-buffer window cache: slot = pos % window
        kc, vc = cache_kv
        wlen = kc.shape[1]
        slot = (cache_pos % wlen).astype(jnp.int32)
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype), (0, slot, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype), (0, slot, 0, 0))
        new_kv = (kc, vc)
        kv_len = jnp.minimum(cache_pos + 1, wlen)
        out = L.attention(q, kc, vc, causal=False, kv_len=kv_len,
                          block_kv=cfg.flash_block_kv)
    else:
        out = L.attention(q, k, v, causal=True, q_offset=0,
                          block_kv=cfg.flash_block_kv,
                          window=cfg.attn_window)
    a = jnp.einsum("bshe,hed->bsd", out, sp["wo"].astype(dtype))
    x = x + a
    hm = L.rms_norm(x, sp["mlp_norm"], cfg.norm_eps)
    x = x + L.swiglu(hm, sp["w_gate"], sp["w_up"], sp["w_down"])
    return x, new_kv


# --------------------------------------------------------------------------
# Forward (train / prefill)
# --------------------------------------------------------------------------

def _slice_layers(layers: Params, start: int, count: int) -> Params:
    return jax.tree.map(lambda a: jax.lax.slice_in_dim(a, start, start + count, axis=0),
                        layers)


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array,
            positions: Optional[jax.Array] = None) -> jax.Array:
    dtype = jnp.dtype(cfg.compute_dtype)
    x = L.constrain(params["embed"].astype(dtype)[tokens], "batch", None, None)
    b, s = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def mamba_layer(x, lp):
        return L.constrain(x + M.mamba2_block(cfg, lp, x),
                           "batch", None, None), None

    if cfg.remat == "full":
        mamba_layer = jax.checkpoint(mamba_layer)
    elif cfg.remat == "dots":
        mamba_layer = jax.checkpoint(
            mamba_layer,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    ae = cfg.attn_every if cfg.attn_every > 0 else cfg.num_layers
    n_sites = _n_attn_sites(cfg)
    done = 0
    for site in range(n_sites):
        grp = _slice_layers(params["layers"], done, ae)
        x, _ = jax.lax.scan(mamba_layer, x, grp)
        x, _ = _shared_attn(cfg, params["shared_attn"], x, positions)
        x = L.constrain(x, "batch", None, None)
        done += ae
    if done < cfg.num_layers:
        grp = _slice_layers(params["layers"], done, cfg.num_layers - done)
        x, _ = jax.lax.scan(mamba_layer, x, grp)

    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(dtype))
    return L.constrain(logits, "batch", None, "model")


def loss_fn(cfg: ModelConfig, params: Params, tokens: jax.Array,
            labels: jax.Array) -> jax.Array:
    return L.cross_entropy_loss(forward(cfg, params, tokens), labels)


# --------------------------------------------------------------------------
# Decode state
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype: str = "bfloat16") -> Dict[str, Any]:
    state: Dict[str, Any] = {
        "ssm": M.init_block_state(cfg, cfg.num_layers, batch)
    }
    n_sites = _n_attn_sites(cfg)
    if n_sites:
        wlen = cfg.attn_window if cfg.attn_window > 0 else max_len
        wlen = min(wlen, max_len)
        shape = (n_sites, batch, wlen, cfg.num_kv_heads, cfg.resolved_head_dim)
        z = jnp.zeros(shape, jnp.dtype(dtype))
        state["attn_k"] = z
        state["attn_v"] = z
    return state


def cache_shapes(cfg: ModelConfig, batch: int, max_len: int,
                 dtype: str = "bfloat16") -> Dict[str, Any]:
    state: Dict[str, Any] = {
        "ssm": M.block_state_shapes(cfg, cfg.num_layers, batch)
    }
    n_sites = _n_attn_sites(cfg)
    if n_sites:
        wlen = cfg.attn_window if cfg.attn_window > 0 else max_len
        wlen = min(wlen, max_len)
        shape = (n_sites, batch, wlen, cfg.num_kv_heads, cfg.resolved_head_dim)
        state["attn_k"] = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
        state["attn_v"] = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    return state


def cache_specs(cfg: ModelConfig, fsdp: str = "data", tp: str = "model") -> Dict[str, Any]:
    state: Dict[str, Any] = {"ssm": M.block_state_specs(cfg, fsdp, tp)}
    if _n_attn_sites(cfg):
        kvh = tp if cfg.num_kv_heads % 16 == 0 else None
        spec = P(None, None, None, kvh, None)
        state["attn_k"] = spec
        state["attn_v"] = spec
    return state


def decode_step(cfg: ModelConfig, params: Params, cache: Dict[str, Any],
                tokens: jax.Array, pos: jax.Array):
    """One token for the whole stack.  tokens (B, 1)."""
    dtype = jnp.dtype(cfg.compute_dtype)
    x = params["embed"].astype(dtype)[tokens]
    b = x.shape[0]
    positions = jnp.broadcast_to(pos.astype(jnp.int32), (b, 1))

    def mamba_layer(x, inputs):
        lp, st = inputs
        out, new_st = M.mamba2_block_decode(cfg, lp, x, st)
        return x + out, new_st

    ae = cfg.attn_every if cfg.attn_every > 0 else cfg.num_layers
    n_sites = _n_attn_sites(cfg)
    done = 0
    new_ssm_parts = []
    new_k, new_v = cache.get("attn_k"), cache.get("attn_v")
    for site in range(n_sites):
        grp = _slice_layers(params["layers"], done, ae)
        st = _slice_layers(cache["ssm"], done, ae)
        x, new_st = jax.lax.scan(mamba_layer, x, (grp, st))
        new_ssm_parts.append(new_st)
        kv = (new_k[site], new_v[site])
        x, (kc, vc) = _shared_attn(cfg, params["shared_attn"], x, positions,
                                   cache_kv=kv, cache_pos=pos)
        new_k = new_k.at[site].set(kc)
        new_v = new_v.at[site].set(vc)
        done += ae
    if done < cfg.num_layers:
        grp = _slice_layers(params["layers"], done, cfg.num_layers - done)
        st = _slice_layers(cache["ssm"], done, cfg.num_layers - done)
        x, new_st = jax.lax.scan(mamba_layer, x, (grp, st))
        new_ssm_parts.append(new_st)

    new_ssm = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                           *new_ssm_parts) if len(new_ssm_parts) > 1 \
        else new_ssm_parts[0]
    new_cache: Dict[str, Any] = {"ssm": new_ssm}
    if n_sites:
        new_cache["attn_k"] = new_k
        new_cache["attn_v"] = new_v
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(dtype))
    return logits, new_cache
