"""Shared neural-net building blocks (pure-pytree JAX, no flax).

Everything here is shape-polymorphic over batch/sequence and written so that
GSPMD can propagate shardings from the parameter/input PartitionSpecs:
no reshapes that merge a sharded axis with an unsharded one, heads kept as
an explicit axis, and attention computed blockwise (online softmax) so the
(S x S) score matrix is never materialized for long sequences.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


# --------------------------------------------------------------------------
# Activation sharding constraints (explicit mesh context; no-op without one)
# --------------------------------------------------------------------------

_ACT_MESH = None     # set by launch/steps + train/serve drivers at trace time

# Per-optimization switches for the §Perf hypothesis loop (set before
# import; the dry-run measures each in an isolated subprocess):
#   REPRO_OPT=norm_vjp,attn_probs16,moe_a2a,...
_OPTS = set(filter(None, os.environ.get("REPRO_OPT", "").split(",")))


def opt_enabled(name: str) -> bool:
    return name in _OPTS


class activation_mesh:
    """Context manager: resolve ``constrain`` specs against this mesh."""

    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        global _ACT_MESH
        self._prev = _ACT_MESH
        _ACT_MESH = self.mesh
        return self.mesh

    def __exit__(self, *exc):
        global _ACT_MESH
        _ACT_MESH = self._prev
        return False


def constrain(x: jax.Array, *elems) -> jax.Array:
    """with_sharding_constraint with symbolic axes.

    Elements: "batch" (resolves to the (pod, data) prefix that divides the
    dim), a mesh axis name (kept if present AND divides the dim), or None.
    Without an ``activation_mesh`` context this is the identity, so model
    code runs unchanged on a single host.  Pinning the residual stream to
    batch-sharded layout is what makes GSPMD do FSDP (all-gather WEIGHTS,
    layer by layer inside the scan) instead of resharding activations along
    d_model and all-reducing every projection.
    """
    mesh = _ACT_MESH
    if mesh is None:
        return x
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    resolved = []
    for i, e in enumerate(elems):
        dim = x.shape[i] if i < x.ndim else 1
        if e == "batch":
            axes = tuple(a for a in ("pod", "data") if a in sizes)
            while axes and dim % math.prod(sizes[a] for a in axes) != 0:
                axes = axes[1:]
            resolved.append(axes if len(axes) > 1 else
                            (axes[0] if axes else None))
        elif isinstance(e, str) and e in sizes and dim % sizes[e] == 0:
            resolved.append(e)
        else:
            resolved.append(None)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, P(*resolved)))

# --------------------------------------------------------------------------
# Initializers
# --------------------------------------------------------------------------

def dense_init(key, shape, in_axis_size, dtype=jnp.float32):
    """Truncated-normal fan-in init (matches common LM inits)."""
    std = 1.0 / math.sqrt(max(in_axis_size, 1))
    return std * jax.random.truncated_normal(key, -3.0, 3.0, shape, dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return 0.02 * jax.random.truncated_normal(key, -3.0, 3.0, shape, dtype)


# --------------------------------------------------------------------------
# RMSNorm -- custom VJP: fp32 row statistics, bf16 element streams.
#
# Autodiff through a naive fp32 upcast materializes ~5 residual-sized fp32
# tensors per norm in the backward pass (measured: the single largest HBM
# consumer of the dense train cells).  The hand-written VJP keeps every
# (B, S, d)-sized read/write in x.dtype and only the per-row reductions in
# fp32:   dx = r * (g*w - x_hat * mean(g*w*x_hat)),  r = rsqrt(var + eps).
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm_custom(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with fp32 statistics and dtype-preserving streams."""
    return _rms_norm_fwd(x, scale, eps)[0]


def _rms_norm_naive(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm in fp32 end to end (autodiff backward)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rms_norm(x, scale, eps: float = 1e-6):
    if opt_enabled("norm_vjp"):
        return _rms_norm_custom(x, scale, eps)
    return _rms_norm_naive(x, scale, eps)


def _rms_norm_fwd(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)                       # (..., 1) fp32
    y = (x32 * r).astype(x.dtype) * scale.astype(x.dtype)
    return y, (x, scale, r)


def _rms_norm_bwd(eps, res, g):
    x, scale, r = res
    dtype = x.dtype
    gw = g * scale.astype(dtype)                       # (..., d) in dtype
    # fp32 only for the row reduction
    m = jnp.sum((gw * x).astype(jnp.float32),
                axis=-1, keepdims=True) / x.shape[-1]  # (..., 1) f32 accum
    rx = r * r * r * m                                 # (..., 1) fp32
    # every (B, S, d)-sized stream stays in x.dtype: the per-row scalars
    # are cast down so no fp32 residual-sized boundary tensor exists
    dx = gw * r.astype(dtype) - x * rx.astype(dtype)
    dscale = jnp.sum((g * (x * r.astype(dtype))).astype(jnp.float32),
                     axis=tuple(range(x.ndim - 1))).astype(scale.dtype)
    return dx, dscale


_rms_norm_custom.defvjp(_rms_norm_fwd, _rms_norm_bwd)


# --------------------------------------------------------------------------
# Rotary position embedding
# --------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 1e4) -> jax.Array:
    """Inverse frequencies (head_dim/2,) in fp32."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 1e4) -> jax.Array:
    """Rotate (..., S, H, hd) by per-token positions (..., S).

    Uses the half-split convention: pairs are (i, i + hd/2), so sharding over
    heads (not head_dim) is safe.
    """
    half = x.shape[-1] // 2
    freqs = rope_frequencies(x.shape[-1], theta)              # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                        # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Blockwise (flash-style) attention -- pure-jnp path
# --------------------------------------------------------------------------

def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, KV, hd) -> (B, S, KV * n_rep, hd) by head repetition (GQA)."""
    if n_rep == 1:
        return k
    b, s, kv, hd = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, hd))
    return k.reshape(b, s, kv * n_rep, hd)


def _block_mask(q_pos, kpos, causal, window, sq, blk):
    """``window``: a static int (0 = full), or a traced int32 scalar (a
    per-layer window scanned with the layers; <= 0 = full)."""
    mask = jnp.ones((sq, blk), dtype=bool)
    if causal:
        mask &= q_pos[:, None] >= kpos[None, :]
        if not isinstance(window, int):
            mask &= (window <= 0) | (q_pos[:, None] - kpos[None, :] < window)
        elif window > 0:
            mask &= q_pos[:, None] - kpos[None, :] < window
    return mask


def _tile_bounds(nq, nk, block, off, causal, window):
    """Key blocks [lo, hi) that each of ``nq`` query blocks visits: those
    holding a key its mask admits.  Query block i holds positions
    ``off + i * block ...``; causal attention stops at the block of its
    last query, and a window > 0 starts at the block of ``q_lo - window +
    1``.  Every key outside [lo, hi) would be masked.  Numpy arrays where
    the window is a static int, traced ones where it is traced."""
    xp = np if isinstance(window, int) else jnp
    i = xp.arange(nq, dtype=xp.int32)
    if not causal:
        return xp.zeros_like(i), xp.full_like(i, nk)
    hi = xp.minimum((off + (i + 1) * block - 1) // block + 1, nk)
    lo = xp.where(window > 0,
                  xp.maximum((off + i * block - window + 1) // block, 0), 0)
    return lo, hi


def flash_tiles(seq_len: int, block: int, window: int = 0,
                causal: bool = True) -> Tuple[int, int]:
    """(visited, total) (query block x key block) score tiles of the train
    path's flash attention over ``seq_len`` tokens in blocks of ``block``
    (at most ``seq_len``), from the bounds the schedule itself uses."""
    block = min(block, seq_len)
    n = -(-seq_len // block)
    lo, hi = _tile_bounds(n, n, block, 0, causal, window)
    return int((hi - lo).sum()), n * n


def _fwd_tile(carry, q32, kblk, vblk, q_pos, blk_idx, causal, window,
              block, sk, pad, neg):
    """One key block into the online-softmax state (m, l, acc) of the
    queries ``q32`` (B, sq, H, hd) at positions ``q_pos``; masked scores
    read ``neg``."""
    m, l, acc = carry
    kpos = blk_idx * block + jnp.arange(block, dtype=jnp.int32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q32, kblk.astype(jnp.float32))
    mask = _block_mask(q_pos, kpos, causal, window, q_pos.shape[0], block)
    if pad:
        mask &= (kpos[None, :] < sk)
    s = jnp.where(mask[None, None], s, neg)
    m_new = jnp.maximum(m, s.max(axis=-1))
    corr = jnp.exp(m - m_new)
    if opt_enabled("attn_probs16"):
        # ONE score-sized tensor in the compute dtype; its row sum
        # accumulates in fp32 inside the reduce
        p = jnp.exp(s - m_new[..., None]).astype(vblk.dtype)
        l_new = l * corr + jnp.sum(p, axis=-1, dtype=jnp.float32)
    else:
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, vblk.astype(p.dtype),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _softmax_init(b, h, sq, hd, neg):
    return (jnp.full((b, h, sq), neg, dtype=jnp.float32),
            jnp.zeros((b, h, sq), dtype=jnp.float32),
            jnp.zeros((b, h, sq, hd), dtype=jnp.float32))


def _softmax_out(m, l, acc):
    l = jnp.maximum(l, 1e-30)
    return acc / l[..., None], m + jnp.log(l)


def _flash_fwd_scan(q32, kb, vb, q_pos, causal, window, sq, block, sk, pad):
    """Online-softmax forward of all queries over every key block.  q32
    (B,Sq,H,hd) pre-scaled fp32; kb/vb (nblocks, B, block, H, hd).
    Returns (out fp32 (B,H,Sq,hd), lse)."""
    b, _, h, hd = q32.shape
    neg = jnp.float32(-1e30)

    def body(carry, inputs):
        kblk, vblk, blk_idx = inputs
        return _fwd_tile(carry, q32, kblk, vblk, q_pos, blk_idx, causal,
                         window, block, sk, pad, neg), None

    (m, l, acc), _ = jax.lax.scan(
        body, _softmax_init(b, h, sq, hd, neg),
        (kb, vb, jnp.arange(kb.shape[0], dtype=jnp.int32)))
    return _softmax_out(m, l, acc)


def _flash_fwd_tiles(q32, kb, vb, off, causal, window, block, sk, pad):
    """The forward over query blocks of ``block``, each visiting only the
    key blocks ``_tile_bounds`` gives it (a run-time range where the
    window is traced).  Returns (out fp32 (nq,B,H,block,hd), lse
    (nq,B,H,block)); padded query rows are the caller's to drop."""
    b, _, h, hd = q32.shape
    qb, _ = _blocks(q32, block)
    nq, nk = qb.shape[0], kb.shape[0]
    lo, hi = _tile_bounds(nq, nk, block, off, causal, window)
    neg = jnp.float32(-1e30)

    def q_step(_, inputs):
        qt, i, lo_i, hi_i = inputs
        q_pos = off + i * block + jnp.arange(block, dtype=jnp.int32)

        def k_step(j, carry):
            return _fwd_tile(carry, qt, kb[j], vb[j], q_pos, j, causal,
                             window, block, sk, pad, neg)

        m, l, acc = jax.lax.fori_loop(lo_i, hi_i, k_step,
                                      _softmax_init(b, h, block, hd, neg))
        return None, _softmax_out(m, l, acc)

    _, (out, lse) = jax.lax.scan(
        q_step, None, (qb, jnp.arange(nq, dtype=jnp.int32), lo, hi))
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_train(q, k, v, causal: bool, window: int, block_kv: int):
    """MHA blockwise attention with flash (recompute) backward.

    q (B,Sq,H,hd); k/v (B,Sk,H,hd) (GQA repeat done by the caller).
    The custom VJP recomputes per-block scores in the backward pass, so AD
    never stores the (Sq x Sk) softmax -- O(S) residuals (q,k,v,out,lse).
    With more than one key block the queries are blocked too, and each
    (query block, key block) tile the mask closes wholly is skipped.
    """
    out, _ = _flash_train_fwd(q, k, v, causal, window, block_kv)
    return out


def _blocks(x, block):
    b, s, h, hd = x.shape
    nb = (s + block - 1) // block
    pad = nb * block - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return x.reshape(b, nb, block, h, hd).transpose(1, 0, 2, 3, 4), pad


def _unblocks(xb, s):
    """(nb, B, block, H, hd) -> (B, s, H, hd), the padding dropped."""
    nb, b, block, h, hd = xb.shape
    return xb.transpose(1, 0, 2, 3, 4).reshape(b, nb * block, h, hd)[:, :s]


def _flash_train_fwd(q, k, v, causal, window, block_kv):
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    block = min(block_kv, sk)
    scale = 1.0 / math.sqrt(hd)
    q32 = q.astype(jnp.float32) * scale
    kb, pad = _blocks(k, block)
    vb, _ = _blocks(v, block)
    if sk > block:
        out32, lse = _flash_fwd_tiles(q32, kb, vb, sk - sq, causal, window,
                                      block, sk, pad)
        out = _unblocks(out32.transpose(0, 1, 3, 2, 4), sq).astype(q.dtype)
        return out, (q, k, v, out, lse)
    q_pos = (sk - sq) + jnp.arange(sq, dtype=jnp.int32)
    out32, lse = _flash_fwd_scan(q32, kb, vb, q_pos, causal, window,
                                 sq, block, sk, pad)
    out = out32.transpose(0, 2, 1, 3).astype(q.dtype)   # (B, Sq, H, hd)
    return out, (q, k, v, out, lse)


def _bwd_tile(dq_acc, q, q32, dout, do32, lse, delta, kblk, vblk, q_pos,
              blk_idx, causal, window, block, sk, pad, scale):
    """The recompute backward of one (queries, key block) tile: dq_acc
    plus this block's share of dq, and the block's dk and dv."""
    kpos = blk_idx * block + jnp.arange(block, dtype=jnp.int32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q32 * scale,
                   kblk.astype(jnp.float32))
    mask = _block_mask(q_pos, kpos, causal, window, q_pos.shape[0], block)
    if pad:
        mask &= (kpos[None, :] < sk)
    s = jnp.where(mask[None, None], s, -1e30)
    if opt_enabled("attn_probs16"):
        p = jnp.exp(s - lse[..., None]).astype(vblk.dtype)
        dv_blk = jnp.einsum("bhqk,bqhd->bkhd", p, dout.astype(p.dtype),
                            preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqhd,bkhd->bhqk", do32,
                        vblk.astype(jnp.float32))
        ds = p * ((dp - delta[..., None]) * scale).astype(p.dtype)
        dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bqhd", ds, kblk,
                                     preferred_element_type=jnp.float32)
        dk_blk = jnp.einsum("bhqk,bqhd->bkhd", ds, q.astype(ds.dtype),
                            preferred_element_type=jnp.float32)
    else:
        p32 = jnp.exp(s - lse[..., None])
        dv_blk = jnp.einsum("bhqk,bqhd->bkhd", p32, do32)
        dp = jnp.einsum("bqhd,bkhd->bhqk", do32,
                        vblk.astype(jnp.float32))
        ds = p32 * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bqhd", ds,
                                     kblk.astype(jnp.float32))
        dk_blk = jnp.einsum("bhqk,bqhd->bkhd", ds, q32)
    return dq_acc, dk_blk, dv_blk


def _flash_train_bwd(causal, window, block_kv, res, dout):
    q, k, v, out, lse = res
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    block = min(block_kv, sk)
    if sk > block:
        return _flash_bwd_tiles(causal, window, block, res, dout)
    scale = 1.0 / math.sqrt(hd)
    q32 = q.astype(jnp.float32)
    do32 = dout.astype(jnp.float32)
    o32 = out.astype(jnp.float32)
    delta = jnp.einsum("bqhd,bqhd->bhq", do32, o32)      # (B, H, Sq)
    kb, pad = _blocks(k, block)
    vb, _ = _blocks(v, block)
    q_pos = (sk - sq) + jnp.arange(sq, dtype=jnp.int32)

    def body(dq_acc, inputs):
        kblk, vblk, blk_idx = inputs
        dq_acc, dk_blk, dv_blk = _bwd_tile(
            dq_acc, q, q32, dout, do32, lse, delta, kblk, vblk, q_pos,
            blk_idx, causal, window, block, sk, pad, scale)
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, sq, h, hd), jnp.float32)
    dq, (dkb, dvb) = jax.lax.scan(
        body, dq0, (kb, vb, jnp.arange(kb.shape[0], dtype=jnp.int32)))
    return (dq.astype(q.dtype), _unblocks(dkb, sk).astype(k.dtype),
            _unblocks(dvb, sk).astype(v.dtype))


def _flash_bwd_tiles(causal, window, block, res, dout):
    """The backward over the forward's tiles in one pass: key blocks
    outside, each visiting the query blocks that see it; dk and dv
    accumulate over those, dq per query block in place."""
    q, k, v, out, lse = res                  # lse (nq, B, H, block)
    sq, sk = q.shape[1], k.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])
    qb, _ = _blocks(q, block)
    q32b = qb.astype(jnp.float32)
    dob, _ = _blocks(dout, block)
    do32b = dob.astype(jnp.float32)
    delta = jnp.einsum("nbqhd,nbqhd->nbhq", do32b,
                       _blocks(out, block)[0].astype(jnp.float32))
    kb, pad = _blocks(k, block)
    vb, _ = _blocks(v, block)
    nq, nk = qb.shape[0], kb.shape[0]
    lo, hi = _tile_bounds(nq, nk, block, sk - sq, causal, window)
    # key block j is seen by the query blocks [q_lo, q_hi): lo, hi rise
    # with the query block
    j = jnp.arange(nk, dtype=jnp.int32)
    q_lo = jnp.sum(hi[None, :] <= j[:, None], axis=1, dtype=jnp.int32)
    q_hi = jnp.sum(lo[None, :] <= j[:, None], axis=1, dtype=jnp.int32)

    def k_step(dq, inputs):
        kblk, vblk, blk_idx, q_lo_j, q_hi_j = inputs

        def q_step(i, carry):
            dq, dk, dv = carry
            q_pos = (sk - sq) + i * block + jnp.arange(block,
                                                       dtype=jnp.int32)
            dq_i, dk_i, dv_i = _bwd_tile(
                dq[i], qb[i], q32b[i], dob[i], do32b[i], lse[i], delta[i],
                kblk, vblk, q_pos, blk_idx, causal, window, block, sk, pad,
                scale)
            return dq.at[i].set(dq_i), dk + dk_i, dv + dv_i

        zero = jnp.zeros(kblk.shape, jnp.float32)
        dq, dk, dv = jax.lax.fori_loop(q_lo_j, q_hi_j, q_step,
                                       (dq, zero, zero))
        return dq, (dk, dv)

    dq, (dkb, dvb) = jax.lax.scan(
        k_step, jnp.zeros(qb.shape, jnp.float32),
        (kb, vb, j, q_lo, q_hi))
    return (_unblocks(dq, sq).astype(q.dtype),
            _unblocks(dkb, sk).astype(k.dtype),
            _unblocks(dvb, sk).astype(v.dtype))


_flash_train.defvjp(_flash_train_fwd, _flash_train_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_train_window(q, k, v, window, causal: bool, block_kv: int):
    """``_flash_train`` with the window a traced int32 scalar."""
    return _flash_train_fwd(q, k, v, causal, window, block_kv)[0]


def _flash_window_fwd(q, k, v, window, causal, block_kv):
    out, res = _flash_train_fwd(q, k, v, causal, window, block_kv)
    return out, (res, window)


def _flash_window_bwd(causal, block_kv, res, dout):
    res, window = res
    return _flash_train_bwd(causal, window, block_kv, res, dout) + (None,)


_flash_train_window.defvjp(_flash_window_fwd, _flash_window_bwd)


def attention(
    q: jax.Array,                    # (B, Sq, H, hd)
    k: jax.Array,                    # (B, Sk, KV, hd)
    v: jax.Array,                    # (B, Sk, KV, hd)
    causal: bool = True,
    q_offset: Optional[jax.Array] = None,   # position of q[0] among keys
    block_kv: int = 1024,
    kv_len: Optional[jax.Array] = None,     # valid key prefix length (decode)
    window=0,                        # sliding window size (0 = full);
                                     # a traced scalar on the train path
) -> jax.Array:
    """Blockwise online-softmax attention; never materializes (Sq, Sk).

    The KV sequence is processed in ``block_kv`` chunks with a running
    (max, denominator, accumulator) triple -- the flash-attention recurrence
    -- via lax.scan, so peak memory is O(B*H*Sq*block) and XLA can overlap
    the chunk matmuls.  Handles GQA by repeating KV heads, causal masks via
    q_offset, decode via kv_len masking, and sliding-window attention.
    The train path (no ``kv_len``, several key blocks) blocks the queries
    too and visits only the tiles the causal and window masks leave open
    (``flash_tiles``), O(B*H*block^2) scores at a time.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    n_rep = h // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = 1.0 / math.sqrt(hd)
    if q_offset is None:
        q_offset = jnp.asarray(sk - sq, dtype=jnp.int32)
    q_pos = q_offset + jnp.arange(sq, dtype=jnp.int32)         # (Sq,)

    if sq == 1:
        # Decode fast path: one dense pass over the KV set.  The (B,H,1,Sk)
        # score tensor is small, and avoiding the block scan means GSPMD
        # inserts ONE reduction when the cache's contraction dim (head_dim)
        # is model-sharded, instead of one per block.
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                       k.astype(jnp.float32))
        kpos = jnp.arange(sk, dtype=jnp.int32)
        mask = jnp.ones((sq, sk), dtype=bool)
        if causal:
            mask &= q_pos[:, None] >= kpos[None, :]
            if window > 0:
                mask &= q_pos[:, None] - kpos[None, :] < window
        if kv_len is not None:
            mask &= kpos[None, :] < kv_len
        s = jnp.where(mask[None, None], s, jnp.float32(-1e30))
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
        return out.transpose(0, 2, 1, 3).astype(q.dtype)

    if kv_len is None:
        # Train / prefill full-sequence path: flash recurrence with a
        # recompute (flash) backward so AD never stores per-block softmax.
        if not isinstance(window, int):
            return _flash_train_window(q, k, v, window, causal, block_kv)
        return _flash_train(q, k, v, causal, window, block_kv)

    block = min(block_kv, sk)
    nblocks = (sk + block - 1) // block
    pad = nblocks * block - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # (nblocks, B, block, H, hd)
    kb = k.reshape(b, nblocks, block, h, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblocks, block, h, hd).transpose(1, 0, 2, 3, 4)

    q32 = q.astype(jnp.float32) * scale
    neg = jnp.float32(-1e30)

    def body(carry, inputs):
        m, l, acc = carry                                      # (B,H,Sq) ... (B,H,Sq,hd)
        kblk, vblk, blk_idx = inputs
        kpos = blk_idx * block + jnp.arange(block, dtype=jnp.int32)
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, kblk.astype(jnp.float32))
        mask = jnp.ones((sq, block), dtype=bool)
        if causal:
            mask &= q_pos[:, None] >= kpos[None, :]
            if window > 0:
                mask &= q_pos[:, None] - kpos[None, :] < window
        if kv_len is not None:
            mask &= kpos[None, :] < kv_len
        if pad:
            mask &= kpos[None, :] < sk
        s = jnp.where(mask[None, None], s, neg)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vblk.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), neg, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, sq), dtype=jnp.float32)
    a0 = jnp.zeros((b, h, sq, hd), dtype=jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kb, vb, jnp.arange(nblocks, dtype=jnp.int32))
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)           # (B, Sq, H, hd)


# --------------------------------------------------------------------------
# SwiGLU MLP
# --------------------------------------------------------------------------

def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    """SwiGLU: down( silu(x @ gate) * (x @ up) )."""
    g = jnp.einsum("...d,df->...f", x, w_gate.astype(x.dtype))
    u = jnp.einsum("...d,df->...f", x, w_up.astype(x.dtype))
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, w_down.astype(x.dtype))


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token cross-entropy in fp32; labels (B, S) int32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()
