"""Pallas TPU kernel for the Mamba2 SSD chunk scan.

One grid step processes one (batch, head, chunk) cell: the within-chunk
part is the attention-like masked (Q x Q) matmul (MXU work), the
across-chunk recurrence is carried in a VMEM scratch state (P x N) across
the sequential innermost grid axis -- the TPU-native replacement for the
CUDA warp-parallel selective-scan: chunk-level parallelism on the grid,
matrix-level parallelism on the MXU, and the only true serialization is
nc = S/Q scratch-carried steps.

Grid: (B, H, nc) with nc innermost (sequential on TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *, q: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    f32 = jnp.float32
    x = x_ref[0, 0].astype(f32)          # (Q, P)
    dt = dt_ref[0, 0].astype(f32)        # (Q, 1)
    A = a_ref[0, pl.program_id(1)]       # scalar head decay (SMEM)
    B = b_ref[0].astype(f32)             # (Q, N)
    C = c_ref[0].astype(f32)             # (Q, N)

    # The prefix sums and the (Q, 1) -> (1, Q) flips are written as masked
    # reductions over the (Q, Q) grid: elementwise f32 VPU work, no
    # cumsum or transpose for the TPU compiler to refuse, and cheap next
    # to the (Q, Q) matmuls below.
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = row >= col
    dA = dt * A                                                 # (Q, 1)
    dt_r = jnp.sum(jnp.where(row == col, dt, 0.0), axis=0,
                   keepdims=True)                               # (1, Q)
    dA_r = dt_r * A                                             # (1, Q)
    lcum = jnp.sum(jnp.where(causal, dA_r, 0.0), axis=1,
                   keepdims=True)                               # (Q, 1) incl.
    lcum_r = jnp.sum(jnp.where(row <= col, dA, 0.0), axis=0,
                     keepdims=True)                             # (1, Q)
    l_end = jnp.sum(dA, axis=0, keepdims=True)                  # (1, 1) = l_Q
    # intra-chunk attention-like term
    decay = jnp.where(causal, jnp.exp(lcum - lcum_r), 0.0)      # l_t - l_s
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)        # (Q, Q)
    w = cb * decay * dt_r                # (Q, Q) * dt_s broadcast on cols
    y_intra = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=f32)   # (Q, P)
    # inter-chunk: y += exp(lcum) * (C @ h^T)
    h = h_ref[...]                       # (P, N)
    ch = jax.lax.dot_general(C, h, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)        # (Q, P)
    y = y_intra + ch * jnp.exp(lcum)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update: h_new = h * exp(l_Q) + x^T @ (B * exp(l_Q - l) * dt)
    tail = jnp.exp(l_end - lcum) * dt                           # (Q, 1)
    wb = B * tail                                               # (Q, N)
    h_new = h * jnp.exp(l_end) + jax.lax.dot_general(
        x, wb, (((0,), (0,)), ((), ())), preferred_element_type=f32)
    h_ref[...] = h_new


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array,
             Bm: jax.Array, Cm: jax.Array, chunk: int = 256,
             interpret: bool = False):
    """x (B, S, H, P), dt (B, S, H), A (H,), Bm/Cm (B, S, N) -> y like x.

    Layout for the kernel: x -> (B, H, S, P); dt -> (B, H, S, 1);
    B/C broadcast over heads are indexed per (b, chunk).
    """
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    q = min(chunk, s)
    assert s % q == 0
    nc = s // q
    xk = x.transpose(0, 2, 1, 3)                       # (B, H, S, P)
    dtk = dt.transpose(0, 2, 1)[..., None]             # (B, H, S, 1)
    grid = (b, h, nc)
    y = pl.pallas_call(
        functools.partial(_kernel, q=q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            # all H decays as SMEM scalars: a (1,) VMEM block of the
            # rank-1 A breaks the TPU's tiling rule
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xk, dtk, A.astype(jnp.float32)[None], Bm, Cm)
    return y.transpose(0, 2, 1, 3)
