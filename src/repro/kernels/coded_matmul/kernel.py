"""Pallas TPU kernel: fused MDS-encode matmul  C_i = sum_j G[i,j] (A_j @ X).

TPU adaptation of the paper's encode-then-compute pipeline (DESIGN.md §4),
with a redundancy-stationary schedule: the grid iterates the coded-output
axis i INNERMOST, so the k source blocks and the X tile stay resident in
VMEM across all n coded outputs (Pallas skips the HBM copy when a block's
index map is unchanged between consecutive grid steps).  Source traffic is
therefore k*M*K per N-tile -- INDEPENDENT of the code rate -- vs the
encode-then-multiply baseline's n*M*K read of the materialized encoded
operand (n/k = 1/rate more bytes) plus its (k+n)*M*K encode pass.

Per-output fp32 accumulators across the K loop live in a (n, bm, bn) VMEM
scratch (n is the small redundancy degree, <= a few dozen: ~12 x 128 x 128
x 4B = 0.8 MiB).

Grid: (M/bm, N/bn, K/bk, n) -- i fastest, then the sequential K axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(g_ref, a_ref, x_ref, o_ref, acc_ref, *, nk: int, k: int):
    t = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when(t == 0)
    def _init():
        acc_ref[i] = jnp.zeros_like(acc_ref[i])

    # encode in VMEM: (bm, bk) = sum_j G[i, j] * a[j], with the G entries
    # read as scalars from SMEM; the a block is fetched from HBM once per
    # (m, n, t) and reused for all n coded outputs
    ae = g_ref[i, 0] * a_ref[0].astype(jnp.float32)
    for j in range(1, k):
        ae = ae + g_ref[i, j] * a_ref[j].astype(jnp.float32)
    acc_ref[i] += jax.lax.dot_general(
        ae, x_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(t == nk - 1)
    def _done():
        o_ref[...] = acc_ref[i].astype(o_ref.dtype)[None]


def coded_matmul(G: jax.Array, A: jax.Array, X: jax.Array,
                 bm: int = 128, bn: int = 128, bk: int = 128,
                 interpret: bool = False) -> jax.Array:
    """G (n, k), A (k, M, K), X (K, N) -> C (n, M, N)."""
    n, k = G.shape
    k2, M, K = A.shape
    K2, N = X.shape
    assert k == k2 and K == K2, (G.shape, A.shape, X.shape)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, \
        f"dims ({M},{N},{K}) must tile by ({bm},{bn},{bk})"
    nk = K // bk
    grid = (M // bm, N // bn, nk, n)

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, k=k),
        grid=grid,
        in_specs=[
            # the whole (n, k) generator, as scalars: a (1, k) VMEM row
            # block would break the TPU's (8, 128) tiling rule
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((k, bm, bk), lambda m, j, t, i: (0, m, t)),  # A blks
            pl.BlockSpec((bk, bn), lambda m, j, t, i: (t, j)),        # X tile
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda m, j, t, i: (i, m, j)),
        out_shape=jax.ShapeDtypeStruct((n, M, N), A.dtype),
        scratch_shapes=[pltpu.VMEM((n, bm, bn), jnp.float32)],
        interpret=interpret,
    )(G.astype(jnp.float32), A, X)
