"""Jitted wrapper: the grouped matmul of the dropless expert-share layer.

Rows arrive sorted by expert, the held experts' groups first; the rows
past them (assignments to experts another chip holds) are left out of
the work.  XLA's ``ragged_dot`` computes it; on a TPU it is a Mosaic
kernel whose grid covers only the groups' tiles, which was faster in a
train step than JAX's Pallas megablox ``gmm`` at the trinity cell's
shapes (PERF.md).  The kernel leaves the rows past the groups unwritten,
so they are masked to zero here, on the way in and out: the result and
the gradient there are zeros.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def grouped_matmul(lhs, rhs, group_sizes):
    """lhs (m, k), rhs (G, k, n), group_sizes (G,) int32 summing to at most
    m -> (m, n) in lhs's dtype (float32 accumulation): row r of group g
    is lhs[r] @ rhs[g], and the rows past the last group are zero."""
    live = (jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes))[:, None]
    out = jax.lax.ragged_dot(jnp.where(live, lhs, 0), rhs.astype(lhs.dtype),
                             group_sizes, preferred_element_type=lhs.dtype)
    return jnp.where(live, out, 0)
