"""Pure-jnp oracle for the grouped matmul over the experts a chip holds."""
from __future__ import annotations

import jax.numpy as jnp


def grouped_matmul_ref(lhs: jnp.ndarray, rhs: jnp.ndarray,
                       group_sizes: jnp.ndarray) -> jnp.ndarray:
    """lhs (m, k) with its rows sorted by group, rhs (G, k, n), group_sizes
    (G,) summing to at most m -> (m, n): row r of group g is lhs[r] @
    rhs[g]; the rows past the last group are zero.  One masked product
    per group, in float32."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    row = jnp.arange(lhs.shape[0])
    x = lhs.astype(jnp.float32)
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for g in range(rhs.shape[0]):
        mask = ((row >= starts[g]) & (row < ends[g]))[:, None]
        out = out + jnp.where(mask, x @ rhs[g].astype(jnp.float32), 0.0)
    return out.astype(lhs.dtype)
