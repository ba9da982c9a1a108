"""Capacity-based top-k Mixture-of-Experts FFN (Mesh-TF / GSPMD style).

Dense dispatch: tokens are grouped, routed top-k, and placed into per-expert
capacity slots via one-hot dispatch/combine einsums.  This is the
GSPMD-friendly formulation (no ragged ops): the expert axis is sharded over
the ``model`` mesh axis (expert parallelism) and the group axis over
``data``; XLA inserts the all-to-alls.

Capacity per expert per group:  C = ceil(g * top_k / E * capacity_factor),
rounded up to a multiple of 4 for layout friendliness.  Overflow tokens are
dropped (standard capacity-based behaviour); the router uses softmax-then-
top-k with probabilities renormalized over the selected experts (DBRX/Qwen3
convention).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import constrain


def expert_capacity(group_size: int, num_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    c = int(group_size * top_k / num_experts * capacity_factor + 0.999)
    return max(4, (c + 3) // 4 * 4)


def moe_ffn(
    x: jax.Array,            # (B, S, d)
    router: jax.Array,       # (d, E)
    w_gate: jax.Array,       # (E, d, f)
    w_up: jax.Array,         # (E, d, f)
    w_down: jax.Array,       # (E, f, d)
    top_k: int,
    capacity_factor: float = 1.25,
    group_size: int = 256,
) -> jax.Array:
    """Top-k capacity-dispatch MoE with SwiGLU experts."""
    b, s, d = x.shape
    e = router.shape[1]
    tokens = b * s
    g = min(group_size, tokens)
    assert tokens % g == 0, f"tokens={tokens} not divisible by group={g}"
    ng = tokens // g
    cap = expert_capacity(g, e, top_k, capacity_factor)

    xg = x.reshape(ng, g, d)
    logits = jnp.einsum("ngd,de->nge", xg, router.astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)          # (ng, g, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9
    )

    # one-hot expert assignment per top-k slot: (ng, g, k, E)
    assign = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)
    # position of each (token, slot) within its expert queue, priority by
    # (slot, token) order: cumsum over flattened (k, g)
    assign_kg = assign.transpose(0, 2, 1, 3).reshape(ng, top_k * g, e)
    pos_kg = jnp.cumsum(assign_kg, axis=1) - assign_kg         # 0-based
    pos = pos_kg.reshape(ng, top_k, g, e).transpose(0, 2, 1, 3)  # (ng,g,k,E)
    keep = (pos < cap) * assign                                 # drop overflow
    gate = gate_vals[..., None] * keep                          # (ng,g,k,E)

    # an expert is picked at most once per token, so the top-k axis can be
    # reduced BEFORE the capacity one-hot: the (ng, g, k, E, C) tensor --
    # which dominates HBM for large E -- is never materialized.
    pos_r = (pos * keep).sum(axis=2)                            # (ng, g, E)
    keep_r = keep.sum(axis=2)                                   # 0/1
    gate_r = gate.sum(axis=2)
    oh = jax.nn.one_hot(pos_r.astype(jnp.int32), cap, dtype=x.dtype) \
        * keep_r[..., None].astype(x.dtype)
    dispatch = oh                                               # (ng, g, E, C)
    combine = oh * gate_r[..., None].astype(x.dtype)            # (ng, g, E, C)

    # dispatch all-to-all: groups stay batch(data)-sharded, experts live on
    # the model axis -- constraining both sides makes GSPMD emit the a2a
    xin = jnp.einsum("ngec,ngd->necd", dispatch, xg)
    xin = constrain(xin, "batch", "model", None, None)
    h_g = jnp.einsum("necd,edf->necf", xin, w_gate.astype(x.dtype))
    h_u = jnp.einsum("necd,edf->necf", xin, w_up.astype(x.dtype))
    h = jax.nn.silu(h_g) * h_u
    xout = jnp.einsum("necf,efd->necd", h, w_down.astype(x.dtype))
    from .layers import opt_enabled
    if opt_enabled("moe_a2a"):
        # return expert outputs to their token owners by RESHARDING expert
        # -> hidden (all-to-all of the capacity rows) and combining
        # locally, instead of letting GSPMD psum token-sized activations
        # over the expert axis
        xout = constrain(xout, "batch", None, None, "model")
        y = jnp.einsum("ngec,necd->ngd", combine, xout)
        y = constrain(y, "batch", None, None)
    else:
        xout = constrain(xout, "batch", "model", None, None)
        y = jnp.einsum("ngec,necd->ngd", combine, xout)
    return y.reshape(b, s, d)
