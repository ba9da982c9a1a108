"""Mixture-of-Experts FFNs: the capacity-based top-k layer (Mesh-TF /
GSPMD style) and the dropless expert-share layer (below).

The capacity layer uses dense dispatch: tokens are grouped, routed top-k, and placed into per-expert
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

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.grouped_matmul import grouped_matmul
from .layers import constrain, swiglu


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


# --------------------------------------------------------------------------
# Dropless expert-share layer (expert parallelism, one chip's share)
# --------------------------------------------------------------------------

def route(u: jax.Array, router: jax.Array, expert_bias: jax.Array,
          top_k: int, route_scale: float = 1.0):
    """Sigmoid router over ALL experts, in float32: (T, d) -> (idx (T, k)
    int32, weights (T, k) float32).  s = sigmoid(u W_r); the experts are
    the top-k of s + expert_bias (the bias moves selection only),
    weighted route_scale * s_e / sum over the selected s."""
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", u.astype(jnp.float32),
                                  router.astype(jnp.float32)))
    _, idx = jax.lax.top_k(s + expert_bias.astype(jnp.float32), top_k)
    sel = jnp.take_along_axis(s, idx, axis=-1)
    return idx, route_scale * sel / sel.sum(-1, keepdims=True)


def expert_share_ffn(
    x: jax.Array,            # (B, S, d)
    router: jax.Array,       # (d, E) over all E experts
    expert_bias: jax.Array,  # (E,) selection-only bias
    w_gate: jax.Array,       # (H, d, f) the H experts held here
    w_up: jax.Array,         # (H, d, f)
    w_down: jax.Array,       # (H, f, d)
    shared: Optional[Tuple[jax.Array, jax.Array, jax.Array]],
    top_k: int,
    first: int = 0,
    route_scale: float = 1.0,
):
    """This chip's part of a dropless MoE layer: (y (B, S, d), rows (H,),
    choices (B, S, k)).

    Every token is routed over all E experts; each (token, slot) pair
    whose expert lies in [first, first + H) is computed, none dropped,
    and weighted into y, beside the shared expert(s), which every chip
    computes alike.  The T * k assignments are sorted by held expert,
    the assignments to absent experts last, so the shapes are static and
    the grouped matmul visits only the held groups (and returns zeros
    past them).  ``rows`` counts the assignments each held expert
    received; ``choices`` are the experts each token selected, held or
    not.
    """
    b, s, d = x.shape
    held = w_gate.shape[0]
    u = x.reshape(b * s, d)
    idx, w = route(u, router, expert_bias, top_k, route_scale)
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True)
    rows = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    xs = u[order // top_k]                                  # (T*k, d)
    h = jax.nn.silu(grouped_matmul(xs, w_gate, rows)) \
        * grouped_matmul(xs, w_up, rows)
    out = grouped_matmul(h, w_down, rows)                   # 0 past held
    back = jnp.argsort(order)                               # unsort
    out = out[back].reshape(b * s, top_k, d)
    wk = jnp.where(key < held, w.reshape(-1), 0.0).reshape(b * s, top_k)
    y = jnp.einsum("tkd,tk->td", out, wk.astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if shared is not None:
        y = y + swiglu(u, *shared)
    return y.reshape(b, s, d), rows, idx.reshape(b, s, top_k)
