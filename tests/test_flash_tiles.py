"""The train path's flash attention visits only the (query block, key
block) tiles its causal and sliding-window masks leave open.

Each case runs ``layers.attention`` (the custom-VJP flash scan) and a
dense reference written here, with the whole (S x S) mask, and compares
the output and the gradients of q, k and v in float32.  The tile counts
come from ``layers.flash_tiles``, the function the schedule's bounds come
from.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import api
from repro.models import layers as L


def dense_attention(q, k, v, causal, window):
    """softmax(q k^T / sqrt(d) + mask) v over the whole sequence, the KV
    heads repeated to the query heads."""
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision="highest") / math.sqrt(d)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= qpos >= kpos
        if window > 0:
            mask &= qpos - kpos < window
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision="highest")


#: (seq, block, window, traced, kv_heads, causal): 4 query heads of 8
CASES = {
    "causal": (64, 16, 0, False, 4, True),
    "causal-traced": (64, 16, 0, True, 4, True),
    "window-under-block": (64, 16, 5, True, 4, True),
    "window-multiple-of-block-gqa": (64, 16, 32, True, 2, True),
    "window-not-multiple-gqa": (64, 16, 20, False, 2, True),
    "window-equals-seq": (64, 16, 64, True, 4, True),
    "window-over-seq": (64, 16, 100, False, 2, True),
    "padded-causal-gqa": (60, 16, 0, True, 2, True),
    "padded-window-multiple": (60, 16, 16, False, 4, True),
    "padded-window-not-multiple-gqa": (60, 16, 7, True, 2, True),
    "padded-window-static": (60, 16, 23, False, 1, True),
    "padded-not-causal": (60, 16, 0, True, 2, False),
    "one-block": (16, 32, 6, True, 2, True),
}


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_tiled_scan_matches_the_dense_mask(case):
    seq, block, window, traced, kv_heads, causal = CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(seq + window), 4)
    q = jax.random.normal(ks[0], (2, seq, 4, 8), jnp.float32)
    k = jax.random.normal(ks[1], (2, seq, kv_heads, 8), jnp.float32)
    v = jax.random.normal(ks[2], (2, seq, kv_heads, 8), jnp.float32)
    cot = jax.random.normal(ks[3], (2, seq, 4, 8), jnp.float32)

    def flash(q, k, v, w):
        return L.attention(q, k, v, causal=causal, q_offset=0,
                           block_kv=block, window=w)

    def loss(fn):
        return lambda q, k, v, *w: (fn(q, k, v, *w) * cot).sum()

    if traced:
        w = jnp.int32(window)
        out = jax.jit(flash)(q, k, v, w)
        grads = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v, w)
    else:
        static = lambda q, k, v: flash(q, k, v, window)
        out = static(q, k, v)
        grads = jax.grad(loss(static), argnums=(0, 1, 2))(q, k, v)
    dense = lambda q, k, v: dense_attention(q, k, v, causal, window)
    want = dense(q, k, v)
    want_grads = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    for name, g, r in zip(("dq", "dk", "dv"), grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("seq, block, window, want", [
    (4096, 512, 0, (36, 64)),       # causal: up to the diagonal
    (4096, 512, 2048, (30, 64)),    # query block i sees max(0, i-4)..i
    (512, 512, 0, (1, 1)),
    (512, 1024, 0, (1, 1)),         # the block is cut to the sequence
    (64, 16, 5, (7, 16)),           # a window under a block: 2 a row
    (60, 16, 0, (10, 16)),          # padded to 4 blocks
], ids=["causal", "window-2048", "one-block", "block-over-seq",
        "window-under-block", "padded"])
def test_flash_tiles_counts(seq, block, window, want):
    assert L.flash_tiles(seq, block, window) == want


def test_models_sum_their_layers_tiles():
    """trinity-mini.train-c2: layers 0-2, 4, 5 slide over 2,048 tokens,
    layer 3 is global; 4,096 tokens in blocks of 512: 186 of 384."""
    full = get_config("trinity-mini")
    cfg = full.scaled(num_layers=6, layer_types=full.layer_types[:6],
                      flash_block_kv=512)
    assert api.attn_tiles(cfg, 4096) == (5 * 30 + 36, 6 * 64)
    qwen = get_config("qwen3-0.6b")
    assert api.attn_tiles(qwen, 512) == (qwen.num_layers, qwen.num_layers)
    # the hybrid's shared block: 6 calls over 38 layers, window 4,096 in
    # blocks of 1,024; the pure SSM has no attention
    assert api.attn_tiles(get_config("zamba2-1.2b"), 8192) == (6 * 30, 6 * 64)
    assert api.attn_tiles(get_config("mamba2-1.3b"), 4096) == (0, 0)
