"""Serving driver: batched decode with hedged (replicated) dispatch.

Autoregressive decode is not a linear job, so MDS coding does not apply
(DESIGN.md §6); the paper's REPLICATION column does: each request batch is
hedged across ``r`` replica servers and the first finisher wins.  The
number of replicas is planned from the fitted service-time tail exactly as
the paper's k=1-vs-k=n analysis prescribes (replication pays off when the
tail is heavy and the deterministic part of latency is small).

This driver runs the real decode step (KV cache serve path) on the host
device and simulates the per-replica service times with the paper's
models; on a pod, replicas are distinct pod slices and the hedge is a
cancel-on-first-completion RPC.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core.distributions import Scaling
from repro.core.order_stats import expected_order_stat
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import TINY, parse_dist
from repro.models import api


def hedge_gain(dist, r: int) -> float:
    """E[min of r] / E[single] for the fitted service-time distribution."""
    single = expected_order_stat(lambda t: dist.tail(t), 1, 1,
                                 scale=max(dist.mean(), 1.0))
    hedged = expected_order_stat(lambda t: dist.tail(t), 1, r,
                                 scale=max(dist.mean(), 1.0))
    return hedged / single


def plan_replicas(dist, max_r: int = 4, cost_weight: float = 0.25) -> int:
    """Smallest r whose marginal latency gain beats the resource cost.

    cost_weight ~ the value of one replica-server's work; the paper's
    replication column corresponds to cost_weight -> 0.
    """
    best_r, best = 1, 1.0
    for r in range(2, max_r + 1):
        score = hedge_gain(dist, r) + cost_weight * (r - 1)
        if score < best:
            best, best_r = score, r
    return best_r


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--straggle", default="pareto:0.05:1.8")
    ap.add_argument("--max-replicas", type=int, default=4)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch).scaled(**TINY)
    dist = parse_dist(args.straggle)
    r = plan_replicas(dist, args.max_replicas) if dist else 1
    print(f"hedging plan: r = {r} replicas "
          f"(tail gain {hedge_gain(dist, r):.2f}x)" if dist else "no hedging")

    key = jax.random.PRNGKey(0)
    params = api.init_params(cfg, key)
    toks = jax.random.randint(key, (args.batch, args.prompt_len), 1,
                              cfg.vocab_size)
    max_len = args.prompt_len + args.gen
    cache = api.init_cache(cfg, args.batch, max_len, dtype="float32")

    # prefill: feed prompt token by token (tiny model; a fused prefill path
    # exists via api.forward for the production cells)
    step = jax.jit(lambda p, c, t, i: api.decode_step(cfg, p, c, t, i))
    t0 = time.time()
    logits = None
    for i in range(args.prompt_len):
        logits, cache = step(params, cache, toks[:, i:i + 1], jnp.asarray(i))
    out = []
    sim_latency = 0.0
    rng = np.random.default_rng(0)
    for i in range(args.gen):
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out.append(np.asarray(nxt)[:, 0])
        logits, cache = step(params, cache, nxt,
                             jnp.asarray(args.prompt_len + i))
        if dist is not None:
            # simulated wall time of the hedged step: min of r replicas
            draws = np.asarray(dist.sample(
                jax.random.PRNGKey(1000 + i), (r,)))
            sim_latency += float(draws.min())
    dt = time.time() - t0
    gen = np.stack(out, axis=1)
    print(f"generated {gen.shape} tokens in {dt:.2f}s wall")
    if dist is not None:
        base = expected_order_stat(lambda t: dist.tail(t), 1, 1,
                                   scale=max(dist.mean(), 1.0)) * args.gen
        print(f"simulated service latency: hedged {sim_latency:.2f} vs "
              f"unhedged E {base:.2f} (r={r})")
    print("sample:", gen[0][:16].tolist())


if __name__ == "__main__":
    main()
