"""Plain float64 reference of the any-k FCFS queueing lane.

A job arrives at A_j and puts one task on each of the n workers; every
worker serves its queue first come, first served.  The job completes at
the k-th smallest natural finish D_j; its latency is D_j - A_j.  Tasks
still running at D_j are cut at D_j when the deployment preempts, and
run to their end when it does not.  Ties at D_j go to the lower worker
index.

The random inputs are drawn from the seed by the same key schedule the
deployment documents (one service key and one arrival key per
replication; bulk draws on the monolithic engine, one key per job index
on the chunked engine), as float32 uniforms, and transformed here in
float64.  Nothing here imports the system under test.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------------
# Random inputs
# --------------------------------------------------------------------------

def rep_keys(seed: int, reps: int):
    """One (service, arrival) key pair per replication."""
    out = []
    for rk in jax.random.split(jax.random.PRNGKey(seed), reps):
        k_svc, k_arrv = jax.random.split(rk)
        out.append((k_svc, k_arrv))
    return out


def _row_keys(key, num_jobs: int):
    idx = jnp.arange(num_jobs, dtype=jnp.uint32)
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)


def _pareto_uniform(u32: np.ndarray) -> np.ndarray:
    """``uniform(minval=2**-24, maxval=1)`` from the unit uniform, in
    float32 as the draw itself is made."""
    lo = np.float32(2.0 ** -24)
    u = u32 * (np.float32(1.0) - lo) + lo
    return np.maximum(lo, u).astype(np.float64)


def noise(law: dict, u32: np.ndarray) -> np.ndarray:
    """The random part Z = X - shift of a service law, from float32
    uniforms.  ``law`` is {"family": ..., parameters}."""
    fam = law["family"]
    if fam == "bimodal":
        hit = u32 < np.float32(law["eps"])
        return np.where(hit, float(np.float32(law["B"])), 1.0)
    if fam == "shifted_exp":
        return float(np.float32(law["W"])) * -np.log1p(-u32.astype(np.float64))
    if fam == "pareto":
        u = _pareto_uniform(u32)
        return float(np.float32(law["lam"])) * u ** (-1.0 / float(np.float32(law["alpha"])))
    raise ValueError(f"unknown service family {fam!r}")


def shift(law: dict) -> float:
    return float(law.get("delta", 0.0)) if law["family"] == "shifted_exp" else 0.0


def service_uniforms(k_svc, num_jobs: int, n: int, row_keyed: bool):
    """(num_jobs, n) float32 uniforms behind the service noise."""
    if row_keyed:
        keys = _row_keys(k_svc, num_jobs)
        u = jax.vmap(lambda kk: jax.random.uniform(kk, (n,)))(keys)
    else:
        u = jax.random.uniform(k_svc, (num_jobs, n))
    return np.asarray(u, np.float32)


def unit_gaps(arrivals: dict, k_arrv, num_jobs: int, row_keyed: bool
              ) -> np.ndarray:
    """Interarrival gaps at unit mean rate, float64.

    ``arrivals`` is {"family": "poisson" | "deterministic" | "mmpp", ...};
    MMPP's chain starts slow and flips after an arrival with probability
    ``switch``, its per-state rates normalised to a unit mean."""
    fam = arrivals["family"]
    if fam == "deterministic":
        return np.ones(num_jobs)
    if fam == "poisson":
        if row_keyed:
            u = jax.vmap(lambda kk: jax.random.uniform(kk, ()))(
                _row_keys(k_arrv, num_jobs))
        else:
            u = jax.random.uniform(k_arrv, (num_jobs,))
        return -np.log1p(-np.asarray(u, np.float64))
    if fam == "mmpp":
        sw = np.float32(arrivals["switch"])
        if row_keyed:
            def draw(kk):
                ke, ks = jax.random.split(kk)
                return (jax.random.uniform(ke, ()),
                        jax.random.uniform(ks, (), jnp.float32))
            ue, us = jax.vmap(draw)(_row_keys(k_arrv, num_jobs))
        else:
            ke, ks = jax.random.split(k_arrv)
            ue = jax.random.uniform(ke, (num_jobs,))
            us = jax.random.uniform(ks, (num_jobs,))
        e = -np.log1p(-np.asarray(ue, np.float64))
        flips = np.asarray(us, np.float32) < sw
        state = np.cumsum(flips.astype(np.int64)) % 2
        slow = float(np.float32(arrivals["slow"]))
        burst = float(np.float32(arrivals["burst"]))
        c = 0.5 * (1.0 / slow + 1.0 / burst)
        rates = c * np.where(state == 0, slow, burst)
        return e / rates
    raise ValueError(f"unknown arrival family {fam!r}")


# --------------------------------------------------------------------------
# The recurrence
# --------------------------------------------------------------------------

def lane_latencies(A: np.ndarray, S: np.ndarray, k: int, preempt: bool
                   ) -> np.ndarray:
    """Latencies of one lane: arrivals A (J,), task times S (J, n)."""
    J, n = S.shape
    F = np.zeros(n)
    lat = np.empty(J)
    for j in range(J):
        a = A[j]
        start = np.maximum(a, F)
        nat = start + S[j]
        D = np.partition(nat, k - 1)[k - 1]
        lt = nat < D
        eq = nat == D
        take = k - int(lt.sum())
        completed = lt | (eq & (np.cumsum(eq) <= take))
        inservice = ~completed & (start < D)
        if preempt:
            F = np.where(completed, nat, np.where(inservice, D, F))
        else:
            F = np.where(completed | inservice, nat, F)
        lat[j] = D - a
    return lat


def lanes_latencies(A: np.ndarray, S: np.ndarray, ks: Sequence[int],
                    preempt: bool) -> np.ndarray:
    """Several lanes at once over small n: A (B, J), S (B, J, n), one k
    per lane.  Returns (B, J) latencies."""
    B, J, n = S.shape
    kk = np.asarray(ks, np.int64)
    rows = np.arange(B)
    F = np.zeros((B, n))
    lat = np.empty((B, J))
    for j in range(J):
        a = A[:, j]
        start = np.maximum(a[:, None], F)
        nat = start + S[:, j]
        D = np.sort(nat, axis=1)[rows, kk - 1]
        lt = nat < D[:, None]
        eq = nat == D[:, None]
        take = kk - lt.sum(axis=1)
        completed = lt | (eq & (np.cumsum(eq, axis=1) <= take[:, None]))
        inservice = ~completed & (start < D[:, None])
        if preempt:
            F = np.where(completed, nat,
                         np.where(inservice, D[:, None], F))
        else:
            F = np.where(completed | inservice, nat, F)
        lat[:, j] = D - a
    return lat


def stats(lat: np.ndarray) -> Dict[str, float]:
    """Mean and quantiles of pooled latencies, as a surface reports them."""
    return {"mean": float(lat.mean()),
            "p50": float(np.quantile(lat, 0.50)),
            "p95": float(np.quantile(lat, 0.95)),
            "p99": float(np.quantile(lat, 0.99))}


def warmup_jobs(num_jobs: int) -> int:
    """Transient jobs left out of the statistics: min(J // 10, 200)."""
    return min(num_jobs // 10, 200)


def surface(law: dict, delta, n: int, ks: Sequence[int],
            loads: Sequence[float], arrivals: dict, num_jobs: int,
            reps: int, seed: int, preempt: bool, row_keyed: bool
            ) -> Dict[tuple, Dict[str, float]]:
    """Every (load, k) lane of a small-n server-dependent surface:
    task time d + s * Z with s = n / k, d the law's shift or ``delta``.
    Returns {(load index, k): stats}."""
    d = shift(law) if delta is None else float(delta)
    warm = warmup_jobs(num_jobs)
    pooled: Dict[tuple, list] = {}
    for k_svc, k_arrv in rep_keys(seed, reps):
        z = noise(law, service_uniforms(k_svc, num_jobs, n, row_keyed))
        g = unit_gaps(arrivals, k_arrv, num_jobs, row_keyed)
        A_unit = np.cumsum(g)
        lanes = [(i, k) for i in range(len(loads)) for k in ks]
        A = np.stack([A_unit / float(np.float32(loads[i])) for i, _ in lanes])
        S = np.stack([d + (n // k) * z for _, k in lanes])
        lat = lanes_latencies(A, S, [k for _, k in lanes], preempt)
        for b, lane in enumerate(lanes):
            pooled.setdefault(lane, []).append(lat[b, warm:])
    return {lane: stats(np.concatenate(v)) for lane, v in pooled.items()}


def fleet_lane(law: dict, n: int, k: int, load: float, num_jobs: int,
               z: np.ndarray, A_unit: np.ndarray, preempt: bool
               ) -> Dict[str, float]:
    """One lane at fleet width, from a shared noise table ``z`` (J, n)."""
    d = shift(law)
    A = A_unit / float(np.float32(load))
    lat = lane_latencies(A, d + (n // k) * z, k, preempt)
    return {"mean": float(lat[warmup_jobs(num_jobs):].mean())}


def fleet_inputs(law: dict, arrivals: dict, n: int, num_jobs: int,
                 seed: int, rep: int = 0):
    """The shared row-keyed noise table and unit-rate arrival instants of
    one replication of a chunked-engine surface."""
    k_svc, k_arrv = rep_keys(seed, rep + 1)[rep]
    z = np.empty((num_jobs, n))
    keys = _row_keys(k_svc, num_jobs)
    draw = jax.jit(jax.vmap(lambda kk: jax.random.uniform(kk, (n,))))
    step = 1024
    for j0 in range(0, num_jobs, step):
        u = np.asarray(draw(keys[j0:j0 + step]), np.float32)
        z[j0:j0 + u.shape[0]] = noise(law, u)
    A_unit = np.cumsum(unit_gaps(arrivals, k_arrv, num_jobs, True))
    return z, A_unit


def relative_gap(a: float, b: float) -> float:
    """|a - b| / |b|; an infinite or missing value reads as infinite."""
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-12)
