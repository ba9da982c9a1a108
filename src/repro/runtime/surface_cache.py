"""Compiled-surface cache: warm (loads x ks) queueing surfaces for the
control loop's steady-state re-plans.

``cluster_batched.sweep`` folds the distribution and arrival-process
PARAMETERS into the executable as compile-time constants — ideal for a
one-off surface, hopeless for a closed control loop: every drift commit
fits slightly different floats, so every load-aware re-plan would pay a
fresh XLA compile (seconds) instead of a kernel launch (milliseconds).

This module runs the SAME lane grid (``cluster_batched._sweep_core``)
through a jit wrapper whose distribution, arrival process, delta, and
load grid are TRACED: the executable is keyed on

    (service family, scaling, n, k-grid, load-grid bucket,
     arrival family, num_jobs, reps, preempt, delta-presence)

— the pytree STRUCTURE of the arguments (``core.distributions.
register_param_pytree``), never the fitted parameter values.  A
steady-state re-plan after a rate or service drift therefore hits a warm
executable and returns in milliseconds (the <50 ms warm gate in
``benchmarks/control_loop.py``).

Shape-bucketing: the load axis is padded up to a fixed bucket length
(the last load repeated) so that planning at 1, 2, or 3 rates reuses ONE
executable per bucket; padded lanes are computed and discarded — lanes
are independent under ``vmap``, so the surviving cells are the same
numbers the unpadded kernel produces.

``cached_sweep`` mirrors ``cluster_batched.sweep``'s signature and is
dispatchable as ``backend="cached"`` everywhere a backend name is taken
(``runtime.cluster.resolve_sweep_backend``, ``api.LoadAwareLatency``).
``surface_cache_stats`` exposes hit/miss accounting for the conformance
suite and the benchmark's warm-latency gate.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..assign.strategies import Assignment, build_lanes
from ..core.policy import RetryPolicy
from ..core.scenario import Scenario
from ..obs import metrics as _metrics
from ..obs import recorder as _trace
from .cluster_batched import (ClusterSweep, _sweep_core, lanes_as_jnp,
                              resolve_failure_args, summarize_sweep,
                              validate_sweep_args)

__all__ = ["cached_sweep", "load_bucket", "record_cache_key",
           "reset_surface_cache_stats", "surface_cache_stats"]

#: Load-grid lengths are padded up to one of these (ascending).
_LOAD_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)

#: Hit/miss accounting lives on the metrics plane (``obs.metrics``) —
#: the registry is the one queryable namespace for every module's
#: counters; the compiled-KEY registry below stays module-local because
#: it mirrors jit executable state, not a statistic.
_C_HITS = _metrics.REGISTRY.counter("surface_cache.hits")
_C_MISSES = _metrics.REGISTRY.counter("surface_cache.misses")
_KEYS: Dict[tuple, int] = {}


def load_bucket(num_loads: int) -> int:
    """The padded load-axis length for a requested grid size."""
    for b in _LOAD_BUCKETS:
        if num_loads <= b:
            return b
    raise ValueError(
        f"load grid of {num_loads} exceeds the largest bucket "
        f"{_LOAD_BUCKETS[-1]}; call cluster_batched.sweep directly")


def surface_cache_stats() -> dict:
    """Hit/miss accounting of the compiled-surface cache.

    A MISS is a call whose (family, scaling, n, ks, load-bucket, ...)
    key has not been compiled yet this process — it pays the XLA trace;
    a HIT reuses a warm executable and costs one kernel launch.
    (Backed by the ``surface_cache.hits``/``.misses`` counters of
    ``obs.metrics.REGISTRY``.)
    """
    return {"hits": _C_HITS.value, "misses": _C_MISSES.value,
            "entries": len(_KEYS)}


def reset_surface_cache_stats() -> None:
    """Zero the hit/miss counters.  The compiled-KEY registry is kept,
    matching the jit executables that stay warm: a post-reset call on an
    already-compiled key still counts as a hit (clearing the registry
    would misreport warm calls as compiles)."""
    _C_HITS.reset()
    _C_MISSES.reset()


def record_cache_key(cache_key: tuple) -> bool:
    """Count one cache lookup; True when the key was already compiled.
    Shared by ``cached_sweep`` and the co-optimizing assignment surface
    (``assign.surface.co_sweep``), which builds its own flattened key.
    Each lookup also lands on the flight recorder (``cache_hit`` /
    ``cache_miss``) when one is installed."""
    warm = cache_key in _KEYS
    if warm:
        _C_HITS.inc()
        _KEYS[cache_key] += 1
    else:
        _C_MISSES.inc()
        _KEYS[cache_key] = 1
    rec = _trace.active()
    if rec is not None:
        rec.event("cache_hit" if warm else "cache_miss",
                  name="surface_cache", family=str(cache_key[0]))
    return warm


def _cached_fleet(scenario, loads, ks, num_jobs, reps, preempt,
                  cancel_overhead, seed, warmup, arrivals, speeds, failures,
                  retry, assignment, chunk_size, stream, reservoir, shard):
    """The chunked engine behind the cache facade: bucket-pad the load
    axis (same executable across re-plans that differ only in the number
    of rates), record the structural key, trim after the kernel."""
    from .fleet import (build_fleet_lanes, default_chunk, run_fleet,
                        summarize_fleet, trim_raw_loads)
    n = scenario.n
    lanes = build_fleet_lanes(assignment, n, ks, scenario.worker_speeds)
    chunk = default_chunk(num_jobs) if chunk_size is None else int(chunk_size)
    L = len(loads)
    bucket = load_bucket(L)
    padded = tuple(loads) + (loads[-1],) * (bucket - L)
    warm = record_cache_key(
        ("fleet", type(scenario.dist).__name__, scenario.scaling.value, n,
         ks, bucket, int(num_jobs), int(reps), bool(preempt),
         type(arrivals).__name__, scenario.delta is None,
         None if failures is None else int(failures.max_events), retry,
         lanes.signature, chunk, bool(stream), int(reservoir),
         0 if shard is None else int(shard)))
    t0 = time.perf_counter()
    raw = run_fleet(scenario, padded, lanes, num_jobs=int(num_jobs),
                    reps=int(reps), preempt=bool(preempt),
                    cancel_overhead=float(cancel_overhead), seed=int(seed),
                    warmup=warmup, arrivals=arrivals, speeds=speeds,
                    failures=failures, retry=retry, chunk=chunk,
                    stream=bool(stream), reservoir=int(reservoir),
                    shard=shard)
    _record_surface_call(warm, (time.perf_counter() - t0) * 1e3,
                         "cached_fleet")
    return summarize_fleet(trim_raw_loads(raw, L), ks)


def _record_surface_call(warm: bool, wall_ms: float, which: str) -> None:
    """Trace one surface call: a MISS's wall time includes the XLA trace
    and lands on a ``compile`` event; a HIT is a kernel launch and is not
    recorded."""
    if not warm:
        rec = _trace.active()
        if rec is not None:
            rec.event("compile", name=which, wall_ms=wall_ms)


@functools.partial(jax.jit, static_argnames=(
    "scaling", "n", "ks", "num_jobs", "reps", "preempt", "retry", "groups"))
def _cached_kernel(key, loads, speeds, cancel_overhead, dist, scaling, n,
                   ks, num_jobs, reps, preempt, arrivals, delta, failures,
                   retry, groups=None, group_r=None, group_ids=None):
    # dist / arrivals / delta / failures arrive as traced pytrees: jax's
    # jit cache keys on their STRUCTURE (the family; for failures the
    # static max_events aux), so new fitted floats reuse the executable.
    # retry is static — it shapes the unrolled relaunch pass.  A grouped
    # assignment contributes ONE static (the max group count); its rank
    # and mask arrays are traced data, so a placement re-plan (e.g.
    # SpeedAware with fresh measured speeds) reuses the executable.  The
    # body is cluster_batched._sweep_core — the identical lane grid the
    # uncached path compiles.
    return _sweep_core(key, loads, speeds, cancel_overhead, dist, scaling,
                       n, ks, num_jobs, reps, preempt, arrivals, delta,
                       failures, retry, groups, group_r, group_ids)


def cached_sweep(scenario: Scenario, loads: Sequence[float],
                 ks: Optional[Sequence[int]] = None, num_jobs: int = 1000,
                 reps: int = 1, preempt: bool = True,
                 cancel_overhead: float = 0.0, seed: int = 0,
                 warmup: Optional[int] = None,
                 retry: Optional[RetryPolicy] = None,
                 assignment: Optional[Assignment] = None,
                 chunk_size: Optional[int] = None, stream: bool = False,
                 reservoir: int = 4096,
                 shard: Optional[int] = None) -> ClusterSweep:
    """``cluster_batched.sweep`` through the compiled-surface cache.

    Same semantics and CRN discipline; parameters are traced and the
    load axis is bucket-padded, so repeated calls that differ only in
    fitted parameter values (or in the precise rates on the same-size
    grid) reuse one warm executable.  The returned surface is trimmed
    back to the requested loads.  A ``scenario.failures`` model rides
    the same cache: its MTTF/MTTR are traced parameters (re-estimated
    failure rates re-plan warm), while ``max_events`` and the ``retry``
    policy shape the executable and so key it.  An ``assignment``
    strategy keys the cache by its STRUCTURAL signature
    (``Assignment.cache_signature`` — group counts, not mask contents),
    so a placement re-plan from fresh telemetry is a warm call.

    Any of ``chunk_size`` / ``stream`` / ``shard`` routes through the
    chunked fleet engine (``runtime.fleet``), whose kernel already
    traces every parameter — the same warm-re-plan property — with the
    chunk size, streaming mode, reservoir capacity, and shard count
    joining the structural cache key (they are jit statics there).
    """
    n = scenario.n
    ks, loads, warmup, arrivals, speeds = validate_sweep_args(
        scenario, loads, ks, num_jobs, reps, warmup)
    failures, retry = resolve_failure_args(scenario, retry)
    if chunk_size is not None or stream or shard is not None:
        return _cached_fleet(scenario, loads, ks, num_jobs, reps, preempt,
                             cancel_overhead, seed, warmup, arrivals,
                             speeds, failures, retry, assignment,
                             chunk_size, stream, reservoir, shard)
    lanes = build_lanes(assignment, n, ks, int(num_jobs),
                        scenario.worker_speeds)
    groups, group_r, group_ids = lanes_as_jnp(lanes)
    L = len(loads)
    bucket = load_bucket(L)
    padded = tuple(loads) + (loads[-1],) * (bucket - L)

    warm = record_cache_key(
        (type(scenario.dist).__name__, scenario.scaling.value, n,
         ks, bucket, int(num_jobs), int(reps), bool(preempt),
         type(arrivals).__name__, scenario.delta is None,
         None if failures is None else int(failures.max_events),
         retry, None if lanes is None else lanes.signature))

    t0 = time.perf_counter()
    with _trace.span("surface.dispatch"):
        out = _cached_kernel(
            jax.random.PRNGKey(seed), jnp.asarray(padded, jnp.float32),
            speeds, jnp.float32(cancel_overhead), scenario.dist,
            scenario.scaling, n, ks, int(num_jobs), int(reps),
            bool(preempt), arrivals,
            None if scenario.delta is None else jnp.float32(scenario.delta),
            failures, retry, groups, group_r, group_ids)
    _record_surface_call(warm, (time.perf_counter() - t0) * 1e3,
                         "cached_sweep")

    # trim the padded lanes before aggregation: the surviving cells are
    # lane-independent under vmap, so they match the unpadded kernel
    with _trace.span("surface.fetch"):
        if retry is None:
            lat, busy, wasted, a_last = out
            ok = horizon = None
        else:
            lat, busy, wasted, a_last, ok, horizon = out
            ok = np.asarray(ok)[:, :L]
            horizon = np.asarray(horizon)[:, :L]
        lat, busy = np.asarray(lat)[:, :L], np.asarray(busy)[:, :L]
        wasted, a_last = np.asarray(wasted)[:, :L], np.asarray(a_last)[:, :L]
    return summarize_sweep(lat, busy, wasted, a_last, loads, ks, warmup,
                           reps, num_jobs, n, ok=ok, horizon=horizon)
