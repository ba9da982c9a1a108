"""Batched JAX cluster engine: a whole (replications x loads x k) grid of
queueing simulations as ONE compiled program — the production backend the
discrete-event oracle (``runtime.cluster_oracle``) validates.

Why this is exact, not an approximation: in this system every arriving
job enqueues one task on EVERY worker and each worker is an exclusive
FCFS server, so all workers process jobs in arrival order.  Conditioned
on the task-time matrix S (num_jobs, n) and the arrival instants A, the
entire discrete-event dynamics collapse to a per-job recurrence over the
worker free-times F:

    start_w = max(A_j, F_w)                  (FCFS: job j waits for j-1)
    nat_w   = start_w + S_{j,w}              (natural finish)
    D_j     = k-th smallest nat_w            (any-k completion; cancelled
                                              tasks are all LATER, so they
                                              cannot move the k-th)
    rank_w < k        -> completed:  F_w = nat_w            (busy)
    start_w >= D_j    -> purged:     F_w unchanged          (free)
    otherwise         -> in service at D_j:
        preempt:    F_w = D_j + cancel_overhead   (busy+wasted, incl. the
                                                   purge window)
        no preempt: F_w = nat_w                   (remnant runs out;
                                                   busy+wasted)

Ties at D are broken by stable sort order (worker index), matching the
oracle's event order for the common idle-arrival case.  The recurrence
runs as a fixed-step ``lax.scan`` over jobs whose carry is (F, busy,
wasted); lane axes are added by ``vmap``: k lanes share one common-
random-number base noise draw (the same CRN discipline as
``core.simulator.completion_curves_grid_mc`` — one ``sample_noise`` /
additive-cumsum table transformed per task size s = n/k), load lanes
share one arrival key with only the rate swept, and replication lanes
fold fresh keys.  One jit trace covers the whole surface
(``sweep_compile_count`` is asserted by tests), which is what makes
load-aware k* maps as cheap as the closed-form k-curves.

``simulate_one`` is the single-cell path: it draws from the SAME
substrate as the oracle (``core.scenario.sample_task_matrix`` + the
legacy arrival stream), so for a given config both backends walk the
same sample path up to float32 accumulation — the exact-parity tests in
``tests/test_cluster_batched.py`` pin this.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..assign.strategies import (Assignment, GroupLanes, build_lanes,
                                 group_ids_matrix, is_all_workers)
from ..core.distributions import Scaling
from ..core.policy import RetryPolicy
from ..core.scenario import FailureModel, PoissonArrivals, Scenario
from ..obs import recorder as _trace
from .cluster import ClusterConfig, ClusterResult, default_warmup
from .failures import (effective_finish, group_resolution, job_resolution,
                       resolve_retry)

__all__ = ["ClusterSweep", "Infeasible", "InfeasibleSurfaceError",
           "resolve_failure_args", "simulate_one", "summarize_sweep",
           "sweep", "sweep_compile_count", "validate_sweep_args"]

_SWEEP_TRACES = 0


def sweep_compile_count() -> int:
    """How many times the sweep kernel has been TRACED (== compiled).

    Ticks once per jit compilation, not per execution — tests assert a
    whole (reps x loads x k) surface costs exactly one compile.
    """
    return _SWEEP_TRACES


# --------------------------------------------------------------------------
# The lane: one (load, k) queueing simulation as a scan over jobs
# --------------------------------------------------------------------------

def _kth_sort(nat, k):
    """k-th smallest via full sort — the historical selection, fastest at
    the monolithic engine's widths (n ~ 10^2)."""
    return jnp.sort(nat)[k - 1]


def _first_ties_cumsum(eq, take_eq):
    """The first ``take_eq`` ties in worker-index order, by a prefix
    count over all n workers — the historical rank, cheap at the
    monolithic engine's widths (n ~ 10^2)."""
    return eq & (jnp.cumsum(eq) * eq <= take_eq)


def make_plain_step(k, cancel_overhead, preempt: bool, kth=_kth_sort,
                    first_ties=_first_ties_cumsum):
    """The per-job step of the fault-free ungrouped lane, as a factory.

    Extracted so the monolithic scan (here) and the chunked fleet engine
    (``runtime.fleet``) run the IDENTICAL recurrence.  ``kth`` is the
    order-statistic selection and ``first_ties`` the rank among workers
    tied at D (the first ``take_eq`` of the mask ``eq`` in index order).
    Both default to the full-width forms (sort, prefix sum); the fleet
    engine swaps in exact bisections — a bit-bisection for D and an
    index bisection for the tie rank — at n ~ 10^4, where a sort or a
    prefix sum over every worker dominates the step.  Same values either
    way, so parity is unaffected.
    """
    def step(carry, inp):
        F, busy, wasted = carry
        a, srow = inp
        start = jnp.maximum(a, F)
        nat = start + srow
        D = kth(nat, k)
        # first k finishers, ties at D broken by worker index (matching
        # the oracle's event order for simultaneous finishes): all
        # strictly-earlier finishers complete, plus the first
        # (k - #earlier) of the ties in index order
        lt = nat < D
        eq = nat == D
        completed = lt | first_ties(eq, k - lt.sum())
        inservice = (~completed) & (start < D)
        if preempt:
            cut = D - start + cancel_overhead
            run = jnp.where(completed, srow,
                            jnp.where(inservice, cut, 0.0))
            waste = jnp.where(inservice, cut, 0.0)
            F_next = jnp.where(completed, nat,
                               jnp.where(inservice, D + cancel_overhead, F))
        else:
            run = jnp.where(completed | inservice, srow, 0.0)
            waste = jnp.where(inservice, srow, 0.0)
            F_next = jnp.where(completed | inservice, nat, F)
        return (F_next, busy + run.sum(), wasted + waste.sum()), D - a

    return step


def _scan_lane(A, S, k, cancel_overhead, preempt: bool):
    """Exact FCFS/any-k/cancel dynamics for one lane.

    A: (num_jobs,) arrivals; S: (num_jobs, n) task times; k: traced int32
    (no recompile across k lanes); preempt is a Python bool (two traced
    branches).  Returns (latencies (num_jobs,), busy, wasted).
    """
    n = S.shape[1]
    step = make_plain_step(k, cancel_overhead, preempt)
    zero = jnp.zeros((), S.dtype)
    (_, busy, wasted), lat = jax.lax.scan(
        step, (jnp.zeros((n,), S.dtype), zero, zero), (A, S))
    return lat, busy, wasted


def _scan_lane_failures(A, S, k, cancel_overhead, preempt: bool, crash,
                        recover, jitter_u, retry: RetryPolicy):
    """The failure-mode lane: the same FCFS/any-k recurrence with task
    times folded through the crash-restart schedule.

    Per job, each task's natural finish becomes its ``effective_finish``
    under the schedule — downtime-inflated service plus a bounded
    relaunch pass (``max_attempts`` is static, so the retry loop is
    unrolled into the scan step).  The job resolves at the k-th
    surviving completion or, when more than n-k tasks exhaust their
    retry budgets, FAILS at the (n-k+1)-th terminal loss
    (``failures.job_resolution``).  Tasks that resolved (completed or
    terminally failed) at or before D release their worker at their
    release instant; tasks still in flight at D are cut exactly like
    the fault-free engine's in-service remnants (preempt: D + overhead;
    no preempt: they run out their FULL effective finish, retries
    included — the oracle relaunches remnants to match, see DESIGN.md
    §9).  Accounting is occupancy-based: a worker counts busy from
    dispatch to release, downtime and backoff waits included.

    Returns (latencies, success mask, busy, wasted).
    """
    n = S.shape[1]
    crash = jnp.asarray(crash, S.dtype)
    recover = jnp.asarray(recover, S.dtype)
    have_jitter = jitter_u is not None
    step = make_failure_step(k, cancel_overhead, preempt, crash, recover,
                             retry, have_jitter, n)
    zero = jnp.zeros((), S.dtype)
    xs = (A, S, jitter_u) if have_jitter else (A, S)
    (_, busy, wasted), (lat, okj) = jax.lax.scan(
        step, (jnp.zeros((n,), S.dtype), zero, zero), xs)
    return lat, okj, busy, wasted


def make_failure_step(k, cancel_overhead, preempt: bool, crash, recover,
                      retry: RetryPolicy, have_jitter: bool, n: int):
    """Per-job step of the failure-mode ungrouped lane (factory; see
    ``make_plain_step`` for why).  ``crash``/``recover`` are bound at
    construction: the monolithic scan binds the absolute (n, M) schedule
    once, the chunked engine re-binds a REBASED schedule per chunk."""
    def step(carry, inp):
        F, busy, wasted = carry
        if have_jitter:
            a, srow, urow = inp
        else:
            a, srow = inp
            urow = None
        start = jnp.maximum(a, F)
        nat, ok, _ = effective_finish(jnp, start, srow, crash, recover,
                                      retry, urow)
        D, success = job_resolution(jnp, nat, ok, k, n)
        natq = jnp.where(ok, nat, jnp.inf)
        lt = natq < D
        eq = natq == D
        # success: first k survivors, ties at D by worker index (the
        # fault-free rule); failure: every survivor that finished by D
        take_eq = jnp.where(success, k - lt.sum(), eq.sum())
        completed = lt | (eq & (jnp.cumsum(eq) * eq <= take_eq))
        resolved_fail = (~ok) & (nat <= D)
        engaged = (~completed) & (~resolved_fail) & (start < D)
        occ = nat - start
        if preempt:
            cut = D - start + cancel_overhead
            run = jnp.where(completed | resolved_fail, occ,
                            jnp.where(engaged, cut, 0.0))
            waste = jnp.where(resolved_fail, occ,
                              jnp.where(engaged, cut, 0.0))
            F_next = jnp.where(completed | resolved_fail, nat,
                               jnp.where(engaged, D + cancel_overhead, F))
        else:
            started = completed | resolved_fail | engaged
            run = jnp.where(started, occ, 0.0)
            waste = jnp.where(resolved_fail | engaged, occ, 0.0)
            F_next = jnp.where(started, nat, F)
        return (F_next, busy + run.sum(), wasted + waste.sum()), \
            (D - a, success)

    return step


def _scan_lane_grouped(A, S, k, cancel_overhead, preempt: bool, r, gid,
                       groups: int):
    """The fault-free lane under a grouped assignment (per-group any-r).

    ``gid`` (num_jobs, n) maps worker -> replication group per job (the
    mask is DATA, riding the scan xs; ``groups`` — the max group count —
    is the only static).  ``r`` is the traced within-group completion
    rank k/g.  Group i resolves at its r-th smallest finish D_i and
    cancels its OWN remnants at D_i (group-local, not at job
    completion); the job completes at D = max_i D_i.  With one group and
    r = k this is exactly ``_scan_lane``; padded empty groups (lanes
    with g < groups) sort to +inf and drop out of the max.
    """
    n = S.shape[1]
    step = make_grouped_step(cancel_overhead, preempt, r, groups)
    zero = jnp.zeros((), S.dtype)
    (_, busy, wasted), lat = jax.lax.scan(
        step, (jnp.zeros((n,), S.dtype), zero, zero), (A, S, gid))
    return lat, busy, wasted


def make_grouped_step(cancel_overhead, preempt: bool, r, groups: int):
    """Per-job step of the fault-free grouped lane (factory; see
    ``make_plain_step``).  The worker->group row rides the step inputs,
    so the chunked engine can feed its per-lane CONSTANT row without
    materializing a (num_jobs, n) mask."""
    garange = jnp.arange(groups, dtype=jnp.int32)

    def step(carry, inp):
        F, busy, wasted = carry
        a, srow, grow = inp
        start = jnp.maximum(a, F)
        nat = start + srow
        maskg = grow[None, :] == garange[:, None]          # (G, n)
        natm = jnp.where(maskg, nat[None, :], jnp.inf)
        # r-th smallest per group via comparison counts — min{v : #(<=v)
        # >= r} — instead of jnp.sort: XLA's CPU sort is comparator-
        # driven and ~8x slower than SIMD compares at these widths, and
        # this runs every job step of the co-planning hot loop.  Exact
        # same value (including ties), so g=1 stays bit-equal to the
        # ungrouped lane; padded empty rows count inf<=inf and read inf.
        cnt = (natm[:, None, :] <= natm[:, :, None]).sum(axis=2)
        Dg = jnp.where(cnt >= r, natm, jnp.inf).min(axis=1)
        nonempty = maskg.any(axis=1)
        D = jnp.where(nonempty, Dg, -jnp.inf).max()
        Dw = Dg[grow]                                      # per-worker cutoff
        # per group: first r finishers, ties at D_i by worker index
        # (membership-masked: a padded empty group has D_i = +inf, and
        # inf == inf must not mark anybody)
        ltg = maskg & (natm < Dg[:, None])
        eqg = maskg & (natm == Dg[:, None])
        take_eq = r - ltg.sum(axis=1)
        compg = ltg | (eqg & (jnp.cumsum(eqg, axis=1) * eqg
                              <= take_eq[:, None]))
        completed = compg.any(axis=0)
        inservice = (~completed) & (start < Dw)
        if preempt:
            cut = Dw - start + cancel_overhead
            run = jnp.where(completed, srow,
                            jnp.where(inservice, cut, 0.0))
            waste = jnp.where(inservice, cut, 0.0)
            F_next = jnp.where(completed, nat,
                               jnp.where(inservice, Dw + cancel_overhead, F))
        else:
            run = jnp.where(completed | inservice, srow, 0.0)
            waste = jnp.where(inservice, srow, 0.0)
            F_next = jnp.where(completed | inservice, nat, F)
        return (F_next, busy + run.sum(), wasted + waste.sum()), D - a

    return step


def _scan_lane_grouped_failures(A, S, k, cancel_overhead, preempt: bool,
                                crash, recover, jitter_u,
                                retry: RetryPolicy, r, gid, groups: int):
    """The failure lane under a grouped assignment.

    Same clairvoyant recurrence as ``_scan_lane_failures`` with
    ``failures.group_resolution`` in place of ``job_resolution``: group i
    completes at its r-th surviving finish or fails at its
    (c-r+1)-th terminal loss, the job succeeds iff every group does
    (completing at max_i D_i) and FAILS the instant the first group
    exhausts its replicas.  Per-worker cutoffs are
    C_w = min(D_{g(w)}, D): a group cancels its own remnants at its own
    resolution, and a job failure cuts every still-unresolved group at
    the failure instant.  The first-r tie cap applies only to groups
    that resolved successfully at or before D; survivors in any other
    group complete whenever they finish by the cutoff (the failure-mode
    rule of the ungrouped lane, applied per group).
    """
    n = S.shape[1]
    crash = jnp.asarray(crash, S.dtype)
    recover = jnp.asarray(recover, S.dtype)
    have_jitter = jitter_u is not None
    step = make_grouped_failure_step(cancel_overhead, preempt, crash,
                                     recover, retry, have_jitter, r, groups)
    zero = jnp.zeros((), S.dtype)
    xs = (A, S, gid, jitter_u) if have_jitter else (A, S, gid)
    (_, busy, wasted), (lat, okj) = jax.lax.scan(
        step, (jnp.zeros((n,), S.dtype), zero, zero), xs)
    return lat, okj, busy, wasted


def make_grouped_failure_step(cancel_overhead, preempt: bool, crash, recover,
                              retry: RetryPolicy, have_jitter: bool, r,
                              groups: int):
    """Per-job step of the failure-mode grouped lane (factory; see
    ``make_plain_step`` / ``make_failure_step`` for the contract)."""
    garange = jnp.arange(groups, dtype=jnp.int32)

    def step(carry, inp):
        F, busy, wasted = carry
        if have_jitter:
            a, srow, grow, urow = inp
        else:
            a, srow, grow = inp
            urow = None
        start = jnp.maximum(a, F)
        nat, ok, _ = effective_finish(jnp, start, srow, crash, recover,
                                      retry, urow)
        maskg = grow[None, :] == garange[:, None]          # (G, n)
        Dg, gok, D, success = group_resolution(jnp, nat, ok, maskg, r)
        Cg = jnp.minimum(Dg, D)
        Cw = Cg[grow]
        natqm = jnp.where(maskg & ok[None, :], nat[None, :], jnp.inf)
        ltg = natqm < Cg[:, None]
        eqg = natqm == Cg[:, None]
        res_ok = gok & (Dg <= D)
        take_eq = jnp.where(res_ok, r - ltg.sum(axis=1), eqg.sum(axis=1))
        compg = ltg | (eqg & (jnp.cumsum(eqg, axis=1) * eqg
                              <= take_eq[:, None]))
        completed = compg.any(axis=0)
        resolved_fail = (~ok) & (nat <= Cw)
        engaged = (~completed) & (~resolved_fail) & (start < Cw)
        occ = nat - start
        if preempt:
            cut = Cw - start + cancel_overhead
            run = jnp.where(completed | resolved_fail, occ,
                            jnp.where(engaged, cut, 0.0))
            waste = jnp.where(resolved_fail, occ,
                              jnp.where(engaged, cut, 0.0))
            F_next = jnp.where(completed | resolved_fail, nat,
                               jnp.where(engaged, Cw + cancel_overhead, F))
        else:
            started = completed | resolved_fail | engaged
            run = jnp.where(started, occ, 0.0)
            waste = jnp.where(resolved_fail | engaged, occ, 0.0)
            F_next = jnp.where(started, nat, F)
        return (F_next, busy + run.sum(), wasted + waste.sum()), \
            (D - a, success)

    return step


@functools.partial(jax.jit, static_argnames=("preempt",))
def _one_kernel(A, S, k, cancel_overhead, preempt):
    return _scan_lane(A, S, k, cancel_overhead, preempt)


@functools.partial(jax.jit, static_argnames=("preempt", "groups"))
def _one_kernel_grouped(A, S, k, cancel_overhead, r, gid, preempt, groups):
    return _scan_lane_grouped(A, S, k, cancel_overhead, preempt, r, gid,
                              groups)


@functools.partial(jax.jit, static_argnames=("preempt", "retry", "groups"))
def _one_kernel_grouped_failures(A, S, k, cancel_overhead, crash, recover,
                                 jitter_u, r, gid, preempt, retry, groups):
    return _scan_lane_grouped_failures(A, S, k, cancel_overhead, preempt,
                                       crash, recover, jitter_u, retry, r,
                                       gid, groups)


@functools.partial(jax.jit, static_argnames=("preempt", "retry"))
def _one_kernel_failures(A, S, k, cancel_overhead, crash, recover, jitter_u,
                         preempt, retry):
    return _scan_lane_failures(A, S, k, cancel_overhead, preempt, crash,
                               recover, jitter_u, retry)


def simulate_one(cfg: ClusterConfig, dist, scaling: Scaling,
                 delta: Optional[float] = None,
                 service_times: Optional[np.ndarray] = None,
                 arrival_times: Optional[np.ndarray] = None,
                 crash_times: Optional[np.ndarray] = None,
                 recovery_times: Optional[np.ndarray] = None
                 ) -> ClusterResult:
    """One cell on the batched engine, sample-path-matched to the oracle.

    Inputs are drawn by the oracle's own ``_draw_inputs`` (shared
    substrate, same keys), so this is the same trajectory the
    discrete-event loop walks — the single-cell parity anchor.  ``k``
    and ``cancel_overhead`` are traced, so sweeping them reuses one
    compiled kernel per (shape, preempt).  Failure cells (a
    ``cfg.failures`` model, an injected ``crash_times``/
    ``recovery_times`` schedule, or a killing ``cfg.retry`` timeout)
    route through the failure lane and share the oracle's
    ``_draw_failures`` substrate the same way.
    """
    from .cluster_oracle import _draw_failures, _draw_inputs
    svc, arrivals = _draw_inputs(cfg, dist, scaling, delta,
                                 service_times, arrival_times)
    fail = _draw_failures(cfg, crash_times, recovery_times)
    assignment = getattr(cfg, "assignment", None)
    lanes = None
    if not is_all_workers(assignment):
        g, r, gid = group_ids_matrix(assignment, cfg.n_workers, cfg.k,
                                     cfg.num_jobs, cfg.worker_speeds)
        lanes = (g, jnp.int32(r), jnp.asarray(gid, jnp.int32))
    if fail is None:
        if lanes is None:
            lat, busy, wasted = _one_kernel(
                jnp.asarray(arrivals, jnp.float32),
                jnp.asarray(svc, jnp.float32),
                jnp.int32(cfg.k), jnp.float32(cfg.cancel_overhead),
                cfg.preempt)
        else:
            g, r, gid = lanes
            lat, busy, wasted = _one_kernel_grouped(
                jnp.asarray(arrivals, jnp.float32),
                jnp.asarray(svc, jnp.float32),
                jnp.int32(cfg.k), jnp.float32(cfg.cancel_overhead), r, gid,
                cfg.preempt, g)
        okj = None
    else:
        crash, recover, jitter_u, retry = fail
        jargs = (jnp.asarray(arrivals, jnp.float32),
                 jnp.asarray(svc, jnp.float32),
                 jnp.int32(cfg.k), jnp.float32(cfg.cancel_overhead),
                 jnp.asarray(crash, jnp.float32),
                 jnp.asarray(recover, jnp.float32),
                 None if jitter_u is None
                 else jnp.asarray(jitter_u, jnp.float32))
        if lanes is None:
            lat, okj, busy, wasted = _one_kernel_failures(
                *jargs, cfg.preempt, retry)
        else:
            g, r, gid = lanes
            lat, okj, busy, wasted = _one_kernel_grouped_failures(
                *jargs, r, gid, cfg.preempt, retry, g)
        okj = np.asarray(okj, dtype=bool)
    lat = np.asarray(lat, dtype=np.float64)
    busy = float(busy)
    horizon = float(np.max(arrivals + lat))
    completions = lat.size if okj is None else int(okj.sum())
    return ClusterResult(
        latencies=lat,
        utilization=busy / (cfg.n_workers * horizon),
        wasted_frac=float(wasted) / max(busy, 1e-12),
        throughput=completions / horizon,
        warmup=cfg.warmup,
        job_failed=None if okj is None else ~okj,
    )


# --------------------------------------------------------------------------
# The surface: vmap lanes over (replications x loads x k), one compile
# --------------------------------------------------------------------------

def _sweep_core(key, loads, speeds, cancel_overhead, dist, scaling, n,
                ks, num_jobs, reps, preempt, arrivals, delta,
                failures=None, retry=None, groups=None, group_r=None,
                group_ids=None):
    """The (reps x loads x ks) lane grid, shared by the two jit wrappers:
    ``_sweep_kernel`` folds dist/arrival parameters as compile-time
    constants (one-off surfaces), while the compiled-surface cache
    (``runtime.surface_cache``) traces them so steady-state re-plans with
    fresh fitted parameters reuse a warm executable.

    With a ``failures`` model (and resolved ``retry`` policy) the lanes
    run the failure recurrence: ONE crash-restart schedule per
    replication (key disjoint from the service/arrival splits via
    ``fold_in``, so fault-free draws are bit-stable), shared across the
    k and load lanes — machines crash identically whatever policy serves
    them, the CRN discipline that pairs the failure surface.  Returns an
    extra (reps, L, K, num_jobs) success mask and per-lane horizon.

    A grouped assignment arrives as (``groups`` static max group count,
    ``group_r`` (K,) within-group ranks, ``group_ids`` (K, num_jobs, n)
    worker->group masks — traced DATA, so re-placements reuse the warm
    executable).  Task size s = n/k is independent of the grouping, so
    the CRN service tables are shared unchanged across assignment lanes:
    placement comparisons are exactly paired.
    """
    global _SWEEP_TRACES
    _SWEEP_TRACES += 1  # trace-time side effect: counts compiles, not calls
    s_of_k = tuple(n // k for k in ks)
    k_arr = jnp.asarray(ks, jnp.int32)

    def one_rep(rep_key):
        k_svc, k_arrv = jax.random.split(rep_key)
        # -- service: one CRN base draw transformed per k lane -------------
        if scaling is Scaling.ADDITIVE:
            draws = dist.sample(k_svc, (num_jobs, n, max(s_of_k)))
            csum = jnp.cumsum(draws, axis=-1)
            S_all = jnp.stack([csum[..., s - 1] for s in s_of_k])
        else:
            d = dist.shift if delta is None else delta
            z = dist.sample_noise(k_svc, (num_jobs, n))
            s_col = jnp.asarray(s_of_k, z.dtype)[:, None, None]
            S_all = (d + s_col * z) if scaling is Scaling.SERVER_DEPENDENT \
                else (s_col * d + z)                        # (K, jobs, n)
        S_all = S_all * speeds[None, None, :]
        # -- arrivals: one key across load lanes, only the rate sweeps ----
        A_all = jax.vmap(
            lambda r: arrivals.times(k_arrv, num_jobs, r))(loads)

        if retry is None:
            if groups is None:
                def lane(A, S, k):
                    return _scan_lane(A, S, k, cancel_overhead, preempt)

                over_k = jax.vmap(lane, in_axes=(None, 0, 0))
                over_loads = jax.vmap(over_k, in_axes=(0, None, None))
                lat, busy, wasted = over_loads(A_all, S_all, k_arr)
            else:
                def lane(A, S, k, r, gid):
                    return _scan_lane_grouped(A, S, k, cancel_overhead,
                                              preempt, r, gid, groups)

                over_k = jax.vmap(lane, in_axes=(None, 0, 0, 0, 0))
                over_loads = jax.vmap(
                    over_k, in_axes=(0, None, None, None, None))
                lat, busy, wasted = over_loads(A_all, S_all, k_arr,
                                               group_r, group_ids)
            return lat, busy, wasted, A_all[:, -1]

        # -- failures: one fleet schedule per rep, shared across lanes ----
        if failures is None:                 # timeout-only retry policy
            crash = jnp.zeros((n, 0), jnp.float32)
            recover = crash
        else:
            crash, recover = failures.schedule(
                jax.random.fold_in(rep_key, 7), n)
            crash = jnp.asarray(crash, jnp.float32)
            recover = jnp.asarray(recover, jnp.float32)
        jitter_u = None
        if retry.max_attempts > 1 and retry.jitter > 0:
            jitter_u = jax.random.uniform(
                jax.random.fold_in(rep_key, 8),
                (num_jobs, n, retry.max_attempts - 1))

        if groups is None:
            def lane(A, S, k):
                return _scan_lane_failures(A, S, k, cancel_overhead, preempt,
                                           crash, recover, jitter_u, retry)

            over_k = jax.vmap(lane, in_axes=(None, 0, 0))
            over_loads = jax.vmap(over_k, in_axes=(0, None, None))
            lat, okj, busy, wasted = over_loads(A_all, S_all, k_arr)
        else:
            def lane(A, S, k, r, gid):
                return _scan_lane_grouped_failures(
                    A, S, k, cancel_overhead, preempt, crash, recover,
                    jitter_u, retry, r, gid, groups)

            over_k = jax.vmap(lane, in_axes=(None, 0, 0, 0, 0))
            over_loads = jax.vmap(over_k, in_axes=(0, None, None, None, None))
            lat, okj, busy, wasted = over_loads(A_all, S_all, k_arr,
                                                group_r, group_ids)
        # failure resolutions need not be monotone in j, so the horizon
        # is the max resolution instant, not the last job's
        horizon = (A_all[:, None, :] + lat).max(axis=-1)
        return lat, busy, wasted, A_all[:, -1], okj, horizon

    return jax.vmap(one_rep)(jax.random.split(key, reps))


_sweep_kernel = functools.partial(jax.jit, static_argnames=(
    "dist", "scaling", "n", "ks", "num_jobs", "reps", "preempt",
    "arrivals", "delta", "failures", "retry", "groups"))(_sweep_core)


def lanes_as_jnp(lanes: Optional[GroupLanes]):
    """GroupLanes -> the (groups, group_r, group_ids) kernel triple."""
    if lanes is None:
        return None, None, None
    return (lanes.groups, jnp.asarray(lanes.r, jnp.int32),
            jnp.asarray(lanes.gid, jnp.int32))


@dataclasses.dataclass(frozen=True)
class Infeasible:
    """Typed marker for a surface row with NO feasible candidate.

    Failure lanes report an all-failed cell as ``np.inf``; a row where
    EVERY candidate carries the sentinel has no optimum, and a silent
    ``argmin`` would return the first candidate as if it had won.
    ``kstar``-style selections return this marker instead so callers can
    branch on it (``isinstance(v, Infeasible)``); planner entry points
    that must produce a single policy raise ``InfeasibleSurfaceError``.
    """

    load: float
    metric: str

    def __bool__(self) -> bool:
        return False


class InfeasibleSurfaceError(RuntimeError):
    """Raised when a planning curve has no finite cell to select from
    (every candidate hit the all-failed ``np.inf`` sentinel)."""


@dataclasses.dataclass
class ClusterSweep:
    """The (loads x ks) result surface, replication-averaged.

    Latency stats pool replications and post-warmup jobs; utilization,
    wasted-work fraction, and throughput are per-lane then averaged over
    replications.  All arrays are (len(loads), len(ks)).
    """

    loads: Tuple[float, ...]
    ks: Tuple[int, ...]
    warmup: int
    reps: int
    mean: np.ndarray
    p50: np.ndarray
    p95: np.ndarray
    p99: np.ndarray
    utilization: np.ndarray
    wasted_frac: np.ndarray
    throughput: np.ndarray
    #: post-warmup fraction of FAILED jobs per cell; None on a fault-free
    #: sweep (kept out of ``_METRICS`` so fault-free summaries are
    #: unchanged; latency stats always pool COMPLETED jobs only)
    failure_rate: Optional[np.ndarray] = None

    _METRICS = ("mean", "p50", "p95", "p99", "utilization", "wasted_frac",
                "throughput")

    def metric(self, name: str) -> np.ndarray:
        if name == "failure_rate":
            if self.failure_rate is None:
                raise ValueError(
                    "failure_rate is only available on a sweep with a "
                    "failure model (Scenario.failures)")
            return self.failure_rate
        if name not in self._METRICS:
            raise ValueError(f"unknown metric {name!r} "
                             f"(one of {self._METRICS + ('failure_rate',)})")
        return getattr(self, name)

    def summary(self, load_idx: int, k_idx: int) -> dict:
        """One cell in ``ClusterResult.summary()``'s dialect."""
        return {m: float(self.metric(m)[load_idx, k_idx])
                for m in self._METRICS}

    def curve(self, load_idx: int = 0, metric: str = "mean"
              ) -> Dict[int, float]:
        """k -> metric at one load (the planner's objective row)."""
        vals = self.metric(metric)[load_idx]
        return {int(k): float(v) for k, v in zip(self.ks, vals)}

    def kstar(self, metric: str = "mean") -> Dict[float, object]:
        """load -> arg-min k (ties to the smaller k; ks are ascending).

        A row where no candidate is finite (every cell carries the
        all-failed ``np.inf`` sentinel) maps to an ``Infeasible`` marker
        instead of a meaningless first-k argmin.
        """
        vals = self.metric(metric)
        out: Dict[float, object] = {}
        for i, lam in enumerate(self.loads):
            if not np.any(np.isfinite(vals[i])):
                out[float(lam)] = Infeasible(load=float(lam), metric=metric)
            else:
                out[float(lam)] = int(self.ks[int(np.argmin(vals[i]))])
        return out


def resolve_failure_args(scenario: Scenario,
                         retry: Optional[RetryPolicy]
                         ) -> Tuple[Optional[FailureModel],
                                    Optional[RetryPolicy]]:
    """Whether a sweep runs the failure lanes, and under what relaunch
    schedule.  (None, None) means fault-free (the historical fast path);
    otherwise the resolved ``retry`` is never None — a timeout-only
    policy (``retry.kills_on_timeout`` without a ``FailureModel``)
    activates the lanes with an empty crash schedule."""
    if scenario.failures is None and (retry is None
                                      or not retry.kills_on_timeout):
        return None, None
    return scenario.failures, resolve_retry(retry)


def validate_sweep_args(scenario: Scenario, loads, ks, num_jobs, reps,
                        warmup):
    """The shared argument contract of every sweep surface (``sweep``
    here, the cached twin in ``runtime.surface_cache``): resolved
    (ks, loads, warmup, arrivals, speeds)."""
    n = scenario.n
    ks = tuple(scenario.legal_ks()) if ks is None \
        else tuple(int(k) for k in ks)
    for k in ks:
        if k < 1 or n % k:
            raise ValueError(f"k={k} must divide n={n}")
    loads = [float(v) for v in loads]
    if not loads or any(v <= 0 for v in loads):
        raise ValueError("loads must be positive arrival rates")
    if reps < 1:
        raise ValueError(f"reps must be >= 1, got {reps}")
    if warmup is None:
        warmup = default_warmup(num_jobs)
    if not (0 <= warmup < num_jobs):
        raise ValueError(f"warmup must be in [0, num_jobs), got {warmup}")
    arrivals = scenario.arrivals if scenario.arrivals is not None \
        else PoissonArrivals(rate=1.0)           # rate overridden per lane
    speeds = jnp.ones((n,), jnp.float32) if scenario.worker_speeds is None \
        else jnp.asarray(scenario.worker_speeds, jnp.float32)
    return ks, loads, int(warmup), arrivals, speeds


def summarize_sweep(lat, busy, wasted, a_last, loads, ks, warmup, reps,
                    num_jobs, n, ok=None, horizon=None) -> ClusterSweep:
    """Kernel outputs -> ``ClusterSweep``; the single aggregation both the
    jit-per-scenario path and the compiled-surface cache run, so a cached
    surface is post-processed identically to an uncached one.

    ``ok`` ((reps, L, K, num_jobs) success mask) and ``horizon``
    ((reps, L, K) max resolution instants) arrive from the failure
    lanes: latency statistics then pool COMPLETED post-warmup jobs only
    (a cell where every job failed reports inf), and ``failure_rate``
    is the failed fraction per cell.
    """
    with _trace.span("surface.summarize"):
        with _trace.span("surface.fetch"):
            lat = np.asarray(lat, np.float64)        # (reps, L, K, num_jobs)
            busy = np.asarray(busy, np.float64)      # (reps, L, K)
            wasted = np.asarray(wasted, np.float64)
            a_last = np.asarray(a_last, np.float64)  # (reps, L)
        if horizon is None:
            # D_last (monotone in j)
            horizon = a_last[:, :, None] + lat[..., -1]
        else:
            horizon = np.asarray(horizon, np.float64)
        steady = lat[..., warmup:]
        L, K = len(loads), len(ks)
        pooled = np.moveaxis(steady, 0, -2).reshape(L, K, -1)
        if ok is None:
            mean = pooled.mean(axis=-1)
            p50 = np.quantile(pooled, 0.50, axis=-1)
            p95 = np.quantile(pooled, 0.95, axis=-1)
            p99 = np.quantile(pooled, 0.99, axis=-1)
            fail_rate = None
            completions = float(num_jobs)
        else:
            ok = np.asarray(ok, bool)
            ok_pooled = np.moveaxis(ok[..., warmup:], 0, -2).reshape(L, K, -1)
            mean = np.full((L, K), np.inf)
            p50, p95, p99 = (np.full((L, K), np.inf) for _ in range(3))
            for i in range(L):
                for j in range(K):
                    good = pooled[i, j][ok_pooled[i, j]]
                    if good.size:
                        mean[i, j] = good.mean()
                        p50[i, j] = np.quantile(good, 0.50)
                        p95[i, j] = np.quantile(good, 0.95)
                        p99[i, j] = np.quantile(good, 0.99)
            fail_rate = 1.0 - ok_pooled.mean(axis=-1)
            completions = np.asarray(ok, bool).sum(axis=-1)  # (reps, L, K)
        return ClusterSweep(
            loads=tuple(loads), ks=tuple(ks), warmup=int(warmup),
            reps=int(reps),
            mean=mean, p50=p50, p95=p95, p99=p99,
            utilization=(busy / (n * horizon)).mean(axis=0),
            wasted_frac=(wasted / np.maximum(busy, 1e-12)).mean(axis=0),
            throughput=(completions / horizon).mean(axis=0),
            failure_rate=fail_rate,
        )


def sweep(scenario: Scenario, loads: Sequence[float],
          ks: Optional[Sequence[int]] = None, num_jobs: int = 1000,
          reps: int = 1, preempt: bool = True, cancel_overhead: float = 0.0,
          seed: int = 0, warmup: Optional[int] = None,
          retry: Optional[RetryPolicy] = None,
          assignment: Optional[Assignment] = None,
          chunk_size: Optional[int] = None, stream: bool = False,
          reservoir: int = 4096,
          shard: Optional[int] = None) -> ClusterSweep:
    """Every (load, k) queueing cell of a scenario in one compiled call.

    ``loads`` are mean arrival rates; the scenario's ``arrivals`` process
    (default Poisson) supplies the SHAPE and is rescaled per load lane.
    ``warmup=None`` discards min(num_jobs // 10, 200) transient jobs from
    the latency statistics.  Heterogeneous ``scenario.worker_speeds``
    multiply every lane's task times.  Additive scaling materializes a
    (num_jobs, n, s_max) CU table per replication — prefer moderate n
    there; server-/data-dependent scaling needs only (num_jobs, n).

    ``scenario.failures`` switches every lane to the crash-restart
    recurrence (relaunches under ``retry``, default ``RetryPolicy()``);
    the resulting surface carries ``failure_rate`` and its latency stats
    cover completed jobs only.

    ``assignment`` switches every lane to the grouped per-group-any-r
    recurrence (see ``assign.strategies``); ``None``/``AllWorkers`` run
    the historical ungrouped path bit-for-bit.

    Any of ``chunk_size`` / ``stream`` / ``shard`` dispatches to the
    fleet-scale chunked engine (``runtime.fleet``): same semantics and
    result type, memory bounded by O(lanes * (n + chunk_size)) instead
    of the full latency cube — the path for n ~ 10^4 workers and 10^5+
    jobs.  Left at their defaults, the historical monolithic kernel
    runs unchanged (bit-for-bit, including its bulk RNG draws; the
    chunked engine's per-job row keys are a different, equal-in-law
    sample path).
    """
    if chunk_size is not None or stream or shard is not None:
        from .fleet import fleet_sweep
        return fleet_sweep(scenario, loads, ks=ks, num_jobs=num_jobs,
                           reps=reps, preempt=preempt,
                           cancel_overhead=cancel_overhead, seed=seed,
                           warmup=warmup, retry=retry,
                           assignment=assignment, chunk_size=chunk_size,
                           stream=stream, reservoir=reservoir, shard=shard)
    n = scenario.n
    ks, loads, warmup, arrivals, speeds = validate_sweep_args(
        scenario, loads, ks, num_jobs, reps, warmup)
    failures, retry = resolve_failure_args(scenario, retry)
    groups, group_r, group_ids = lanes_as_jnp(build_lanes(
        assignment, n, ks, int(num_jobs), scenario.worker_speeds))

    rec = _trace.active()
    traces0 = _SWEEP_TRACES
    t0 = rec.now() if rec is not None else 0.0
    with _trace.span("surface.dispatch"):
        out = _sweep_kernel(
            jax.random.PRNGKey(seed), jnp.asarray(loads, jnp.float32),
            speeds, jnp.float32(cancel_overhead), scenario.dist,
            scenario.scaling, n, ks, int(num_jobs), int(reps),
            bool(preempt), arrivals,
            None if scenario.delta is None else float(scenario.delta),
            failures, retry, groups, group_r, group_ids)
    if rec is not None:
        rec.event("sweep", name="batched", dur=rec.now() - t0,
                  n=n, num_jobs=int(num_jobs), reps=int(reps),
                  lanes=len(loads) * len(ks),
                  compiled=_SWEEP_TRACES > traces0)

    if retry is None:
        lat, busy, wasted, a_last = out
        ok = horizon = None
    else:
        lat, busy, wasted, a_last, ok, horizon = out
    return summarize_sweep(lat, busy, wasted, a_last, loads, ks, warmup,
                           reps, num_jobs, n, ok=ok, horizon=horizon)
