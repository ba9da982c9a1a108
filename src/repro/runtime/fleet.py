"""Fleet-scale chunked cluster engine: n ~ 10^4 workers x 10^6 jobs.

The monolithic batched engine (``runtime.cluster_batched``) materializes
the full (reps, loads, K, num_jobs, n) sampling tables and the
(reps, loads, K, num_jobs) latency cube — perfect at n ~ 10^2, hopeless
at fleet scale (a single n=10^4 x 10^6-job lane's service table alone is
40 TB).  This module re-pipelines the SAME per-job recurrence (the step
factories of ``cluster_batched`` are reused verbatim — ``make_plain_step``
etc., so the dynamics are shared code, not a re-implementation) into a
memory-bounded streaming form:

  * **Chunked scan** — an outer ``lax.scan`` over fixed-size job chunks;
    the carry holds only the (lanes, n) worker free-times, the arrival-
    process state, a per-lane clock base, and the streaming-statistics
    state.  Peak memory is O(lanes * (n + chunk)) independent of
    num_jobs.
  * **Chunk-offset sampling** — every random input (service noise,
    arrival gaps, retry jitter, reservoir acceptance) is drawn from
    per-GLOBAL-job-index row keys (``core.scenario.job_row_keys``), so
    any chunking of [0, N) walks the bit-identical sample path: the
    chunk size is a pure performance knob, pinned by the parity tests
    in ``tests/test_fleet.py``.  (This is a different, equal-in-law
    path from the monolithic engine's bulk threefry draws, whose
    counters depend on the total array length.)
  * **Per-chunk clock rebasing** — at each chunk boundary the free
    times, the failure schedule, and the statistics are re-expressed
    relative to the chunk's last arrival, so float32 never accumulates
    a large absolute clock (at 10^6 jobs the monolithic engine's
    absolute float32 clock has ulp ~ the whole service time; see the
    pitfall note in ``tests/test_conformance.py``).  Absolute horizons
    are reconstructed on the host in float64 from the per-chunk
    offsets.
  * **Streaming statistics** — Welford count/mean/M2 merged per chunk
    plus a fixed-size Algorithm-R reservoir for p50/p95/p99
    (``runtime.streamstats``); warmup is a job-index mask.  The exact
    small-trace path (identical ``summarize_sweep`` aggregation) is
    kept for parity and moderate sizes.
  * **Sharded lanes** — the flattened (loads x K) lane axis can be
    ``shard_map``-ped over a device mesh; ``shard=1`` is semantically
    identical to the unsharded path (pinned by tests).  On this
    single-core CPU box sharding buys nothing — it is a correctness
    surface for multi-device deployments.
  * **Order-statistic selection** — at n ~ 10^4 XLA's CPU sort is the
    step bottleneck; the fault-free lane swaps in an exact radix
    bisection over the float32 bit patterns (``_kth_bisect``; ~9x
    faster at n=10^4, measured), bit-equal to ``sort(nat)[k-1]`` for
    the non-negative finish times the recurrence produces, and ranks
    the workers tied at D by an index bisection
    (``_first_ties_bisect``) instead of a prefix sum over all n.

Entry points: ``fleet_sweep`` mirrors ``cluster_batched.sweep`` and
returns the same ``ClusterSweep``; ``cluster_batched.sweep(...,
chunk_size=...)`` and the compiled-surface cache dispatch here.
``run_fleet``/``summarize_fleet`` are the raw lane-level API the
(k, assignment) co-optimizer (``assign.surface.co_sweep``) slices.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..assign.strategies import (Assignment, group_ids_matrix,
                                 is_all_workers)
from ..core.distributions import Scaling
from ..core.policy import RetryPolicy
from ..core.scenario import Scenario, job_row_keys
from ..obs import recorder as _trace
from .cluster_batched import (ClusterSweep, make_failure_step,
                              make_grouped_failure_step, make_grouped_step,
                              make_plain_step, resolve_failure_args,
                              summarize_sweep, validate_sweep_args)
from .streamstats import (reservoir_init, reservoir_update_chunk,
                          reservoir_values_host, welford_finalize_host,
                          welford_init, welford_merge_chunk)

__all__ = ["FleetLanes", "FleetRaw", "build_fleet_lanes", "co_fleet_lanes",
           "default_chunk", "fleet_compile_count", "fleet_sweep",
           "run_fleet", "summarize_fleet", "trim_raw_loads"]

_FLEET_TRACES = 0

#: below this width the plain sort selection and prefix-sum tie rank
#: win; above it the bisections do (measured on CPU: ~9x at n = 10^4
#: for the selection)
_BISECT_MIN_N = 1024

_DEFAULT_CHUNK = 512


def fleet_compile_count() -> int:
    """How many times a fleet kernel has been TRACED (== compiled) —
    the chunked twin of ``cluster_batched.sweep_compile_count``."""
    return _FLEET_TRACES


def default_chunk(num_jobs: int) -> int:
    """The ``chunk_size=None`` resolution: one chunk for small traces; at
    scale, the smallest chunk that keeps the chunk COUNT of the 512
    bound — balanced chunks instead of a padded ragged tail (600 jobs at
    a flat 512 would scan 1024 padded steps, 1.7x the work; balancing
    gives 2 x 300 with zero padding).  The last chunk still pads by at
    most one job per chunk-count, and padded steps freeze the carry, so
    this is a throughput knob only."""
    num_jobs = int(num_jobs)
    if num_jobs <= _DEFAULT_CHUNK:
        return num_jobs
    num_chunks = -(-num_jobs // _DEFAULT_CHUNK)
    return -(-num_jobs // num_chunks)


def _kth_bisect(nat, k):
    """Exact k-th smallest of non-negative float32 values by radix
    bisection on the bit patterns.

    For floats >= 0 the int32 bit pattern is order-isomorphic to the
    float ordering (+inf included), so building the answer bit by bit
    from the MSB — keep a candidate bit iff fewer than k values lie
    strictly below it — lands exactly on ``sort(nat)[k-1]`` in 31
    comparison passes, with no data movement.  The lane recurrence only
    ever selects over ``start + srow`` with ``start > 0``, so the
    precondition holds by construction.
    """
    x = jax.lax.bitcast_convert_type(nat, jnp.int32)

    def body(i, pre):
        cand = pre | (jnp.int32(1) << (30 - i))
        return jnp.where((x < cand).sum() >= k, pre, cand)

    out = jax.lax.fori_loop(0, 31, body, jnp.int32(0))
    return jax.lax.bitcast_convert_type(out, jnp.float32)


def _first_ties_bisect(eq, take_eq):
    """The first ``take_eq`` True entries of ``eq`` in index order, by
    bisection on the index.

    The answer is ``eq & (iota <= I)`` with I the index of the
    ``take_eq``-th tie, i.e. the largest index with fewer than
    ``take_eq`` ties strictly below it; building I bit by bit from the
    MSB takes ceil(log2 n) counting passes (14 at n = 10^4) and no
    prefix sum.  Bit-equal to ``cluster_batched._first_ties_cumsum``
    for every 1 <= take_eq <= eq.sum(), which the step guarantees (D is
    one of the values, and fewer than k lie strictly below it).  The
    passes are unrolled: on a TPU v5e that takes 2.5% off the n = 10^4
    sweep against the rolled loop.
    """
    n = eq.shape[-1]
    iota = jnp.arange(n, dtype=jnp.int32)
    bits = max(1, (n - 1).bit_length())

    def body(i, pre):
        cand = pre | (jnp.int32(1) << (bits - 1 - i))
        return jnp.where((eq & (iota < cand)).sum() < take_eq, cand, pre)

    last = jax.lax.fori_loop(0, bits, body, jnp.int32(0), unroll=True)
    return eq & (iota <= last)


# --------------------------------------------------------------------------
# Lane bundles: the flattened (k [, assignment]) axis
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FleetLanes:
    """The chunked engine's flattened lane bundle (one entry per k —
    or per (assignment, k) when the co-optimizer builds it).

    Unlike ``assign.strategies.GroupLanes`` the worker->group masks are
    per-lane CONSTANT rows (B, n), not (B, num_jobs, n) — the chunked
    engine requires a per-job-constant placement (``RandomGroups``
    re-draws masks every job and is rejected at build time).
    """

    k: np.ndarray               # (B,) int32 per-lane k
    s: np.ndarray               # (B,) int32 task size n // k
    r: np.ndarray               # (B,) int32 within-group rank (k ungrouped)
    gid: np.ndarray             # (B, n) int32 (or (B, 0) ungrouped)
    grouped: bool
    groups: Optional[int]       # static max group count (None ungrouped)
    signature: Optional[tuple]  # structural cache key


def _reject_per_job(assignment: Assignment) -> None:
    if assignment.per_job():
        raise ValueError(
            f"{type(assignment).__name__} re-draws its placement per job; "
            "the chunked engine carries one constant worker->group row per "
            "lane — use the monolithic engine (chunk_size=None) for "
            "per-job-random placements")


def build_fleet_lanes(assignment: Optional[Assignment], n: int,
                      ks: Sequence[int],
                      speeds: Optional[Tuple[float, ...]] = None
                      ) -> FleetLanes:
    """Resolve one strategy into the chunked engine's lane bundle."""
    karr = np.asarray([int(k) for k in ks], np.int32)
    if is_all_workers(assignment):
        return FleetLanes(k=karr, s=(n // karr).astype(np.int32),
                          r=karr.copy(), gid=np.zeros((karr.size, 0),
                                                      np.int32),
                          grouped=False, groups=None, signature=None)
    _reject_per_job(assignment)
    rs, gids, gmax = [], [], 1
    for k in karr:
        g, r, gid = group_ids_matrix(assignment, n, int(k), 1, speeds)
        gmax = max(gmax, g)
        rs.append(r)
        gids.append(gid[0])
    return FleetLanes(k=karr, s=(n // karr).astype(np.int32),
                      r=np.asarray(rs, np.int32),
                      gid=np.asarray(gids, np.int32), grouped=True,
                      groups=gmax,
                      signature=assignment.cache_signature(n, tuple(
                          int(k) for k in karr)))


def co_fleet_lanes(assignments: Sequence[Assignment], n: int,
                   ks: Sequence[int],
                   speeds: Optional[Tuple[float, ...]] = None
                   ) -> FleetLanes:
    """Flatten an A x K (assignment, k) grid into one grouped lane axis —
    the chunked twin of ``assign.surface.co_sweep``'s lane flattening.
    ``AllWorkers`` rides as a single-group lane (g=1, r=k), which the
    grouped recurrence reduces to the ungrouped dynamics bit-for-bit."""
    karr, rs, gids, gmax = [], [], [], 1
    kt = tuple(int(k) for k in ks)
    for a in assignments:
        _reject_per_job(a)
        for k in kt:
            g, r, gid = group_ids_matrix(a, n, k, 1, speeds)
            gmax = max(gmax, g)
            karr.append(k)
            rs.append(r)
            gids.append(gid[0])
    karr = np.asarray(karr, np.int32)
    return FleetLanes(k=karr, s=(n // karr).astype(np.int32),
                      r=np.asarray(rs, np.int32),
                      gid=np.asarray(gids, np.int32), grouped=True,
                      groups=gmax,
                      signature=tuple(a.cache_signature(n, kt)
                                      for a in assignments))


# --------------------------------------------------------------------------
# The kernel: outer chunk scan, inner per-lane job scan
# --------------------------------------------------------------------------

def _fleet_core(key, rates, speeds, cancel_overhead, dist, arrivals, delta,
                failures, warm, lane_k, lane_s, lane_r, lane_gid, *,
                scaling, n, num_jobs, chunk, preempt, retry, grouped,
                groups, stream, reservoir, ndev, s_max):
    """One replication of the chunked lane grid.

    ``rates``/``lane_*`` are lane-major over the flattened
    (loads x K[-per-assignment]) axis.  The outer scan walks
    ceil(num_jobs / chunk) chunks; each chunk samples its shared
    (chunk, n) inputs from global-job-index row keys, runs every lane's
    inner job scan through the step factories of ``cluster_batched``,
    folds the streaming statistics, and REBASES the clock: the carry's
    free times drop the chunk's last arrival instant, so the in-scan
    float32 clock stays O(chunk / rate) at any horizon.  Per-chunk
    scalars (busy/wasted increments, arrival offsets, horizon
    candidates) come back as stacked ys for float64 reconstruction on
    the host.

    CRN discipline matches the monolithic engine: one service/arrival
    key pair per replication shared across lanes (arrival gaps are
    sampled once at unit rate and scaled per lane), one failure
    schedule per replication shared across lanes, service noise
    transformed per lane's task size inside the step — the (chunk, n)
    base draw is the only materialization, never (lanes, chunk, n).
    """
    global _FLEET_TRACES
    _FLEET_TRACES += 1
    have_fail = retry is not None
    have_jitter = have_fail and retry.max_attempts > 1 and retry.jitter > 0
    k_svc, k_arrv = jax.random.split(key)
    k_jit = jax.random.fold_in(key, 8)
    k_stat = jax.random.fold_in(key, 9)
    if have_fail and failures is not None:
        c0, r0 = failures.schedule(jax.random.fold_in(key, 7), n)
        crash = jnp.asarray(c0, jnp.float32)
        recover = jnp.asarray(r0, jnp.float32)
    else:
        crash = jnp.zeros((n, 0), jnp.float32)
        recover = crash
    bisect_kw = (dict(kth=_kth_bisect, first_ties=_first_ties_bisect)
                 if n >= _BISECT_MIN_N else {})
    num_chunks = -(-num_jobs // chunk)

    def run_lanes(lane_pack, shared):
        rates_l, k_l, s_l, r_l, gid_l = lane_pack
        (k_svc, k_arrv, k_jit, k_stat, crash, recover, speeds,
         cancel_overhead, warm, dist, arrivals, delta) = shared
        b = rates_l.shape[0]

        def chunk_body(carry, cidx):
            F, ast, base, stats = carry
            j0 = cidx * chunk
            idx = j0 + jnp.arange(chunk, dtype=jnp.int32)
            valid = idx < num_jobs
            post = idx >= warm
            # -- shared chunk inputs (row-keyed: chunking-invariant) -------
            g_unit, ast2 = arrivals.gaps_chunk(k_arrv, j0, chunk, rate=1.0,
                                               state=ast)
            g_unit = jnp.where(valid, g_unit.astype(jnp.float32), 0.0)
            A_unit = jnp.cumsum(g_unit)
            rks = job_row_keys(k_svc, j0, chunk)
            if scaling is Scaling.ADDITIVE:
                z = jnp.cumsum(jax.vmap(
                    lambda kk: dist.sample(kk, (n, s_max)))(rks), axis=-1)
                d0 = None
            else:
                z = jax.vmap(lambda kk: dist.sample_noise(kk, (n,)))(rks)
                d0 = dist.shift if delta is None else delta
            ujit = None
            if have_jitter:
                ujit = jax.vmap(lambda kk: jax.random.uniform(
                    kk, (n, retry.max_attempts - 1)))(
                        job_row_keys(k_jit, j0, chunk))

            def one_lane(F0, base0, rate, kq, s, rr, gidrow):
                A = A_unit / rate
                sf = s.astype(jnp.float32)
                if scaling is Scaling.ADDITIVE:
                    def to_srow(zrow):                   # zrow (n, s_max)
                        sr = jax.lax.dynamic_slice_in_dim(
                            zrow, s - 1, 1, axis=1)[:, 0]
                        return sr * speeds
                elif scaling is Scaling.SERVER_DEPENDENT:
                    def to_srow(zrow):
                        return (d0 + sf * zrow) * speeds
                else:
                    def to_srow(zrow):
                        return (sf * d0 + zrow) * speeds
                if have_fail:
                    # rebased schedule: chunk clocks start at the last
                    # arrival of the previous chunk
                    cr = crash - base0
                    rec = recover - base0
                    if grouped:
                        base_step = make_grouped_failure_step(
                            cancel_overhead, preempt, cr, rec, retry,
                            have_jitter, rr, groups)
                    else:
                        base_step = make_failure_step(
                            kq, cancel_overhead, preempt, cr, rec, retry,
                            have_jitter, n)
                elif grouped:
                    base_step = make_grouped_step(cancel_overhead, preempt,
                                                  rr, groups)
                else:
                    base_step = make_plain_step(kq, cancel_overhead,
                                                preempt, **bisect_kw)

                def step(carry, inp):
                    F1, busy, wasted, last = carry
                    if have_jitter:
                        vld, a, zrow, urow = inp
                    else:
                        vld, a, zrow = inp
                        urow = None
                    srow = to_srow(zrow)
                    if grouped:
                        binp = (a, srow, gidrow) + \
                            ((urow,) if have_jitter else ())
                    else:
                        binp = (a, srow) + ((urow,) if have_jitter else ())
                    (F2, b2, w2), y = base_step((F1, busy, wasted), binp)
                    if have_fail:
                        lat, okj = y
                        okj = okj & vld
                    else:
                        lat, okj = y, vld
                    # padded tail jobs: freeze the carry, zero the output
                    F3 = jnp.where(vld, F2, F1)
                    b3 = jnp.where(vld, b2, busy)
                    w3 = jnp.where(vld, w2, wasted)
                    last2 = jnp.where(vld, lat, last)
                    return (F3, b3, w3, last2), (jnp.where(vld, lat, 0.0),
                                                 okj)

                zero = jnp.zeros((), jnp.float32)
                xs = (valid, A, z) + ((ujit,) if have_jitter else ())
                (F4, busy_d, wasted_d, last), (lat, okj) = jax.lax.scan(
                    step, (F0, zero, zero, zero), xs)
                return F4, busy_d, wasted_d, last, lat, okj

            run = jax.vmap(one_lane, in_axes=(0, 0, 0, 0, 0, 0, 0))
            F2, busy_d, wasted_d, last, lat, okj = run(
                F, base, rates_l, k_l, s_l, r_l, gid_l)

            a_last = A_unit[-1] / rates_l                  # (b,)
            if stream:
                cnt, mean, m2, res = stats
                include = okj & post[None, :]
                u = jax.vmap(jax.random.uniform)(
                    job_row_keys(k_stat, j0, chunk))
                res, _ = reservoir_update_chunk(res, cnt, lat, include, u)
                cnt, mean, m2 = welford_merge_chunk((cnt, mean, m2), lat,
                                                    include)
                stats2 = (cnt, mean, m2, res)
            else:
                stats2 = stats
            ys = {"busy": busy_d, "wasted": wasted_d, "a_last": a_last,
                  "last": last}
            if have_fail:
                # failure resolutions need not be monotone in j: track the
                # chunk-relative horizon candidate per lane
                Arel = A_unit[None, :] / rates_l[:, None]
                ys["hrel"] = jnp.max(
                    jnp.where(valid[None, :], Arel + lat, -jnp.inf), axis=1)
                ys["nok"] = okj.sum(axis=1).astype(jnp.float32)
            if not stream:
                ys["lat"] = lat
                ys["ok"] = okj
            return (F2 - a_last[:, None], ast2, base + a_last, stats2), ys

        stats0 = (welford_init(b) + (reservoir_init(b, reservoir),)) \
            if stream else ()
        carry0 = (jnp.zeros((b, n), jnp.float32), arrivals.arrival_state0(),
                  jnp.zeros((b,), jnp.float32), stats0)
        (_, _, _, statsf), ys = jax.lax.scan(
            chunk_body, carry0, jnp.arange(num_chunks, dtype=jnp.int32))
        return statsf, ys

    lane_pack = (rates, lane_k, lane_s, lane_r, lane_gid)
    shared = (k_svc, k_arrv, k_jit, k_stat, crash, recover, speeds,
              cancel_overhead, warm, dist, arrivals, delta)
    if ndev == 0:
        return run_lanes(lane_pack, shared)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:ndev]), ("lanes",))
    P = jax.sharding.PartitionSpec
    # lanes are fully independent: lane tensors split on their lane axis
    # (axis 0 of the inputs and the final stats, axis 1 of the per-chunk
    # ys), everything else replicated
    f = jax.shard_map(run_lanes, mesh=mesh, in_specs=(P("lanes"), P()),
                      out_specs=(P("lanes"), P(None, "lanes")),
                      check_vma=False)
    return f(lane_pack, shared)


_fleet_kernel = functools.partial(jax.jit, static_argnames=(
    "scaling", "n", "num_jobs", "chunk", "preempt", "retry", "grouped",
    "groups", "stream", "reservoir", "ndev", "s_max"))(_fleet_core)


# --------------------------------------------------------------------------
# Host driver: replication loop, float64 reconstruction
# --------------------------------------------------------------------------

@dataclasses.dataclass
class FleetRaw:
    """Raw per-lane outputs of a chunked run, host-side, reps stacked.

    The lane axis is reshaped back to (loads, KL); ``summarize_fleet``
    turns a KL slice of it into a ``ClusterSweep`` (the co-optimizer
    slices per assignment).  Exactly one of the exact cube (``lat``/
    ``ok``) and the streaming state (``cnt``/``mean``/``m2``/``res``)
    is populated.
    """

    loads: Tuple[float, ...]
    warmup: int
    reps: int
    num_jobs: int
    n: int
    stream: bool
    have_fail: bool
    busy: np.ndarray                 # (reps, L, KL) float64
    wasted: np.ndarray               # (reps, L, KL) float64
    horizon: np.ndarray              # (reps, L, KL) float64
    a_last: np.ndarray               # (reps, L)     float64
    lat: Optional[np.ndarray]        # (reps, L, KL, num_jobs) float64
    ok: Optional[np.ndarray]         # (reps, L, KL, num_jobs) bool
    cnt: Optional[np.ndarray]        # (reps, L, KL) int
    mean: Optional[np.ndarray]       # (reps, L, KL) float32
    m2: Optional[np.ndarray]         # (reps, L, KL) float32
    res: Optional[np.ndarray]        # (reps, L, KL, R) float32
    nok: Optional[np.ndarray]        # (reps, L, KL) float64 completions


def run_fleet(scenario: Scenario, loads: Sequence[float], lanes: FleetLanes,
              *, num_jobs: int, reps: int, preempt: bool,
              cancel_overhead: float, seed: int, warmup: int, arrivals,
              speeds, failures, retry: Optional[RetryPolicy], chunk: int,
              stream: bool, reservoir: int,
              shard: Optional[int]) -> FleetRaw:
    """Run the chunked kernel over (loads x lanes), one call per
    replication (warm executable reuse — the rep axis multiplies wall
    time, not memory), and reconstruct absolute-clock quantities in
    float64 from the per-chunk ys."""
    n = scenario.n
    if chunk < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk}")
    if reservoir < 1:
        raise ValueError(f"reservoir must be >= 1, got {reservoir}")
    L, KL = len(loads), int(lanes.k.size)
    B = L * KL
    rates = np.repeat(np.asarray(loads, np.float32), KL)
    lk = np.tile(lanes.k.astype(np.int32), L)
    ls = np.tile(lanes.s.astype(np.int32), L)
    lr = np.tile(lanes.r.astype(np.int32), L)
    gid = np.tile(lanes.gid.astype(np.int32), (L, 1))
    ndev = 0 if shard is None else int(shard)
    if ndev:
        avail = len(jax.devices())
        if not (1 <= ndev <= avail):
            raise ValueError(f"shard={ndev} needs 1..{avail} devices "
                             f"(have {avail})")
        pad = (-B) % ndev
        if pad:        # duplicate the last lane; trimmed after the kernel
            rates = np.concatenate([rates, np.repeat(rates[-1], pad)])
            lk = np.concatenate([lk, np.repeat(lk[-1], pad)])
            ls = np.concatenate([ls, np.repeat(ls[-1], pad)])
            lr = np.concatenate([lr, np.repeat(lr[-1], pad)])
            gid = np.concatenate(
                [gid, np.tile(gid[-1:], (pad, 1))], axis=0)
    s_max = int(ls.max())
    have_fail = retry is not None
    delta = None if scenario.delta is None else jnp.float32(scenario.delta)
    select = "bisect" if n >= _BISECT_MIN_N else "sort"

    acc = {k: [] for k in ("busy", "wasted", "horizon", "a_last", "lat",
                           "ok", "cnt", "mean", "m2", "res", "nok")}
    rec = _trace.active()
    for rep, rk in enumerate(
            jax.random.split(jax.random.PRNGKey(seed), int(reps))):
        traces0 = _FLEET_TRACES
        t0 = rec.now() if rec is not None else 0.0
        with _trace.span("surface.dispatch"):
            statsf, ys = _fleet_kernel(
                rk, jnp.asarray(rates), speeds,
                jnp.float32(cancel_overhead), scenario.dist, arrivals, delta,
                failures if have_fail else None, jnp.int32(warmup),
                jnp.asarray(lk), jnp.asarray(ls), jnp.asarray(lr),
                jnp.asarray(gid), scaling=scenario.scaling, n=n,
                num_jobs=int(num_jobs), chunk=int(chunk),
                preempt=bool(preempt), retry=retry, grouped=lanes.grouped,
                groups=lanes.groups,
                stream=bool(stream), reservoir=int(reservoir), ndev=ndev,
                s_max=s_max)
        with _trace.span("surface.summarize"):
            with _trace.span("surface.fetch"):               # (C, B, ...)
                ysn = {k: np.asarray(v)[:, :B] for k, v in ys.items()}
            al_c = ysn["a_last"].astype(np.float64)
            a_abs = np.cumsum(al_c, axis=0)
            a_fin = a_abs[-1]                                   # (B,)
            acc["busy"].append(
                ysn["busy"].astype(np.float64).sum(0).reshape(L, KL))
            acc["wasted"].append(
                ysn["wasted"].astype(np.float64).sum(0).reshape(L, KL))
            acc["a_last"].append(a_fin.reshape(L, KL)[:, 0])
            if have_fail:
                base_before = a_abs - al_c
                horizon = (base_before
                           + ysn["hrel"].astype(np.float64)).max(0)
                acc["nok"].append(
                    ysn["nok"].astype(np.float64).sum(0).reshape(L, KL))
            else:
                horizon = a_fin + ysn["last"][-1].astype(np.float64)
            acc["horizon"].append(horizon.reshape(L, KL))
            if stream:
                with _trace.span("surface.fetch"):
                    cnt, mean, m2, res = (np.asarray(x)[:B]
                                          for x in statsf)
                acc["cnt"].append(cnt.reshape(L, KL))
                acc["mean"].append(mean.reshape(L, KL))
                acc["m2"].append(m2.reshape(L, KL))
                acc["res"].append(res.reshape(L, KL, -1))
            else:
                lat = np.moveaxis(ysn["lat"], 0, 1).reshape(
                    B, -1)[:, :num_jobs]
                okc = np.moveaxis(ysn["ok"], 0, 1).reshape(
                    B, -1)[:, :num_jobs]
                acc["lat"].append(
                    lat.astype(np.float64).reshape(L, KL, num_jobs))
                if have_fail:
                    acc["ok"].append(
                        okc.astype(bool).reshape(L, KL, num_jobs))
        if rec is not None:
            # per-REPLICATION granularity: the chunk loop is a lax.scan
            # inside the jit boundary, so the host (and the recorder)
            # cannot see individual chunks — DESIGN.md §12 documents
            # the boundary.
            rec.event("sweep", name="fleet", dur=rec.now() - t0,
                      rep=rep, reps=int(reps), n=n, lanes=B,
                      num_chunks=-(-int(num_jobs) // int(chunk)),
                      chunk=int(chunk), jobs=int(num_jobs),
                      stream=bool(stream), select=select,
                      compiled=_FLEET_TRACES > traces0)

    def stk(name):
        return np.stack(acc[name]) if acc[name] else None

    return FleetRaw(
        loads=tuple(float(v) for v in loads), warmup=int(warmup),
        reps=int(reps), num_jobs=int(num_jobs), n=n, stream=bool(stream),
        have_fail=have_fail, busy=stk("busy"), wasted=stk("wasted"),
        horizon=stk("horizon"), a_last=stk("a_last"), lat=stk("lat"),
        ok=stk("ok"), cnt=stk("cnt"), mean=stk("mean"), m2=stk("m2"),
        res=stk("res"), nok=stk("nok"))


def summarize_fleet(raw: FleetRaw, ks: Sequence[int],
                    kslice: Optional[slice] = None) -> ClusterSweep:
    """A KL slice of a raw chunked run -> ``ClusterSweep``.

    Exact mode feeds the UNCHANGED ``cluster_batched.summarize_sweep``
    (identical post-processing to the monolithic engine); streaming mode
    finalizes the Welford/reservoir state on the host — quantiles are
    exact whenever every replication's included-sample count fits the
    reservoir, and a uniform-sample estimate beyond that.
    """
    sl = slice(None) if kslice is None else kslice
    loads, ks = raw.loads, tuple(int(k) for k in ks)
    L, K = len(loads), len(ks)
    busy = raw.busy[:, :, sl]
    wasted = raw.wasted[:, :, sl]
    horizon = raw.horizon[:, :, sl]
    if busy.shape[2] != K:
        raise ValueError(f"kslice selects {busy.shape[2]} lanes, ks has {K}")
    if not raw.stream:
        return summarize_sweep(
            raw.lat[:, :, sl], busy, wasted, raw.a_last, loads, ks,
            raw.warmup, raw.reps, raw.num_jobs, raw.n,
            ok=None if raw.ok is None else raw.ok[:, :, sl],
            horizon=horizon)
    with _trace.span("surface.summarize"):
        cnt = raw.cnt[:, :, sl].reshape(raw.reps, -1)
        tot, mean, _ = welford_finalize_host(
            cnt, raw.mean[:, :, sl].reshape(raw.reps, -1),
            raw.m2[:, :, sl].reshape(raw.reps, -1))
        R = raw.res.shape[-1]
        vals = reservoir_values_host(
            raw.res[:, :, sl].reshape(raw.reps, -1, R), cnt)
        qs = np.full((3, L * K), np.inf)
        for i, v in enumerate(vals):
            if v.size:
                qs[:, i] = np.quantile(v, [0.50, 0.95, 0.99])
        mean = np.where(tot > 0, mean, np.inf).reshape(L, K)
        if raw.have_fail:
            completions = raw.nok[:, :, sl]
            fail = (1.0 - cnt.sum(axis=0)
                    / (raw.reps * (raw.num_jobs - raw.warmup))).reshape(L, K)
        else:
            completions = float(raw.num_jobs)
            fail = None
        return ClusterSweep(
            loads=loads, ks=ks, warmup=raw.warmup, reps=raw.reps, mean=mean,
            p50=qs[0].reshape(L, K), p95=qs[1].reshape(L, K),
            p99=qs[2].reshape(L, K),
            utilization=(busy / (raw.n * horizon)).mean(axis=0),
            wasted_frac=(wasted / np.maximum(busy, 1e-12)).mean(axis=0),
            throughput=(completions / horizon).mean(axis=0),
            failure_rate=fail)


def trim_raw_loads(raw: FleetRaw, num_loads: int) -> FleetRaw:
    """Drop bucket-padded load rows (the compiled-surface cache pads the
    load axis; lanes are independent, so trimming after the kernel is
    exact)."""
    def cut(x):
        return None if x is None else x[:, :num_loads]

    return dataclasses.replace(
        raw, loads=raw.loads[:num_loads], busy=cut(raw.busy),
        wasted=cut(raw.wasted), horizon=cut(raw.horizon),
        a_last=cut(raw.a_last), lat=cut(raw.lat), ok=cut(raw.ok),
        cnt=cut(raw.cnt), mean=cut(raw.mean), m2=cut(raw.m2),
        res=cut(raw.res), nok=cut(raw.nok))


# --------------------------------------------------------------------------
# Public entry point
# --------------------------------------------------------------------------

def fleet_sweep(scenario: Scenario, loads: Sequence[float],
                ks: Optional[Sequence[int]] = None, num_jobs: int = 1000,
                reps: int = 1, preempt: bool = True,
                cancel_overhead: float = 0.0, seed: int = 0,
                warmup: Optional[int] = None,
                retry: Optional[RetryPolicy] = None,
                assignment: Optional[Assignment] = None, *,
                chunk_size: Optional[int] = None, stream: bool = False,
                reservoir: int = 4096,
                shard: Optional[int] = None) -> ClusterSweep:
    """``cluster_batched.sweep`` semantics on the chunked engine.

    ``chunk_size`` bounds the in-flight job window (None -> one chunk
    for small traces, 512 at scale); ``stream=True`` replaces the exact
    latency cube with the bounded-memory Welford + reservoir statistics
    (``reservoir`` samples per lane); ``shard`` maps the lane axis over
    that many devices (None/0 = single-device vmap, identical results).
    The chunk size and shard count are performance knobs, not semantics:
    any chunking draws the bit-identical sample path (per-job row keys),
    pinned by ``tests/test_fleet.py``.
    """
    n = scenario.n
    ks, loads, warmup, arrivals, speeds = validate_sweep_args(
        scenario, loads, ks, num_jobs, reps, warmup)
    failures, retry = resolve_failure_args(scenario, retry)
    lanes = build_fleet_lanes(assignment, n, ks, scenario.worker_speeds)
    chunk = default_chunk(num_jobs) if chunk_size is None else int(chunk_size)
    raw = run_fleet(scenario, loads, lanes, num_jobs=int(num_jobs),
                    reps=int(reps), preempt=bool(preempt),
                    cancel_overhead=float(cancel_overhead), seed=int(seed),
                    warmup=warmup, arrivals=arrivals, speeds=speeds,
                    failures=failures, retry=retry, chunk=chunk,
                    stream=bool(stream), reservoir=int(reservoir),
                    shard=shard)
    return summarize_fleet(raw, ks)
