"""Fleet-scale chunked engine: chunk-offset sampling pins, chunk-size
parity, streaming statistics, sharding, and the dispatch wiring.

The load-bearing contract: the chunk size is a PERFORMANCE knob.  Every
random input is drawn from per-global-job-index row keys
(``core.scenario.job_row_keys``), so any chunking of [0, N) consumes the
bit-identical sample path; the only chunking-dependent arithmetic is the
per-chunk clock rebase (a float32 re-association).  On a dyadic-exact
scenario (integer-atom service times, power-of-two arrival gaps) even
the rebase is exact and the parity is BITWISE; continuous families agree
to float32 rounding.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import LoadAwareLatency
from repro.assign import AllWorkers, RandomGroups, ReplicationGroups
from repro.core import BiModal, FailureModel, RetryPolicy, Scaling, ShiftedExp
from repro.core.scenario import (DeterministicArrivals, MMPPArrivals,
                                 PoissonArrivals, Scenario, job_row_keys,
                                 sample_task_matrix)
from repro.runtime.cluster_batched import (_first_ties_cumsum, _kth_sort,
                                           _scan_lane, resolve_failure_args,
                                           sweep, validate_sweep_args)
from repro.runtime.fleet import (_first_ties_bisect, build_fleet_lanes,
                                 co_fleet_lanes, default_chunk,
                                 fleet_compile_count, fleet_sweep,
                                 run_fleet, summarize_fleet)

SERVER = Scaling.SERVER_DEPENDENT
METRICS = ("mean", "p50", "p95", "p99", "utilization", "wasted_frac",
           "throughput")


def _raw(sc, loads, ks, num_jobs, chunk, *, reps=1, seed=3, retry=None,
         assignment=None, stream=False, reservoir=64, shard=None,
         preempt=True):
    ks_r, loads_r, warm, arrivals, speeds = validate_sweep_args(
        sc, loads, ks, num_jobs, reps, None)
    failures, retry_r = resolve_failure_args(sc, retry)
    lanes = build_fleet_lanes(assignment, sc.n, ks_r, sc.worker_speeds)
    return run_fleet(sc, loads_r, lanes, num_jobs=num_jobs, reps=reps,
                     preempt=preempt, cancel_overhead=0.0, seed=seed,
                     warmup=warm, arrivals=arrivals, speeds=speeds,
                     failures=failures, retry=retry_r, chunk=chunk,
                     stream=stream, reservoir=reservoir, shard=shard)


# ==========================================================================
# chunk-offset sampling: any chunking == slicing, bit for bit
# ==========================================================================

class TestChunkOffsetSampling:
    N, JOBS = 8, 60

    def test_service_rows_chunk_equals_slice(self):
        key = jax.random.PRNGKey(7)
        dist = ShiftedExp(1.0, 2.0)
        full = np.asarray(sample_task_matrix(
            dist, SERVER, self.N, 2, self.JOBS, key, start_job=0))
        for splits in ((0, 13, 27, 60), (0, 1, 60), (0, 60)):
            parts = [np.asarray(sample_task_matrix(
                dist, SERVER, self.N, 2, b - a, key, start_job=a))
                for a, b in zip(splits, splits[1:])]
            np.testing.assert_array_equal(np.concatenate(parts), full)

    @pytest.mark.parametrize("proc", [
        PoissonArrivals(rate=1.0),
        DeterministicArrivals(rate=1.0),
        MMPPArrivals(rate=1.0, slow=0.25, burst=4.0, switch=0.2),
    ])
    def test_gaps_chunk_equals_slice(self, proc):
        """gaps of [0, N) in one call == any chunking with the state
        carried — including MMPP's modulating-chain parity."""
        key = jax.random.PRNGKey(9)
        gaps_full, _ = proc.gaps_chunk(key, 0, self.JOBS, rate=0.37)
        gaps_full = np.asarray(gaps_full)
        for splits in ((0, 7, 20, 41, 60), (0, 59, 60)):
            state = proc.arrival_state0()
            parts = []
            for a, b in zip(splits, splits[1:]):
                g, state = proc.gaps_chunk(key, a, b - a, rate=0.37,
                                           state=state)
                parts.append(np.asarray(g))
            np.testing.assert_array_equal(np.concatenate(parts), gaps_full)

    def test_gaps_chunk_independent_of_total_length(self):
        """Row keys depend only on the global index — extending the
        horizon never perturbs earlier draws (bulk threefry draws do)."""
        key = jax.random.PRNGKey(2)
        proc = PoissonArrivals(rate=1.0)
        g30, _ = proc.gaps_chunk(key, 0, 30)
        g60, _ = proc.gaps_chunk(key, 0, 60)
        np.testing.assert_array_equal(np.asarray(g60)[:30], np.asarray(g30))

    def test_schedule_chunk_matches_bulk_columns(self):
        """Chunked failure schedules: the up/down interval draws are
        row-keyed per event column, so chunked instants agree with the
        one-call schedule to float rounding (the cumsum restarts at a
        chunk boundary — bit-identity is over the draws, not the sums)."""
        fm = FailureModel(mttf=50.0, mttr=5.0, max_events=12)
        key = jax.random.PRNGKey(4)
        c_full, r_full, _ = fm.schedule_chunk(key, self.N, 0, 12)
        state = None
        cs, rs = [], []
        for a, b in ((0, 5), (5, 6), (6, 12)):
            c, r, state = fm.schedule_chunk(key, self.N, a, b - a,
                                            state=state)
            cs.append(np.asarray(c))
            rs.append(np.asarray(r))
        np.testing.assert_allclose(np.concatenate(cs, axis=1),
                                   np.asarray(c_full), rtol=1e-6)
        np.testing.assert_allclose(np.concatenate(rs, axis=1),
                                   np.asarray(r_full), rtol=1e-6)


# ==========================================================================
# chunk-size parity: 1 == 7 == 64 == one chunk
# ==========================================================================

class TestChunkParity:
    N = 12

    def _dyadic_scenario(self):
        # every arithmetic step lands on dyadic rationals: BiModal atoms
        # {1, 4}, task sizes {1, 4, 12}, arrival gaps exactly 4.0 -> the
        # per-chunk rebase subtracts exactly representable sums and the
        # parity is bit-for-bit
        return Scenario(BiModal(4.0, 0.25), SERVER, self.N,
                        arrivals=DeterministicArrivals(rate=1.0))

    def test_dyadic_bitwise_across_chunkings(self):
        sc = self._dyadic_scenario()
        raws = {c: _raw(sc, [0.25], [1, 3, 12], 60, c, reps=2)
                for c in (1, 7, 64)}
        for c in (1, 7):
            np.testing.assert_array_equal(raws[c].lat, raws[64].lat)
            np.testing.assert_array_equal(raws[c].busy, raws[64].busy)

    def test_continuous_tolerance_across_chunkings(self):
        sc = Scenario(ShiftedExp(1.0, 2.0), SERVER, self.N)
        sws = {c: fleet_sweep(sc, [0.05, 0.2], ks=[1, 3, 12], num_jobs=60,
                              reps=2, seed=3, chunk_size=c)
               for c in (1, 7, 64)}
        for c in (1, 7):
            for m in METRICS:
                np.testing.assert_allclose(sws[c].metric(m),
                                           sws[64].metric(m), rtol=2e-5,
                                           atol=1e-5, err_msg=f"{c}/{m}")

    def test_grouped_lanes_parity(self):
        sc = self._dyadic_scenario()
        raws = {c: _raw(sc, [0.25], [3, 12], 48, c,
                        assignment=ReplicationGroups())
                for c in (1, 7, 64)}
        for c in (1, 7):
            np.testing.assert_array_equal(raws[c].lat, raws[64].lat)

    @pytest.mark.parametrize("preempt", [True, False])
    def test_failure_lanes_parity(self, preempt):
        """Crash-restart lanes: the rebased schedule re-associates the
        float32 clock, so the parity is tolerance-level, not bitwise."""
        sc = Scenario(ShiftedExp(1.0, 2.0), SERVER, self.N,
                      failures=FailureModel(mttf=80.0, mttr=4.0,
                                            max_events=16))
        retry = RetryPolicy(max_attempts=3, backoff_base=0.5, jitter=0.3)
        sws = {c: fleet_sweep(sc, [0.2], ks=[3, 12], num_jobs=60, reps=2,
                              seed=5, retry=retry, chunk_size=c,
                              preempt=preempt)
               for c in (7, 64)}
        for m in METRICS + ("failure_rate",):
            np.testing.assert_allclose(sws[7].metric(m), sws[64].metric(m),
                                       rtol=1e-4, atol=1e-5, err_msg=m)

    def test_matches_monolithic_in_law(self):
        """Different RNG path (row keys vs bulk draws) -> statistical
        agreement with the untouched monolithic engine."""
        sc = Scenario(ShiftedExp(1.0, 2.0), SERVER, self.N)
        kw = dict(loads=[0.05], ks=[3], num_jobs=4000, reps=2, seed=5)
        mono = sweep(sc, **kw)
        chnk = fleet_sweep(sc, **kw, chunk_size=256, stream=True)
        assert chnk.mean[0, 0] == pytest.approx(mono.mean[0, 0], rel=0.05)
        assert chnk.utilization[0, 0] == pytest.approx(
            mono.utilization[0, 0], rel=0.05)


# ==========================================================================
# the n >= _BISECT_MIN_N selections: bit-equal to sort + prefix sum
# ==========================================================================

class TestBisectionPath:
    @pytest.mark.parametrize("n", [1024, 1025, 10_000])
    def test_first_ties_bisect_equals_cumsum(self, n):
        """The index-bisection tie rank picks exactly the ties the
        prefix-sum rule picks, on integer rows where ties are heavy."""
        lanes = 4
        rng = np.random.default_rng(n)
        nat = rng.integers(0, 4, (lanes, n)).astype(np.float32)
        ks = np.stack([np.ones(lanes, np.int64), np.full(lanes, n),
                       rng.integers(1, n + 1, lanes),
                       rng.integers(1, n + 1, lanes)], axis=1)

        def masks(row, k):
            D = _kth_sort(row, k)
            lt, eq = row < D, row == D
            take = k - lt.sum()
            return (lt | _first_ties_bisect(eq, take),
                    lt | _first_ties_cumsum(eq, take))

        run = jax.jit(jax.vmap(jax.vmap(masks, in_axes=(None, 0)),
                               in_axes=(0, 0)))
        got, want = run(jnp.asarray(nat), jnp.asarray(ks, jnp.int32))
        got, want = np.asarray(got), np.asarray(want)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got.sum(-1), ks)

    def test_fleet_matches_monolithic_at_n1024(self):
        """n = 1024 is the first width on the bisection path: the fleet
        engine's lanes equal the monolithic lane (sort + prefix sum) run
        on the same draws, bit for bit, under two chunkings.  Dyadic
        service (BiModal atoms {1, 4} times s) and arrivals (gap 4) keep
        every sum exact, so any difference is a different mask."""
        n, ks, jobs, load = 1024, [1, 256, 1024], 40, 0.25
        sc = Scenario(BiModal(4.0, 0.25), SERVER, n,
                      arrivals=DeterministicArrivals(rate=1.0))
        raws = [_raw(sc, [load], ks, jobs, c) for c in (jobs, 16)]
        rk = jax.random.split(jax.random.PRNGKey(3), 1)[0]
        k_svc, _ = jax.random.split(rk)
        z = jax.vmap(lambda kk: sc.dist.sample_noise(kk, (n,)))(
            job_row_keys(k_svc, 0, jobs))
        A = jnp.arange(1, jobs + 1, dtype=jnp.float32) / load
        for i, k in enumerate(ks):
            S = sc.dist.shift + (n // k) * z
            lat, busy, wasted = _scan_lane(A, S, jnp.int32(k), 0.0, True)
            for raw in raws:
                np.testing.assert_array_equal(raw.lat[0, 0, i],
                                              np.asarray(lat), err_msg=k)
                assert raw.busy[0, 0, i] == float(busy), k
                assert raw.wasted[0, 0, i] == float(wasted), k


# ==========================================================================
# streaming statistics vs the exact cube
# ==========================================================================

class TestStreamingStats:
    N = 12

    def test_stream_equals_exact_when_reservoir_holds_all(self):
        """Same kernel, same draws; with capacity >= included samples
        the reservoir holds the full multiset, so the quantiles are
        EXACTLY the exact path's and the Welford mean matches to float
        rounding — the bench's p99 gate in code form."""
        sc = Scenario(ShiftedExp(1.0, 2.0), SERVER, self.N)
        kw = dict(loads=[0.05, 0.2], ks=[1, 3, 12], num_jobs=300, reps=2,
                  seed=3, chunk_size=64)
        ex = fleet_sweep(sc, **kw)
        st = fleet_sweep(sc, **kw, stream=True, reservoir=4096)
        for m in ("p50", "p95", "p99"):
            np.testing.assert_array_equal(st.metric(m), ex.metric(m),
                                          err_msg=m)
        np.testing.assert_allclose(st.mean, ex.mean, rtol=1e-5)
        for m in ("utilization", "wasted_frac", "throughput"):
            np.testing.assert_array_equal(st.metric(m), ex.metric(m),
                                          err_msg=m)

    def test_stream_pools_reps_before_quantile(self):
        """REGRESSION: multi-rep streaming quantiles must be the
        quantile of the POOLED per-rep multiset (the exact path's rule),
        not the average of per-rep quantiles — the two genuinely differ
        on this surface, so this test discriminates the failure mode."""
        from repro.runtime.streamstats import reservoir_values_host
        sc = Scenario(ShiftedExp(1.0, 2.0), SERVER, self.N)
        raw = _raw(sc, loads=[0.2], ks=[3, 12], num_jobs=200, chunk=64,
                   reps=3, seed=11, stream=True, reservoir=4096,
                   preempt=False)
        st = summarize_fleet(raw, ks=[3, 12])
        R = raw.res.shape[-1]
        flat = raw.res.reshape(raw.reps, -1, R)
        cnt = raw.cnt.reshape(raw.reps, -1)
        pooled = reservoir_values_host(flat, cnt)
        per_rep = [reservoir_values_host(flat[r:r + 1], cnt[r:r + 1])
                   for r in range(raw.reps)]
        for lane in range(len(pooled)):
            want = np.quantile(pooled[lane], 0.99)
            avg_of_reps = np.mean([np.quantile(per_rep[r][lane], 0.99)
                                   for r in range(raw.reps)])
            assert want != avg_of_reps          # the rules disagree here
            assert st.p99.ravel()[lane] == want
        # and the whole stream surface equals the exact path's
        kw = dict(loads=[0.2], ks=[3, 12], num_jobs=200, reps=3, seed=11,
                  chunk_size=64, preempt=False)
        ex = fleet_sweep(sc, **kw)
        np.testing.assert_array_equal(st.p99, ex.p99)
        np.testing.assert_array_equal(st.p50, ex.p50)

    def test_stream_failure_lanes(self):
        sc = Scenario(ShiftedExp(1.0, 2.0), SERVER, self.N,
                      failures=FailureModel(mttf=60.0, mttr=5.0,
                                            max_events=16))
        kw = dict(loads=[0.2], ks=[3, 12], num_jobs=200, reps=2, seed=7,
                  retry=RetryPolicy(max_attempts=2), chunk_size=32)
        ex = fleet_sweep(sc, **kw)
        st = fleet_sweep(sc, **kw, stream=True, reservoir=4096)
        np.testing.assert_array_equal(st.failure_rate, ex.failure_rate)
        np.testing.assert_array_equal(st.p99, ex.p99)
        np.testing.assert_allclose(st.mean, ex.mean, rtol=1e-5)

    def test_small_reservoir_is_an_estimate(self):
        """Capacity << samples: Algorithm R degrades to a uniform
        subsample — quantiles stay in a sane band of the exact values."""
        sc = Scenario(ShiftedExp(1.0, 2.0), SERVER, self.N)
        kw = dict(loads=[0.1], ks=[3], num_jobs=2000, reps=1, seed=3,
                  chunk_size=128)
        ex = fleet_sweep(sc, **kw)
        st = fleet_sweep(sc, **kw, stream=True, reservoir=256)
        assert st.p50[0, 0] == pytest.approx(ex.p50[0, 0], rel=0.15)
        assert st.p95[0, 0] == pytest.approx(ex.p95[0, 0], rel=0.25)
        # mean/count are Welford state, not sketched: still near-exact
        np.testing.assert_allclose(st.mean, ex.mean, rtol=1e-5)


# ==========================================================================
# sharded lanes
# ==========================================================================

class TestShardedLanes:
    def test_shard_one_device_identical(self):
        """shard_map over a 1-device mesh must be bit-identical to the
        plain vmap path — the semantic pin for multi-device meshes."""
        sc = Scenario(ShiftedExp(1.0, 2.0), SERVER, 12)
        kw = dict(loads=[0.05, 0.2], ks=[1, 3, 12], num_jobs=50, reps=1,
                  seed=3, chunk_size=16)
        un = fleet_sweep(sc, **kw)
        sh = fleet_sweep(sc, **kw, shard=1)
        for m in METRICS:
            np.testing.assert_array_equal(sh.metric(m), un.metric(m),
                                          err_msg=m)

    def test_shard_validation(self):
        sc = Scenario(ShiftedExp(1.0, 2.0), SERVER, 12)
        with pytest.raises(ValueError, match="shard"):
            fleet_sweep(sc, [0.1], ks=[3], num_jobs=20, chunk_size=8,
                        shard=10 ** 6)


# ==========================================================================
# wiring: dispatch, cache, co-optimizer, validation
# ==========================================================================

class TestFleetWiring:
    def _sc(self):
        return Scenario(ShiftedExp(1.0, 2.0), SERVER, 12)

    def test_sweep_dispatches_on_chunk_knobs(self):
        kw = dict(loads=[0.1], ks=[3], num_jobs=40, reps=1, seed=1)
        a = sweep(self._sc(), **kw, chunk_size=16)
        b = fleet_sweep(self._sc(), **kw, chunk_size=16)
        np.testing.assert_array_equal(a.mean, b.mean)

    def test_cached_chunked_equals_uncached_and_stays_warm(self):
        from repro.runtime.surface_cache import (cached_sweep,
                                                 surface_cache_stats)
        sc = self._sc()
        kw = dict(ks=[1, 3], num_jobs=40, reps=1, seed=1, chunk_size=16)
        c1 = cached_sweep(sc, [0.1], **kw)
        u1 = fleet_sweep(sc, [0.1], **kw)
        np.testing.assert_array_equal(c1.mean, u1.mean)
        misses0 = surface_cache_stats()["misses"]
        cached_sweep(sc, [0.11], **kw)      # same bucket, fresh rate
        assert surface_cache_stats()["misses"] == misses0

    def test_co_sweep_chunked_matches_per_assignment(self):
        from repro.assign.surface import co_sweep
        sc = self._sc()
        assigns = [AllWorkers(), ReplicationGroups()]
        surf = co_sweep(sc, [0.05, 0.2], assigns, ks=[3, 12], num_jobs=40,
                        reps=1, seed=2, chunk_size=16)
        for a in assigns:
            ref = fleet_sweep(sc, [0.05, 0.2], ks=[3, 12], num_jobs=40,
                              reps=1, seed=2, chunk_size=16, assignment=a)
            np.testing.assert_allclose(surf.sweep_for(a).mean, ref.mean,
                                       rtol=1e-6)

    def test_random_groups_rejected(self):
        with pytest.raises(ValueError, match="per job"):
            fleet_sweep(self._sc(), [0.1], ks=[3], num_jobs=20,
                        chunk_size=8, assignment=RandomGroups())

    def test_bad_knobs_rejected(self):
        sc = self._sc()
        with pytest.raises(ValueError, match="chunk_size"):
            fleet_sweep(sc, [0.1], ks=[3], num_jobs=20, chunk_size=0)
        with pytest.raises(ValueError, match="reservoir"):
            fleet_sweep(sc, [0.1], ks=[3], num_jobs=20, chunk_size=8,
                        stream=True, reservoir=0)
        with pytest.raises(ValueError, match="backend"):
            LoadAwareLatency(backend="oracle", stream=True)

    def test_default_chunk(self):
        assert default_chunk(100) == 100
        assert default_chunk(512) == 512
        # balanced, not ragged: 600 -> 2 x 300, never 512 + 88-pad-to-512
        assert default_chunk(600) == 300
        assert default_chunk(10 ** 6) == 512
        for j in (513, 600, 999, 12345):
            c = default_chunk(j)
            assert c <= 512 and c * (-(-j // c)) - j < -(-j // 512)

    def test_one_compile_per_config(self):
        sc = self._sc()
        kw = dict(ks=[1, 3], num_jobs=40, reps=2, seed=1, chunk_size=16)
        fleet_sweep(sc, [0.1, 0.2], **kw)
        before = fleet_compile_count()
        # fresh rates + fresh seed on the same shapes: zero new traces
        # (reps ride a host loop over one warm executable)
        fleet_sweep(sc, [0.11, 0.19], **{**kw, "seed": 9})
        assert fleet_compile_count() == before

    def test_co_lanes_signature_covers_all_assignments(self):
        lanes = co_fleet_lanes([AllWorkers(), ReplicationGroups()], 12,
                               [3, 12])
        assert lanes.grouped and lanes.k.size == 4
        assert len(lanes.signature) == 2

    def test_summarize_fleet_slice_guard(self):
        raw = _raw(self._sc(), [0.1], [1, 3], 30, 8)
        with pytest.raises(ValueError, match="kslice"):
            summarize_fleet(raw, [1, 3], kslice=slice(0, 1))
