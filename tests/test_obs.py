"""Observability plane (DESIGN.md §12): the flight recorder, the
metrics registry, the streaming SLO monitor, run reports, and the
instrumented hot paths — including the disabled-recorder overhead gate,
the trace-vs-decision-log bit-for-bit contract, and the spans the
profiler sink puts in a JAX profiler trace."""
import glob
import io
import time
import tracemalloc

import numpy as np
import pytest

from repro.api import LoadAwareLatency, Scenario
from repro.control import RedundancyController, replay
from repro.control import controller as controller_mod
from repro.core import (BiModal, Pareto, Regime, Scaling, ShiftedExp,
                        sample_regime_trace)
from repro.core.scenario import PoissonArrivals
from repro.obs import (EVENT_KINDS, NULL_SPAN, REGISTRY, SPAN_NAMES, Event,
                       MetricsRegistry, Recorder, SLOMonitor, StreamHist,
                       active, parse_jsonl, profile_spans, recording)
from repro.obs import recorder as recorder_mod
from repro.obs.report import (decision_log, decision_log_from_control_events,
                              render_report)

pytestmark = pytest.mark.obs

N = 12
SERVER = Scaling.SERVER_DEPENDENT
PRIOR = Scenario(BiModal(10.0, 0.3), SERVER, N)


# ==========================================================================
# Recorder: schema round-trip, ring bound, disabled path
# ==========================================================================

class TestRecorder:
    def test_jsonl_round_trip_is_exact(self, tmp_path):
        rec = Recorder()
        rec.event("drift_alarm", name="service", channel="service",
                  alarm_kind="cusum_up", at=128, start=100, stat=7.25,
                  threshold=6.0)
        rec.event("commit", name="drift", at=224, old_k=6, new_k=12,
                  switched=True, assignment=None,
                  quarantined=(1, 3), replan_ms=0.42)
        with rec.span("replan", k=8, family="pareto"):
            pass
        rec.event("mark", name="regime", regime=0, rate=0.002)
        path = str(tmp_path / "trace.jsonl")
        assert rec.export_jsonl(path) == 4
        assert parse_jsonl(path) == rec.events()

    def test_round_trip_through_file_object(self):
        rec = Recorder()
        rec.event("cache_hit", name="surface_cache", key="('a', 1)")
        buf = io.StringIO()
        rec.export_jsonl(buf)
        buf.seek(0)
        assert parse_jsonl(buf) == rec.events()

    def test_unknown_kind_rejected_on_both_ends(self):
        rec = Recorder()
        with pytest.raises(ValueError, match="unknown event kind"):
            rec.event("telemetry")
        with pytest.raises(ValueError, match="unknown event kind"):
            Event.from_json('{"ts": 0.0, "kind": "nope", "fields": {}}')

    def test_ring_is_bounded_and_counts_drops(self):
        rec = Recorder(capacity=8)
        for i in range(20):
            rec.event("mark", name="m", i=i)
        assert len(rec) == 8
        assert rec.dropped == 12
        assert [e.field_dict()["i"] for e in rec.events()] == \
            list(range(12, 20))

    def test_clock_is_monotonic_from_install_epoch(self):
        rec = Recorder()
        rec.event("mark")
        rec.event("mark")
        ts = [e.ts for e in rec.events()]
        assert 0.0 <= ts[0] <= ts[1]

    def test_events_filter_by_kind(self):
        rec = Recorder()
        rec.event("mark", name="a")
        rec.event("commit", name="boot", at=0, old_k=1, new_k=2)
        assert [e.name for e in rec.events("mark")] == ["a"]

    def test_recording_context_installs_and_restores(self):
        assert active() is None
        with recording() as outer:
            assert active() is outer
            with recording() as inner:
                assert active() is inner
            assert active() is outer
        assert active() is None

    def test_disabled_span_is_the_shared_singleton(self):
        assert active() is None
        assert recorder_mod.span("replan", k=8) is NULL_SPAN
        assert recorder_mod.span("other") is NULL_SPAN

    def test_disabled_module_event_is_noop(self):
        assert active() is None
        recorder_mod.event("mark", name="ignored")   # must not raise

    def test_ring_spans_carry_their_parents_name(self):
        with recording() as rec:
            with recorder_mod.span("ctl.observe"):
                with recorder_mod.span("replan", kind="load"):
                    with recorder_mod.span("surface.fetch"):
                        pass
                with recorder_mod.span("ctl.actuate"):
                    pass
            with recorder_mod.span("train.batch"):
                pass
        got = [(e.name, e.field_dict()["parent"]) for e in rec.events()]
        assert got == [("surface.fetch", "replan"),
                       ("replan", "ctl.observe"),
                       ("ctl.actuate", "ctl.observe"),
                       ("ctl.observe", None), ("train.batch", None)]
        assert rec.events()[1].field_dict()["kind"] == "load"
        assert parse_jsonl(io.StringIO(
            "\n".join(e.to_json() for e in rec.events()))) == rec.events()

    def test_unknown_span_name_rejected_while_anything_listens(self):
        with recording() as rec:
            with pytest.raises(ValueError, match="unknown span name"):
                recorder_mod.span("nope")
            with pytest.raises(ValueError, match="unknown span name"):
                rec.span("replan ")
        profile_spans(True)
        try:
            with pytest.raises(ValueError, match="unknown span name"):
                recorder_mod.span("surface")
        finally:
            profile_spans(False)
        with pytest.raises(ValueError, match="unknown span name"):
            Event.from_json('{"ts": 0.0, "kind": "span", "name": "nope", '
                            '"dur": 1.0, "fields": {}}')
        assert recorder_mod.span("nope") is NULL_SPAN     # nothing listens

    def test_profiler_sink_alone_leaves_the_recorder_off(self):
        assert profile_spans(True) is False
        try:
            assert active() is None
            sp = recorder_mod.span("replan", kind="load")
            assert sp is not NULL_SPAN
            with sp:
                recorder_mod.event("mark", name="ignored")
        finally:
            assert profile_spans(False) is True
        assert recorder_mod.span("replan") is NULL_SPAN
        assert set(SPAN_NAMES) >= {"ctl.observe", "replan",
                                   "surface.fetch", "train.dispatch"}

    def test_numpy_fields_canonicalize_to_python_scalars(self):
        rec = Recorder()
        rec.event("mark", a=np.int64(3), b=np.float64(0.5), c=[1, 2])
        f = rec.events()[0].field_dict()
        assert f == {"a": 3, "b": 0.5, "c": (1, 2)}
        assert type(f["a"]) is int and type(f["b"]) is float


# ==========================================================================
# Disabled-recorder overhead: the <2% gate + zero per-event allocations
# ==========================================================================

class TestDisabledOverhead:
    def test_observe_loop_overhead_under_two_percent(self):
        """The disabled path costs one ``active()`` read per
        instrumented site, and a ``with`` on the shared no-op span per
        span site.  Bound: the sites one observe can hit, each at its
        own cost, must be under 2% of one ``observe()`` call's wall
        time."""
        assert active() is None
        ctl = RedundancyController(PRIOR)
        x = np.full(N, 11.0)
        for _ in range(32):                      # steady state, warm caches
            ctl.observe(x)
        reps = 300
        t0 = time.perf_counter()
        for _ in range(reps):
            ctl.observe(x)
        observe_s = (time.perf_counter() - t0) / reps
        guards = 10_000
        t0 = time.perf_counter()
        for _ in range(guards):
            active()
        guard_s = (time.perf_counter() - t0) / guards
        t0 = time.perf_counter()
        for _ in range(guards):
            with recorder_mod.span("ctl.observe"):
                pass
        span_s = (time.perf_counter() - t0) / guards
        # generous ceiling on instrumented sites one observe can hit;
        # span sites: ctl.observe, and ctl.fit, replan, ctl.actuate on
        # a commit
        sites_per_observe, spans_per_observe = 16, 4
        cost = sites_per_observe * guard_s + spans_per_observe * span_s
        assert cost < 0.02 * observe_s, (
            f"guard {guard_s * 1e9:.1f} ns x {sites_per_observe} sites + "
            f"span {span_s * 1e9:.1f} ns x {spans_per_observe} vs "
            f"observe {observe_s * 1e6:.1f} us")

    def test_disabled_path_allocates_no_event_objects(self):
        assert active() is None
        ctl = RedundancyController(PRIOR)
        x = np.full(N, 11.0)
        for _ in range(8):
            ctl.observe(x)
        tracemalloc.start()
        try:
            for _ in range(50):
                ctl.observe(x)
            snap = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        obs_bytes = sum(
            st.size for st in snap.statistics("filename")
            if "repro/obs" in st.traceback[0].filename.replace("\\", "/"))
        assert obs_bytes == 0, f"{obs_bytes} bytes allocated in repro.obs"


# ==========================================================================
# Metrics: counters, gauges, streaming histograms, the registry
# ==========================================================================

class TestMetrics:
    def test_counter_and_gauge(self):
        reg = MetricsRegistry()
        c = reg.counter("c")
        c.inc()
        c.inc(3)
        assert c.value == 4
        g = reg.gauge("g")
        g.set(2.5)
        assert g.value == 2.5
        c.reset()
        assert c.value == 0

    def test_registry_returns_same_instrument_and_rejects_collisions(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_streamhist_exact_below_capacity(self):
        rng = np.random.default_rng(0)
        x = rng.lognormal(0.0, 1.0, size=1000)
        h = StreamHist(capacity=4096)
        for v in x:
            h.update(v)
        assert h.count == 1000
        np.testing.assert_allclose(h.mean, x.mean(), rtol=1e-12)
        np.testing.assert_allclose(h.var, x.var(), rtol=1e-9)
        for q in (0.5, 0.95, 0.99):
            np.testing.assert_allclose(h.quantile(q), np.quantile(x, q),
                                       rtol=1e-12)

    def test_streamhist_reservoir_is_deterministic_and_close(self):
        rng = np.random.default_rng(1)
        x = rng.exponential(1.0, size=20_000)
        h1, h2 = StreamHist(capacity=2048, seed=7), \
            StreamHist(capacity=2048, seed=7)
        for v in x:
            h1.update(v)
            h2.update(v)
        np.testing.assert_array_equal(h1.values(), h2.values())
        assert abs(h1.quantile(0.99) - np.quantile(x, 0.99)) \
            / np.quantile(x, 0.99) < 0.15
        assert h1.count == 20_000 and len(h1.values()) == 2048

    def test_snapshot_shape(self):
        reg = MetricsRegistry()
        reg.counter("a").inc()
        reg.gauge("b").set(1.0)
        h = reg.hist("h")
        h.update(3.0)
        snap = reg.snapshot()
        assert snap["a"] == 1 and snap["b"] == 1.0
        assert snap["h"]["count"] == 1 and snap["h"]["p99"] == 3.0


# ==========================================================================
# Surface cache: registry-backed stats + hit/miss/compile events
# ==========================================================================

class TestSurfaceCacheObservability:
    def test_stats_are_registry_backed_and_events_flow(self):
        from repro.runtime.surface_cache import (cached_sweep,
                                                 surface_cache_stats)
        sc = Scenario(ShiftedExp(1.0, 10.0), SERVER, 6)
        kw = dict(loads=[0.001], ks=[1, 2], num_jobs=40, reps=1, seed=0,
                  preempt=False)
        before = surface_cache_stats()
        with recording() as rec:
            cached_sweep(sc, **kw)      # miss or hit depending on order
            cached_sweep(sc, **kw)      # structurally identical: hit
        after = surface_cache_stats()
        assert after["hits"] >= before["hits"] + 1
        assert after["hits"] + after["misses"] >= \
            before["hits"] + before["misses"] + 2
        assert REGISTRY.counter("surface_cache.hits").value == after["hits"]
        hits = rec.events("cache_hit")
        assert hits and hits[-1].field_dict()["family"]
        # a compile event fires iff the first call missed
        if rec.events("cache_miss"):
            assert rec.events("compile")
            assert rec.events("compile")[0].field_dict()["wall_ms"] > 0


# ==========================================================================
# Satellite (a): fallback counter + monotonic-time rate-limited warning
# ==========================================================================

class TestFallbackRateLimit:
    def test_counter_increments_even_while_log_suppressed(self, monkeypatch,
                                                          caplog):
        fake = [1000.0]
        monkeypatch.setattr(controller_mod.time, "monotonic",
                            lambda: fake[0])
        monkeypatch.setattr(controller_mod, "_fallback_last_log", None)
        c = REGISTRY.counter("controller.surface_fallbacks")
        start = c.value
        exc = RuntimeError("boom")
        with recording() as rec, caplog.at_level("WARNING"):
            controller_mod._warn_surface_fallback(exc)     # logs
            fake[0] += 1.0
            controller_mod._warn_surface_fallback(exc)     # suppressed
            fake[0] += 1.0
            controller_mod._warn_surface_fallback(exc)     # suppressed
            fake[0] += controller_mod._FALLBACK_LOG_SECONDS
            controller_mod._warn_surface_fallback(exc)     # logs again
        warnings = [r for r in caplog.records
                    if "falling back" in r.getMessage()]
        assert len(warnings) == 2                # rate limit held
        assert c.value - start == 4              # every fallback counted
        assert len(rec.events("oracle_fallback")) == 4   # ...and traced
        assert rec.events("oracle_fallback")[0].name == "RuntimeError"


# ==========================================================================
# SLO monitor: exact quantile, burn alarm timing, latch/re-arm
# ==========================================================================

class TestSLOMonitor:
    def test_streaming_p99_exact_below_capacity(self):
        rng = np.random.default_rng(2)
        x = rng.lognormal(0.0, 0.8, size=2000)
        m = SLOMonitor(target=10.0, capacity=4096)
        for v in x:
            m.observe(v)
        np.testing.assert_allclose(m.quantile_estimate(),
                                   np.quantile(x, 0.99), rtol=1e-12)

    def test_no_alarm_while_healthy(self):
        m = SLOMonitor(target=1.0, min_count=8, fast_window=8,
                       slow_window=16)
        assert all(m.observe(0.5) is None for _ in range(200))
        assert m.alarms == 0

    def test_burn_alarm_fires_and_latches(self):
        m = SLOMonitor(target=1.0, quantile=0.9, min_count=8,
                       fast_window=8, slow_window=16, burn_threshold=4.0)
        alarms = [m.observe(5.0) for _ in range(40)]
        fired = [a for a in alarms if a is not None]
        assert len(fired) == 1                    # latched: one page
        a = fired[0]
        assert a.at >= m.min_count - 1
        assert a.burn_fast >= 4.0 and a.burn_slow >= 4.0
        assert a.target == 1.0

    def test_rearms_after_slow_window_recovers(self):
        m = SLOMonitor(target=1.0, quantile=0.9, min_count=8,
                       fast_window=8, slow_window=16, burn_threshold=4.0)
        for _ in range(30):
            m.observe(5.0)                        # breach #1
        for _ in range(40):
            m.observe(0.2)                        # recovery: burn -> 0
        assert not m._latched
        fired = [m.observe(5.0) for _ in range(30)]
        assert sum(a is not None for a in fired) == 1     # breach #2 pages
        assert m.alarms == 2

    def test_single_straggler_cannot_page(self):
        m = SLOMonitor(target=1.0, min_count=8, fast_window=8,
                       slow_window=64)
        for _ in range(64):
            m.observe(0.5)
        assert m.observe(100.0) is None           # slow window gates it
        assert m.alarms == 0

    def test_validation(self):
        with pytest.raises(ValueError):
            SLOMonitor(target=0.0)
        with pytest.raises(ValueError):
            SLOMonitor(target=1.0, quantile=1.0)
        with pytest.raises(ValueError):
            SLOMonitor(target=1.0, fast_window=32, slow_window=8)


# ==========================================================================
# Controller integration: traces reconstruct the decision log
# ==========================================================================

REGIMES = [Regime(ShiftedExp(1.0, 10.0), 400),
           Regime(BiModal(1e4, 5e-4), 400),
           Regime(Pareto(1.0, 2.5), 400)]


class TestControllerTracing:
    @pytest.fixture(scope="class")
    def traced(self):
        trace = sample_regime_trace(REGIMES, SERVER, N, seed=0)
        with recording() as rec:
            res = replay(trace, RedundancyController(PRIOR))
        return trace, rec, res

    def test_decision_log_bit_for_bit(self, traced):
        _, rec, res = traced
        assert decision_log(rec.events()) == \
            decision_log_from_control_events(res.events)
        assert len(rec.events("commit")) == len(res.events) >= 2

    def test_decision_log_survives_jsonl_round_trip(self, traced, tmp_path):
        _, rec, res = traced
        path = str(tmp_path / "t.jsonl")
        rec.export_jsonl(path)
        assert decision_log(parse_jsonl(path)) == \
            decision_log_from_control_events(res.events)

    def test_drift_alarms_recorded_with_logical_index(self, traced):
        _, rec, res = traced
        alarms = rec.events("drift_alarm")
        assert alarms, "regime changes must raise recorded alarms"
        for e in alarms:
            f = e.field_dict()
            assert f["channel"] in ("service", "load", "failure")
            assert isinstance(f["at"], int) and f["at"] >= 0

    def test_tracing_does_not_perturb_decisions(self, traced):
        trace, _, res = traced
        plain = replay(trace, RedundancyController(PRIOR))
        np.testing.assert_array_equal(res.policy_k, plain.policy_k)

    def test_render_report_covers_the_run(self, traced):
        _, rec, res = traced
        text = render_report(rec.events())
        assert "committed decisions" in text
        assert "decision log" in text
        for e in res.events:
            assert f"at={e.at}" in text.replace(" ", "") or \
                str(e.at) in text

    def test_actuate_events_fire_per_actuator(self):
        applied = []

        class Spy:
            def apply(self, policy, model):
                applied.append(policy.k)

        trace = sample_regime_trace([Regime(ShiftedExp(1.0, 10.0), 150)],
                                    SERVER, N, seed=1)
        with recording() as rec:
            replay(trace, RedundancyController(PRIOR, actuators=[Spy()]))
        acts = rec.events("actuate")
        assert len(acts) == len(applied) >= 1
        assert acts[0].name == "Spy" and acts[0].dur is not None


class TestSLODriftChannel:
    def test_burn_alarm_becomes_a_drift_commit(self):
        """An SLO burn parks a pending drift the normal refit path
        commits: trigger ``slo_burn`` in both the live event and the
        trace."""
        slo = SLOMonitor(target=1.0, quantile=0.9, min_count=8,
                         fast_window=8, slow_window=16)
        ctl = RedundancyController(PRIOR, slo=slo)
        x = np.full(N, 11.0)
        with recording() as rec:
            for _ in range(60):                  # boot on healthy latency
                ctl.observe(x, latency=0.5)
            events = [ctl.observe(x, latency=50.0) for _ in range(40)]
        commits = [e for e in events if e is not None]
        assert slo.alarms >= 1
        assert rec.events("slo_alarm")
        assert any(e.kind == "drift" and e.drift.kind == "slo_burn"
                   for e in commits)
        log = decision_log(rec.events())
        assert any(row[5] == "slo_burn" for row in log)

    def test_slo_drift_false_observes_without_steering(self):
        slo = SLOMonitor(target=1.0, quantile=0.9, min_count=8,
                         fast_window=8, slow_window=16)
        ctl = RedundancyController(PRIOR, slo=slo, slo_drift=False)
        x = np.full(N, 11.0)
        for _ in range(60):
            ctl.observe(x, latency=0.5)
        events = [ctl.observe(x, latency=50.0) for _ in range(40)]
        assert slo.alarms >= 1                   # the monitor saw it
        assert not any(e is not None and e.kind == "drift"
                       for e in events)          # the policy did not move


# ==========================================================================
# Telemetry latency feed
# ==========================================================================

class TestTelemetryLatencyFeed:
    def test_record_latency_feeds_slo_and_traces_alarms(self):
        from repro.runtime.telemetry import Telemetry
        t = Telemetry(slo=SLOMonitor(target=1.0, quantile=0.9, min_count=8,
                                     fast_window=8, slow_window=16))
        with recording() as rec:
            alarms = [t.record_latency(5.0) for _ in range(40)]
        assert sum(a is not None for a in alarms) == 1
        assert len(rec.events("slo_alarm")) == 1
        assert t.num_latencies == 40
        with pytest.raises(ValueError):
            t.record_latency(float("nan"))

    def test_record_latency_without_monitor_is_plain_storage(self):
        from repro.runtime.telemetry import Telemetry
        t = Telemetry()
        assert t.record_latency(2.0) is None
        np.testing.assert_array_equal(t.latencies(), [2.0])


# ==========================================================================
# Engine sweeps land on the recorder
# ==========================================================================

class TestEngineSweepEvents:
    def test_batched_sweep_event(self):
        from repro.runtime.cluster_batched import sweep
        sc = Scenario(ShiftedExp(1.0, 10.0), SERVER, 6)
        with recording() as rec:
            sweep(sc, loads=[0.001], ks=[1, 2], num_jobs=40, reps=1,
                  preempt=False, seed=0)
        evs = rec.events("sweep")
        assert len(evs) == 1 and evs[0].name == "batched"
        f = evs[0].field_dict()
        assert f["lanes"] == 2 and f["n"] == 6
        assert evs[0].dur is not None and evs[0].dur >= 0.0

    def test_fleet_sweep_per_rep_events(self):
        from repro.runtime.fleet import fleet_sweep
        sc = Scenario(ShiftedExp(1.0, 10.0), SERVER, 6)
        with recording() as rec:
            fleet_sweep(sc, loads=[0.001], ks=[1, 2], num_jobs=60, reps=2,
                        preempt=False, seed=0, chunk_size=20)
        evs = rec.events("sweep")
        assert [e.name for e in evs] == ["fleet", "fleet"]
        f = evs[0].field_dict()
        assert f["rep"] == 0 and f["num_chunks"] == 3
        assert f["select"] == "sort"            # n=6 < _BISECT_MIN_N


# ==========================================================================
# The profiler sink: program spans in a JAX profiler trace
# ==========================================================================

SPAN_SC = Scenario(ShiftedExp(1.0, 10.0), SERVER, N)
SPAN_SWEEP = dict(loads=[0.01], ks=[1, 2], num_jobs=40, reps=1, seed=0,
                  preempt=False)


def _span_controller():
    """A load-aware controller on the cached chunked engine (two reps,
    so two launches per re-plan) that commits within a dozen samples."""
    from repro.control.controller import ControllerConfig
    return RedundancyController(
        SPAN_SC, objective=LoadAwareLatency(
            num_jobs=60, reps=2, backend="cached", chunk_size=32,
            preempt=False),
        config=ControllerConfig(boot_samples=24, arrival_min_gaps=4,
                                arrival_refit_gaps=4))


def _span_loop(ctl, steps=12):
    rng = np.random.default_rng(0)
    t, events = 0.0, []
    for _ in range(steps):
        t += rng.exponential(50.0)
        events.append(ctl.observe(1.0 + rng.exponential(10.0, N),
                                  timestamp=t))
    return [e for e in events if e is not None]


def _span_trainer():
    from repro.configs.base import ModelConfig
    from repro.data import DataConfig
    from repro.models import api
    from repro.optim import adamw
    from repro.runtime import CodedStepConfig, CodedTrainer
    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=32,
                      num_heads=2, num_kv_heads=1, d_ff=64, vocab_size=64,
                      flash_block_kv=8, remat="none",
                      compute_dtype="float32", param_dtype="float32")
    import jax
    trainer = CodedTrainer(cfg, DataConfig(vocab_size=64, seq_len=8,
                                           global_batch=4),
                           CodedStepConfig(n_workers=4, c=2, unique_batch=4),
                           adamw.AdamWConfig(lr=1e-3))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    return trainer, params, adamw.init(trainer.opt_cfg, params)


def _span_workload(trainer, params, opt):
    """The controller's commit path, a monolithic cached surface, a
    chunked fleet surface and a coded train step."""
    from repro.runtime.fleet import fleet_sweep
    from repro.runtime.surface_cache import cached_sweep
    commits = _span_loop(_span_controller())
    cached_sweep(SPAN_SC, **SPAN_SWEEP)
    fleet_sweep(SPAN_SC, chunk_size=20, **SPAN_SWEEP)
    params, opt, m = trainer.run_step(params, opt, 0)   # donates its input
    float(m["loss"])
    return commits, params, opt


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """The span workload, warmed, then run once under a profiler trace
    with the sink on: its commits and the program spans of the host
    planes as (name, start ns, end ns), by host thread."""
    import jax
    from jax.profiler import ProfileData
    trainer, params, opt = _span_trainer()
    _, params, opt = _span_workload(trainer, params, opt)   # compile outside
    d = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    was = profile_spans(True)
    try:
        with jax.profiler.trace(d, profiler_options=opts):
            assert active() is None
            commits, _, _ = _span_workload(trainer, params, opt)
    finally:
        profile_spans(was)
    path, = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
    threads = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                     for ev in line.events if ev.name in SPAN_NAMES]
            if spans:
                threads.append(spans)
    return commits, threads


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


class TestProfilerSpans:
    def test_every_span_reaches_the_host_plane_by_its_exact_name(
            self, profiled):
        commits, threads = profiled
        assert commits, "the traced loop must commit a plan"
        assert len(threads) == 1               # one host thread runs it all
        assert {name for name, _, _ in threads[0]} == set(SPAN_NAMES)

    def test_fetch_nests_in_replan_nests_in_observe(self, profiled):
        commits, (spans,) = profiled
        replans = [s for s in spans if s[0] == "replan"]
        assert len(replans) == len(commits)
        for r in replans:
            o, = [s for s in spans if s[0] == "ctl.observe" and _inside(r, s)]
            inner = [s[0] for s in spans if _inside(s, r) and s != r]
            # one launch per replication, each fetched and summarized
            assert inner.count("surface.dispatch") == 2
            assert "surface.fetch" in inner and "surface.summarize" in inner
            beside = {s[0] for s in spans
                      if _inside(s, o) and not _inside(s, r)}
            assert beside == {"ctl.observe", "ctl.fit", "ctl.actuate"}

    def test_surfaces_and_train_step_outside_the_controller(self, profiled):
        _, (spans,) = profiled
        obs = [s for s in spans if s[0] == "ctl.observe"]
        alone = [s for s in spans
                 if not any(_inside(s, o) for o in obs)]
        names = [s[0] for s in alone]
        # cached_sweep: 1 launch; fleet_sweep: 1 launch (one rep)
        assert names.count("surface.dispatch") == 2
        assert "surface.fetch" in names and "surface.summarize" in names
        train = [s for s in alone if s[0].startswith("train.")]
        assert [s[0] for s in sorted(train, key=lambda s: s[1])] == \
            ["train.batch", "train.decode", "train.dispatch"]

    def test_ring_spans_on_the_commit_path_name_their_parents(self):
        ctl = _span_controller()
        with recording() as rec:
            commits = _span_loop(ctl)
        assert commits
        parents = {}
        for e in rec.events("span"):
            parents.setdefault(e.name, set()).add(e.field_dict()["parent"])
        assert parents["ctl.observe"] == {None}
        assert parents["ctl.fit"] == {"ctl.observe"}
        assert parents["replan"] == {"ctl.observe"}
        assert parents["ctl.actuate"] == {"ctl.observe"}
        assert parents["surface.dispatch"] == {"replan"}
        assert parents["surface.fetch"] == {"surface.summarize"}
        assert parents["surface.summarize"] == {"replan"}


# ==========================================================================
# Satellite (b): the provenance header on benchmark artifacts
# ==========================================================================

class TestRunHeader:
    def test_header_fields(self):
        import benchmarks.common as common
        hdr = common.run_header()
        for key in ("git_sha", "timestamp_utc", "python", "platform",
                    "peak_rss_mb_at_header", "jax"):
            assert key in hdr, key
        assert hdr["timestamp_utc"].endswith("+00:00")

    def test_emit_json_stamps_run_header(self, tmp_path, monkeypatch):
        import json
        import benchmarks.common as common
        monkeypatch.setattr(common, "OUT_DIR", str(tmp_path))
        path = common.emit_json("BENCH_test", {"x": 1})
        obj = json.load(open(path))
        assert obj["x"] == 1
        assert obj["run"]["git_sha"]
