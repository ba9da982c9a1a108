"""The serving controller's closed loop: telemetry in, committed plan out.

The window feeds ``RedundancyController.observe`` one completed job at a
time from a seeded trace of the serving day (its regimes are the traffic
file's phases), cycled for the whole window with the clock carried on.
The loop is closed: each job's latency comes from the FCFS any-k queue
at the k the controller has committed (``queue_step``, the serving
front end's view).  ``replan_p95_ms`` is the 95th percentile, over every
``observe`` call that returned a committed ``ControlEvent``, of that
call's wall time.

``correct``: for commits drawn from the seed, the surface the commit
planned on is recomputed by the float64 reference (``reference/lanes``)
from the scenario the commit asked about (its fitted service and arrival
models, as the controller maps them to a plan), and compared:

  surface_gap   the largest relative gap between the tail curve the
                commit read (handed to the actuators with the plan) and
                the reference's, over lanes not past saturation;
  commit_gap    the relative cost, on the reference curve, between the
                k the program committed and the k the commit rule
                (move to the arg-min when it gains at least the
                hysteresis) commits on the reference curve.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from reference import lanes as ref


def queue_step(F, a, srow, k, preempt):
    """One job through the FCFS any-k queue (float64): the serving front
    end's latency for the controller's latency and completion feeds."""
    start = np.maximum(a, F)
    nat = start + srow
    D = float(np.partition(nat, k - 1)[k - 1])
    lt = nat < D
    eq = nat == D
    completed = lt | (eq & (np.cumsum(eq) <= k - lt.sum()))
    inservice = ~completed & (start < D)
    if preempt:
        F = np.where(completed, nat, np.where(inservice, D, F))
    else:
        F = np.where(completed | inservice, nat, F)
    return F, D - a


def _service(law):
    from repro.core import BiModal, ShiftedExp
    if law["family"] == "bimodal":
        return BiModal(law["B"], law["eps"])
    return ShiftedExp(law["delta"], law["W"])


def _arrivals(ph):
    from repro.core.scenario import MMPPArrivals, PoissonArrivals
    if ph["arrivals"] == "poisson":
        return PoissonArrivals(ph["rate"])
    return MMPPArrivals(ph["rate"], slow=ph["slow"], burst=ph["burst"])


def day_trace(cfg, tr, seed):
    from repro.core import Regime, Scaling, sample_regime_trace
    svc = _service(cfg["service"])
    regimes = [Regime(svc, ph["jobs"], arrivals=_arrivals(ph))
               for ph in tr["phases"]]
    n = cfg["n"]
    s_values = sorted({n // k for k in cfg["candidate_ks"]} | {1})
    return sample_regime_trace(regimes, Scaling(cfg["scaling"]), n,
                               seed=seed, s_values=s_values)


class PlanRecorder:
    """An actuator that keeps what each commit planned on: the committed
    k, the fitted model, the arrival model and the plan's tail curve."""

    def __init__(self):
        self.ctl = None
        self.records = []

    def apply(self, policy, model):
        pass

    def apply_plan(self, policy, model, tail_curve, unit):
        self.records.append(dict(k=int(policy.k), model=model,
                                 arrival=self.ctl.arrival_model,
                                 tail=None if tail_curve is None
                                 else dict(tail_curve), unit=float(unit)))


def controller(cfg, objective_seed):
    from repro.api import LoadAwareLatency, Scenario
    from repro.control import RedundancyController
    from repro.control.controller import (ControllerConfig,
                                          HedgedServeActuator)
    from repro.core import Scaling
    from repro.obs import SLOMonitor
    c = cfg["controller"]
    obj = cfg["objective"]
    objective = LoadAwareLatency(
        num_jobs=obj["num_jobs"], reps=obj["reps"], backend=obj["backend"],
        preempt=cfg["preempt"], metric=obj["metric"],
        chunk_size=obj["chunk_size"], seed=objective_seed)
    slo = SLOMonitor(**cfg["slo"])
    rec = PlanRecorder()
    ctl = RedundancyController(
        Scenario(_service(cfg["service"]), Scaling(cfg["scaling"]),
                 cfg["n"], candidate_ks=tuple(cfg["candidate_ks"])),
        objective=objective, config=ControllerConfig(**c),
        actuators=[HedgedServeActuator(), rec], slo=slo)
    rec.ctl = ctl
    return ctl, rec


def _loop(run, ctl, rec, trace, cfg, deadline, trace_seconds=None,
          max_steps=None):
    n = cfg["n"]
    A = np.asarray(trace.arrivals, np.float64)
    steps = A.size
    period = float(A[-1]) + float(np.mean(np.diff(A)))
    times = {s: trace.times(s) for s in trace.s_values}
    cu = times[1]
    F = np.zeros(n)
    commits, silent, t, traced = [], 0, 0, 0
    while time.perf_counter() < deadline and t != max_steps:
        i, p = t % steps, t // steps
        a = float(A[i]) + p * period
        k = ctl.policy.k
        F, cost = queue_step(F, a, times[n // k][i], k, cfg["preempt"])
        before = len(rec.records)
        t0 = time.perf_counter()
        with run.annotate("observe"):
            ev = ctl.observe(cu[i], timestamp=a, latency=cost,
                             completion=a + cost)
        dt = time.perf_counter() - t0
        if ev is not None:
            commits.append(dict(ms=dt * 1e3, replan_ms=ev.replan_ms,
                                rec=len(rec.records) - 1,
                                old_k=int(ev.old_policy.k),
                                new_k=int(ev.new_policy.k), kind=ev.kind,
                                cached=ev.cached, warm=ev.warm,
                                fallback=ev.fallback))
        elif len(rec.records) > before:
            silent += 1
        if run.tracing:
            traced += len(rec.records) - before
        t += 1
        if trace_seconds is not None and run.trace_due(trace_seconds):
            run.stop_trace()
    return t, commits, silent, traced


def setup(run):
    cfg, tr = run.config, run.traffic
    trace = day_trace(cfg, tr, run.sub_seed("trace"))
    obj_seed = run.sub_seed("objective")
    # a throwaway controller walks one pass of the day: every surface
    # family and argument type the window's commits meet is compiled
    warm, wrec = controller(cfg, obj_seed)
    _loop(run, warm, wrec, trace, cfg, float("inf"),
          max_steps=len(trace.arrivals))
    ctl, rec = controller(cfg, obj_seed)
    return dict(trace=trace, ctl=ctl, rec=rec, obj_seed=obj_seed)


def counters(state):
    from repro.obs.metrics import REGISTRY
    from repro.runtime.fleet import fleet_compile_count
    return {"surface_cache_misses":
            REGISTRY.counter("surface_cache.misses").value,
            "surface_fallbacks":
            REGISTRY.counter("controller.surface_fallbacks").value,
            "fleet_compiles": fleet_compile_count()}


def window(run, state):
    tr = run.traffic
    deadline = time.perf_counter() + run.seconds
    steps, commits, silent, traced = _loop(
        run, state["ctl"], state["rec"], state["trace"], run.config,
        deadline, tr["trace_seconds"])
    ms = np.asarray([c["ms"] for c in commits])
    p95 = float(np.percentile(ms, 95)) if ms.size else float("nan")
    kinds = {}
    for c in commits:
        kinds[c["kind"]] = kinds.get(c["kind"], 0) + 1
    lines = [f"replan: {steps} observe calls, {len(commits)} commits "
             f"{kinds}, {silent} silent re-plans; commit observe ms "
             f"median {float(np.median(ms)) if ms.size else float('nan')!r}"
             f" p95 {p95!r} max "
             f"{float(ms.max()) if ms.size else float('nan')!r}",
             f"replan: committed k sequence (first 40) "
             f"{[c['new_k'] for c in commits[:40]]}"]
    return dict(attempted=len(commits),
                failed=sum(1 for c in commits if c["fallback"]),
                commits=commits, steps=steps, silent=silent, lines=lines,
                traced_replans=traced,
                end_to_end={"replan_p95_ms": p95})


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------

def law_of(dist) -> dict:
    name = type(dist).__name__
    if name == "BiModal":
        return {"family": "bimodal", "B": float(dist.B),
                "eps": float(dist.eps)}
    if name == "ShiftedExp":
        return {"family": "shifted_exp", "delta": float(dist.delta),
                "W": float(dist.W)}
    if name == "Pareto":
        return {"family": "pareto", "lam": float(dist.lam),
                "alpha": float(dist.alpha)}
    raise ValueError(f"no reference for service law {name}")


def arrivals_of(proc) -> dict:
    name = type(proc).__name__
    if name == "PoissonArrivals":
        return {"family": "poisson"}
    if name == "DeterministicArrivals":
        return {"family": "deterministic"}
    if name == "MMPPArrivals":
        return {"family": "mmpp", "slow": float(proc.slow),
                "burst": float(proc.burst), "switch": float(proc.switch)}
    raise ValueError(f"no reference for arrival process {name}")


def plan_question(ctl, record):
    """What the commit asked the surface: (law, delta, ks, load, arrivals,
    unit) — the fitted models as the controller maps them to a plan."""
    dist, delta, _, unit = ctl._hedged_plan_dist(record["model"])
    sc = dataclasses.replace(ctl.scenario, dist=dist, delta=delta)
    am = record["arrival"]
    return (law_of(dist), delta, list(sc.legal_ks()), float(am.rate * unit),
            arrivals_of(am.process()), float(unit))


def reference_curve(cfg, question, seed, preempt=None):
    law, delta, ks, load, arrivals, unit = question
    obj = cfg["objective"]
    stats = ref.surface(law, delta, cfg["n"], ks, [load], arrivals,
                        obj["num_jobs"], obj["reps"], seed,
                        cfg["preempt"] if preempt is None else preempt,
                        row_keyed=obj["chunk_size"] is not None)
    return {k: stats[(0, k)] for k in ks}


def commit_rule(curve, old_k, hysteresis):
    """The k the commit rule keeps on ``curve`` (k -> cost) from old_k."""
    new = min(curve, key=lambda k: (curve[k], k))
    if new == old_k or old_k not in curve:
        return new
    gain = curve[old_k] - curve[new]
    return new if gain / max(curve[new], 1e-12) >= hysteresis else old_k


def compare_commit(cfg, tr, question, tail, committed_k, old_k, refc):
    """(surface_gap, commit_gap) of one commit against its reference."""
    unit = question[-1]
    metric = cfg["objective"]["metric"]
    best = min(st["mean"] for st in refc.values())
    gap = 0.0
    for k, st in refc.items():
        if st["mean"] > tr["overload_ratio"] * best:
            continue
        gap = max(gap, ref.relative_gap(tail[k] / unit, st[metric]))
    curve = {k: st[metric] for k, st in refc.items()}
    k_rule = commit_rule(curve, old_k, cfg["controller"]["hysteresis"])
    cgap = abs(curve[committed_k] - curve[k_rule]) / curve[k_rule]
    return gap, cgap


def check(run, state, out):
    cfg, tr = run.config, run.traffic
    ctl, rec = state["ctl"], state["rec"]
    commits = out["commits"]
    lim = tr["limits"]
    if not commits:
        return {"surface_gap": {"value": float("inf"),
                                "limit": lim["surface_gap"]}}
    rng = np.random.default_rng(run.sub_seed("check"))
    m = min(tr["check_commits"], len(commits))
    picks = sorted(int(x) for x in rng.choice(len(commits), m,
                                              replace=False))
    gap = cgap = 0.0
    worst = {}
    for p in picks:
        c = commits[p]
        r = rec.records[c["rec"]]
        if r["tail"] is None:
            gap = float("inf")
            continue
        q = plan_question(ctl, r)
        refc = reference_curve(cfg, q, state["obj_seed"])
        g, cg = compare_commit(cfg, tr, q, r["tail"], r["k"], c["old_k"],
                               refc)
        if g >= gap:
            metric = cfg["objective"]["metric"]
            worst = dict(commit=p, kind=c["kind"], law=q[0], load=q[3],
                         arrivals=q[4]["family"], program={
                             k: v / q[-1] for k, v in r["tail"].items()},
                         reference={k: st[metric] for k, st in refc.items()})
        gap, cgap = max(gap, g), max(cgap, cg)
    print(f"check: {len(picks)} commits compared; widest gap at {worst}",
          flush=True)
    return {"surface_gap": {"value": gap, "limit": lim["surface_gap"]},
            "commit_gap": {"value": cgap, "limit": lim["commit_gap"]}}
