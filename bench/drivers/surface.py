"""Back-to-back warm k x load surfaces, as an operator asks for them.

The window drives ``LoadAwareLatency.surface`` and ``ClusterSweep.kstar``
(the two calls ``Planner.kstar_vs_load`` makes, kept apart so that the
lane statistics it returns can be compared), each sweep on a seed of its
own.  The end-to-end metric, named by the traffic file's ``rate_metric``,
is the lane-jobs (ks x loads x reps x jobs) of every whole sweep
completed in the window, over the time from the window's start to the
end of the last one.

``correct``: sweeps drawn from the seed are re-run by the float64
reference (``reference/lanes.py``) on the same draws, and these numbers
are compared, each against the limit the traffic file states:

  lane_gap       the largest relative gap of a compared lane statistic;
                 a lane is compared unless its reference mean is over
                 ``overload_ratio`` times its load's best (past
                 saturation a lane has no steady state, and one rounding
                 at a tie moves its statistics by percents);
  lane_gap_median  the median compared lane's gap, where the traffic
                 file gives it a limit;
  kstar_regret   how much worse, by the reference, the program's k* is
                 than the best of the lanes the reference computed at
                 that load.
"""
from __future__ import annotations

import time

import numpy as np

from reference import lanes as ref


def _scenario(cfg):
    from repro.core import BiModal, Scaling, ShiftedExp
    from repro.core.scenario import Scenario
    law = cfg["service"]
    dist = {"bimodal": lambda: BiModal(law["B"], law["eps"]),
            "shifted_exp": lambda: ShiftedExp(law["delta"], law["W"])
            }[law["family"]]()
    return Scenario(dist, Scaling(cfg["scaling"]), cfg["n"])


def _ks(cfg, sc):
    return list(sc.legal_ks()) if cfg["ks"] == "legal" else list(cfg["ks"])


def _objective(cfg, traffic, seed):
    from repro.api import LoadAwareLatency
    eng = cfg["engine"]
    return LoadAwareLatency(
        num_jobs=traffic["num_jobs"], reps=traffic["reps"],
        metric=traffic["metric"], preempt=cfg["preempt"],
        backend="batched", seed=seed, chunk_size=eng.get("chunk_size"),
        stream=bool(eng.get("stream", False)))


def setup(run):
    cfg, tr = run.config, run.traffic
    sc = _scenario(cfg)
    ks = _ks(cfg, sc)
    loads = list(tr["loads"])
    # the one shape the window uses, compiled and run once
    surf = _objective(cfg, tr, run.sub_seed("warm")).surface(sc, loads, ks)
    surf.kstar(tr["metric"])
    return dict(sc=sc, ks=ks, loads=loads)


def counters(state):
    from repro.runtime.cluster_batched import sweep_compile_count
    from repro.runtime.fleet import fleet_compile_count
    return {"sweep_compiles": sweep_compile_count(),
            "fleet_compiles": fleet_compile_count()}


def window(run, state):
    cfg, tr = run.config, run.traffic
    sc, ks, loads = state["sc"], state["ks"], state["loads"]
    per_sweep = len(ks) * len(loads) * tr["reps"] * tr["num_jobs"]
    sweeps, ends = [], []
    failed = 0
    t0 = time.perf_counter()
    deadline = t0 + run.seconds
    i = traced = 0
    while time.perf_counter() < deadline:
        seed = run.sub_seed("sweep", i)
        try:
            with run.annotate("surface"):
                surf = _objective(cfg, tr, seed).surface(sc, loads, ks)
            with run.annotate("summarize"):
                kstar = surf.kstar(tr["metric"])
        except Exception:
            failed += 1
            i += 1
            continue
        now = time.perf_counter()
        if now <= deadline:
            sweeps.append(dict(seed=seed, surf=surf, kstar=kstar))
            ends.append(now - t0)
            traced += run.tracing
        i += 1
        if run.trace_due(tr["trace_seconds"]):
            run.stop_trace()
    whole = len(sweeps)
    rate = whole * per_sweep / ends[-1] if whole else float("nan")
    dts = np.diff([0.0] + ends)
    lines = [f"surface: {whole} whole sweeps of {per_sweep} lane-jobs in "
             f"{ends[-1] if whole else float('nan')!r} s ({i} started); "
             f"sweep seconds min/median/max "
             f"{[float(x) for x in np.percentile(dts, [0, 50, 100])] if whole else []}"]
    if whole:
        lines.append(f"k* of the first sweep: {sweeps[0]['kstar']}")
    return dict(attempted=i, failed=failed, sweeps=sweeps, ends=ends,
                lane_jobs_per_sweep=per_sweep, lines=lines,
                traced_sweeps=traced,
                end_to_end={tr["rate_metric"]: rate})


def reference_sweep(cfg, tr, sc, ks, loads, seed, lanes_at=None,
                    preempt=None):
    """The reference statistics of one sweep: {(load index, k): stats}.
    ``lanes_at`` ({load index: [k, ...]}) limits a fleet-width sweep to
    those lanes; ``preempt`` overrides the deployment's (the control)."""
    preempt = cfg["preempt"] if preempt is None else preempt
    law = cfg["service"]
    eng = cfg["engine"]
    row_keyed = eng.get("chunk_size") is not None or eng.get("stream")
    arrivals = {"family": "poisson"}
    if lanes_at is None:
        return ref.surface(law, None, cfg["n"], ks, loads, arrivals,
                           tr["num_jobs"], tr["reps"], seed, preempt,
                           row_keyed)
    out = {}
    for rep in range(tr["reps"]):
        z, A_unit = ref.fleet_inputs(law, arrivals, cfg["n"],
                                     tr["num_jobs"], seed, rep)
        for i, kl in lanes_at.items():
            for k in kl:
                s = ref.fleet_lane(law, cfg["n"], k, loads[i],
                                   tr["num_jobs"], z, A_unit, preempt)
                out.setdefault((i, k), []).append(s["mean"])
        del z
    return {lane: {"mean": float(np.mean(v))} for lane, v in out.items()}


def compare(tr, ks, loads, surf_stats, kstar, refs, worst=None):
    """(lane gaps, kstar_regret) of one sweep: each compared lane's
    largest relative gap over the compared statistics, and the regret.
    ``surf_stats(i, k, stat)`` reads the program's surface; ``worst``, a
    dict, is updated with the lane behind the largest gap."""
    ratio = tr["overload_ratio"]
    gap, regret, lane_gaps = 0.0, 0.0, []
    by_load = {}
    for (i, k), st in refs.items():
        by_load.setdefault(i, {})[k] = st
    m = tr["metric"]
    for i, row in by_load.items():
        best = min(st["mean"] for st in row.values())
        for k, st in row.items():
            if st["mean"] > ratio * best:
                continue
            lane = 0.0
            for name in tr["compare_stats"]:
                prog = surf_stats(i, k, name)
                g = ref.relative_gap(prog, st[name])
                lane = max(lane, g)
                if g > gap:
                    gap = g
                    if worst is not None:
                        worst.update(load=loads[i], k=k, stat=name,
                                     program=prog, reference=st[name],
                                     load_best_mean=best)
            lane_gaps.append(lane)
        best_m = min(st[m] for st in row.values())
        k_prog = kstar[float(loads[i])]
        if k_prog in row:
            regret = max(regret, (row[k_prog][m] - best_m)
                         / max(best_m, 1e-12))
        else:
            regret = float("inf")
    return lane_gaps, regret


def numbers(tr, lane_gaps, regret):
    """The numbers compared, by the names of the traffic file's limits:
    ``lane_gap`` (the widest lane gap), ``lane_gap_median`` (the median
    lane's, steady where single lanes near saturation swing) and
    ``kstar_regret``."""
    vals = {"lane_gap": max(lane_gaps, default=float("inf")),
            "lane_gap_median": float(np.median(lane_gaps))
            if lane_gaps else float("inf"),
            "kstar_regret": regret}
    return {k: {"value": vals[k], "limit": v}
            for k, v in tr["limits"].items()}


def lanes_to_check(tr, ks, loads, kstar, rng):
    """Which lanes the reference computes: all of them, or at fleet width
    the program's k* and its neighbours at loads drawn from the seed."""
    if tr["check_lanes"] == "all":
        return None
    picks = rng.choice(len(loads), size=tr["check_loads"], replace=False)
    out = {}
    for i in sorted(int(p) for p in picks):
        j = ks.index(kstar[float(loads[i])])
        out[i] = ks[max(j - 1, 0):j + 2]
    return out


def check(run, state, out):
    cfg, tr = run.config, run.traffic
    ks, loads, sc = state["ks"], state["loads"], state["sc"]
    sweeps = out["sweeps"]
    if not sweeps:
        return numbers(tr, [], float("inf"))
    rng = np.random.default_rng(run.sub_seed("check"))
    picks = rng.choice(len(sweeps), size=min(tr["check_sweeps"],
                                             len(sweeps)), replace=False)
    lane_gaps, regret, worst = [], 0.0, {}
    for p in sorted(int(x) for x in picks):
        sw = sweeps[p]
        lanes_at = lanes_to_check(tr, ks, loads, sw["kstar"], rng)
        refs = reference_sweep(cfg, tr, sc, ks, loads, sw["seed"], lanes_at)
        surf = sw["surf"]

        def stat(i, k, name, surf=surf):
            return float(surf.metric(name)[i, ks.index(k)])

        w = {}
        g, r = compare(tr, ks, loads, stat, sw["kstar"], refs, w)
        if max(g, default=0.0) >= max(lane_gaps, default=0.0):
            worst = dict(w, sweep=p)
        lane_gaps, regret = lane_gaps + g, max(regret, r)
    print(f"check: {len(picks)} sweeps, {len(lane_gaps)} lanes compared; "
          f"widest gap at {worst}", flush=True)
    return numbers(tr, lane_gaps, regret)
