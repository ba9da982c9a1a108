"""Readings that the limits of ``correct`` are set from.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--seconds 5] [--control 1,2,3] [--faults 1,2,3]

In one process, on the accelerator: for each ``--seeds`` seed, the
cell's driver runs its set-up, a short window and its check, and the
numbers compared are printed (the lower readings: sound runs of the
program).  For each ``--control`` seed, the control is put in the
program's place and read by the same comparison (the upper readings):

  surface, replan  the reference with the deployment's preemption
                   guarantee broken (remnants cancelled on a
                   non-preemptable fleet, left running on a preemptive
                   one);
  train            the reference computed with float8 e4m3 matrix
                   products, the precision below the bfloat16 that the
                   configuration computes in.

``--faults`` (train only) reads the fault of a batch half left out, the
mean taken over the rest, the same way.  Each reading is one JSON line.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run as harness  # noqa: E402


def _seeds(text):
    return [int(s) for s in text.split(",") if s.strip()] if text else []


def program_reading(cell, driver, seed, seconds, devices):
    run = harness.Run(cell, seed, seconds, False, devices)
    state = driver.setup(run)
    out = driver.window(run, state)
    checks = driver.check(run, state, out)
    return {k: c["value"] for k, c in checks.items()}, run, state, out


def control_surface(cell, driver, seed, devices):
    """The preemption-flipped reference in the program's place."""
    import numpy as np
    cfg, tr = cell["config"], cell["traffic"]
    run = harness.Run(cell, seed, 0.0, False, devices)
    sc = driver._scenario(cfg)
    ks, loads = driver._ks(cfg, sc), list(tr["loads"])
    sweep_seed = run.sub_seed("sweep", 0)
    flip = not cfg["preempt"]
    rng = np.random.default_rng(run.sub_seed("check"))
    if tr["check_lanes"] == "all":
        ctl = driver.reference_sweep(cfg, tr, sc, ks, loads, sweep_seed,
                                     preempt=flip)
        lanes_at = None
    else:
        i = int(rng.choice(len(loads)))
        ctl = driver.reference_sweep(cfg, tr, sc, ks, loads, sweep_seed,
                                     lanes_at={i: ks}, preempt=flip)
    m = tr["metric"]
    kstar = {}
    for i, lam in enumerate(loads):
        row = {k: ctl[(i, k)][m] for k in ks if (i, k) in ctl}
        if row:
            kstar[float(lam)] = min(row, key=lambda k: (row[k], k))
    if tr["check_lanes"] != "all":
        i = next(iter({i for i, _ in ctl}))
        j = ks.index(kstar[float(loads[i])])
        lanes_at = {i: ks[max(j - 1, 0):j + 2]}
    refs = driver.reference_sweep(cfg, tr, sc, ks, loads, sweep_seed,
                                  lanes_at=lanes_at)

    def stat(i, k, name):
        return ctl[(i, k)][name]

    gaps, regret = driver.compare(tr, ks, loads, stat, kstar, refs)
    return {k: c["value"] for k, c in driver.numbers(tr, gaps,
                                                      regret).items()}


def control_replan(cell, driver, seed, seconds, devices):
    """The program runs a short window to pose its commits' questions;
    the preemption-flipped reference answers them in its place."""
    import numpy as np
    cfg, tr = cell["config"], cell["traffic"]
    _, run, state, out = program_reading(cell, driver, seed, seconds,
                                         devices)
    ctl, rec = state["ctl"], state["rec"]
    rng = np.random.default_rng(run.sub_seed("check"))
    commits = out["commits"]
    picks = rng.choice(len(commits), min(tr["check_commits"], len(commits)),
                       replace=False)
    gap = cgap = 0.0
    for p in sorted(int(x) for x in picks):
        c = commits[p]
        q = driver.plan_question(ctl, rec.records[c["rec"]])
        refc = driver.reference_curve(cfg, q, state["obj_seed"])
        flip = driver.reference_curve(cfg, q, state["obj_seed"],
                                      preempt=not cfg["preempt"])
        metric = cfg["objective"]["metric"]
        curve = {k: st[metric] for k, st in flip.items()}
        k_ctl = driver.commit_rule(curve, c["old_k"],
                                   cfg["controller"]["hysteresis"])
        tail = {k: v * q[-1] for k, v in curve.items()}
        g, cg = driver.compare_commit(cfg, tr, q, tail, k_ctl, c["old_k"],
                                      refc)
        gap, cgap = max(gap, g), max(cgap, cg)
    return {"surface_gap": gap, "commit_gap": cgap}


def reading_train(cell, driver, seed, devices, precision="highest",
                  rows="all"):
    """Reference readings with a broken precision or batch, compared as
    the program's would be."""
    cfg, tr = cell["config"], cell["traffic"]
    run = harness.Run(cell, seed, 0.0, False, devices)
    sd = driver.seeds(run)
    refr = driver.reference_readings(cfg, tr, sd)
    bad = driver.reference_readings(cfg, tr, sd, precision=precision,
                                    rows=rows)
    return dict(driver.gaps(bad, refr),
                step_loss_gaps=driver.loss_gaps(bad[0], refr[0]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devices, why = harness.find_chips(int(cell["cell"]["chips"]))
    if devices is None:
        print(f"calibrate: {why}", file=sys.stderr)
        return 2
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    enable_compile_cache()
    driver = harness.driver_for(cell)
    kind = cell["traffic"]["driver"]

    def emit(role, seed, numbers, t0):
        print(json.dumps({"cell": args.workload, "role": role, "seed": seed,
                          "numbers": numbers,
                          "seconds": time.perf_counter() - t0}), flush=True)

    for s in _seeds(args.seeds):
        t0 = time.perf_counter()
        nums = program_reading(cell, driver, s, args.seconds, devices)[0]
        emit("program", s, nums, t0)
    for s in _seeds(args.control):
        t0 = time.perf_counter()
        if kind == "surface":
            nums = control_surface(cell, driver, s, devices)
        elif kind == "replan":
            nums = control_replan(cell, driver, s, args.seconds, devices)
        else:
            nums = reading_train(cell, driver, s, devices, precision="fp8")
        emit("control", s, nums, t0)
    for s in _seeds(args.faults):
        t0 = time.perf_counter()
        nums = reading_train(cell, driver, s, devices, rows="half")
        emit("fault_half_batch", s, nums, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
