"""``correct`` comes out false when it should, on the CPU at tiny sizes.

Two kinds of test:

* the control: the reference with one guarantee broken (the scheduler
  cells: remnants cancelled on a non-preemptable fleet, left running on
  a preemptive one) or in the precision below the configuration's (the
  train cell: float8 e4m3 matrix products), put in the program's place,
  fails at least one of the cell's limits;
* faults: the run is driven as ``bench/run.py`` drives it (set-up,
  window, check) with the timed path broken underneath, and ``correct``
  comes out false: a step that returns its state unchanged, half of the
  batch (or of the jobs) left out with the mean over the rest, an answer
  altered where it is produced.  One chip has no exchange between chips
  to leave out.

The same readings at the cells' own sizes come from
``bench/calibrate.py`` on the chip.
"""
from __future__ import annotations

import contextlib
import sys
from pathlib import Path

import jax
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))

import calibrate  # noqa: E402
import cpu_run  # noqa: E402


def _fails(checks) -> bool:
    return any(not (c["value"] <= c["limit"]) for c in checks.values())


# --------------------------------------------------------------------------
# the control
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["serve-n12.surface", "fleet-n10k.surface"])
def test_surface_control_fails(name):
    run, driver = cpu_run.tiny_run(name, seed=2147483911)
    nums = calibrate.control_surface(run.cell, driver, 2147483911,
                                     jax.devices()[:1])
    lim = run.traffic["limits"]
    assert any(nums[k] > lim[k] for k in nums), nums


def test_replan_control_fails():
    run, driver = cpu_run.tiny_run("serve-n12.replan", seed=2147483913)
    nums = calibrate.control_replan(run.cell, driver, 2147483913, 1.5,
                                    jax.devices()[:1])
    lim = run.traffic["limits"]
    assert any(nums[k] > lim[k] for k in nums), nums


@pytest.mark.parametrize("seed", [2147483917, 2147485002])
def test_train_control_reads_apart_from_the_program(seed):
    """The cell's limits are set at its own size on the chip (readings
    in PERF.md); at a size the CPU holds, the float8 control reads at
    least three times what the bfloat16 program does on some number."""
    run, driver = cpu_run.tiny_run("qwen3-0.6b.train-c2", seed=seed)
    ctl = calibrate.reading_train(run.cell, driver, seed, jax.devices()[:1],
                                  precision="fp8")
    st = driver.first_steps(run.config, run.traffic, driver.seeds(run))
    prog = driver.gaps((st["losses"], st["g1"], st["upd"]),
                       driver.reference_readings(run.config, run.traffic,
                                                 driver.seeds(run)))
    assert max(ctl[n] / prog[n] for n in prog) >= 3.0, (ctl, prog)


# --------------------------------------------------------------------------
# faults planted in the program
# --------------------------------------------------------------------------

@contextlib.contextmanager
def planted(monkeypatch):
    """Compiled programs are cached by function: clear them around a
    fault so that the broken code is traced, and nothing broken stays."""
    jax.clear_caches()
    try:
        yield monkeypatch
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def _frozen_step(factory):
    def make(*a, **kw):
        step = factory(*a, **kw)

        def frozen(carry, inp):
            _, y = step(carry, inp)
            return carry, y
        return frozen
    return make


def fault_state_unchanged(mp, name):
    """The lane step, or the optimizer, returns its state unchanged."""
    if name.startswith("qwen3"):
        from repro.optim import adamw
        mp.setattr(adamw, "apply_updates",
                   lambda cfg, p, g, s: (p, s, {"grad_norm": 0.0 * sum(
                       x.sum() for x in jax.tree.leaves(g)), "lr": 0.0}))
        return
    from repro.runtime import cluster_batched, fleet
    frozen = _frozen_step(cluster_batched.make_plain_step)
    mp.setattr(cluster_batched, "make_plain_step", frozen)
    mp.setattr(fleet, "make_plain_step", frozen)


def fault_half_batch(mp, name):
    """Half of the batch (the coded rows, or the jobs) left out; the mean
    is taken over the rest."""
    if name.startswith("qwen3"):
        import jax.numpy as jnp
        from repro.runtime import coded_step
        orig = coded_step.expand_worker_weights

        def half(w, rows, scale):
            x = orig(w, rows, scale)
            keep = jnp.arange(x.shape[0]) < x.shape[0] // 2
            return jnp.where(keep, 2.0 * x, 0.0)
        mp.setattr(coded_step, "expand_worker_weights", half)
        return
    from repro.runtime import cluster_batched, fleet
    orig_f = fleet._fleet_kernel

    def fleet_half(*a, num_jobs, stream, **kw):
        # streaming statistics: fold only the first half of the jobs
        return orig_f(*a, num_jobs=num_jobs // 2 if stream else num_jobs,
                      stream=stream, **kw)
    mp.setattr(fleet, "_fleet_kernel", fleet_half)
    orig_sum = cluster_batched.summarize_sweep

    def summarize_half(lat, busy, wasted, a_last, loads, ks, warmup, reps,
                       num_jobs, n, **kw):
        # exact statistics: pool only the first half of the jobs
        half = num_jobs // 2
        return orig_sum(lat[..., :half], busy, wasted, a_last, loads, ks,
                        warmup // 2, reps, half, n, **kw)
    mp.setattr(cluster_batched, "summarize_sweep", summarize_half)
    mp.setattr(fleet, "summarize_sweep", summarize_half)


def fault_answer_altered(mp, name):
    """An answer altered where it is produced: the summary files each
    lane's latency statistics under the k of the lane mirrored to it
    (the k axis read backwards)."""
    import dataclasses
    from repro.runtime import cluster_batched, fleet

    def mirrored(sw):
        return dataclasses.replace(sw, **{
            m: sw.metric(m)[:, ::-1].copy()
            for m in ("mean", "p50", "p95", "p99")})

    def altered(summarize):
        return lambda *a, **kw: mirrored(summarize(*a, **kw))

    # exact statistics go through summarize_sweep, streaming ones not
    mp.setattr(cluster_batched, "summarize_sweep",
               altered(cluster_batched.summarize_sweep))
    mp.setattr(fleet, "summarize_sweep", altered(fleet.summarize_sweep))
    orig_fleet = fleet.summarize_fleet

    def summarize_fleet(raw, *a, **kw):
        sw = orig_fleet(raw, *a, **kw)
        return mirrored(sw) if raw.stream else sw
    mp.setattr(fleet, "summarize_fleet", summarize_fleet)


FAULTS = {
    "serve-n12.replan": [fault_state_unchanged, fault_half_batch,
                         fault_answer_altered],
    "fleet-n10k.surface": [fault_state_unchanged, fault_half_batch,
                           fault_answer_altered],
    "serve-n12.surface": [fault_state_unchanged, fault_half_batch,
                          fault_answer_altered],
    "qwen3-0.6b.train-c2": [fault_state_unchanged, fault_half_batch],
}


@pytest.mark.parametrize("name,fault", [(n, f) for n, fs in FAULTS.items()
                                        for f in fs],
                         ids=lambda x: getattr(x, "__name__", x))
def test_fault_makes_correct_false(name, fault, monkeypatch):
    run, driver = cpu_run.tiny_run(name, seed=2147483921, seconds=1.5)
    with planted(monkeypatch) as mp:
        fault(mp, name)
        state = driver.setup(run)
        out = driver.window(run, state)
    # the reference runs on the sound program's library, as on the chip
    checks = driver.check(run, state, out)
    assert _fails(checks), checks
