"""Readings that the limits of ``trinity-mini.train-c2`` are set from.

    python bench/calibrate_cells.py --workload <cell> --seeds 1,2 \
        [--seconds 5] [--control 1] [--faults 1] [--capacity 1]

As ``calibrate.py`` (whose readings it reuses), in one process on the
accelerator, one JSON line per reading:

  --seeds     sound runs of the program: set-up, a short window, check;
  --control   the reference in float8 e4m3 in the program's place;
  --faults    the reference with half of each step's batch left out;
  --capacity  the program with a capacity drop planted on its held
              experts (``capacity_drop``), read by the same check.

The sound reference of a seed is computed once and every reading of
that seed is compared with it.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import calibrate  # noqa: E402
import run as harness  # noqa: E402

#: a capacity layer's factor: each expert keeps ceil(1.25 T k / E) rows
CAPACITY_FACTOR = 1.25


@contextlib.contextmanager
def capacity_drop(num_experts: int, factor: float = CAPACITY_FACTOR):
    """The fault of a capacity layer in a dropless one: every grouped
    matmul of the expert-share layer returns zeros for the rows past
    ceil(factor * T * k / num_experts) in their expert's group, so those
    (token, slot) pairs add nothing.  Compiled programs are cleared on
    entry and exit so that the fault is traced, and nothing faulty
    stays."""
    import jax
    import jax.numpy as jnp
    from repro.models import moe
    orig = moe.grouped_matmul

    def dropping(lhs, rhs, sizes, **kw):
        out = orig(lhs, rhs, sizes, **kw)
        cap = math.ceil(factor * lhs.shape[0] / num_experts)
        ends = jnp.cumsum(sizes)
        row = jnp.arange(lhs.shape[0])
        group = jnp.searchsorted(ends, row, side="right")
        start = (ends - sizes)[jnp.minimum(group, sizes.shape[0] - 1)]
        keep = (group >= sizes.shape[0]) | (row - start < cap)
        return jnp.where(keep[:, None], out, 0).astype(out.dtype)

    jax.clear_caches()
    moe.grouped_matmul = dropping
    try:
        yield
    finally:
        moe.grouped_matmul = orig
        jax.clear_caches()


def train_readings(cell, driver, devices, control=(), faults=(),
                   capacity=()):
    """(role, seed, numbers) of the cell's controls and faults,
    each compared with its seed's sound reference, computed once."""
    cfg, tr = cell["config"], cell["traffic"]
    refs = {}

    def seeds_of(s):
        return driver.seeds(harness.Run(cell, s, 0.0, False, devices))

    def sound(s):
        if s not in refs:
            refs[s] = driver.reference_readings(cfg, tr, seeds_of(s))
        return refs[s]

    for s in control:
        yield "control", s, driver.gaps(driver.reference_readings(
            cfg, tr, seeds_of(s), precision="fp8"), sound(s))
    for s in faults:
        yield "fault_half_batch", s, driver.gaps(driver.reference_readings(
            cfg, tr, seeds_of(s), rows="half"), sound(s))
    for s in capacity:
        with capacity_drop(cfg["num_experts_routed"]):
            st = driver.first_steps(cfg, tr, seeds_of(s))
        prog = (st["losses"], st["g1"], st["upd"], st["choices"], st["rows"])
        del st
        yield "fault_capacity_drop", s, driver.gaps(prog, sound(s))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--capacity", default="")
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
    seeds = calibrate._seeds

    def emit(role, seed, numbers, t0):
        print(json.dumps({"cell": args.workload, "role": role, "seed": seed,
                          "numbers": numbers,
                          "seconds": time.perf_counter() - t0}), flush=True)

    for s in seeds(args.seeds):
        t0 = time.perf_counter()
        emit("program", s, calibrate.program_reading(
            cell, driver, s, args.seconds, devices)[0], t0)
    t0 = time.perf_counter()
    for role, s, nums in train_readings(
            cell, driver, devices, seeds(args.control), seeds(args.faults),
            seeds(args.capacity)):
        emit(role, s, nums, t0)
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
