"""Drive a cell's driver on the CPU at a tiny size, without the harness's
look for a chip: for the harness tests and for rehearsals.

    JAX_PLATFORMS=cpu python bench/tests/cpu_run.py <cell> [seed]
"""
from __future__ import annotations

import copy
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run as harness  # noqa: E402

#: tiny sizes per cell, merged over the configuration and traffic files
TINY = {
    "serve-n12.replan": (
        {"objective": {"num_jobs": 100, "reps": 2, "chunk_size": 32}},
        {"phases": [
            {"arrivals": "poisson", "rate": 0.01, "jobs": 150},
            {"arrivals": "mmpp", "rate": 0.065, "slow": 0.5, "burst": 2.0,
             "jobs": 150},
            {"arrivals": "poisson", "rate": 0.28, "jobs": 100}],
         "check_commits": 4}),
    "fleet-n10k.surface": (
        {"n": 64, "engine": {"chunk_size": 64, "stream": True}},
        {"num_jobs": 300, "check_sweeps": 1}),
    "serve-n12.surface": (
        {}, {"loads": [0.01, 0.1, 0.28], "num_jobs": 200, "reps": 2,
             "check_sweeps": 1}),
    # at smaller widths bfloat16 and float8 read alike on the CPU
    "qwen3-0.6b.train-c2": (
        {"num_hidden_layers": 4, "hidden_size": 128, "intermediate_size": 384,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "vocab_size": 4096},
        {"seq_len": 64}),
}


def merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = v
    return out


def tiny_run(name: str, seed: int = 1, seconds: float = 2.0, root=None,
             sizes=None):
    """(run, driver) for ``name`` at its tiny size on the CPU."""
    import jax
    cell = harness.load_cell(name, Path(root) if root else BENCH.parent)
    c_over, t_over = (sizes or TINY)[name]
    cell["config"] = merge(cell["config"], c_over)
    cell["traffic"] = merge(cell["traffic"], t_over)
    run = harness.Run(cell, seed, seconds, False, jax.devices()[:1])
    return run, harness.driver_for(cell)


def drive(run, driver):
    """Set up, measure, check: (window output, checks)."""
    state = driver.setup(run)
    out = driver.window(run, state)
    return out, driver.check(run, state, out)


if __name__ == "__main__":
    name = sys.argv[1]
    seed = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    t0 = time.perf_counter()
    run, driver = tiny_run(name, seed)
    out, checks = drive(run, driver)
    for line in out.get("lines", []):
        print(line)
    print(json.dumps({"attempted": out["attempted"], "failed": out["failed"],
                      "end_to_end": out["end_to_end"], "checks": checks,
                      "seconds": time.perf_counter() - t0}))
