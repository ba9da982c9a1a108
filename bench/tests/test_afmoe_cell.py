"""The cell ``trinity-mini.train-c2`` at a tiny size on the CPU: it runs
and checks through its driver as ``bench/run.py`` drives it, its
per-layer readers read, and its controls and planted faults come out
not correct.

    python -m pytest bench/tests -q
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))

import calibrate  # noqa: E402
import calibrate_cells  # noqa: E402
import cpu_run  # noqa: E402
import run as harness  # noqa: E402

#: tiny sizes, merged over the configuration and traffic files; the
#: trinity share holds 4 of 16 routed experts, top-4, window 16 < 48.  It
#: computes in float32: at width 64 bfloat16's rounding reads above the
#: limits set at the published widths on the chip (PERF.md)
SIZES = {
    "trinity-mini.train-c2": (
        {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
         "moe_intermediate_size": 32, "num_experts": 4,
         "num_experts_routed": 16, "num_experts_per_tok": 4,
         "sliding_window": 16, "vocab_size": 512,
         "precision": {"compute": "float32"}},
        {"seq_len": 48}),
}
SEED = 2147483927


def tiny(name, seed=SEED, seconds=1.5):
    return cpu_run.tiny_run(name, seed=seed, seconds=seconds, sizes=SIZES)


def _fails(nums, limits) -> bool:
    return any(not (nums[k] <= limits[k]) for k in limits)


@pytest.fixture(scope="module")
def trinity():
    run, driver = tiny("trinity-mini.train-c2")
    state = driver.setup(run)
    out = driver.window(run, state)
    return run, driver, state, out, driver.check(run, state, out)


@pytest.mark.parametrize("name", ["trinity-mini.train-c2"])
def test_cell_is_found_by_name(name):
    cell = harness.load_cell(name)
    assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
    assert len(cell["end_to_end"]) == 2 and cell["per_layer"]
    for m in cell["per_layer"]:
        assert harness.metric_reader(cell, m["name"]).read is not None


def test_trinity_runs_and_checks_at_a_tiny_size(trinity):
    run, _, state, out, checks = trinity
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["end_to_end"]["train_tokens_per_s"] > 0
    assert set(checks) == set(run.traffic["limits"])
    assert all(c["value"] <= c["limit"] for c in checks.values()), checks
    # the counters: every (token, slot) of the held experts, by layer
    rows = out["expert_rows"]
    assert rows.shape == (len(out["step_seconds"]), 4, 4)
    assert state["choices"].shape == (4, 2 * 48, 4)


def test_trinity_readers(trinity):
    run, _, _, out, _ = trinity
    cell = run.cell
    mfu = harness.metric_reader(cell, "train_mfu.trinity")
    load = harness.metric_reader(cell, "expert_load_max.trinity")
    roof = harness.metric_reader(cell, "expert_gmm_roofline.trinity")
    # no peaks for a CPU device kind: the reader refuses rather than guess
    with pytest.raises(KeyError):
        mfu.read(run, out)
    assert load.read(run, out) >= 1.0
    assert roof.read(run, out) is None          # no trace taken


def test_kernel_ops_are_named_from_the_compiled_step(trinity):
    run, driver, state, _, _ = trinity
    import jax.numpy as jnp
    from repro.optim import adamw
    p = driver.model_config(run.config)
    from repro.models import api
    params = api.param_shapes(p)
    opt = jax.eval_shape(lambda q: adamw.init(state["trainer"].opt_cfg, q),
                         params)
    # on the CPU the grouped matmul is no TPU custom call: nothing named
    assert driver.kernel_ops(state["trainer"], params, opt,
                             run.traffic) == []
    assert driver.KERNEL_OPS.match("ragged-dot-none.3")
    assert driver.KERNEL_OPS.match("ragged-dot-metadata.1")
    assert not driver.KERNEL_OPS.match("fusion.12")
    del jnp


def test_trinity_float8_control_reads_apart_from_the_program(trinity):
    """The limits are set at the cell's size on the chip (PERF.md); here
    the float8 control reads at least three times what the bfloat16
    program does on some number."""
    run, driver, state, _, checks = trinity
    ctl = calibrate.reading_train(run.cell, driver, SEED, jax.devices()[:1],
                                  precision="fp8")
    prog = {k: c["value"] for k, c in checks.items()}
    assert max(ctl[n] / max(prog[n], 1e-12) for n in prog) >= 3.0, \
        (ctl, prog)


def test_trinity_half_batch_and_capacity_drop_fail():
    """Half the batch left out (the mean over the rest), and a capacity
    layer's drop at factor 1.25 planted in the timed path (the Zipf
    traffic routes most tokens alike, so experts overflow), each read
    against the seed's sound reference as ``calibrate_cells.py`` reads
    them on the chip."""
    run, driver = tiny("trinity-mini.train-c2")
    got = list(calibrate_cells.train_readings(
        run.cell, driver, jax.devices()[:1], faults=[SEED], capacity=[SEED]))
    assert [r for r, _, _ in got] == ["fault_half_batch",
                                      "fault_capacity_drop"]
    for role, _, nums in got:
        assert _fails(nums, run.traffic["limits"]), (role, nums)

