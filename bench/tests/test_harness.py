"""Tests of the benchmark harness, on the CPU at tiny sizes.

    python -m pytest bench/tests -q

The trace reduction runs on a small trace recorded on a TPU v5e
(``bench/testdata/probe.xplane.pb``: two jitted programs, three rounds,
inside ``surface``/``summarize``/``observe`` host spans).  Each driver
runs at a tiny size with the harness's look for a chip skipped, and the
harness itself refuses to measure without a TPU.  A cell and a metric
that exist only as files in a temporary directory are found by name.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
sys.path.insert(0, str(HERE))

import cpu_run  # noqa: E402
import run as harness  # noqa: E402

trace_mod = harness.load_module(BENCH / "trace.py")
PROBE = BENCH / "testdata" / "probe.xplane.pb"


# --------------------------------------------------------------------------
# trace reduction
# --------------------------------------------------------------------------

def test_union_merges_overlapping_intervals():
    assert trace_mod.union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 9)]) == \
        [(0, 4), (5, 7), (8, 9)]


def test_names_drop_hash_and_shape():
    assert trace_mod.program_name("jit__fleet_core(123456)") == \
        "jit__fleet_core"
    assert trace_mod.op_name(
        "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %x), kind=kLoop") == \
        "fusion.3"


@pytest.fixture(scope="module")
def probe():
    return trace_mod.reduce_trace(str(PROBE))


def test_busy_time_is_the_union_of_device_ops(probe):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(PROBE))
    tpu = [p for p in pd.planes if p.name == "/device:TPU:0"][0]
    ops = [(e.start_ns, e.start_ns + e.duration_ns)
           for line in tpu.lines if line.name == "XLA Ops"
           for e in line.events]
    mods = [(e.start_ns, e.start_ns + e.duration_ns)
            for line in tpu.lines if line.name == "XLA Modules"
            for e in line.events]
    covered = sum(e - s for s, e in trace_mod.union(ops)) / 1e9
    assert probe.busy_s == pytest.approx(covered)
    assert 0 < probe.busy_s <= sum(e - s for s, e in mods) / 1e9
    assert 0 < probe.idle_share() < 1


def test_programs_and_ops_are_named(probe):
    assert probe.program_count(["jit__lambda"]) == 6
    assert probe.program_seconds(["jit__lambda"]) > probe.busy_s * 0.9
    ops = dict(probe.device_ops())
    assert any(k.startswith("jit__lambda:fusion") for k in ops)
    assert sum(ops.values()) <= probe.busy_s * 1.5


def test_idle_gaps_are_labelled_by_host_spans(probe):
    gaps = dict(probe.idle_gaps())
    assert set(gaps) <= set(trace_mod.ANNOTATIONS) | {"host"}
    # the probe sleeps 10 ms inside each ``summarize`` span
    assert gaps["summarize"] > 0.025
    total = sum(gaps.values())
    assert total == pytest.approx(probe.window_s - probe.busy_s, rel=1e-6)
    bd = probe.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


# --------------------------------------------------------------------------
# the harness refuses to measure without a chip
# --------------------------------------------------------------------------

def _bench_run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "serve-n12.surface",
         "--seed", "2147483905", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _bench_run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_run_without_the_system_under_test_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench_run(tmp_path)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


# --------------------------------------------------------------------------
# each driver at a tiny size
# --------------------------------------------------------------------------

CELLS = ["serve-n12.replan", "fleet-n10k.surface", "qwen3-0.6b.train-c2",
         "serve-n12.surface"]


@pytest.mark.parametrize("name", CELLS)
def test_driver_runs_and_checks_at_a_tiny_size(name):
    run, driver = cpu_run.tiny_run(name, seed=2147483907, seconds=1.5)
    out, checks = cpu_run.drive(run, driver)
    assert out["attempted"] >= 1 and out["failed"] == 0
    (metric, value), = out["end_to_end"].items()
    assert metric in {m["name"] for m in run.cell["end_to_end"]}
    assert np.isfinite(value) and value > 0
    assert checks and all(c["value"] <= c["limit"] for c in checks.values()), \
        checks


def test_each_per_layer_metric_has_a_reader_and_its_cells_report_what_it_moves():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"] for w in spec["workloads"]}
    reports = {m["name"]: set(m.get("workloads", cells))
               for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]
        assert set(m["workloads"]) <= reports[m["moves"]], m["name"]
    for w in spec["workloads"]:
        traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                             .read_text())
        if "rate_metric" in traffic:
            assert w["name"] in reports[traffic["rate_metric"]], w["name"]


# --------------------------------------------------------------------------
# a cell and a metric added as files only
# --------------------------------------------------------------------------

def test_new_cell_and_metric_are_found_by_name(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    traffic = json.loads((BENCH / "traffic" / "surface_table.json")
                         .read_text())
    traffic.update(loads=[0.05, 0.2], num_jobs=120, reps=1,
                   why="a tiny table added as data")
    (tmp_path / "bench" / "traffic" / "surface_tiny.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "metrics" / "sweeps_done.py").write_text(
        "def read(run, out):\n    return float(len(out['sweeps']))\n")
    spec["workloads"].append({"name": "serve-n12.tiny", "config": "serve-n12",
                              "traffic": "surface_tiny", "chips": 1,
                              "why": "added by files only"})
    spec["per_layer"].append({"name": "sweeps_done", "unit": "sweeps",
                              "better": "higher", "source": "host_clock",
                              "layer": "lane recurrence",
                              "moves": "table_lanejobs_per_s",
                              "workloads": ["serve-n12.tiny"]})
    for m in spec["end_to_end"]:
        if m["name"] == "table_lanejobs_per_s":
            m["workloads"].append("serve-n12.tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("serve-n12.tiny", tmp_path)
    assert [m["name"] for m in cell["per_layer"]] == ["sweeps_done"]
    assert {m["name"] for m in cell["end_to_end"]} == \
        {"table_lanejobs_per_s", "setup_s"}
    driver = harness.driver_for(cell)
    assert Path(driver.__file__).parent == tmp_path / "bench" / "drivers"
    run, driver = cpu_run.tiny_run("serve-n12.tiny", seed=5, seconds=1.0,
                                   root=tmp_path,
                                   sizes={"serve-n12.tiny": ({}, {})})
    out, checks = cpu_run.drive(run, driver)
    value = harness.metric_reader(cell, "sweeps_done").read(run, out)
    assert value == len(out["sweeps"]) >= 1
    assert all(c["value"] <= c["limit"] for c in checks.values())


# --------------------------------------------------------------------------
# the reference's inputs are the program's
# --------------------------------------------------------------------------

def test_reference_rows_are_the_pipelines_unique_rows():
    from reference import data as ref_data
    from repro.core.coding import FractionalRepetitionCode
    from repro.data.pipeline import DataConfig, coded_batch
    code = FractionalRepetitionCode(n=8, c=2)
    cfg = DataConfig(vocab_size=151936, seq_len=64, global_batch=4,
                     seed=2147483647)
    for step in (0, 5):
        toks, labs = coded_batch(cfg, step, code)
        rt, rl = ref_data.unique_rows(151936, 64, 4, 4, 2147483647, step)
        # coded row i carries part group_of(i); each part is one row here
        for i in range(8):
            np.testing.assert_array_equal(toks[i], rt[code.group_of(i)])
            np.testing.assert_array_equal(labs[i], rl[code.group_of(i)])


def test_reference_weights_have_the_programs_layout():
    import jax
    from reference import qwen3
    from repro.models import api
    run, driver = cpu_run.tiny_run("qwen3-0.6b.train-c2")
    mcfg = driver.model_config(run.config)
    want = jax.tree.map(lambda s: tuple(s.shape), api.param_shapes(mcfg))
    got = jax.tree.map(tuple, qwen3.param_shapes(run.config),
                       is_leaf=lambda x: isinstance(x, tuple))
    assert want == got


@pytest.mark.parametrize("chunk", [None, 64])
def test_reference_lanes_follow_the_engines_draws(chunk):
    from reference import lanes
    from repro.core import BiModal, Scaling
    from repro.core.scenario import Scenario
    from repro.runtime.cluster_batched import sweep
    sc = Scenario(BiModal(10.0, 0.2), Scaling.SERVER_DEPENDENT, 12)
    ks, loads = [2, 4, 12], [0.02, 0.08]
    sw = sweep(sc, loads, ks=ks, num_jobs=150, reps=2, preempt=False,
               seed=11, chunk_size=chunk)
    ref = lanes.surface({"family": "bimodal", "B": 10.0, "eps": 0.2}, None,
                        12, ks, loads, {"family": "poisson"}, 150, 2, 11,
                        False, chunk is not None)
    for i in range(len(loads)):
        for j, k in enumerate(ks):
            for m in ("mean", "p99"):
                assert lanes.relative_gap(float(sw.metric(m)[i, j]),
                                          ref[(i, k)][m]) < 1e-4
