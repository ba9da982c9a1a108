"""Dry-run machinery smoke tests.

The full 512-placeholder-device sweep lives in benchmarks/roofline.py (it
sets XLA_FLAGS before jax init, which cannot happen inside this pytest
process).  Here we (a) compile one representative cell per step-kind on a
small in-process mesh to prove the builders + shardings are coherent, and
(b) run one real subprocess dry-run cell end to end.
"""
import json
import os
import subprocess
import sys

import jax
import pytest

from repro.configs.base import SHAPES, applicable_shapes, get_config
from repro.launch import hlo_analysis as H
from repro.launch.mesh import batch_spec, make_host_mesh
from repro.launch.steps import (build_decode_cell, build_prefill_cell,
                                build_train_cell)
from tests.test_models_smoke import reduced

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _small_shape(kind):
    from repro.configs.base import ShapeConfig
    if kind == "train":
        return ShapeConfig("train_4k", "train", 64, 4)
    if kind == "prefill":
        return ShapeConfig("prefill_32k", "prefill", 64, 2)
    return ShapeConfig("decode_32k", "decode", 64, 4)


@pytest.mark.parametrize("arch,kind", [
    ("qwen3-0.6b", "train"), ("dbrx-132b", "train"),
    ("mamba2-1.3b", "train"), ("zamba2-1.2b", "decode"),
    ("hubert-xlarge", "prefill"), ("yi-9b", "decode"),
])
def test_cell_compiles_on_host_mesh(arch, kind):
    cfg = reduced(arch)
    mesh = make_host_mesh()
    shape = _small_shape(kind)
    if kind == "train":
        cell = build_train_cell(cfg, shape, mesh)
    elif kind == "prefill":
        cell = build_prefill_cell(cfg, shape, mesh)
    else:
        cell = build_decode_cell(cfg, shape, mesh)
    with mesh:
        compiled = cell.lower().compile()
    cost = H.hlo_cost(compiled.as_text())
    assert cost["flops"] > 0
    assert cost["bytes"] > 0


def test_applicable_shapes_matrix():
    """The 31-cell assignment matrix from DESIGN.md §6."""
    total = 0
    for arch in [a for a in
                 __import__("repro.configs.base", fromlist=["ARCH_IDS"]).ARCH_IDS
                 if a != "paper-matvec"]:
        cfg = get_config(arch)
        shapes = applicable_shapes(cfg)
        total += len(shapes)
        if cfg.family in ("encoder", "audio"):
            assert "decode_32k" not in shapes and "long_500k" not in shapes
        if cfg.family in ("ssm", "hybrid"):
            assert "long_500k" in shapes
        if cfg.family in ("dense", "moe", "vlm"):
            assert "long_500k" not in shapes
    assert total == 31


def test_batch_spec_divisibility():
    mesh = make_host_mesh()
    assert batch_spec(mesh, 1) is not None        # B=1 must not crash


def test_host_mesh_axes_are_auto():
    """``constrain`` places activations with with_sharding_constraint,
    which refuses Explicit mesh axes (JAX's make_mesh default)."""
    from jax.sharding import AxisType
    mesh = make_host_mesh()
    assert all(t == AxisType.Auto for t in mesh.axis_types)


def test_host_mesh_refuses_more_devices_than_exist():
    with pytest.raises(ValueError, match="devices"):
        make_host_mesh(data=len(jax.devices()) + 1)


@pytest.mark.slow
def test_subprocess_dryrun_single_cell():
    """One real 256-chip dry-run in a subprocess (XLA_FLAGS isolation)."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "qwen3-0.6b", "--shape", "decode_32k",
         "--mesh", "single"],
        env=env, capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ok" in out.stdout
