"""Compiles for a described TPU v5e, with no chip attached.

The Pallas kernels at the shapes of the main paths must reach the chip's
compiler as Mosaic kernels (``tpu_custom_call``), not be refused for
their tiling, and the full-width qwen3-0.6b coded train step must fit one
chip's memory.  Nothing runs: these are compiles only.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.configs.paper_matvec import CONFIG as MATVEC
from repro.kernels.coded_matmul import coded_matmul
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.models import api
from repro.optim import adamw
from repro.runtime.coded_step import CodedStepConfig, make_coded_train_step

#: one v5e chip's HBM as its compiler counts it (15.75 GiB)
V5E_HBM_BYTES = int(15.75 * 2**30)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(name, sds):
    """(jitted kernel, argument shapes, static args) at a main-path shape."""
    if name == "coded_matmul":      # the paper-matvec job, n=12, k=6
        n, k = MATVEC.n_workers, 6
        dt = jnp.dtype(MATVEC.dtype)
        return coded_matmul, (sds((n, k), dt),
                              sds((k, MATVEC.rows // k, MATVEC.cols), dt),
                              sds((MATVEC.cols, 128), dt)), {}
    if name == "flash_attention":   # qwen3-0.6b heads, 4k tokens, bf16
        cfg = get_config("qwen3-0.6b")
        d = cfg.resolved_head_dim
        return flash_attention, (
            sds((1, 4096, cfg.num_heads, d), jnp.bfloat16),
            sds((1, 4096, cfg.num_kv_heads, d), jnp.bfloat16),
            sds((1, 4096, cfg.num_kv_heads, d), jnp.bfloat16)), {}
    cfg = get_config("mamba2-1.3b")  # ssd_scan: one mamba2-1.3b SSD layer
    h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    return ssd_scan, (sds((1, 4096, h, p), jnp.float32),
                      sds((1, 4096, h), jnp.float32), sds((h,), jnp.float32),
                      sds((1, 4096, n), jnp.float32),
                      sds((1, 4096, n), jnp.float32)), \
        {"chunk": cfg.ssm_chunk}


@pytest.mark.parametrize("name", ["coded_matmul", "flash_attention",
                                  "ssd_scan"])
def test_kernel_compiles_for_v5e(one_chip, name):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    fn, args, static = _kernel_case(name, sds)
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("c", [1, 2])
def test_qwen3_coded_train_step_fits_one_v5e(one_chip, c):
    """8 coded rows x 512 tokens at n_workers=8: unique batch 8 // c, so
    the planner's c=2 keeps the rows (and the memory) of c=1."""
    cfg = get_config("qwen3-0.6b")
    opt = adamw.AdamWConfig()
    step_cfg = CodedStepConfig(n_workers=8, c=c, unique_batch=8 // c)
    step = make_coded_train_step(cfg, opt, step_cfg)
    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(put, api.param_shapes(cfg))
    opt_state = jax.tree.map(put, adamw.state_shapes(opt,
                                                     api.param_shapes(cfg)))
    toks = jax.ShapeDtypeStruct((step_cfg.coded_batch_rows, 512), jnp.int32,
                                sharding=one_chip)
    coeffs = jax.ShapeDtypeStruct((8,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt_state, toks, toks, coeffs).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used <= V5E_HBM_BYTES, (used, V5E_HBM_BYTES)


def test_trinity_share_train_step_fits_one_v5e(one_chip):
    """The trinity-mini.train-c2 cut (6 layers, 8 of 128 experts held,
    one eighth of the vocabulary) at its 4 coded rows x 4,096 tokens: the
    grouped matmul reaches the chip's compiler as a Mosaic kernel and the
    step fits one chip."""
    full = get_config("trinity-mini")
    cfg = full.scaled(num_layers=6, layer_types=full.layer_types[:6],
                      experts_held=8, vocab_size=25024, flash_block_kv=512)
    opt = adamw.AdamWConfig()
    step_cfg = CodedStepConfig(n_workers=4, c=2, unique_batch=2)
    step = make_coded_train_step(cfg, opt, step_cfg)
    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(put, api.param_shapes(cfg))
    opt_state = jax.tree.map(put, adamw.state_shapes(opt,
                                                     api.param_shapes(cfg)))
    toks = jax.ShapeDtypeStruct((4, 4096), jnp.int32, sharding=one_chip)
    coeffs = jax.ShapeDtypeStruct((4,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt_state, toks, toks, coeffs).compile()
    assert "ragged-dot" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used <= V5E_HBM_BYTES, (used, V5E_HBM_BYTES)
