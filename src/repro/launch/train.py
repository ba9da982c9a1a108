"""Production training driver: coded data parallelism with online re-planning.

Runs the full control loop of DESIGN.md §2 on real hardware (here: CPU-host
mesh with simulated straggling; on a pod: the same code with gather
timeouts feeding the telemetry):

  1. each step is dispatched as an [n, c] fractional-repetition coded job;
  2. per-worker completion times land in Telemetry;
  3. every ``replan_every`` steps the best-fit service model is re-fitted
     and the replication factor c* re-planned (paper Secs. IV-VI / Table I);
  4. async checkpoints every ``ckpt_every`` steps; restart resumes from the
     latest complete checkpoint, on ANY worker count (elastic).

Usage (CPU example -- a reduced config):
    PYTHONPATH=src python -m repro.launch.train \\
        --arch qwen3-0.6b --scale tiny --steps 50 --n-workers 8 \\
        --straggle bimodal:10:0.3 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import checkpoint as ckpt
from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.core.distributions import BiModal, Pareto, Scaling, ShiftedExp
from repro.data import DataConfig
from repro.models import api
from repro.optim import adamw
from repro.api import Scenario
from repro.runtime import (CodedStepConfig, CodedTrainer, StragglerSim,
                           Telemetry, best_fr_policy)

#: the key the initial weights are drawn from
INIT_SEED = 0

TINY = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
            vocab_size=512, ssm_state=16, ssm_head_dim=16, num_experts=0,
            attn_every=0, flash_block_kv=64, remat="none",
            embedding_inputs=False, qk_norm=False, head_dim=None,
            compute_dtype="float32", param_dtype="float32")
SMALL = dict(num_layers=4, d_model=256, num_heads=8, num_kv_heads=4,
             d_ff=1024, vocab_size=2048, flash_block_kv=128,
             num_experts=0, attn_every=0, embedding_inputs=False,
             head_dim=None)


def exo_delta(dist, delta):
    """Exogenous per-CU delta for a Scenario: ShiftedExp carries its own
    shift, so only Pareto/Bi-Modal take the override (Sec. V-B, VI-B)."""
    return None if isinstance(dist, ShiftedExp) else delta


def parse_dist(spec: str):
    """'bimodal:B:eps' | 'sexp:delta:W' | 'pareto:lam:alpha' | 'none'."""
    if spec == "none":
        return None
    kind, a, b = spec.split(":")
    a, b = float(a), float(b)
    if kind == "bimodal":
        return BiModal(B=a, eps=b)
    if kind == "sexp":
        return ShiftedExp(delta=a, W=b)
    if kind == "pareto":
        return Pareto(lam=a, alpha=b)
    raise ValueError(spec)


def model_config(arch: str, scale: str):
    """The ``arch`` config at ``scale``: "full" as published, or cut to
    the "tiny"/"small" widths above."""
    cfg = get_config(arch)
    cut = {"tiny": TINY, "small": SMALL, "full": {}}[scale]
    return cfg.scaled(**{k: v for k, v in cut.items() if hasattr(cfg, k)})


def planned_c(dist, n_workers: int) -> int:
    """The planner's replication factor c* for a straggler law on
    ``n_workers`` data-dependent workers (1 when nothing straggles)."""
    if dist is None:
        return 1
    policy, _ = best_fr_policy(
        Scenario(dist, Scaling.DATA_DEPENDENT, n_workers,
                 delta=exo_delta(dist, 1.0)))
    return policy.c


def main(argv=None):
    """Train; returns the planned ``c``, the ``trainer`` and the per-step
    ``losses``, ``grad_norms`` and wall ``step_seconds``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--scale", choices=["tiny", "small", "full"], default="tiny")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--unique-batch", type=int, default=8)
    ap.add_argument("--n-workers", type=int, default=8)
    ap.add_argument("--c", type=int, default=0, help="0 = plan from model")
    ap.add_argument("--straggle", default="bimodal:10:0.2")
    ap.add_argument("--deadline", type=float, default=5.0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--replan-every", type=int, default=25)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = model_config(args.arch, args.scale)

    dist = parse_dist(args.straggle)
    scaling = Scaling.DATA_DEPENDENT
    c = args.c or planned_c(dist, args.n_workers)
    print(f"redundancy plan: n={args.n_workers} c={c} "
          f"(rate {(args.n_workers - c + 1)}/{args.n_workers})")

    step_cfg = CodedStepConfig(n_workers=args.n_workers, c=c,
                               unique_batch=args.unique_batch)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                          global_batch=args.unique_batch)
    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=10,
                                decay_steps=max(args.steps, 100))

    sim = None
    alive_fn = None
    if dist is not None:
        sim = StragglerSim(dist, scaling, n=args.n_workers, s=c,
                           delta=1.0, seed=7)
        alive_fn = sim.alive_fn(args.deadline)

    trainer = CodedTrainer(cfg, data_cfg, step_cfg, opt_cfg,
                           alive_fn=alive_fn)
    telem = Telemetry(window=256)

    # ---- init or resume -------------------------------------------------
    start = 0
    params = api.init_params(cfg, jax.random.PRNGKey(INIT_SEED))
    opt_state = adamw.init(opt_cfg, params)
    if args.ckpt_dir:
        latest = ckpt.latest_step(args.ckpt_dir)
        if latest is not None:
            (restored, _) = ckpt.restore(args.ckpt_dir, latest,
                                         {"p": params, "o": opt_state})
            params = jax.tree.map(jax.numpy.asarray, restored["p"])
            opt_state = jax.tree.map(jax.numpy.asarray, restored["o"])
            start = latest
            print(f"resumed from step {start}")

    pending = None
    losses, grad_norms, step_seconds = [], [], []
    t0 = time.time()
    for step in range(start, args.steps):
        ts = time.perf_counter()
        params, opt_state, metrics = trainer.run_step(params, opt_state, step)
        losses.append(float(metrics["loss"]))          # waits for the step
        grad_norms.append(float(metrics["grad_norm"]))
        step_seconds.append(time.perf_counter() - ts)
        if sim is not None:
            telem.record_step(sim.sample_times(step), task_size=c)
        if (step + 1) % 10 == 0:
            print(f"step {step+1:5d} loss {losses[-1]:.4f} "
                  f"gnorm {grad_norms[-1]:.3f} "
                  f"dropped {trainer.stragglers_dropped} "
                  f"barrier-fallbacks {trainer.decode_failures}")
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            if pending is not None:
                pending.result()
            pending = ckpt.save_async(args.ckpt_dir, step + 1,
                                      {"p": params, "o": opt_state})
        if dist is not None and (step + 1) % args.replan_every == 0 \
                and telem.num_samples >= 32:
            fitted, family = telem.fit()
            new_policy, _ = best_fr_policy(
                Scenario(fitted, scaling, args.n_workers,
                         delta=exo_delta(fitted, 1.0)))
            if new_policy.c != trainer.step_cfg.c:
                print(f"re-plan @ {step+1}: fitted {family} -> "
                      f"c* = {new_policy.c} (was {trainer.step_cfg.c})")
                trainer.step_cfg = CodedStepConfig.from_policy(
                    new_policy, unique_batch=args.unique_batch)
    if pending is not None:
        pending.result()
    dt = time.time() - t0
    print(f"done: {args.steps - start} steps in {dt:.1f}s "
          f"({(args.steps - start)/max(dt,1e-9):.2f} steps/s)")
    return dict(c=c, trainer=trainer, losses=losses, grad_norms=grad_norms,
                step_seconds=step_seconds)


if __name__ == "__main__":
    main()
