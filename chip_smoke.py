"""Drive the system's main paths once on a TPU and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip paths only

One chip runs four phases, each through the entry points a user calls:

  kernels   ``coded_matmul`` at the paper-matvec job (encode, compute,
            decode from k of the n coded outputs), ``flash_attention`` at
            qwen3-0.6b's heads and ``ssd_scan`` at mamba2-1.3b's, compiled
            for the chip and compared with their references;
  trainer   ``repro.launch.train.main`` on qwen3-0.6b at full width and
            depth, n_workers=8, 8 coded rows x 512 tokens, at c=1 and at
            the planner's c*; the step-0 loss against a float32 reference;
  fleet     a load-aware k x load surface at n=10^4 workers on the fleet
            engine, and its negligible-load lanes against the closed form;
  adaptive  a load-aware ``AdaptivePlanner`` on the compiled-surface cache
            through a seeded load flip, to two commits with no fallback.

``--chips 4`` runs instead the sharded fleet surface against the same
surface on one device, and the 4-way data-parallel train step of
``launch/steps.build_train_cell`` against the one-device step.

Each check prints on its own line.  The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU the script stops before any phase with a non-zero exit.
Times printed here are smoke timings of one run, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
import traceback

import numpy as np

SEED = 0
#: the trainer's straggler law (``launch/train.py``'s default)
STRAGGLE = "bimodal:10:0.2"

# Tolerances, each a bound on max|out - ref| / max|ref| unless noted.
#: f32 kernels vs their references at "highest" precision: the MXU may
#: run an f32 product as one bf16 pass (2^-9 relative per product); sums
#: of random-sign terms keep the error near that fraction of the output
#: scale.  A wrong tile or a lost carry errs by O(1).
TOL_F32_KERNEL = 1e-2
#: bf16 flash attention: the output is rounded to bf16 (2^-9 relative)
#: on top of the f32 path's product error.
TOL_BF16_KERNEL = 2e-2
#: decode from k of n coded outputs: the kernel's error times the decode
#: matrix's row-sum norm, which ``phase_kernels`` prints beside it.
#: step-0 loss, bf16 train step vs f32 reference at "highest" precision:
#: bf16 activations (2^-9 relative per op) through 28 layers perturb the
#: logits by ~1e-2 relative; the loss, a mean over 4096 tokens of a
#: smooth function of them, moves less.
TOL_LOSS_REL = 1e-2
#: 4-chip vs 1-chip train step (same weights and batch, both bf16): the
#: programs sum the same bf16 products in different orders across
#: devices.
TOL_SHARDED_REL = 1e-2
#: closed-form check at negligible load: |mean - E[Y_k:n]| in standard
#: errors of the run's own mean.  Four keeps the chance that any of the
#: 25 lanes of a correct engine trips below 0.2%.
Z_CLOSED_FORM = 4.0


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases run at.  The defaults are the full sizes the chip
    runs; a rehearsal on the CPU passes smaller ones with ``interpret``."""

    matvec_rows: int = 12288          # configs/paper_matvec.py
    matvec_cols: int = 8192
    matvec_n: int = 12
    matvec_k: int = 6
    matvec_vectors: int = 128         # right-hand sides of A @ X
    attn_seq: int = 4096              # qwen3-0.6b heads
    attn_heads: int = 16
    attn_kv_heads: int = 8
    attn_head_dim: int = 128
    ssd_seq: int = 4096               # mamba2-1.3b SSD layer
    ssd_heads: int = 64
    ssd_head_dim: int = 64
    ssd_state: int = 128
    ssd_chunk: int = 256
    train_scale: str = "full"
    train_seq: int = 512
    train_rows: int = 8               # coded rows per step (fits 16 GB)
    train_workers: int = 8
    train_steps: int = 3
    fleet_n: int = 10_000
    fleet_jobs: int = 10_000
    fleet_chunk: int = 512
    fleet_loads: tuple = (0.02, 0.05, 0.1)
    # A job that arrives while the previous one runs waits for it, which
    # biases a lane's mean by about load x E[Y^2] / 2: at 1e-3 that is 7
    # standard errors of the k=5000 lane (sd 0.1, 10^4 jobs).  At 1e-5
    # it is under 0.3.  One job per chunk keeps the rebased f32 clock
    # near the ~1e5 gap (ulp 0.008, rounding that averages out).
    zero_load: float = 1e-5
    zero_load_chunk: int = 1
    adaptive_n: int = 12
    adaptive_steps: int = 150         # steps per regime
    interpret: bool = False


class Checks:
    """Collects named pass/fail lines."""

    def __init__(self):
        self.failed = []

    def expect(self, phase: str, what: str, ok: bool, detail: str = ""):
        print(f"[{phase}] {'PASS' if ok else 'FAIL'} {what}"
              + (f": {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(f"{phase}: {what}")


def _rel_err(out, ref) -> float:
    import jax.numpy as jnp
    out = jnp.asarray(out, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))


def _compiled_kernel(chk, name, fn, args, interpret, **static):
    """Compile ``fn`` for the device, check that the program holds a Mosaic
    kernel (and none in interpret mode), and return the executable."""
    compiled = fn.lower(*args, interpret=interpret, **static).compile()
    has = "tpu_custom_call" in compiled.as_text()
    chk.expect("kernels", f"{name} compiled program "
               f"{'has no' if interpret else 'holds a'} tpu_custom_call",
               has != interpret)
    return compiled


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------

def phase_kernels(chk: Checks, sz: Sizes) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import decode_blocks, decode_matrix, mds_generator
    from repro.kernels.coded_matmul import coded_matmul, coded_matmul_ref
    from repro.kernels.flash_attention import attention_ref, flash_attention
    from repro.kernels.ssd_scan import ssd_ref, ssd_scan

    highest = jax.default_matmul_precision("highest")
    keys = jax.random.split(jax.random.PRNGKey(SEED), 8)

    # the paper's job: A (rows x cols) in k row-blocks, n coded tasks
    n, k = sz.matvec_n, sz.matvec_k
    G = jnp.asarray(mds_generator(n, k))
    A = jax.random.normal(keys[0], (k, sz.matvec_rows // k, sz.matvec_cols))
    X = jax.random.normal(keys[1], (sz.matvec_cols, sz.matvec_vectors))
    run = _compiled_kernel(chk, "coded_matmul", coded_matmul, (G, A, X),
                           sz.interpret)
    coded = run(G, A, X)
    with highest:
        ref = coded_matmul_ref(G, A, X)
    err = _rel_err(coded, ref)
    chk.expect("kernels", f"coded_matmul n={n} k={k} A{tuple(A.shape)} "
               f"X{tuple(X.shape)} f32 vs ref", err <= TOL_F32_KERNEL,
               f"{err:.3e} <= {TOL_F32_KERNEL:g}")
    survivors = sorted(np.random.default_rng(SEED).choice(
        n, k, replace=False).tolist())
    with highest:
        decoded = decode_blocks(G, survivors, coded[jnp.asarray(survivors)])
        blocks = jnp.einsum("kmd,dv->kmv", A, X)
    norm = float(np.abs(decode_matrix(np.asarray(G), survivors)).sum(1).max())
    err = _rel_err(decoded, blocks)
    tol = TOL_F32_KERNEL * norm
    chk.expect("kernels", f"decode from workers {survivors} vs A_j @ X",
               err <= tol, f"{err:.3e} <= {TOL_F32_KERNEL:g} x decode "
               f"norm {norm:.2f}")
    del A, X, coded, ref, decoded, blocks

    # qwen3-0.6b attention heads, bf16, causal
    S, H, KV, D = (sz.attn_seq, sz.attn_heads, sz.attn_kv_heads,
                   sz.attn_head_dim)
    q = jax.random.normal(keys[2], (1, S, H, D), jnp.bfloat16)
    kk = jax.random.normal(keys[3], (1, S, KV, D), jnp.bfloat16)
    v = jax.random.normal(keys[4], (1, S, KV, D), jnp.bfloat16)
    run = _compiled_kernel(chk, "flash_attention", flash_attention,
                           (q, kk, v), sz.interpret)
    out = run(q, kk, v)
    with highest:
        rep = lambda t: jnp.repeat(t, H // KV, axis=2).transpose(0, 2, 1, 3)
        ref = attention_ref(q.astype(jnp.float32).transpose(0, 2, 1, 3),
                            rep(kk.astype(jnp.float32)),
                            rep(v.astype(jnp.float32))).transpose(0, 2, 1, 3)
    err = _rel_err(out, ref)
    chk.expect("kernels", f"flash_attention S={S} {H}q/{KV}kv x {D} bf16 "
               f"vs f32 ref", err <= TOL_BF16_KERNEL,
               f"{err:.3e} <= {TOL_BF16_KERNEL:g}")
    del q, kk, v, out, ref

    # mamba2-1.3b SSD layer, f32
    S, H, P, N = sz.ssd_seq, sz.ssd_heads, sz.ssd_head_dim, sz.ssd_state
    x = jax.random.normal(keys[5], (1, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(keys[6], (1, S, H)) - 2.0)
    a_log = jax.random.normal(keys[7], (H,))
    Adecay = -jnp.exp(a_log)
    Bm = jax.random.normal(keys[0], (1, S, N)) / math.sqrt(N)
    Cm = jax.random.normal(keys[1], (1, S, N)) / math.sqrt(N)
    args = (x, dt, Adecay, Bm, Cm)
    run = _compiled_kernel(chk, "ssd_scan", ssd_scan, args, sz.interpret,
                           chunk=sz.ssd_chunk)
    out = run(*args)
    with highest:
        ref = ssd_ref(*args)[0]
    err = _rel_err(out, ref)
    chk.expect("kernels", f"ssd_scan S={S} {H} heads x {P} state {N} "
               f"chunk {sz.ssd_chunk} f32 vs sequential ref",
               err <= TOL_F32_KERNEL, f"{err:.3e} <= {TOL_F32_KERNEL:g}")


def phase_trainer(chk: Checks, sz: Sizes) -> None:
    import jax

    from repro.data.pipeline import coded_batch
    from repro.launch import train
    from repro.models import api
    from repro.runtime.coded_step import weighted_loss_fn

    c_star = train.planned_c(train.parse_dist(STRAGGLE), sz.train_workers)
    chk.expect("trainer", f"planner's c* for {STRAGGLE} at "
               f"n={sz.train_workers} divides the coded rows",
               sz.train_rows % c_star == 0, f"c*={c_star}")
    ref_loss = None
    for c in sorted({1, c_star}):
        unique = sz.train_rows // c
        out = train.main([
            "--arch", "qwen3-0.6b", "--scale", sz.train_scale,
            "--steps", str(sz.train_steps), "--seq-len", str(sz.train_seq),
            "--unique-batch", str(unique),
            "--n-workers", str(sz.train_workers), "--c", str(c),
            "--straggle", STRAGGLE])
        trainer = out["trainer"]
        tag = f"c={c} unique={unique} rows={trainer.step_cfg.coded_batch_rows}"
        chk.expect("trainer", f"{tag}: ran {sz.train_steps} steps at the "
                   f"asked c", out["c"] == c and trainer.step_cfg.c == c
                   and len(out["losses"]) == sz.train_steps,
                   f"losses {out['losses']}")
        finite = np.isfinite(out["losses"] + out["grad_norms"])
        chk.expect("trainer", f"{tag}: losses and grad norms finite",
                   bool(finite.all()), f"grad norms {out['grad_norms']}")
        warm = out["step_seconds"][1:]
        print(f"[trainer] smoke timing (not a metric), {tag}: step 0 "
              f"{out['step_seconds'][0]:.3f} s incl. compile; warm steps "
              f"{[round(t, 4) for t in warm]} s", flush=True)

        # the same coded batch, decode weights and initial weights, in f32
        cfg = trainer.model_cfg
        toks, labs = coded_batch(trainer.data_cfg, 0, trainer.step_cfg.code)
        weights = trainer.weights_for(trainer.gather_alive(0))
        if ref_loss is None:
            ref_cfg = cfg.scaled(compute_dtype="float32")
            ref_loss = jax.jit(weighted_loss_fn(ref_cfg))
        params = api.init_params(cfg, jax.random.PRNGKey(train.INIT_SEED))
        with jax.default_matmul_precision("highest"):
            ref = float(ref_loss(params, toks, labs, weights))
        del params
        got = out["losses"][0]
        rel = abs(got - ref) / abs(ref)
        chk.expect("trainer", f"{tag}: step-0 loss vs f32 reference",
                   rel <= TOL_LOSS_REL,
                   f"{got:.6f} vs {ref:.6f}, rel {rel:.2e} <= "
                   f"{TOL_LOSS_REL:g}")


def _fleet_scenario(n: int):
    from repro.core import Scaling, ShiftedExp
    from repro.core.scenario import Scenario
    return Scenario(ShiftedExp(1.0, 5.0), Scaling.SERVER_DEPENDENT, n)


def phase_fleet(chk: Checks, sz: Sizes) -> None:
    from repro.api import LoadAwareLatency, MeanCompletionTime, Planner
    from repro.runtime.cluster_batched import validate_sweep_args
    from repro.runtime.fleet import build_fleet_lanes, run_fleet
    from repro.runtime.streamstats import welford_finalize_host

    sc = _fleet_scenario(sz.fleet_n)
    ks = sc.legal_ks()
    obj = LoadAwareLatency(num_jobs=sz.fleet_jobs, chunk_size=sz.fleet_chunk,
                           stream=True, seed=SEED)
    t0 = time.perf_counter()
    kstar = Planner().kstar_vs_load(sc, list(sz.fleet_loads), obj)
    dt = time.perf_counter() - t0
    chk.expect("fleet", f"k* surface n={sz.fleet_n} x {len(ks)} ks x "
               f"{len(sz.fleet_loads)} loads x {sz.fleet_jobs} jobs "
               f"(streaming, chunk {sz.fleet_chunk}): a legal k at "
               f"every load", all(k in ks for k in kstar.values()),
               f"{kstar}; smoke timing {dt:.2f} s incl. compile")

    # negligible load: every job meets an empty fleet, so each lane's mean
    # latency is the single-job E[Y_k:n]; the engine's own Welford state
    # gives the run's standard error
    ks_, loads, warmup, arrivals, speeds = validate_sweep_args(
        sc, [sz.zero_load], ks, sz.fleet_jobs, 1, 0)
    raw = run_fleet(sc, loads, build_fleet_lanes(None, sc.n, ks_, None),
                    num_jobs=sz.fleet_jobs, reps=1, preempt=True,
                    cancel_overhead=0.0, seed=SEED, warmup=warmup,
                    arrivals=arrivals, speeds=speeds, failures=None,
                    retry=None, chunk=sz.zero_load_chunk, stream=True,
                    reservoir=16, shard=None)
    cnt, mean, var = welford_finalize_host(
        raw.cnt.reshape(1, -1), raw.mean.reshape(1, -1),
        raw.m2.reshape(1, -1))
    exact = MeanCompletionTime().curve(sc, ks_)
    z = np.array([abs(mean[j] - exact[k]) / math.sqrt(var[j] / cnt[j])
                  for j, k in enumerate(ks_)])
    worst = int(np.argmax(z))
    chk.expect("fleet", f"load {sz.zero_load:g}: every k lane's mean vs "
               f"closed-form E[Y_k:n] within {Z_CLOSED_FORM:g} standard "
               f"errors", bool(np.all(z <= Z_CLOSED_FORM)),
               f"worst k={ks_[worst]}: {mean[worst]:.4f} vs "
               f"{exact[ks_[worst]]:.4f} ({z[worst]:.2f} se); k=1 "
               f"{mean[0]:.4f} vs {exact[ks_[0]]:.4f}, k={ks_[-1]} "
               f"{mean[-1]:.4f} vs {exact[ks_[-1]]:.4f}")


def phase_adaptive(chk: Checks, sz: Sizes) -> None:
    from repro.api import AdaptivePlanner
    from repro.control import replay
    from repro.core import BiModal, Regime, Scaling, ShiftedExp, \
        sample_regime_trace
    from repro.core.scenario import PoissonArrivals, Scenario
    from repro.obs.metrics import REGISTRY

    n, steps = sz.adaptive_n, sz.adaptive_steps
    service, scaling = ShiftedExp(1.0, 10.0), Scaling.SERVER_DEPENDENT
    trace = sample_regime_trace(
        [Regime(service, steps, arrivals=PoissonArrivals(0.001)),
         Regime(service, steps, arrivals=PoissonArrivals(0.03))],
        scaling, n, seed=SEED)
    planner = AdaptivePlanner(Scenario(BiModal(10.0, 0.3), scaling, n),
                              objective="load_aware")
    res = replay(trace, planner.controller, preempt=False)
    ev = res.events
    log = [(e.kind, e.old_policy.k, e.new_policy.k, round(e.replan_ms, 2),
            e.cached, e.warm) for e in ev]
    chk.expect("adaptive", "committed at least twice, every commit on the "
               "compiled-surface cache", len(ev) >= 2
               and all(e.cached and not e.fallback for e in ev),
               f"(kind, k, new k, replan ms, cached, warm): {log}")
    chk.expect("adaptive", "second re-plan hit a warm executable",
               len(ev) >= 2 and ev[1].warm)
    fallbacks = REGISTRY.counter("controller.surface_fallbacks").value
    chk.expect("adaptive", "controller.surface_fallbacks reads 0",
               fallbacks == 0, f"{fallbacks}")


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------

def phase_fleet_sharded(chk: Checks, sz: Sizes, ndev: int) -> None:
    from repro.runtime.fleet import fleet_sweep

    sc = _fleet_scenario(sz.fleet_n)
    kw = dict(loads=list(sz.fleet_loads), ks=sc.legal_ks(),
              num_jobs=sz.fleet_jobs, seed=SEED, chunk_size=sz.fleet_chunk,
              stream=True)
    one = fleet_sweep(sc, **kw)
    shard = fleet_sweep(sc, **kw, shard=ndev)
    names = ("mean", "p50", "p95", "p99", "utilization", "wasted_frac",
             "throughput")
    diff = {m: float(np.max(np.abs(one.metric(m) - shard.metric(m))))
            for m in names}
    chk.expect("fleet4", f"shard={ndev} surface equals the one-device "
               f"surface array for array", all(
                   np.array_equal(one.metric(m), shard.metric(m))
                   for m in names), f"max |diff| {diff}")


def phase_train_sharded(chk: Checks, sz: Sizes, ndev: int) -> None:
    import jax

    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import DataConfig, synthetic_batch
    from repro.launch import train
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_train_cell, default_opt_cfg
    from repro.models import api
    from repro.optim import adamw
    from repro.runtime.coded_step import make_train_step

    cfg = train.model_config("qwen3-0.6b", sz.train_scale)
    shape = ShapeConfig("smoke", "train", sz.train_seq, sz.train_rows)
    cell = build_train_cell(cfg, shape, make_host_mesh(data=ndev))
    toks, labs = synthetic_batch(
        DataConfig(cfg.vocab_size, sz.train_seq, sz.train_rows, SEED), 0)
    weights = np.ones(sz.train_rows, np.float32)
    key = jax.random.PRNGKey(train.INIT_SEED)
    opt_cfg = default_opt_cfg()           # what build_train_cell steps with

    def step(run, place):
        params = api.init_params(cfg, key)
        args = place((params, adamw.init(opt_cfg, params), toks, labs,
                      weights))
        del params
        _, _, metrics = run(*args)
        return float(metrics["loss"]), float(metrics["grad_norm"])

    loss4, gn4 = step(cell.lower().compile(),
                      lambda a: jax.device_put(a, cell.in_shardings))
    loss1, gn1 = step(jax.jit(make_train_step(cfg, opt_cfg),
                              donate_argnums=(0, 1)),
                      lambda a: jax.device_put(a, jax.devices()[0]))
    for name, a, b in (("loss", loss4, loss1), ("grad norm", gn4, gn1)):
        rel = abs(a - b) / abs(b)
        chk.expect("train4", f"{ndev}-chip (data={ndev}) vs one-chip "
                   f"train step {name}, {sz.train_rows} rows x "
                   f"{sz.train_seq}", rel <= TOL_SHARDED_REL,
                   f"{a:.6f} vs {b:.6f}, rel {rel:.2e} <= "
                   f"{TOL_SHARDED_REL:g}")


# --------------------------------------------------------------------------

def run_phases(phases, sz: Sizes) -> Checks:
    chk = Checks()
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn(chk, sz)
        except Exception:                     # report, go on to the next
            traceback.print_exc()
            chk.expect(name, "phase raised", False)
        print(f"[{name}] phase took {time.perf_counter() - t0:.1f} s "
              f"(smoke timing)", flush=True)
    return chk


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU found ({e})", file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX found {dev.platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind}, count {len(devices)}", flush=True)

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    if args.chips == 4:
        phases = [("fleet4", lambda c, s: phase_fleet_sharded(c, s, 4)),
                  ("train4", lambda c, s: phase_train_sharded(c, s, 4))]
    else:
        phases = [("kernels", phase_kernels), ("trainer", phase_trainer),
                  ("fleet", phase_fleet), ("adaptive", phase_adaptive)]
    chk = run_phases(phases, Sizes())
    if chk.failed:
        print(f"chip_smoke: {len(chk.failed)} check(s) failed: "
              f"{chk.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
