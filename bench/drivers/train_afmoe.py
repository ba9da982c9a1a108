"""The coded data-parallel train step of an AFMoE model on one chip's
share of its experts.

As ``train.py`` drives the dense model: set-up builds one
``CodedTrainer`` with its compiled step (data from the seed, straggler
drops from the seeded simulator, decode weights from the
fractional-repetition code), makes the weights from the seed on the
device, and drives the first ``checked_steps`` steps through
``CodedTrainer.run_step``, the object and call the window then drives.
Each step's loss is fetched on the host; each step's expert counters
(rows per held expert, by expert layer) stay on the device until the
window ends.  ``train_tokens_per_s`` counts the UNIQUE tokens of every
step completed in the window, over the time from the window's start to
the end of the last one.  At set-up the names of the grouped-matmul
kernel's operations are read from the compiled step's HLO text, for the
kernel's roofline share in a traced run.

``correct``: the reference (``reference/afmoe.py``, float32 at
``highest``) trains the same seeded weights through the same first
steps on the same unique rows.  Compared, each against the traffic
file's limit: ``loss_gap``, ``grad_gap``, ``update_gap`` and
``grad_gap_median`` as ``train.py`` defines them, and

  route_gap      the share of the first step's (token, slot) selections
                 of every expert layer that are not among the
                 reference's, the program's read from one replica of
                 each unique row;
  held_rows_gap  sum |rows / c - reference rows| / sum reference rows
                 over the held experts of every expert layer, the
                 program's rows from the first step's counters (c
                 replicas of each unique row).
"""
from __future__ import annotations

import re
import time

import numpy as np

from drivers import train as dense
from reference import afmoe as ref_model
from reference import data as ref_data

#: the grouped-matmul kernel's custom calls (XLA's ragged dot and the
#: metadata call it launches), by their instruction names in the HLO
KERNEL_OPS = re.compile(r"^ragged-dot")


def model_config(cfg):
    """The program's ModelConfig for this configuration file."""
    from repro.configs.base import get_config
    kinds = tuple("sliding" if k == "sliding_attention" else "global"
                  for k in cfg["layer_types"][:cfg["num_hidden_layers"]])
    return get_config("trinity-mini").scaled(
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        num_experts=cfg["num_experts_routed"],
        experts_held=cfg["num_experts"], expert_first=cfg["expert_first"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_d_ff=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["num_shared_experts"],
        route_scale=cfg["route_scale"],
        num_dense_layers=cfg["num_dense_layers"], layer_types=kinds,
        attn_window=cfg["sliding_window"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"],
        param_dtype=cfg["precision"]["master"],
        compute_dtype=cfg["precision"]["compute"], remat="full",
        flash_block_kv=512)


def build_trainer(cfg, tr, data_seed, sim_seed):
    from repro.core.distributions import Scaling
    from repro.data import DataConfig
    from repro.launch.train import parse_dist
    from repro.runtime import CodedStepConfig, CodedTrainer, StragglerSim
    coded = cfg["coded"]
    c = tr["c"]
    step_cfg = CodedStepConfig(n_workers=coded["n_workers"], c=c,
                               unique_batch=tr["unique_batch"])
    data_cfg = DataConfig(vocab_size=cfg["vocab_size"], seq_len=tr["seq_len"],
                          global_batch=tr["unique_batch"], seed=data_seed)
    sim = StragglerSim(parse_dist(coded["straggle"]), Scaling.DATA_DEPENDENT,
                       n=coded["n_workers"], s=c, delta=1.0, seed=sim_seed)
    return CodedTrainer(model_config(cfg), data_cfg, step_cfg,
                        dense.opt_config(tr),
                        alive_fn=sim.alive_fn(coded["deadline"]))


seeds = dense.seeds


def _unique_choices(trainer, choices):
    """(expert layers, coded rows, S, k) -> (expert layers, unique rows *
    S, k): the first replica of each part group's rows."""
    code = trainer.step_cfg.code
    per = trainer.step_cfg.per_worker_rows
    workers = [min(i for i in range(code.n) if code.group_of(i) == j)
               for j in range(code.num_groups)]
    x = np.asarray(choices)[:, [i * per + r for i in workers
                                for r in range(per)]]
    return x.reshape(x.shape[0], -1, x.shape[-1])


def kernel_ops(trainer, params, opt, tr):
    """Names of the grouped-matmul kernel's operations in the compiled
    step (the trace's op events carry no op_name on a v5e)."""
    import jax
    import jax.numpy as jnp
    rows = tr["unique_batch"] * tr["c"]
    tok = jax.ShapeDtypeStruct((rows, tr["seq_len"]), jnp.int32)
    w = jax.ShapeDtypeStruct((trainer.step_cfg.n_workers,), jnp.float32)
    text = trainer.step_fn.lower(params, opt, tok, tok, w).compile().as_text()
    names = set()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = .*custom-call\(", line)
        if m and "tpu_custom_call" in line and KERNEL_OPS.match(m.group(1)):
            names.add(m.group(1))
    return sorted(names)


def first_steps(cfg, tr, sd, steps=None):
    """Build the trainer, make the weights, drive the checked steps; the
    program's readings and the state the window continues from."""
    import jax
    from repro.models import api
    from repro.optim import adamw
    trainer = build_trainer(cfg, tr, sd["data"], sd["sim"])
    params = ref_model.init_params(cfg, sd["weights"])
    want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)),
                        api.param_shapes(trainer.model_cfg))
    got = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), params)
    if want != got:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{got} vs {want}")
    opt_state = adamw.init(trainer.opt_cfg, params)
    losses, g1, choices, rows = [], None, None, None
    b1 = trainer.opt_cfg.b1
    for t in range(tr["checked_steps"] if steps is None else steps):
        params, opt_state, m = trainer.run_step(params, opt_state, t)
        losses.append(float(m["loss"]))
        if t == 0:
            g1 = {k: v / (1.0 - b1)
                  for k, v in ref_model.leaf_norms(opt_state.mu).items()}
            choices = _unique_choices(trainer, m["expert_choices"])
            rows = np.asarray(m["expert_rows"]) / tr["c"]
        del m
    p0 = ref_model.init_params(cfg, sd["weights"])
    upd = ref_model.diff_norms(params, p0)
    del p0
    return dict(trainer=trainer, params=params, opt=opt_state,
                losses=losses, g1=g1, upd=upd, choices=choices, rows=rows,
                step=len(losses))


def setup(run):
    state = first_steps(run.config, run.traffic, seeds(run))
    if run.trace:
        state["kernel_ops"] = kernel_ops(state["trainer"], state["params"],
                                         state["opt"], run.traffic)
    return state


def counters(state):
    return {}


def window(run, state):
    tr = run.traffic
    trainer = state["trainer"]
    params, opt = state["params"], state["opt"]
    state["params"] = state["opt"] = None
    step = state["step"]
    ends, losses, loads, traced, failed = [], [], [], [], 0
    t0 = time.perf_counter()
    deadline = t0 + run.seconds
    while time.perf_counter() < deadline:
        tracing = run.tracing
        with run.annotate("train_step"):
            params, opt, m = trainer.run_step(params, opt, step)
            loss = float(m["loss"])
        now = time.perf_counter()
        step += 1
        if not np.isfinite(loss):
            failed += 1
        if now <= deadline:
            ends.append(now - t0)
            losses.append(loss)
            loads.append(m["expert_rows"])
            traced.append(tracing)
        del m
        if run.trace_due(tr["trace_seconds"]):
            run.stop_trace()
    del params, opt
    # device-to-host copies after the window: no compile, no sync inside
    rows = np.stack([np.asarray(r) for r in loads]) if loads \
        else np.zeros((0,))
    tokens = tr["unique_batch"] * tr["seq_len"]
    rate = len(ends) * tokens / ends[-1] if ends else float("nan")
    dts = np.diff([0.0] + ends)
    lines = [f"train: {len(ends)} steps of {tokens} unique tokens in "
             f"{ends[-1] if ends else float('nan')!r} s; step seconds "
             f"min/median/max {[float(x) for x in np.percentile(dts, [0, 50, 100])] if ends else []};"
             f" dropped stragglers {trainer.stragglers_dropped}, barrier "
             f"fallbacks {trainer.decode_failures}",
             f"train: checked-step losses {state['losses']}, window losses "
             f"first/last {losses[:1]} {losses[-1:]}",
             f"train: rows per held expert, window total by expert layer "
             f"{rows.sum(axis=0).tolist() if rows.size else []}"]
    return dict(attempted=step - state["step"], failed=failed, lines=lines,
                step_seconds=dts.tolist(), unique_tokens_per_step=tokens,
                expert_rows=rows, traced_steps=traced,
                kernel_ops=state.get("kernel_ops", []),
                end_to_end={"train_tokens_per_s": rate})


def reference_readings(cfg, tr, sd, precision="highest", rows="all"):
    """The reference's (losses, g1, upd, choices, rows) for the checked
    steps.  ``rows`` ``"half"`` trains on the first half of each step's
    unique rows, repeated in place of the rest (the fault of a batch half
    left out, the mean taken over the rest, at the batch's shape)."""
    groups = cfg["coded"]["n_workers"] // tr["c"]
    batches = []
    for t in range(tr["checked_steps"]):
        tok, lab = ref_data.unique_rows(cfg["vocab_size"], tr["seq_len"],
                                        tr["unique_batch"], groups,
                                        sd["data"], t)
        if rows == "half":
            h = len(tok) // 2
            tok, lab = np.tile(tok[:h], (2, 1)), np.tile(lab[:h], (2, 1))
        batches.append((tok, lab))
    return ref_model.train_steps(cfg, tr["optimizer"], sd["weights"],
                                 batches, precision)


loss_gaps = dense.loss_gaps


def route_gap(prog, ref):
    """Share of (token, slot) selections not among the reference's."""
    p, r = prog[..., :, None], ref[..., None, :]
    return 1.0 - float(np.mean(np.any(p == r, axis=-1)))


def gaps(prog, refr):
    """``train.py``'s numbers, with ``route_gap`` and ``held_rows_gap``."""
    out = dense.gaps(prog[:3], refr[:3])
    out["route_gap"] = route_gap(np.asarray(prog[3]), np.asarray(refr[3]))
    pr, rr = np.asarray(prog[4], float), np.asarray(refr[4], float)
    out["held_rows_gap"] = float(np.abs(pr - rr).sum() / rr.sum())
    return out


def check(run, state, out):
    tr = run.traffic
    prog = (state["losses"], state["g1"], state["upd"], state["choices"],
            state["rows"])
    refr = reference_readings(run.config, tr, seeds(run))
    nums = gaps(prog, refr)
    worst = {name: max(g.items(), key=lambda kv: kv[1])
             for name, g in (("grad", dense._leaf_gaps(prog[1], refr[1])),
                             ("update", dense._leaf_gaps(prog[2], refr[2])))}
    print(f"check: losses {state['losses']} vs reference {refr[0]} (gaps "
          f"{loss_gaps(state['losses'], refr[0])}); rows {prog[4].tolist()} "
          f"vs reference {np.asarray(refr[4]).tolist()}; worst leaves "
          f"{worst}; numbers {nums}", flush=True)
    return {k: {"value": nums[k], "limit": v}
            for k, v in tr["limits"].items()}
