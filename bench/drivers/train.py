"""The coded data-parallel train step at the planned c.

Set-up builds one ``CodedTrainer`` with its compiled step, as
``launch/train.main`` does (data from the seed, straggler drops from the
seeded simulator, decode weights from the fractional-repetition code),
makes the weights from the seed on the device, and drives the first
``checked_steps`` steps through ``CodedTrainer.run_step``: the same
object and call the window then drives.  Each step's loss is fetched on
the host.  ``train_tokens_per_s`` counts the UNIQUE tokens of every step
completed in the window, over the time from the window's start to the
end of the last one.

``correct``: the reference (``reference/qwen3.py``, float32 at
``highest``) trains the same seeded weights through the same first
steps on the same unique rows (``reference/data.py``).  Compared, each
against the traffic file's limit:

  loss_gap     the relative gap of the first step's loss (the later
               steps' losses are printed; see ``gaps`` for why they are
               not compared);
  grad_gap     the first gradient as AdamW received it, read from its
               first moment after step 1 (mu / (1 - b1)): the largest
               gap of a leaf's norm, over the larger of that leaf's
               reference norm and the median leaf's;
  update_gap   the parameters' change over the checked steps, by leaf,
               measured the same way; leaves whose reference gradient is
               under a thousandth of the median leaf's are left out;
  grad_gap_median, update_gap_median  the median leaf's gaps, where the
               traffic file gives them a limit.
"""
from __future__ import annotations

import time

import numpy as np

from reference import data as ref_data
from reference import qwen3 as ref_model

#: the configuration keys of the program's ModelConfig, by the published name
_WIDTHS = {"hidden_size": "d_model", "num_hidden_layers": "num_layers",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
           "intermediate_size": "d_ff", "vocab_size": "vocab_size",
           "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}


def model_config(cfg):
    """The program's ModelConfig for this configuration file."""
    from repro.configs.base import get_config
    base = get_config("qwen3-0.6b")
    over = {v: cfg[k] for k, v in _WIDTHS.items()}
    over.update(param_dtype=cfg["precision"]["master"],
                compute_dtype=cfg["precision"]["compute"], remat="full",
                qk_norm=True)
    return base.scaled(**over)


def opt_config(tr):
    from repro.optim import adamw
    o = tr["optimizer"]
    return adamw.AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"],
                             eps=o["eps"], weight_decay=o["weight_decay"],
                             clip_norm=o["clip_norm"],
                             warmup_steps=o["warmup_steps"],
                             decay_steps=o["decay_steps"],
                             min_lr_ratio=o["min_lr_ratio"])


def build_trainer(cfg, tr, data_seed, sim_seed):
    from repro.core.distributions import Scaling
    from repro.data import DataConfig
    from repro.launch.train import parse_dist
    from repro.runtime import CodedStepConfig, CodedTrainer, StragglerSim
    mcfg = model_config(cfg)
    coded = cfg["coded"]
    c = tr["c"]
    step_cfg = CodedStepConfig(n_workers=coded["n_workers"], c=c,
                               unique_batch=tr["unique_batch"])
    data_cfg = DataConfig(vocab_size=cfg["vocab_size"], seq_len=tr["seq_len"],
                          global_batch=tr["unique_batch"], seed=data_seed)
    sim = StragglerSim(parse_dist(coded["straggle"]), Scaling.DATA_DEPENDENT,
                       n=coded["n_workers"], s=c, delta=1.0, seed=sim_seed)
    return CodedTrainer(mcfg, data_cfg, step_cfg, opt_config(tr),
                        alive_fn=sim.alive_fn(coded["deadline"]))


def seeds(run):
    return dict(weights=run.sub_seed("weights"), data=run.sub_seed("data"),
                sim=run.sub_seed("stragglers"))


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
    losses, g1 = [], None
    b1 = trainer.opt_cfg.b1
    for t in range(tr["checked_steps"] if steps is None else steps):
        params, opt_state, m = trainer.run_step(params, opt_state, t)
        losses.append(float(m["loss"]))
        if t == 0:
            g1 = {k: v / (1.0 - b1)
                  for k, v in ref_model.leaf_norms(opt_state.mu).items()}
    p0 = ref_model.init_params(cfg, sd["weights"])
    upd = ref_model.diff_norms(params, p0)
    del p0
    return dict(trainer=trainer, params=params, opt=opt_state,
                losses=losses, g1=g1, upd=upd, step=len(losses))


def setup(run):
    return first_steps(run.config, run.traffic, seeds(run))


def counters(state):
    return {}


def window(run, state):
    tr = run.traffic
    trainer = state["trainer"]
    params, opt = state["params"], state["opt"]
    state["params"] = state["opt"] = None
    step = state["step"]
    ends, losses, failed = [], [], 0
    t0 = time.perf_counter()
    deadline = t0 + run.seconds
    while time.perf_counter() < deadline:
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
        if run.trace_due(tr["trace_seconds"]):
            run.stop_trace()
    del params, opt
    tokens = tr["unique_batch"] * tr["seq_len"]
    rate = len(ends) * tokens / ends[-1] if ends else float("nan")
    dts = np.diff([0.0] + ends)
    lines = [f"train: {len(ends)} steps of {tokens} unique tokens in "
             f"{ends[-1] if ends else float('nan')!r} s; step seconds "
             f"min/median/max {[float(x) for x in np.percentile(dts, [0, 50, 100])] if ends else []};"
             f" dropped stragglers {trainer.stragglers_dropped}, barrier "
             f"fallbacks {trainer.decode_failures}",
             f"train: checked-step losses {state['losses']}, window losses "
             f"first/last {losses[:1]} {losses[-1:]}"]
    return dict(attempted=step - state["step"], failed=failed, lines=lines,
                step_seconds=dts.tolist(), unique_tokens_per_step=tokens,
                end_to_end={"train_tokens_per_s": rate})


def reference_readings(cfg, tr, sd, precision="highest", rows="all"):
    """The reference's (losses, g1, upd) for the checked steps.  ``rows``
    ``"half"`` trains on the first half of each step's unique rows only
    (the fault of a batch half left out)."""
    groups = cfg["coded"]["n_workers"] // tr["c"]
    batches = []
    for t in range(tr["checked_steps"]):
        tok, lab = ref_data.unique_rows(cfg["vocab_size"], tr["seq_len"],
                                        tr["unique_batch"], groups,
                                        sd["data"], t)
        if rows == "half":
            tok, lab = tok[:len(tok) // 2], lab[:len(lab) // 2]
        batches.append((tok, lab))
    return ref_model.train_steps(cfg, tr["optimizer"], sd["weights"],
                                 batches, precision)


def _leaf_gaps(prog, ref, keep=None):
    """{leaf: |prog - ref| / max(ref, median leaf)} over the kept leaves."""
    keys = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in ref]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys}


def loss_gaps(prog_losses, ref_losses):
    return [abs(a - b) / abs(b) for a, b in zip(prog_losses, ref_losses)]


def gaps(prog, refr):
    """The numbers of readings (losses, g1, upd) against the reference's:
    ``loss_gap``, ``grad_gap`` and ``update_gap`` (the worst leaf), and
    ``grad_gap_median`` and ``update_gap_median`` (the median leaf's).
    The loss compared is the first step's: after it, AdamW moves every
    element by about its learning rate whatever the gradient's size, so
    rounding that flips the sign of a near-zero gradient moves the later
    losses about as much in bfloat16 as in float8."""
    lp, gp, up = prog
    lr, gr, ur = refr
    med = float(np.median(list(gr.values())))
    moved = {k for k, v in gr.items() if v >= 1e-3 * med}
    g = list(_leaf_gaps(gp, gr).values())
    u = list(_leaf_gaps(up, ur, moved).values())
    return {"loss_gap": loss_gaps(lp, lr)[0],
            "grad_gap": max(g), "update_gap": max(u),
            "grad_gap_median": float(np.median(g)),
            "update_gap_median": float(np.median(u))}


def check(run, state, out):
    tr = run.traffic
    prog = (state["losses"], state["g1"], state["upd"])
    refr = reference_readings(run.config, tr, seeds(run))
    nums = gaps(prog, refr)
    worst = {name: max(g.items(), key=lambda kv: kv[1])
             for name, g in (("grad", _leaf_gaps(prog[1], refr[1])),
                             ("update", _leaf_gaps(prog[2], refr[2])))}
    print(f"check: losses {state['losses']} vs reference {refr[0]} (gaps "
          f"{loss_gaps(state['losses'], refr[0])}); worst leaves {worst}; "
          f"numbers {nums}", flush=True)
    return {k: {"value": nums[k], "limit": v}
            for k, v in tr["limits"].items()}
