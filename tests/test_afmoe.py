"""The AFMoE (Trinity) path against its plain float32 reference, on the CPU
at small widths with seeded weights.

The reference is ``bench/reference/afmoe.py`` (plain ``jax.numpy``,
imported by path); the program is ``models.api`` with the dropless
expert-share layer (``models/moe.expert_share_ffn``) holding a share of
the routed experts.  Both compute in float32 here, so the tolerances
only have to absorb the order of float32 sums: the program's online
(flash) softmax and grouped matmul against the reference's direct
softmax and masked loop.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, get_config
from repro.kernels.grouped_matmul import grouped_matmul, grouped_matmul_ref
from repro.models import api, moe
from repro.optim import adamw
from repro.runtime.coded_step import make_train_step

BENCH = Path(__file__).resolve().parents[1] / "bench"


def _load(name, path):
    sys.path.insert(0, str(BENCH))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("afmoe_reference", BENCH / "reference" / "afmoe.py")

#: a small AFMoE: 2 dense layers, then sliding, global, sliding, sliding;
#: 16 routed experts of which this share holds 4 (experts 4..7), top-4
TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 6, "num_dense_layers": 2, "num_experts": 4,
    "num_experts_routed": 16, "expert_first": 4, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "route_scale": 2.826,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 2,
    "sliding_window": 8, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "vocab_size": 250,
}
B, S = 2, 24


def program_config(c: dict, **over) -> ModelConfig:
    kinds = tuple("sliding" if k == "sliding_attention" else "global"
                  for k in c["layer_types"][:c["num_hidden_layers"]])
    return get_config("trinity-mini").scaled(
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        num_experts=c["num_experts_routed"], experts_held=c["num_experts"],
        expert_first=c["expert_first"],
        experts_per_token=c["num_experts_per_tok"],
        expert_d_ff=c["moe_intermediate_size"],
        num_shared_experts=c["num_shared_experts"],
        route_scale=c["route_scale"], num_dense_layers=c["num_dense_layers"],
        layer_types=kinds, attn_window=c["sliding_window"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        flash_block_kv=8, **over)


@pytest.fixture(scope="module")
def setup():
    cfg = program_config(TINY, compute_dtype="float32", remat="none")
    params = ref.init_params(TINY, 2147483911)
    key = jax.random.PRNGKey(3)
    tokens = jax.random.randint(key, (B, S), 1, TINY["vocab_size"])
    labels = jax.random.randint(jax.random.fold_in(key, 1), (B, S), 1,
                                TINY["vocab_size"])
    return cfg, params, tokens, labels


def test_published_config_holds_every_expert():
    cfg = get_config("trinity-mini")
    assert (cfg.num_layers, cfg.d_model, cfg.num_experts,
            cfg.experts_held, cfg.experts_per_token, cfg.expert_d_ff) == \
        (32, 2048, 128, 128, 8, 1024)
    assert cfg.layer_types.count("global") == 8
    assert [cfg.layer_types[i] for i in (2, 3, 4, 7)] == \
        ["sliding", "global", "sliding", "global"]


def test_weights_have_the_programs_layout(setup):
    cfg, params, _, _ = setup
    want = jax.tree.map(lambda s: tuple(s.shape), api.param_shapes(cfg))
    got = jax.tree.map(lambda x: tuple(x.shape), params)
    assert want == got
    assert jax.tree.structure(api.init_params(cfg, jax.random.PRNGKey(0))) \
        == jax.tree.structure(params)
    specs = api.partition_specs(cfg)
    assert jax.tree.structure(specs, is_leaf=lambda s: isinstance(
        s, jax.sharding.PartitionSpec)) == jax.tree.structure(params)


def test_logits_and_routing_match_the_reference(setup):
    """Every layer kind is present (dense, sliding, global); the window
    (8) is shorter than the sequence (24), so the sliding mask acts.
    Logits: float32 sums in another order, within 1e-4 of their scale."""
    cfg, params, tokens, _ = setup
    logits, aux = jax.jit(lambda p, t: api.forward_aux(cfg, p, t))(
        params, tokens)
    with jax.default_matmul_precision("highest"):
        want, (sels, rows) = jax.jit(
            lambda p, t: ref.forward(TINY, p, t))(params, tokens)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               atol=1e-4 * scale, rtol=0)
    np.testing.assert_array_equal(np.asarray(aux["expert_rows"]),
                                  np.asarray(rows))
    np.testing.assert_array_equal(
        np.asarray(aux["expert_choices"]).reshape(sels.shape),
        np.asarray(sels))
    assert int(rows.sum()) > 0          # the share holds selected experts


def test_global_layer_differs_from_a_sliding_one(setup):
    """The layer kinds are not interchangeable: making every layer
    sliding moves the logits well past the tolerance above."""
    cfg, params, tokens, _ = setup
    base = api.forward(cfg, params, tokens)
    all_sliding = cfg.scaled(layer_types=("sliding",) * 6)
    moved = api.forward(all_sliding, params, tokens)
    assert float(jnp.abs(moved - base).max()) > 1e-2 * float(
        jnp.abs(base).max())


@pytest.mark.parametrize("block", ["pre_norm", "afmoe"])
def test_block_layout_adds_the_gate_and_post_norms(setup, block):
    """``block="afmoe"`` alone adds the attention gate and the post norms
    to the dense and the expert stacks, and they act: the same weights
    without them, run as a pre-norm block, move the logits."""
    cfg, params, tokens, _ = setup
    extra = {"w_attn_gate", "post_attn_norm", "post_mlp_norm"}
    shapes = api.param_shapes(cfg.scaled(block=block))
    for stack in ("layers", "dense_layers"):
        assert extra & set(shapes[stack]) == (
            extra if block == "afmoe" else set())
    if block == "pre_norm":
        trimmed = {k: {n: w for n, w in v.items() if n not in extra}
                   if isinstance(v, dict) else v for k, v in params.items()}
        base = api.forward(cfg, params, tokens)
        moved = api.forward(cfg.scaled(block=block), trimmed, tokens)
        assert float(jnp.abs(moved - base).max()) > 1e-2 * float(
            jnp.abs(base).max())


def test_loss_and_gradients_match_the_reference(setup):
    """The loss within 1e-5 (relative), each gradient leaf within 1e-3 of
    the larger of its norm and the median leaf's: float32 order of sums,
    and the flash backward's recomputed softmax."""
    cfg, params, tokens, labels = setup
    w = jnp.ones((B,), jnp.float32)

    def prog(p):
        from repro.runtime.coded_step import weighted_loss_fn
        return weighted_loss_fn(cfg)(p, tokens, labels, w)

    lp, gp = jax.jit(jax.value_and_grad(prog))(params)
    with jax.default_matmul_precision("highest"):
        (lr, _), gr = jax.jit(jax.value_and_grad(
            ref.loss_fn(TINY), has_aux=True))(params, tokens, labels)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    flat = jax.tree_util.tree_flatten_with_path(gr)[0]
    norms = [float(jnp.linalg.norm(v)) for _, v in flat]
    med = float(np.median(norms))
    for (path, b), a, n in zip(flat, jax.tree.leaves(gp), norms):
        gap = float(jnp.linalg.norm(a - b)) / max(n, med)
        assert gap < 1e-3, (jax.tree_util.keystr(path), gap)
    # the selection bias gets no gradient: it moves selection only
    assert float(jnp.abs(gp["layers"]["expert_bias"]).max()) == 0.0


def test_train_step_returns_the_counters(setup):
    cfg, params, tokens, labels = setup
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=1, decay_steps=10)
    step = jax.jit(make_train_step(cfg, opt_cfg))
    _, _, m = step(params, adamw.init(opt_cfg, params), tokens, labels,
                   jnp.ones((B,), jnp.float32))
    rows = np.asarray(m["expert_rows"])
    assert rows.shape == (4, TINY["num_experts"]) and rows.dtype == np.int32
    assert np.isfinite(float(m["loss"]))


def test_trainer_records_expert_load_only_while_recording(setup):
    """``CodedTrainer.run_step`` emits one ``expert_load`` event per step
    with the step's rows per held expert while a recorder is on, and
    none otherwise."""
    from repro.data import DataConfig
    from repro.obs import recording
    from repro.runtime import CodedStepConfig, CodedTrainer
    cfg, params, _, _ = setup
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=1, decay_steps=10)
    trainer = CodedTrainer(cfg, DataConfig(TINY["vocab_size"], S, 2, seed=4),
                           CodedStepConfig(n_workers=4, c=2, unique_batch=2),
                           opt_cfg, donate=False)
    opt = adamw.init(opt_cfg, params)
    with recording() as rec:
        _, _, m = trainer.run_step(params, opt, 0)
    (ev,) = rec.events("expert_load")
    assert ev.field_dict()["step"] == 0
    assert np.array_equal(np.asarray(ev.field_dict()["rows"]),
                          np.asarray(m["expert_rows"]))
    with recording() as rec:
        pass
    trainer.run_step(params, opt, 1)
    assert rec.events("expert_load") == []


def test_trainer_records_attn_tiles_once_per_build_while_recording(setup):
    """``CodedTrainer.run_step`` emits one ``attn_tiles`` event per built
    step, at the first step run while a recorder is on: the score tiles
    the flash scan visits of all it could, summed over the layers."""
    from repro.data import DataConfig
    from repro.obs import recording
    from repro.runtime import CodedStepConfig, CodedTrainer
    cfg, params, _, _ = setup
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=1, decay_steps=10)
    step_cfg = CodedStepConfig(n_workers=4, c=2, unique_batch=2)
    trainer = CodedTrainer(cfg, DataConfig(TINY["vocab_size"], S, 2, seed=4),
                           step_cfg, opt_cfg, donate=False)
    opt = adamw.init(opt_cfg, params)
    trainer.run_step(params, opt, 0)              # no recorder: no event
    with recording() as rec:
        trainer.run_step(params, opt, 1)
        trainer.run_step(params, opt, 2)
    (ev,) = rec.events("attn_tiles")
    # 24 tokens in blocks of 8: 5 sliding layers (window 8) visit 5 of 9
    # tiles, the global one 6
    assert ev.field_dict() == {"step": 1, "visited": 5 * 5 + 6,
                               "total": 6 * 9}
    assert api.attn_tiles(cfg, S) == (31, 54)
    trainer.step_cfg = step_cfg                   # a new build
    with recording() as rec:
        trainer.run_step(params, opt, 3)
    assert [e.field_dict()["step"] for e in rec.events("attn_tiles")] == [3]


# --------------------------------------------------------------------------
# the expert-share layer
# --------------------------------------------------------------------------

def _layer_weights(seed, d=64, e=16, f=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = lambda k, s, fan: jax.random.normal(k, s, jnp.float32) / fan ** 0.5
    return dict(router=n(ks[0], (d, e), d), expert_bias=jnp.zeros((e,)),
                w_gate=n(ks[1], (e, d, f), d), w_up=n(ks[2], (e, d, f), d),
                w_down=n(ks[3], (e, f, d), f), shared_gate=n(ks[4], (d, f), d),
                shared_up=n(ks[5], (d, f), d), shared_down=n(ks[6], (f, d), f),
                x=jax.random.normal(ks[7], (2, 12, d), jnp.float32))


def _share(lw, first, held, top_k=4):
    sl = slice(first, first + held)
    return moe.expert_share_ffn(
        lw["x"], lw["router"], lw["expert_bias"], lw["w_gate"][sl],
        lw["w_up"][sl], lw["w_down"][sl],
        (lw["shared_gate"], lw["shared_up"], lw["shared_down"]),
        top_k=top_k, first=first, route_scale=2.826)


def _whole_layer(lw, top_k=4):
    """The unsharded reference layer: every expert held."""
    c = dict(num_experts_per_tok=top_k, route_scale=2.826,
             num_experts=lw["w_gate"].shape[0], expert_first=0)
    act, mm = ref._precision("highest")
    u = lw["x"].reshape(-1, lw["x"].shape[-1])
    with jax.default_matmul_precision("highest"):
        return ref._routed(c, act, mm, u, lw)


@pytest.mark.parametrize("held", [2, 4, 8])
def test_shares_sum_to_the_whole_layer(held):
    """The disjoint shares of 16 experts, each computed as its chip would
    (shared expert included), sum to the whole layer with the shared
    expert counted once; their rows add up to every assignment."""
    lw = _layer_weights(7)
    e = lw["w_gate"].shape[0]
    outs, rows, _ = zip(*[_share(lw, f, held) for f in range(0, e, held)])
    u = lw["x"].reshape(-1, lw["x"].shape[-1])
    shared = ref._swiglu(lambda t: t, lambda q, a, b: jnp.einsum(
        q, a, b, precision="highest"), u, lw["shared_gate"], lw["shared_up"],
        lw["shared_down"])
    total = sum(o.reshape(u.shape) for o in outs) - (len(outs) - 1) * shared
    want, _, want_rows = _whole_layer(lw)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
    got_rows = np.concatenate([np.asarray(r) for r in rows])
    np.testing.assert_array_equal(got_rows, np.asarray(want_rows))
    assert got_rows.sum() == u.shape[0] * 4


def test_routing_forced_onto_one_expert_drops_no_row():
    """With the selection bias forcing every token onto experts 0-3,
    expert 0 receives all T tokens (a capacity layer at factor 1.25 of
    T * k / E would keep 5 of 24) and every row is computed."""
    lw = _layer_weights(11)
    lw["expert_bias"] = jnp.zeros((16,)).at[:4].set(100.0)
    y, rows, _ = _share(lw, 0, 4)
    t = lw["x"].shape[0] * lw["x"].shape[1]
    np.testing.assert_array_equal(np.asarray(rows), [t] * 4)
    want, _, _ = _whole_layer(lw)
    np.testing.assert_allclose(np.asarray(y).reshape(want.shape),
                               np.asarray(want), atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------
# the grouped matmul
# --------------------------------------------------------------------------

def test_grouped_matmul_matches_its_oracle():
    """Forward and backward, with an empty group and rows past the last
    group (the absent experts' assignments), which come back as zeros
    and take no gradient.  float32 order of sums: 1e-5."""
    key = jax.random.PRNGKey(5)
    m, k, n = 200, 64, 48
    x = jax.random.normal(key, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, k, n), jnp.float32)
    sizes = jnp.array([37, 0, 90], jnp.int32)
    with jax.default_matmul_precision("highest"):
        out = grouped_matmul(x, w, sizes)
        want = grouped_matmul_ref(x, w, sizes)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        assert float(jnp.abs(out[127:]).max()) == 0.0
        ct = jax.random.normal(jax.random.fold_in(key, 2), (m, n))
        f = lambda fn: jax.grad(lambda a, b: jnp.sum(fn(a, b) * ct),
                                (0, 1))(x, w)
        got = f(lambda a, b: grouped_matmul(a, b, sizes))
        exp = f(lambda a, b: grouped_matmul_ref(a, b, sizes))
    for a, b in zip(got, exp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-5)
    assert float(jnp.abs(got[0][127:]).max()) == 0.0


def test_rows_past_the_groups_cannot_leak(monkeypatch):
    """A TPU kernel leaves the rows past the groups unwritten: with NaN
    planted there by the kernel, the wrapper's result and gradients are
    the same finite numbers as with zeros."""
    from repro.kernels.grouped_matmul import ops
    key = jax.random.PRNGKey(9)
    x = jax.random.normal(key, (64, 16), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, 8), jnp.float32)
    sizes = jnp.array([20, 11], jnp.int32)
    ct = jax.random.normal(jax.random.fold_in(key, 2), (64, 8))

    def run():
        jax.clear_caches()
        return jax.value_and_grad(lambda a, b: jnp.sum(
            ops.grouped_matmul(a, b, sizes) * ct), (0, 1))(x, w)

    want = run()
    ragged = jax.lax.ragged_dot

    def unwritten(lhs, rhs, gs, **kw):
        past = jnp.arange(lhs.shape[0]) >= jnp.sum(gs)
        return jnp.where(past[:, None], jnp.nan, ragged(lhs, rhs, gs, **kw))

    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten)
    got = run()
    monkeypatch.undo()
    jax.clear_caches()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
