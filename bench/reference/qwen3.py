"""Plain float32 reference of a Qwen3 dense decoder's training steps.

The published Qwen3 layer (hf ``Qwen3ForCausalLM``): RMSNorm; q, k, v
projections with grouped-query heads; RMSNorm over each q and k head;
rotary position embedding over half-split pairs; causal softmax
attention; output projection; residual; RMSNorm; SwiGLU; residual.  A
final RMSNorm, then the output head.  The loss is the mean next-token
negative log-likelihood over every unique token.  Departure, as the
configuration file states: the embedding and the output head are two
matrices.

Weights live in the layout the system under test takes (layers stacked
on a leading axis) and are made here, from a seed, on the device in one
jitted call.  Every matrix product runs at ``precision="highest"``.
``precision="fp8"`` is the control: the model computed in float8 e4m3
where the system under test computes in bfloat16 (every activation,
matrix-product operand and product, and the gradients flowing back
through them, rounded with one scale per tensor).

AdamW as ``launch/train.py`` configures it: global-norm clipping, bias
correction, decoupled weight decay, linear warmup then cosine decay.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# Shapes and weights
# --------------------------------------------------------------------------

def param_shapes(cfg: dict) -> Dict:
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, f, L = cfg["head_dim"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    V = (cfg["vocab_size"] + 127) // 128 * 128
    layers = {"attn_norm": (L, d), "wq": (L, d, h, hd), "wk": (L, d, kv, hd),
              "wv": (L, d, kv, hd), "wo": (L, h, hd, d), "mlp_norm": (L, d),
              "q_norm": (L, hd), "k_norm": (L, hd), "w_gate": (L, d, f),
              "w_up": (L, d, f), "w_down": (L, f, d)}
    return {"layers": layers, "embed": (V, d), "final_norm": (d,),
            "lm_head": (d, V)}


def _fan_in(name, shape):
    if name == "wo":
        return shape[1] * shape[2]
    return shape[1] if len(shape) > 2 else shape[0]


def init_params(cfg: dict, seed: int):
    """Float32 weights from ``seed``: norms at one, matrices normal with
    standard deviation 1/sqrt(fan-in), the embedding at 0.02."""
    shapes = param_shapes(cfg)
    names = sorted(shapes["layers"]) + ["embed", "final_norm", "lm_head"]

    def make(key):
        out = {"layers": {}}
        for i, name in enumerate(names):
            shape = shapes["layers"].get(name) or shapes[name]
            k = jax.random.fold_in(key, i)
            if "norm" in name:
                v = jnp.ones(shape, jnp.float32)
            elif name == "embed":
                v = 0.02 * jax.random.normal(k, shape, jnp.float32)
            elif name == "lm_head":
                v = jax.random.normal(k, shape, jnp.float32) / math.sqrt(
                    shape[0])
            else:
                v = jax.random.normal(k, shape, jnp.float32) / math.sqrt(
                    _fan_in(name, shape[1:]))
            if name in shapes["layers"]:
                out["layers"][name] = v
            else:
                out[name] = v
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed))


# --------------------------------------------------------------------------
# Forward and loss
# --------------------------------------------------------------------------

def _round8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    y = jnp.clip(x / s, -448.0, 448.0)          # e4m3fn has no infinity
    return y.astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor, and round the
    gradient that flows back through it the same way."""
    return _round8(x)


_fp8.defvjp(lambda x: (_round8(x), None), lambda _, g: (_round8(g),))


def _precision(precision):
    """(act, mm): what each activation and each matrix product does.
    ``highest``: nothing, and float32 products; ``fp8``: every
    activation, product operand and product is rounded to float8."""
    if precision == "fp8":
        def mm(eq, a, b):
            return _fp8(jnp.einsum(eq, _fp8(a), _fp8(b),
                                   precision="highest"))
        return _fp8, mm
    return (lambda x: x), (lambda eq, a, b: jnp.einsum(
        eq, a, b, precision="highest"))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg, act, mm, x, lp):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    a = act(_rms(x, lp["attn_norm"], eps))
    q = act(_rope(act(_rms(mm("bsd,dhe->bshe", a, lp["wq"]), lp["q_norm"],
                           eps)), theta))
    k = act(_rope(act(_rms(mm("bsd,dhe->bshe", a, lp["wk"]), lp["k_norm"],
                           eps)), theta))
    v = mm("bsd,dhe->bshe", a, lp["wv"])
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    s = mm("bqhe,bkhe->bhqk", q, k) / math.sqrt(hd)
    n = x.shape[1]
    causal = jnp.tril(jnp.ones((n, n), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = mm("bhqk,bkhe->bqhe", p, v)
    x = act(x + mm("bshe,hed->bsd", o, lp["wo"]))
    m = act(_rms(x, lp["mlp_norm"], eps))
    g = mm("bsd,df->bsf", m, lp["w_gate"])
    u = mm("bsd,df->bsf", m, lp["w_up"])
    return act(x + mm("bsf,fd->bsd", act(jax.nn.silu(g) * u),
                      lp["w_down"]))


def loss_fn(cfg: dict, precision: str = "highest"):
    """loss(params, tokens (B, S), labels (B, S)) -> mean NLL."""
    act, mm = _precision(precision)

    def head_nll(h, w, lab):
        logits = mm("sd,dv->sv", h, w)
        lz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab[:, None], axis=-1)[:, 0]
        return jnp.mean(lz - gold)

    def loss(params, tokens, labels):
        x = act(params["embed"][tokens])
        body = jax.checkpoint(
            lambda x, lp: (_layer(cfg, act, mm, x, lp), None))
        x, _ = jax.lax.scan(body, x, params["layers"])
        x = act(_rms(x, params["final_norm"], cfg["rms_norm_eps"]))
        head = jax.checkpoint(head_nll)
        rows = [head(x[b], params["lm_head"], labels[b])
                for b in range(tokens.shape[0])]
        return jnp.mean(jnp.stack(rows))

    return loss


# --------------------------------------------------------------------------
# AdamW
# --------------------------------------------------------------------------

def lr_at(opt: dict, step: int) -> float:
    """Learning rate of 1-based ``step``: linear warmup, cosine decay."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    t = min(max((step - opt["warmup_steps"])
                / max(opt["decay_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    r = opt["min_lr_ratio"]
    return opt["lr"] * warm * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * t)))


def adamw_step(opt: dict):
    """(params, mu, nu, grads, step) -> (params, mu, nu, clipped grads'
    global norm before clipping); ``step`` is 1-based."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]

    def step_fn(params, mu, nu, grads, step, lr):
        leaves = jax.tree.leaves(grads)
        gn = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
        scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gn, 1e-12))
        c1 = 1.0 - b1 ** step
        c2 = 1.0 - b2 ** step

        def upd(p, g, m, v):
            g = g * scale
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            d = (m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p
            return p - lr * d, m, v

        out = jax.tree.map(upd, params, grads, mu, nu)
        pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                      is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), pick(1), pick(2), scale

    return jax.jit(step_fn, donate_argnums=(0, 1, 2))


def leaf_norms(tree) -> Dict[str, float]:
    """Frobenius norm of each leaf, by its path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    fn = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])
    vals = fn([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(v)
            for (p, _), v in zip(flat, vals)}


def diff_norms(a, b) -> Dict[str, float]:
    """Norm of each leaf of a - b, by its path."""
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree.leaves(b)
    fn = jax.jit(lambda xs, ys: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(xs, ys)])
    vals = fn([x for _, x in fa], fb)
    return {jax.tree_util.keystr(p): float(v) for (p, _), v in zip(fa, vals)}


def train_steps(cfg: dict, opt: dict, seed: int, batches, precision="highest"):
    """The first ``len(batches)`` steps from the seeded weights.

    ``batches`` is a list of (tokens, labels) of the unique rows.  Returns
    the losses, the first step's clipped gradient norms by leaf, and the
    norms by leaf of the parameters' change over all the steps."""
    loss = loss_fn(cfg, precision)
    with jax.default_matmul_precision("highest"):
        vg = jax.jit(jax.value_and_grad(loss))
        step = adamw_step(opt)
        params = init_params(cfg, seed)
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        losses, g1 = [], None
        for t, (tok, lab) in enumerate(batches):
            lval, grads = vg(params, jnp.asarray(tok), jnp.asarray(lab))
            losses.append(float(lval))
            params, mu, nu, scale = step(params, mu, nu, grads,
                                         jnp.float32(t + 1),
                                         jnp.float32(lr_at(opt, t + 1)))
            if t == 0:
                g1 = {k: v * float(scale)
                      for k, v in leaf_norms(grads).items()}
            del grads
        del mu, nu
        p0 = init_params(cfg, seed)
        upd = diff_norms(params, p0)
    return losses, g1, upd
