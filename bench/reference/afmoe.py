"""Plain float32 reference of an AFMoE (Arcee Trinity) decoder's training
steps, on one chip's share of its experts.

The layer (hf ``AfmoeForCausalLM``), with h = RMSNorm_in(x):

  q = RMSNorm_q(h W_q), k = RMSNorm_k(h W_k) per head, v = h W_v;
  RoPE on q and k on sliding layers only (global layers use none);
  causal softmax attention with grouped-query heads, sliding layers
  masked to i - j < window;
  o <- o * sigmoid(h W_g);  x <- x + RMSNorm_post_attn(o W_o);
  u = RMSNorm_pre_mlp(x);   x <- x + RMSNorm_post_mlp(MLP(u)).

MLP is a SwiGLU of the dense width on the leading ``num_dense_layers``
layers.  On the others it is the shared expert(s) plus the routed
experts this chip holds: s = sigmoid(u W_r) over ALL routed experts; the
top-k of s + b (b moves selection only) are selected and weighted
route_scale * s_e / sum of the selected s; each held selected expert adds
its weight times its SwiGLU.  What the absent experts would add is left
out, as the program leaves it out.  Embeddings are scaled by
sqrt(hidden_size); a final RMSNorm, then the untied head.  The loss is
the mean next-token negative log-likelihood over every unique token.

Plain by design: the routed experts are a loop over the held experts
with masks over every token (no sort, no grouped kernel), attention is
computed in query blocks with plain masks, each block and each layer
rematerialized so that the whole thing fits one chip beside nothing
else.  Every matrix product runs at ``precision="highest"``;
``precision="fp8"`` is the control, as in ``reference/qwen3.py``.

Weights live in the layout the system under test takes (the dense
layers and the expert layers each stacked on a leading axis) and are
made here, from a seed, on the device in one jitted call.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from reference.qwen3 import (_precision, _rms, _rope, adamw_step, diff_norms,
                             leaf_norms, lr_at)

#: query rows per attention block
Q_BLOCK = 512


# --------------------------------------------------------------------------
# Shapes and weights
# --------------------------------------------------------------------------

def param_shapes(cfg: dict) -> Dict:
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, f = cfg["head_dim"], cfg["intermediate_size"]
    fe, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    nd = cfg["num_dense_layers"]
    ne = cfg["num_hidden_layers"] - nd
    fs = fe * cfg["num_shared_experts"]
    V = (cfg["vocab_size"] + 127) // 128 * 128

    def attn(n):
        return {"attn_norm": (n, d), "wq": (n, d, h, hd), "wk": (n, d, kv, hd),
                "wv": (n, d, kv, hd), "wo": (n, h, hd, d), "q_norm": (n, hd),
                "k_norm": (n, hd), "w_attn_gate": (n, d, h, hd),
                "post_attn_norm": (n, d), "mlp_norm": (n, d),
                "post_mlp_norm": (n, d)}

    dense = dict(attn(nd), w_gate=(nd, d, f), w_up=(nd, d, f),
                 w_down=(nd, f, d))
    experts = dict(attn(ne), router=(ne, d, cfg["num_experts_routed"]),
                   expert_bias=(ne, cfg["num_experts_routed"]),
                   w_gate=(ne, held, d, fe), w_up=(ne, held, d, fe),
                   w_down=(ne, held, fe, d), shared_gate=(ne, d, fs),
                   shared_up=(ne, d, fs), shared_down=(ne, fs, d))
    return {"dense_layers": dense, "layers": experts, "embed": (V, d),
            "final_norm": (d,), "lm_head": (d, V)}


def _fan_in(name, shape):
    """Fan-in of a matrix, its leading layer axis dropped."""
    if name == "wo":
        return shape[0] * shape[1]
    if name in ("w_gate", "w_up", "w_down") and len(shape) == 3:
        return shape[1]                  # (held, in, out)
    return shape[0]


def init_params(cfg: dict, seed: int):
    """Float32 weights from ``seed``: norms at one, the selection bias at
    zero, matrices normal with standard deviation 1/sqrt(fan-in), the
    embedding at 0.02."""
    shapes = param_shapes(cfg)
    names = [(g, n) for g in ("dense_layers", "layers")
             for n in sorted(shapes[g])] + \
        [(None, "embed"), (None, "final_norm"), (None, "lm_head")]

    def make(key):
        out = {"dense_layers": {}, "layers": {}}
        for i, (group, name) in enumerate(names):
            shape = shapes[group][name] if group else shapes[name]
            k = jax.random.fold_in(key, i)
            if "norm" in name:
                v = jnp.ones(shape, jnp.float32)
            elif name == "expert_bias":
                v = jnp.zeros(shape, jnp.float32)
            elif name == "embed":
                v = 0.02 * jax.random.normal(k, shape, jnp.float32)
            elif name == "lm_head":
                v = jax.random.normal(k, shape, jnp.float32) / math.sqrt(
                    shape[0])
            else:
                v = jax.random.normal(k, shape, jnp.float32) / math.sqrt(
                    _fan_in(name, shape[1:]))
            if group:
                out[group][name] = v
            else:
                out[name] = v
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed))


# --------------------------------------------------------------------------
# Forward and loss
# --------------------------------------------------------------------------

def layer_kinds(cfg: dict):
    """'sliding' or 'global' for each of the configuration's layers."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return ["sliding" if k == "sliding_attention" else "global"
            for k in kinds]


def _attention(act, mm, q, k, v, window):
    """Causal GQA attention of (B, S, H, hd) q over (B, S, KV, hd) k, v,
    keys masked to i - j < window: one block of query rows at a time,
    each over every key, rematerialized."""
    b, s, h, hd = q.shape
    k = jnp.repeat(k, h // k.shape[2], axis=2)
    v = jnp.repeat(v, h // v.shape[2], axis=2)
    nb = -(-s // Q_BLOCK)
    while s % nb:
        nb += 1

    @jax.checkpoint
    def block(args):
        qb, q0 = args
        sc = mm("bqhe,bkhe->bhqk", qb, k) / math.sqrt(hd)
        i = q0 + jnp.arange(qb.shape[1])[:, None]
        j = jnp.arange(s)[None, :]
        keep = (j <= i) & (i - j < window)
        p = act(jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1))
        return mm("bhqk,bkhe->bqhe", p, v)

    qs = q.reshape(b, nb, s // nb, h, hd).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(block, (qs, jnp.arange(nb) * (s // nb)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)


def _swiglu(act, mm, u, wg, wu, wd):
    g = mm("td,df->tf", u, wg)
    up = mm("td,df->tf", u, wu)
    return mm("tf,fd->td", act(jax.nn.silu(g) * up), wd)


def _routed(cfg, act, mm, u, lp):
    """Shared expert plus the held routed experts: (out (T, d),
    selections (T, k), rows (held,))."""
    s = jax.nn.sigmoid(mm("td,de->te", u, lp["router"]))
    _, idx = jax.lax.top_k(s + lp["expert_bias"], cfg["num_experts_per_tok"])
    sel = jnp.take_along_axis(s, idx, axis=-1)
    w = cfg["route_scale"] * sel / jnp.sum(sel, -1, keepdims=True)
    shared = _swiglu(act, mm, u, lp["shared_gate"], lp["shared_up"],
                     lp["shared_down"])

    def expert(out, args):
        e, wg, wu, wd = args
        pick = idx == cfg["expert_first"] + e
        we = jnp.sum(jnp.where(pick, w, 0.0), axis=-1)
        return out + we[:, None] * _swiglu(act, mm, u, wg, wu, wd), \
            jnp.sum(pick)

    out, rows = jax.lax.scan(expert, shared, (
        jnp.arange(cfg["num_experts"]), lp["w_gate"], lp["w_up"],
        lp["w_down"]))
    return out, idx, rows.astype(jnp.int32)


def _layer(cfg, act, mm, x, lp, window, rope, experts):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, s, d = x.shape
    a = act(_rms(x, lp["attn_norm"], eps))
    q = act(_rms(mm("bsd,dhe->bshe", a, lp["wq"]), lp["q_norm"], eps))
    k = act(_rms(mm("bsd,dhe->bshe", a, lp["wk"]), lp["k_norm"], eps))
    v = mm("bsd,dhe->bshe", a, lp["wv"])
    q = jnp.where(rope, act(_rope(q, theta)), q)
    k = jnp.where(rope, act(_rope(k, theta)), k)
    o = _attention(act, mm, q, k, v, window)
    o = act(o * jax.nn.sigmoid(mm("bsd,dhe->bshe", a, lp["w_attn_gate"])))
    x = act(x + _rms(mm("bshe,hed->bsd", o, lp["wo"]), lp["post_attn_norm"],
                     eps))
    u = act(_rms(x, lp["mlp_norm"], eps)).reshape(b * s, d)
    if experts:
        m, idx, rows = _routed(cfg, act, mm, u, lp)
    else:
        m = _swiglu(act, mm, u, lp["w_gate"], lp["w_up"], lp["w_down"])
        idx = rows = None
    x = act(x + _rms(m.reshape(b, s, d), lp["post_mlp_norm"], eps))
    return x, (idx, rows)


def _body(cfg, act, mm, params, tokens):
    """The final-normed hidden states (B, S, d), with the selections
    (expert layers, B*S, k) and rows per held expert (expert layers,
    held) of every expert layer.  Each stack of layers is a loop over
    its layers, each layer rematerialized; a sliding layer attends
    within the window with RoPE, a global one over every earlier key
    with no position encoding."""
    nd = cfg["num_dense_layers"]
    s = tokens.shape[1]
    kinds = layer_kinds(cfg)
    window = jnp.asarray([cfg["sliding_window"] if k == "sliding" else s
                          for k in kinds])
    rope = jnp.asarray([k == "sliding" for k in kinds])
    x = act(params["embed"][tokens] * math.sqrt(cfg["hidden_size"]))

    def stack(x, layers, lo, hi, experts):
        def body(x, args):
            lp, w, r = args
            return _layer(cfg, act, mm, x, lp, w, r, experts)
        return jax.lax.scan(jax.checkpoint(body), x,
                            (layers, window[lo:hi], rope[lo:hi]))

    x, _ = stack(x, params["dense_layers"], 0, nd, False)
    x, routing = stack(x, params["layers"], nd, len(kinds), True)
    x = act(_rms(x, params["final_norm"], cfg["rms_norm_eps"]))
    return x, routing


def forward(cfg: dict, params, tokens, precision: str = "highest"):
    """Logits (B, S, V_pad) and the routing of every expert layer."""
    act, mm = _precision(precision)
    x, routing = _body(cfg, act, mm, params, tokens)
    return mm("bsd,dv->bsv", x, params["lm_head"]), routing


def loss_fn(cfg: dict, precision: str = "highest"):
    """loss(params, tokens (B, S), labels (B, S)) -> (mean NLL,
    (selections (expert layers, B*S, k), rows (expert layers, held)))."""
    act, mm = _precision(precision)

    def head_nll(h, w, lab):
        logits = mm("sd,dv->sv", h, w)
        lz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab[:, None], axis=-1)[:, 0]
        return jnp.mean(lz - gold)

    def loss(params, tokens, labels):
        x, routing = _body(cfg, act, mm, params, tokens)
        head = jax.checkpoint(head_nll)
        nll = [head(x[b], params["lm_head"], labels[b])
               for b in range(tokens.shape[0])]
        return jnp.mean(jnp.stack(nll)), routing

    return loss


# --------------------------------------------------------------------------
# Training steps
# --------------------------------------------------------------------------

def train_steps(cfg: dict, opt: dict, seed: int, batches, precision="highest"):
    """The first ``len(batches)`` steps from the seeded weights.

    ``batches`` is a list of (tokens, labels) of the unique rows.  Returns
    the losses, the first step's clipped gradient norms by leaf, the
    norms by leaf of the parameters' change over all the steps, and the
    first step's selections (expert layers, tokens, k) and rows per held
    expert (expert layers, held)."""
    loss = loss_fn(cfg, precision)
    with jax.default_matmul_precision("highest"):
        vg = jax.jit(jax.value_and_grad(loss, has_aux=True))
        step = adamw_step(opt)
        params = init_params(cfg, seed)
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        losses, g1, first = [], None, None
        for t, (tok, lab) in enumerate(batches):
            (lval, aux), grads = vg(params, jnp.asarray(tok),
                                    jnp.asarray(lab))
            losses.append(float(lval))
            params, mu, nu, scale = step(params, mu, nu, grads,
                                         jnp.float32(t + 1),
                                         jnp.float32(lr_at(opt, t + 1)))
            if t == 0:
                g1 = {k: v * float(scale)
                      for k, v in leaf_norms(grads).items()}
                first = jax.device_get(aux)
            del grads
        del mu, nu
        p0 = init_params(cfg, seed)
        upd = diff_norms(params, p0)
    return losses, g1, upd, first[0], first[1]
