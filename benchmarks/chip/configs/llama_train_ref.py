"""Plain reference of a Llama-architecture decoder in training.

Written from the published architecture (HF ``LlamaForCausalLM``: pre-norm
RMSNorm, rotary embeddings over split halves, grouped-query attention whose
query head ``h`` reads key/value head ``h // (heads / kv_heads)``, a SiLU-gated
MLP, tied input and output embeddings) and from the training recipe the
configuration file states (its ``init`` and ``optimizer`` sections).  It imports
nothing of the program under test and takes no weights from it: the weights are
drawn here from the seed by the procedure the configuration file states.

Every product runs in float32 at ``highest`` precision.  ``quant="fp8"`` rounds
both operands of every product, and the cotangent of every product in the
backward pass, to float8 e4m3 with one scale per tensor: the control, one
precision below the configuration's bfloat16.  ``rows`` keeps only that many
rows of each batch (the mean taken over them): a fault planted in the
reference.  The layers run under ``lax.scan`` with each layer recomputed in the
backward pass, so the reference holds one layer's activations at a time.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                       # largest finite float8_e4m3fn


def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def make_einsum(quant: str):
    """``einsum(spec, a, b)`` in float32; with ``quant="fp8"`` both operands
    and, in the backward pass, the cotangent are rounded to scaled fp8."""
    def plain(spec, a, b):
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    if quant == "f32":
        return plain
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")

    def einsum(spec, a, b):
        @jax.custom_vjp
        def f(a, b):
            return plain(spec, _fp8(a), _fp8(b))

        def fwd(a, b):
            return f(a, b), (a, b)

        def bwd(res, g):
            a, b = res
            _, vjp = jax.vjp(lambda x, y: plain(spec, x, y), _fp8(a), _fp8(b))
            return vjp(_fp8(g))

        f.defvjp(fwd, bwd)
        return f(a, b)

    return einsum


# ------------------------------------------------------------------ weights
def init_params(cfg: dict, seed: int) -> dict:
    """Float32 copies of the bfloat16 weights the stated init draws.

    Keys: ``split(PRNGKey(seed), 8)``; the embedding is ``normal(keys[0]) *
    0.02``; layer ``i`` takes ``split(split(keys[2], L)[i], 4)``, whose first
    key splits in 8 for wq, wk, wv, wo and whose second splits in 3 for the
    gate, up and down projections, each ``normal / sqrt(fan_in)``; norm scales
    are ones.  Every weight is rounded to bfloat16 as drawn."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    h, kv, ff = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["intermediate_size"])
    n_layers, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]

    def dense(key, shape, scale=None):
        scale = 1.0 / math.sqrt(shape[0]) if scale is None else scale
        w = jax.random.normal(key, shape, jnp.float32) * scale
        return w.astype(jnp.bfloat16).astype(jnp.float32)

    def layer(key):
        ks = jax.random.split(key, 4)
        a = jax.random.split(ks[0], 8)
        m = jax.random.split(ks[1], 3)
        return {"ln1": {"scale": jnp.ones((d,))}, "ln2": {"scale": jnp.ones((d,))},
                "attn": {"wq": dense(a[0], (d, h * hd)),
                         "wk": dense(a[1], (d, kv * hd)),
                         "wv": dense(a[2], (d, kv * hd)),
                         "wo": dense(a[3], (h * hd, d))},
                "mlp": {"w_gate": dense(m[0], (d, ff)),
                        "w_up": dense(m[1], (d, ff)),
                        "w_down": dense(m[2], (ff, d))}}

    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    layers = [layer(k) for k in jax.random.split(keys[2], n_layers)]
    return {"embed": dense(keys[0], (vocab, d), scale=0.02),
            "final_norm": {"scale": jnp.ones((d,))},
            "blocks": jax.tree_util.tree_map(lambda *x: jnp.stack(x), *layers)}


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


# ------------------------------------------------------------------ forward
def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotary embedding over split halves; x: (B, T, heads, hd)."""
    t, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(t)[:, None] * freqs[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss_fn(params, tokens, labels, cfg: dict, quant: str = "f32"):
    """Mean next-token cross-entropy of the batch."""
    es = make_einsum(quant)
    d, hd = cfg["hidden_size"], head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, t = tokens.shape
    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(x, p):
        a = _rmsnorm(x, p["ln1"]["scale"], eps)
        q = _rope(es("btd,dk->btk", a, p["attn"]["wq"]).reshape(b, t, h, hd), theta)
        k = _rope(es("btd,dk->btk", a, p["attn"]["wk"]).reshape(b, t, kv, hd), theta)
        v = es("btd,dk->btk", a, p["attn"]["wv"]).reshape(b, t, kv, hd)
        k = jnp.repeat(k, h // kv, axis=2)              # query head -> kv head
        v = jnp.repeat(v, h // kv, axis=2)
        s = es("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = es("bhqk,bkhd->bqhd", w, v).reshape(b, t, h * hd)
        x = x + es("btk,kd->btd", o, p["attn"]["wo"])
        m = _rmsnorm(x, p["ln2"]["scale"], eps)
        g = es("btd,df->btf", m, p["mlp"]["w_gate"])
        u = es("btd,df->btf", m, p["mlp"]["w_up"])
        x = x + es("btf,fd->btd", jax.nn.silu(g) * u, p["mlp"]["w_down"])
        return x, None

    x = params["embed"][tokens]
    x, _ = jax.lax.scan(jax.checkpoint(block), x, params["blocks"])
    x = _rmsnorm(x, params["final_norm"]["scale"], eps)
    logits = es("btd,vd->btv", x, params["embed"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


# ---------------------------------------------------------------- optimizer
def lr_at(step: int, opt: dict) -> float:
    """Linear warm-up from 0 over ``warmup`` steps, then cosine to
    ``floor * peak_lr`` at ``total_steps``; ``step`` counts updates already
    made."""
    peak, warm, total, floor = (opt["peak_lr"], opt["warmup"],
                                opt["total_steps"], opt["floor"])
    if step < warm:
        return peak * step / max(1, warm)
    prog = min(1.0, max(0.0, (step - warm) / max(1, total - warm)))
    return peak * (floor + (1 - floor) * 0.5 * (1 + math.cos(math.pi * prog)))


def train_steps(cfg: dict, opt: dict, seed: int, batches, quant: str = "f32",
                rows: int | None = None) -> dict:
    """Run ``len(batches)`` AdamW steps from the seed's weights.

    Returns each step's loss, the first step's gradient as AdamW takes it
    (after the global-norm clip) as a per-leaf norm, and the per-leaf norm of
    the float32 weights' change over all the steps."""
    params = init_params(cfg, seed)
    p0 = params
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, x, y: loss_fn(p, x, y, cfg, quant)))
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    losses, grad1 = [], None
    for i, batch in enumerate(batches):
        x, y = (jnp.asarray(batch[k][:rows]) for k in ("tokens", "labels"))
        loss, g = grad_fn(params, x, y)
        gnorm = jnp.sqrt(sum(jnp.sum(l * l) for l in jax.tree_util.tree_leaves(g)))
        scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
        g = jax.tree_util.tree_map(lambda l: l * scale, g)
        if i == 0:
            grad1 = leaf_norms(g)
        lr, t = lr_at(i, opt), i + 1
        m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        params = jax.tree_util.tree_map(
            lambda p, mm, vv: p - lr * ((mm / (1 - b1 ** t))
                                        / (jnp.sqrt(vv / (1 - b2 ** t)) + eps)
                                        + wd * p), params, m, v)
        losses.append(float(loss))
    change = leaf_norms(jax.tree_util.tree_map(jnp.subtract, params, p0))
    return {"losses": losses, "grad1": grad1, "change": change}


def leaf_norms(tree) -> dict:
    """``{path: float32 norm}`` of every leaf."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): float(jnp.sqrt(jnp.sum(jnp.square(
        jnp.asarray(l, jnp.float32))))) for k, l in flat}
