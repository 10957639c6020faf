"""Plain reference of DeepSeek-V2-Lite in training, at one chip's share of
its routed experts.

Written from the published architecture (HF ``DeepseekV2ForCausalLM`` at the
configuration file's keys): pre-norm RMSNorm; multi-head latent attention
with no query compression (queries of ``qk_nope + qk_rope`` dims per head, a
``kv_lora_rank`` latent normed and expanded to per-head keys and values, one
rotary key shared by every head), YaRN rotary frequencies and YaRN's factor
on the softmax scale; a dense SiLU-gated MLP in the first
``first_k_dense_replace`` layers and in every later one a softmax router over
all ``published.n_routed_experts`` experts with greedy top-k, unnormalised
top-k weights (``norm_topk_prob``), ``n_shared_experts`` shared experts as
one MLP of that many times the expert width, and the sequence-wise balance
loss weighted by ``aux_loss_alpha``; untied embedding and head.  The weights
are drawn here from the seed by the procedure the configuration file's
``init`` states; nothing of the program under test is imported.

This chip holds experts ``[first_held_expert, + n_routed_experts)``: the
router keeps every published output, and only the held experts' part of the
routed result is added, as the program does.  Each held expert runs over
every token, weighted by a routing mask built from the top-k choices and a
cumulative count of each expert's tokens: an expert keeps its first ``C``
tokens in token order, ``C = ceil8(floor(N * top_k * capacity_factor / E))``
and at least 8.

Departures from the published code, each also in the configuration's
``assumed``:

* rotary pairs are split halves; the published code pairs adjacent dims,
  which a fixed permutation of the rope columns of ``q_proj`` and
  ``kv_a_proj_with_mqa`` turns into split halves;
* ``kv_a_proj_with_mqa`` is held as ``w_dkv`` and ``w_krope``, two column
  blocks of one matrix, and ``kv_b_proj`` as ``w_uk`` and ``w_uv``, its key
  and value columns;
* capacity drops by token order per expert; the paper drops by affinity per
  device.

Every product runs in float32 at ``highest`` precision, attention in blocks
of query rows so that it fits a chip.  ``quant="fp8"`` and ``rows`` are the
control and the planted fault of ``llama_train_ref.py``.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def _llama_ref():
    path = Path(__file__).with_name("llama_train_ref.py")
    spec = importlib.util.spec_from_file_location("llama_train_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_llama = _llama_ref()
make_einsum, lr_at, leaf_norms = _llama.make_einsum, _llama.lr_at, _llama.leaf_norms

QUERY_BLOCK = 512


# ------------------------------------------------------------------ weights
def init_params(cfg: dict, seed: int) -> dict:
    """Float32 copies of the bfloat16 weights the stated init draws.

    ``split(PRNGKey(seed), 8)``: the embedding is ``normal(keys[0]) * 0.02``,
    the head ``normal(keys[1]) / sqrt(d)``; the leading dense layers take
    ``split(keys[3], lead)``, the expert layers ``split(keys[2], n)``.  A
    layer's key splits in 4: the first splits in 6 for wq, w_dkv, w_krope,
    w_uk, w_uv, wo; the second splits in 3 for a dense layer's gate, up and
    down, or in 5 for an expert layer's router (``normal * 0.02``), gate, up
    and down (each split in ``published.n_routed_experts``, expert ``e``
    drawn from key ``e``) and shared experts (split in 3, as a dense
    layer's).  Every matrix is ``normal / sqrt(its input width)``, norm
    scales are ones, and every weight is rounded to bfloat16 as drawn."""
    d, vocab, h = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    r, ff_e = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    n_exp = cfg["published"]["n_routed_experts"]
    e0, held = cfg["first_held_expert"], cfg["n_routed_experts"]
    lead = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - lead

    def dense(key, shape, scale=None):
        scale = 1.0 / math.sqrt(shape[0]) if scale is None else scale
        w = jax.random.normal(key, shape, jnp.float32) * scale
        return w.astype(jnp.bfloat16).astype(jnp.float32)

    def norm(n):
        return {"scale": jnp.ones((n,))}

    def mlp(key, ff):
        k = jax.random.split(key, 3)
        return {"w_gate": dense(k[0], (d, ff)), "w_up": dense(k[1], (d, ff)),
                "w_down": dense(k[2], (ff, d))}

    def attn(key):
        k = jax.random.split(key, 6)
        return {"wq": dense(k[0], (d, h * (nope + rope))),
                "w_dkv": dense(k[1], (d, r)), "w_krope": dense(k[2], (d, rope)),
                "kv_norm": norm(r), "w_uk": dense(k[3], (r, h * nope)),
                "w_uv": dense(k[4], (r, h * vd)), "wo": dense(k[5], (h * vd, d))}

    def experts(key, shape):
        keys = jax.random.split(key, n_exp)[e0:e0 + held]
        return jnp.stack([dense(k, shape) for k in keys])

    def moe(key):
        k = jax.random.split(key, 5)
        return {"router": dense(k[0], (d, n_exp), scale=0.02),
                "w_gate": experts(k[1], (d, ff_e)),
                "w_up": experts(k[2], (d, ff_e)),
                "w_down": experts(k[3], (ff_e, d)),
                "shared": mlp(k[4], cfg["n_shared_experts"] * ff_e)}

    def layer(key, experts_here):
        k = jax.random.split(key, 4)
        ffn = ({"moe": moe(k[1])} if experts_here
               else {"mlp": mlp(k[1], cfg["intermediate_size"])})
        return {"ln1": norm(d), "ln2": norm(d), "attn": attn(k[0]), **ffn}

    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    blocks = [layer(k, True) for k in jax.random.split(keys[2], n_moe)]
    return {"embed": dense(keys[0], (vocab, d), scale=0.02),
            "final_norm": norm(d),
            "lm_head": dense(keys[1], (d, vocab)),
            "lead": [layer(k, False) for k in jax.random.split(keys[3], lead)],
            "blocks": jax.tree_util.tree_map(lambda *x: jnp.stack(x), *blocks)}


# -------------------------------------------------------------------- YaRN
def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, rs: dict) -> np.ndarray:
    """``freq_inter * (1 - mask) + freq_extra * mask`` over the ``dim / 2``
    pairs, ``mask = 1 - clamp((i - low) / (high - low), 0, 1)``, with the
    correction range ``low = floor(c(beta_fast))``, ``high =
    ceil(c(beta_slow))``, ``c(n) = dim ln(orig / (2 pi n)) / (2 ln theta)``."""
    def c(n):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (2 * math.pi * n)) / (2 * math.log(theta))
    low = max(math.floor(c(rs["beta_fast"])), 0)
    high = min(math.ceil(c(rs["beta_slow"])), dim - 1)
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / rs["factor"]
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                         0.0, 1.0)
    return inter * (1 - mask) + extra * mask


def softmax_scale(cfg: dict) -> float:
    """``q_head_dim ** -0.5 * mscale(factor, mscale_all_dim) ** 2``."""
    rs = cfg["rope_scaling"]
    q_hd = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return q_hd ** -0.5 * yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


def _rope(x, cfg: dict):
    """YaRN rotary embedding over split halves; x: (B, T, heads, hd)."""
    t, hd = x.shape[1], x.shape[-1]
    rs = cfg["rope_scaling"]
    ang = np.arange(t)[:, None] * yarn_inv_freq(hd, cfg["rope_theta"], rs)[None]
    m = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(
        rs["factor"], rs["mscale_all_dim"])
    cos = jnp.asarray(np.cos(ang) * m, jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(ang) * m, jnp.float32)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ------------------------------------------------------------------ forward
def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mlp(p, x, es):
    g = es("btd,df->btf", x, p["w_gate"])
    u = es("btd,df->btf", x, p["w_up"])
    return es("btf,fd->btd", jax.nn.silu(g) * u, p["w_down"])


def mla(p, a, cfg: dict, es):
    """Latent attention of the normed input ``a`` (B, T, d), causal, in
    blocks of query rows."""
    b, t, _ = a.shape
    h, nope, rope, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    q = es("btd,dk->btk", a, p["wq"]).reshape(b, t, h, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], cfg)
    c = _rmsnorm(es("btd,dr->btr", a, p["w_dkv"]), p["kv_norm"]["scale"],
                 cfg["rms_norm_eps"])
    k_rope = _rope(es("btd,dr->btr", a, p["w_krope"])[:, :, None], cfg)[:, :, 0]
    k_nope = es("btr,rk->btk", c, p["w_uk"]).reshape(b, t, h, nope)
    v = es("btr,rk->btk", c, p["w_uv"]).reshape(b, t, h, vd)
    scale = softmax_scale(cfg)
    qb = min(t, QUERY_BLOCK)
    nb = t // qb

    @jax.checkpoint
    def block(xs):
        qn, qr, i = xs                                  # (b, qb, h, .)
        s = (es("bqhn,bkhn->bhqk", qn, k_nope)
             + es("bqhr,bkr->bhqk", qr, k_rope)) * scale
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(t)[None]
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return es("bhqk,bkhv->bqhv", w, v)

    def blocks(x):
        return jnp.moveaxis(x.reshape((b, nb, qb) + x.shape[2:]), 1, 0)

    o = jax.lax.map(block, (blocks(q_nope), blocks(q_rope), jnp.arange(nb)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t, h * vd)
    return es("btk,kd->btd", o, p["wo"])


def capacity(n_tokens: int, cfg: dict) -> int:
    c = int(n_tokens * cfg["num_experts_per_tok"] * cfg["capacity_factor"]
            / cfg["published"]["n_routed_experts"])
    return max(8, -(-c // 8) * 8)


def moe(p, x, cfg: dict, es):
    """The held experts' part of the routed output, the shared experts, the
    sequence-wise balance loss (unweighted) and the top-k choices."""
    b, t, d = x.shape
    n, k = b * t, cfg["num_experts_per_tok"]
    n_exp = cfg["published"]["n_routed_experts"]
    e0, held = cfg["first_held_expert"], cfg["n_routed_experts"]
    xf = x.reshape(n, d)
    probs = jax.nn.softmax(es("nd,de->ne", xf, p["router"]), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    onehot = jax.nn.one_hot(top_i, n_exp)               # (n, k, E)
    chosen = jnp.sum(onehot, 1)                         # (n, E) 0/1
    weight = jnp.sum(onehot * top_w[..., None], 1)      # (n, E)
    kept = chosen * (jnp.cumsum(chosen, 0) <= capacity(n, cfg))
    w_held = (weight * kept)[:, e0:e0 + held]           # (n, held)

    @jax.checkpoint
    def expert(acc, e):                                 # every token
        w_gate, w_up, w_down, w = e
        h = jax.nn.silu(es("nd,df->nf", xf, w_gate)) * es("nd,df->nf", xf, w_up)
        return acc + w[:, None] * es("nf,fd->nd", h, w_down), None

    routed, _ = jax.lax.scan(expert, jnp.zeros((n, d)), (
        p["w_gate"], p["w_up"], p["w_down"], w_held.T))
    count = jnp.sum(chosen.reshape(b, t, n_exp), 1)
    f = count * n_exp / (k * t)
    aux = jnp.mean(jnp.sum(f * jnp.mean(probs.reshape(b, t, n_exp), 1), -1))
    out = routed.reshape(b, t, d) + _mlp(p["shared"], x, es)
    return out, aux, top_i


def loss_fn(params, tokens, labels, cfg: dict, quant: str = "f32"):
    """Mean next-token cross-entropy plus ``aux_loss_alpha`` times the
    balance losses summed over the expert layers; and each expert layer's
    top-k choices."""
    es = make_einsum(quant)
    eps = cfg["rms_norm_eps"]

    def attend(x, p):
        return x + mla(p["attn"], _rmsnorm(x, p["ln1"]["scale"], eps), cfg, es)

    @jax.checkpoint
    def dense_block(x, p):
        x = attend(x, p)
        return x + _mlp(p["mlp"], _rmsnorm(x, p["ln2"]["scale"], eps), es)

    @jax.checkpoint
    def expert_block(x, p):
        x = attend(x, p)
        f, aux, top_i = moe(p["moe"], _rmsnorm(x, p["ln2"]["scale"], eps),
                            cfg, es)
        return x + f, (aux, top_i)

    x = params["embed"][tokens]
    for p in params["lead"]:
        x = dense_block(x, p)
    x, (aux, routes) = jax.lax.scan(expert_block, x, params["blocks"])
    x = _rmsnorm(x, params["final_norm"]["scale"], eps)
    logits = es("btd,dv->btv", x, params["lm_head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    loss = jnp.mean(lse - picked) + cfg["aux_loss_alpha"] * jnp.sum(aux)
    return loss, routes


# ---------------------------------------------------------------- optimizer
@functools.lru_cache(maxsize=8)
def _grad_fn(cfg_json: str, quant: str):
    cfg = json.loads(cfg_json)
    return jax.jit(jax.value_and_grad(
        lambda p, x, y: loss_fn(p, x, y, cfg, quant), has_aux=True))


@jax.jit
def _adamw_leaf(p, m, v, g, lr, t, b1, b2, eps, wd):
    """One leaf's AdamW step on its clipped gradient ``g``."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    p = p - lr * ((m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
                  + wd * p)
    return p, m, v


def train_steps(cfg: dict, opt: dict, seed: int, batches, quant: str = "f32",
                rows: int | None = None) -> dict:
    """Run ``len(batches)`` AdamW steps from the seed's weights.

    Returns each step's loss, the first step's gradient as AdamW takes it
    (after the global-norm clip) as a per-leaf norm, the per-leaf norm of
    the float32 weights' change over all the steps, and the first step's
    top-k choices of each expert layer (``routes1``, (layers, tokens, k)).
    The state is updated leaf by leaf, and the first weights are drawn again
    for the change, so that the reference fits beside nothing else."""
    grad_fn = _grad_fn(json.dumps(cfg, sort_keys=True), quant)
    flat, tree = jax.tree_util.tree_flatten(init_params(cfg, seed))
    m = [jnp.zeros_like(x) for x in flat]
    v = [jnp.zeros_like(x) for x in flat]
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    losses, grad1, routes1 = [], None, None
    for i, batch in enumerate(batches):
        x, y = (jnp.asarray(batch[k][:rows]) for k in ("tokens", "labels"))
        (loss, routes), g = grad_fn(tree.unflatten(flat), x, y)
        g = jax.tree_util.tree_leaves(g)
        gnorm = jnp.sqrt(sum(jnp.sum(l * l) for l in g))
        scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
        for j in range(len(flat)):
            g[j] = g[j] * scale
            flat[j], m[j], v[j] = _adamw_leaf(flat[j], m[j], v[j], g[j],
                                              lr_at(i, opt), i + 1, b1, b2,
                                              eps, wd)
        if i == 0:
            grad1 = leaf_norms(tree.unflatten(g))
            routes1 = np.asarray(routes)
        del g
        losses.append(float(loss))
    del m, v
    p0 = jax.tree_util.tree_leaves(init_params(cfg, seed))
    change = leaf_norms(tree.unflatten([a - b for a, b in zip(flat, p0)]))
    return {"losses": losses, "grad1": grad1, "change": change,
            "routes1": routes1}
