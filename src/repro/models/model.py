"""Unified decoder LM: init, train loss, prefill, decode — all 10 architectures.

Layer stacks lower via ``jax.lax.scan`` over stacked parameter banks so 62-layer
models compile quickly and HLO stays small.  Heterogeneous patterns use group
scans (gemma3 5-local:1-global; zamba2 6-mamba2-then-shared-attn).

Modes
-----
* train:   ``loss_fn(params, batch)`` — full-sequence causal LM loss.
* prefill: ``prefill(params, tokens, cache)`` — fills a zero-initialized cache.
* decode:  ``decode_step(params, cache, token, t)`` — one token, cache update.
"""
from __future__ import annotations

import functools
import operator
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.axes import constrain
from repro.models.config import ModelConfig

Params = Dict[str, Any]
Cache = Dict[str, Any]


# ===================================================================== blocks
def init_attn_block(key, cfg: ModelConfig, use_moe: bool, dense_ff: int = 0,
                    dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {"ln1": L.init_rmsnorm(cfg.d_model, dtype),
                 "ln2": L.init_rmsnorm(cfg.d_model, dtype)}
    if cfg.mla is not None:
        p["attn"] = L.init_mla(ks[0], cfg, dtype)
    else:
        p["attn"] = L.init_attention(ks[0], cfg, dtype)
    if use_moe:
        p["moe"] = MOE.init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg.d_model, dense_ff or cfg.d_ff, dtype)
    return p


def attn_block(p: Params, cfg: ModelConfig, x, positions, cache=None,
               cache_index=None, window=None, positions3=None, use_moe=False):
    """Pre-norm transformer block.  Returns (x, new_cache, aux): a dense
    block's balance loss (0.0), or an expert block's stats (``moe_forward``)."""
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.mla is not None:
        a, new_cache = L.mla_attention(p["attn"], cfg, h, positions, cache, cache_index)
    else:
        a, new_cache = L.attention(p["attn"], cfg, h, positions, cache,
                                   cache_index, window, positions3)
    x = x + a
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if use_moe:
        f, aux = MOE.moe_forward(p["moe"], cfg, h)
    else:
        f, aux = L.mlp(p["mlp"], h), jnp.float32(0.0)
    x = x + f
    x = constrain(x, ("batch", "seq", None))
    return x, new_cache, aux


def init_ssm_layer(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    k1, k2 = jax.random.split(key)
    return {"ln": L.init_rmsnorm(cfg.d_model, dtype),
            "ssm": SSM.init_ssm_block(k1, cfg, dtype)}


def ssm_layer(p: Params, cfg: ModelConfig, x, state=None, return_state=False):
    h = L.rmsnorm(p["ln"], x, cfg.norm_eps)
    y, new_state = SSM.ssm_block(p["ssm"], cfg, h, state, return_state)
    x = x + y
    x = constrain(x, ("batch", "seq", None))
    return x, new_state


# ============================================================ cache structures
def _kv_cache_shape(cfg: ModelConfig, batch: int, seq: int):
    if cfg.mla is not None:
        m = cfg.mla
        return L.MLACache(
            c_kv=jnp.zeros((batch, seq, m.kv_lora_rank), jnp.bfloat16),
            k_rope=jnp.zeros((batch, seq, m.qk_rope_head_dim), jnp.bfloat16))
    return L.KVCache(
        k=jnp.zeros((batch, seq, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16),
        v=jnp.zeros((batch, seq, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16))


def _ssm_state_shape(cfg: ModelConfig, batch: int):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    if s.version == 1:
        return SSM.Mamba1State(
            conv=jnp.zeros((batch, s.d_conv - 1, d_in), jnp.bfloat16),
            h=jnp.zeros((batch, d_in, s.d_state), jnp.float32))
    H = d_in // s.headdim
    conv_dim = d_in + 2 * s.n_groups * s.d_state
    return SSM.Mamba2State(
        conv=jnp.zeros((batch, s.d_conv - 1, conv_dim), jnp.bfloat16),
        h=jnp.zeros((batch, H, s.headdim, s.d_state), jnp.float32))


def _stack(n: int, leaf_fn):
    """Stack n zero-caches along a new leading axis."""
    proto = leaf_fn()
    return jax.tree_util.tree_map(
        lambda a: jnp.zeros((n,) + a.shape, a.dtype), proto)


# ===================================================================== pattern
class Pattern(NamedTuple):
    """Static description of the layer stack (derived from cfg)."""
    kind: str            # uniform_attn | local_global | moe | ssm | hybrid
    n_scan: int          # layers in the main scanned bank
    n_lead: int = 0
    n_groups: int = 0
    group_local: int = 0  # local layers per group (gemma3) / ssm per group (zamba2)
    n_tail: int = 0


def derive_pattern(cfg: ModelConfig) -> Pattern:
    if cfg.family == "ssm":
        return Pattern("ssm", n_scan=cfg.n_layers)
    if cfg.hybrid is not None:
        e = cfg.hybrid.shared_attn_every
        g = cfg.n_layers // e
        return Pattern("hybrid", n_scan=0, n_groups=g, group_local=e,
                       n_tail=cfg.n_layers - g * e)
    if cfg.local_global_ratio > 0:
        r = cfg.local_global_ratio
        g = cfg.n_layers // (r + 1)
        return Pattern("local_global", n_scan=0, n_groups=g, group_local=r,
                       n_tail=cfg.n_layers - g * (r + 1))
    if cfg.moe is not None:
        lead = cfg.moe.first_dense_layers
        return Pattern("moe", n_scan=cfg.n_layers - lead, n_lead=lead)
    return Pattern("uniform_attn", n_scan=cfg.n_layers)


# ======================================================================== model
class LM:
    def __init__(self, cfg: ModelConfig, dtype=jnp.bfloat16, remat: bool = True):
        self.cfg = cfg
        self.dtype = dtype
        self.remat = remat
        self.pattern = derive_pattern(cfg)

    # ------------------------------------------------------------------ init
    def init(self, key) -> Params:
        cfg, dtype = self.cfg, self.dtype
        pat = self.pattern
        keys = jax.random.split(key, 8)
        p: Params = {}
        if cfg.embed_inputs:
            if cfg.n_codebooks > 1:
                p["embed"] = L._dense_init(
                    keys[0], (cfg.n_codebooks, cfg.vocab_size, cfg.d_model),
                    dtype, scale=0.02)
            else:
                p["embed"] = L._dense_init(
                    keys[0], (cfg.vocab_size, cfg.d_model), dtype, scale=0.02)
        else:
            # decode path still needs a text-token embedding (frontend supplies
            # merged embeddings for train/prefill)
            p["embed"] = L._dense_init(
                keys[0], (cfg.vocab_size, cfg.d_model), dtype, scale=0.02)
        p["final_norm"] = L.init_rmsnorm(cfg.d_model, dtype)
        if not cfg.tie_embeddings:
            if cfg.n_codebooks > 1:
                p["lm_head"] = L._dense_init(
                    keys[1], (cfg.n_codebooks, cfg.d_model, cfg.vocab_size), dtype)
            else:
                p["lm_head"] = L._dense_init(
                    keys[1], (cfg.d_model, cfg.vocab_size), dtype)

        def stack_init(n, fn):
            ks = jax.random.split(keys[2], max(n, 1))
            return jax.vmap(fn)(ks[:n]) if n > 0 else None

        if pat.kind == "uniform_attn":
            p["blocks"] = stack_init(
                pat.n_scan, lambda k: init_attn_block(k, cfg, False, dtype=dtype))
        elif pat.kind == "moe":
            m = cfg.moe
            if pat.n_lead:
                ks = jax.random.split(keys[3], pat.n_lead)
                p["lead"] = [init_attn_block(k, cfg, False, dense_ff=m.d_ff_dense,
                                             dtype=dtype) for k in ks]
            p["blocks"] = stack_init(
                pat.n_scan, lambda k: init_attn_block(k, cfg, True, dtype=dtype))
        elif pat.kind == "ssm":
            p["blocks"] = stack_init(
                pat.n_scan, lambda k: init_ssm_layer(k, cfg, dtype))
        elif pat.kind == "local_global":
            def group_init(k):
                k1, k2 = jax.random.split(k)
                lk = jax.random.split(k1, pat.group_local)
                return {
                    "local": jax.vmap(
                        lambda kk: init_attn_block(kk, cfg, False, dtype=dtype))(lk),
                    "global": init_attn_block(k2, cfg, False, dtype=dtype),
                }
            gk = jax.random.split(keys[3], pat.n_groups)
            p["groups"] = jax.vmap(group_init)(gk)
            p["tail"] = stack_init(
                pat.n_tail, lambda k: init_attn_block(k, cfg, False, dtype=dtype))
        elif pat.kind == "hybrid":
            def group_init(k):
                lk = jax.random.split(k, pat.group_local)
                return jax.vmap(lambda kk: init_ssm_layer(kk, cfg, dtype))(lk)
            gk = jax.random.split(keys[3], pat.n_groups)
            p["groups"] = jax.vmap(group_init)(gk)
            p["shared"] = init_attn_block(keys[4], cfg, False, dtype=dtype)
            p["tail"] = stack_init(
                pat.n_tail, lambda k: init_ssm_layer(k, cfg, dtype))
        else:
            raise ValueError(pat.kind)
        return p

    # ----------------------------------------------------------------- cache
    def init_cache(self, batch: int, max_seq: int) -> Cache:
        cfg, pat = self.cfg, self.pattern
        c: Cache = {}
        if pat.kind in ("uniform_attn", "moe"):
            c["blocks"] = _stack(pat.n_scan, lambda: _kv_cache_shape(cfg, batch, max_seq))
            if pat.n_lead:
                c["lead"] = [_kv_cache_shape(cfg, batch, max_seq)
                             for _ in range(pat.n_lead)]
        elif pat.kind == "ssm":
            c["blocks"] = _stack(pat.n_scan, lambda: _ssm_state_shape(cfg, batch))
        elif pat.kind == "local_global":
            w = min(cfg.sliding_window or max_seq, max_seq)
            c["groups"] = {
                "local": _stack(pat.n_groups * pat.group_local,
                                lambda: _kv_cache_shape(cfg, batch, w)),
                "global": _stack(pat.n_groups,
                                 lambda: _kv_cache_shape(cfg, batch, max_seq)),
            }
            # reshape local to (G, R, ...)
            c["groups"]["local"] = jax.tree_util.tree_map(
                lambda a: a.reshape((pat.n_groups, pat.group_local) + a.shape[1:]),
                c["groups"]["local"])
            if pat.n_tail:
                c["tail"] = _stack(pat.n_tail, lambda: _kv_cache_shape(cfg, batch, w))
        elif pat.kind == "hybrid":
            c["groups"] = _stack(pat.n_groups * pat.group_local,
                                 lambda: _ssm_state_shape(cfg, batch))
            c["groups"] = jax.tree_util.tree_map(
                lambda a: a.reshape((pat.n_groups, pat.group_local) + a.shape[1:]),
                c["groups"])
            c["shared"] = _stack(pat.n_groups, lambda: _kv_cache_shape(cfg, batch, max_seq))
            if pat.n_tail:
                c["tail"] = _stack(pat.n_tail, lambda: _ssm_state_shape(cfg, batch))
        return c

    # ------------------------------------------------------------- embedding
    def embed(self, params: Params, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        cfg = self.cfg
        if not cfg.embed_inputs and "embeds" in batch:
            return batch["embeds"].astype(self.dtype)
        tokens = batch["tokens"]
        if cfg.n_codebooks > 1:
            # (B, T, K) -> sum_k embed[k][tok]
            xs = [jnp.take(params["embed"][k], tokens[..., k], axis=0)
                  for k in range(cfg.n_codebooks)]
            return functools.reduce(jnp.add, xs)
        return jnp.take(params["embed"], tokens, axis=0)

    def unembed(self, params: Params, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        if cfg.n_codebooks > 1:
            if cfg.tie_embeddings:
                logits = jnp.einsum("btd,kvd->btkv", x, head)
            else:
                logits = jnp.einsum("btd,kdv->btkv", x, head)
        else:
            if cfg.tie_embeddings:
                logits = x @ head.T
            else:
                logits = x @ head
        return constrain(logits, ("batch", "seq", None, "vocab")
                         if cfg.n_codebooks > 1 else ("batch", "seq", "vocab"))

    # ------------------------------------------------------------- backbone
    def _maybe_remat(self, fn, mode: str):
        # nothing_saveable = full per-layer recompute: the backward pass holds
        # one layer's activations at a time (scan carries only layer inputs).
        # dots_with_no_batch_dims_saveable would store every projection output
        # (~300 GB/device for gemma3-27b at train_4k — measured in the dry-run).
        if self.remat and mode == "train":
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.nothing_saveable)
        return fn

    def backbone(self, params: Params, x: jnp.ndarray, positions: jnp.ndarray,
                 cache: Optional[Cache] = None, t: Optional[jnp.ndarray] = None,
                 positions3: Optional[jnp.ndarray] = None, mode: str = "train",
                 ) -> Tuple[jnp.ndarray, Optional[Cache], jnp.ndarray]:
        cfg, pat = self.cfg, self.pattern
        aux0 = jnp.float32(0.0)
        serving = cache is not None
        new_cache: Cache = {}

        if pat.kind in ("uniform_attn", "moe"):
            use_moe = pat.kind == "moe"
            acc = MOE.add_stats if use_moe else operator.add
            if use_moe:
                aux0 = MOE.zero_stats()
            if pat.n_lead:
                lead_caches = cache["lead"] if serving else [None] * pat.n_lead
                new_lead = []
                for i, lp in enumerate(params["lead"]):
                    # a leading dense block: no balance loss, no counters
                    x, nc, _ = attn_block(lp, cfg, x, positions, lead_caches[i],
                                          t, None, positions3, use_moe=False)
                    new_lead.append(nc)
                if serving:
                    new_cache["lead"] = new_lead

            if serving and x.shape[1] == 1:
                # single-token decode: python-unrolled layers with in-place
                # dynamic-update-slice on the donated stacked cache.  A scan
                # would return fresh ys buffers (a full cache copy per step —
                # +6.4 GB/device for musicgen-large at decode_32k, measured).
                stacked = cache["blocks"]
                for i in range(pat.n_scan):
                    bp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
                    bc = jax.tree_util.tree_map(lambda a: a[i], stacked)
                    x, nc, a = attn_block(bp, cfg, x, positions, bc, t,
                                          cfg.sliding_window, positions3,
                                          use_moe)
                    aux0 = acc(aux0, a)
                    stacked = jax.tree_util.tree_map(
                        lambda full, upd, i=i: full.at[i].set(
                            upd.astype(full.dtype)), stacked, nc)
                new_cache["blocks"] = stacked
            elif serving:
                def body(carry, layer):
                    xx, aux = carry
                    bp, bc = layer
                    y, nc, a = attn_block(bp, cfg, xx, positions, bc, t,
                                          cfg.sliding_window, positions3, use_moe)
                    return (y, acc(aux, a)), nc
                (x, aux0), ncs = jax.lax.scan(
                    body, (x, aux0), (params["blocks"], cache["blocks"]))
                new_cache["blocks"] = ncs
            else:
                def body(carry, bp):
                    xx, aux = carry
                    y, _, a = attn_block(bp, cfg, xx, positions, None, None,
                                         cfg.sliding_window, positions3, use_moe)
                    return (y, acc(aux, a)), None
                (x, aux0), _ = jax.lax.scan(
                    self._maybe_remat(body, mode), (x, aux0), params["blocks"])

        elif pat.kind == "ssm":
            if serving:
                def body(xx, layer):
                    bp, st = layer
                    y, ns = ssm_layer(bp, cfg, xx, st)
                    return y, ns
                x, ncs = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
                new_cache["blocks"] = ncs
            else:
                def body(xx, bp):
                    y, _ = ssm_layer(bp, cfg, xx)
                    return y, None
                x, _ = jax.lax.scan(self._maybe_remat(body, mode), x, params["blocks"])

        elif pat.kind == "local_global":
            w = cfg.sliding_window
            if serving:
                def group(carry, layer):
                    xx, aux = carry
                    gp, gc = layer
                    def local_body(c2, lay2):
                        xx2, aux2 = c2
                        lp, lc = lay2
                        y, nc, a = attn_block(lp, cfg, xx2, positions, lc, t, w)
                        return (y, aux2 + a), nc
                    (xx, aux), nlc = jax.lax.scan(
                        local_body, (xx, aux), (gp["local"], gc["local"]))
                    xx, ngc, a = attn_block(gp["global"], cfg, xx, positions,
                                            gc["global"], t, None)
                    return (xx, aux + a), {"local": nlc, "global": ngc}
                (x, aux0), ncs = jax.lax.scan(
                    group, (x, aux0), (params["groups"], cache["groups"]))
                new_cache["groups"] = ncs
                if pat.n_tail:
                    def tail_body(c2, lay2):
                        xx2, aux2 = c2
                        lp, lc = lay2
                        y, nc, a = attn_block(lp, cfg, xx2, positions, lc, t, w)
                        return (y, aux2 + a), nc
                    (x, aux0), ntc = jax.lax.scan(
                        tail_body, (x, aux0), (params["tail"], cache["tail"]))
                    new_cache["tail"] = ntc
            else:
                def group(carry, gp):
                    xx, aux = carry
                    def local_body(c2, lp):
                        xx2, aux2 = c2
                        y, _, a = attn_block(lp, cfg, xx2, positions, None, None, w)
                        return (y, aux2 + a), None
                    (xx, aux), _ = jax.lax.scan(local_body, (xx, aux), gp["local"])
                    xx, _, a = attn_block(gp["global"], cfg, xx, positions, None, None, None)
                    return (xx, aux + a), None
                (x, aux0), _ = jax.lax.scan(
                    self._maybe_remat(group, mode), (x, aux0), params["groups"])
                if pat.n_tail:
                    def tail_body(c2, lp):
                        xx2, aux2 = c2
                        y, _, a = attn_block(lp, cfg, xx2, positions, None, None, w)
                        return (y, aux2 + a), None
                    (x, aux0), _ = jax.lax.scan(
                        self._maybe_remat(tail_body, mode), (x, aux0), params["tail"])

        elif pat.kind == "hybrid":
            shared_p = params["shared"]
            if serving:
                def group(carry, layer):
                    xx = carry
                    gp, gst, sc = layer
                    def ssm_body(xx2, lay2):
                        lp, st = lay2
                        y, ns = ssm_layer(lp, cfg, xx2, st)
                        return y, ns
                    xx, nst = jax.lax.scan(ssm_body, xx, (gp, gst))
                    xx, nsc, _ = attn_block(shared_p, cfg, xx, positions, sc, t)
                    return xx, (nst, nsc)
                x, (nst, nsc) = jax.lax.scan(
                    group, x, (params["groups"], cache["groups"], cache["shared"]))
                new_cache["groups"] = nst
                new_cache["shared"] = nsc
                if pat.n_tail:
                    def tail_body(xx2, lay2):
                        lp, st = lay2
                        y, ns = ssm_layer(lp, cfg, xx2, st)
                        return y, ns
                    x, ntc = jax.lax.scan(tail_body, x, (params["tail"], cache["tail"]))
                    new_cache["tail"] = ntc
            else:
                def group(xx, gp):
                    def ssm_body(xx2, lp):
                        y, _ = ssm_layer(lp, cfg, xx2)
                        return y, None
                    xx, _ = jax.lax.scan(ssm_body, xx, gp)
                    xx, _, _ = attn_block(shared_p, cfg, xx, positions, None)
                    return xx, None
                x, _ = jax.lax.scan(self._maybe_remat(group, mode), x, params["groups"])
                if pat.n_tail:
                    def tail_body(xx2, lp):
                        y, _ = ssm_layer(lp, cfg, xx2)
                        return y, None
                    x, _ = jax.lax.scan(
                        self._maybe_remat(tail_body, mode), x, params["tail"])

        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return x, (new_cache if serving else None), aux0

    # ------------------------------------------------------------------ loss
    def loss_fn(self, params: Params, batch: Dict[str, jnp.ndarray],
                aux_weight: float = 0.01) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        """Cross-entropy plus the weighted balance loss.  An expert model
        weighs its balance loss by ``moe.aux_weight`` and returns its held
        experts' counters as ``metrics["moe"]``."""
        cfg = self.cfg
        x = self.embed(params, batch)
        x = constrain(x, ("batch", "seq", None))
        B, T = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        positions3 = batch.get("positions3")
        x, _, aux = self.backbone(params, x, positions, positions3=positions3,
                                  mode="train")
        logits = self.unembed(params, x)
        labels = batch["labels"]
        ce = softmax_xent(logits, labels)
        counters = {}
        if isinstance(aux, dict):
            counters["moe"] = {k: v for k, v in aux.items() if k != "aux"}
            aux, aux_weight = aux["aux"], cfg.moe.aux_weight
        loss = ce + aux_weight * aux
        return loss, {"ce": ce, "aux": aux, **counters}

    # --------------------------------------------------------------- serving
    def prefill(self, params: Params, batch: Dict[str, jnp.ndarray],
                cache: Cache) -> Tuple[jnp.ndarray, Cache]:
        """Run the prompt through the model, writing cache at positions 0..T."""
        cfg = self.cfg
        x = self.embed(params, batch)
        B, T = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        positions3 = batch.get("positions3")
        x, new_cache, _ = self.backbone(
            params, x, positions, cache=cache, t=jnp.int32(0),
            positions3=positions3, mode="prefill")
        logits = self.unembed(params, x[:, -1:])
        return logits, new_cache

    def decode_step(self, params: Params, cache: Cache, token: jnp.ndarray,
                    t: jnp.ndarray) -> Tuple[jnp.ndarray, Cache]:
        """token: (B, 1) int32 (or (B, 1, K) for multi-codebook); t: scalar."""
        cfg = self.cfg
        batch: Dict[str, jnp.ndarray] = {"tokens": token}
        x = self.embed(params, batch)
        B = x.shape[0]
        positions = jnp.full((B, 1), t, jnp.int32)
        positions3 = None
        if cfg.mrope:
            positions3 = jnp.broadcast_to(
                jnp.full((1, B, 1), t, jnp.int32), (3, B, 1))
        x, new_cache, _ = self.backbone(
            params, x, positions, cache=cache, t=t,
            positions3=positions3, mode="decode")
        logits = self.unembed(params, x)
        return logits, new_cache


# ------------------------------------------------------------------ loss util
def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean cross-entropy; partition-friendly over a vocab-sharded last dim.

    logits: (..., V) ; labels: (...) int32.  Uses a one-hot pick (elementwise,
    partitionable) instead of take_along_axis (gather over a sharded dim).
    """
    lf = logits.astype(jnp.float32)
    m = jnp.max(lf, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    V = logits.shape[-1]
    onehot = (labels[..., None] == jnp.arange(V, dtype=labels.dtype)).astype(jnp.float32)
    picked = jnp.sum(lf * onehot, axis=-1)
    return jnp.mean(lse - picked)
