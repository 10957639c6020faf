"""Core transformer layers: norms, RoPE/M-RoPE, GQA / MLA attention, gated MLP.

Conventions
-----------
* All functions are pure; params are dicts of jnp arrays (bf16 by default).
* ``x``: (B, T, D) activations.  ``segment positions``: (B, T) int32.
* Attention supports: full causal, sliding-window causal, decode-with-KV-cache.
* Norms and softmax computed in f32, cast back to input dtype.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.axes import constrain
from repro.models.config import ModelConfig, MLAConfig, YarnScaling

Params = dict
NEG_INF = -1e30


# --------------------------------------------------------------------------- init
def _dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_rmsnorm(d: int, dtype=jnp.bfloat16) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------- rope
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim: int, theta: float, s: YarnScaling):
    """The pair indices between which YaRN ramps from the original to the
    interpolated frequencies (DeepSeek-V2's ``yarn_find_correction_range``)."""
    def at(rotations):
        return (dim * math.log(s.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(at(s.beta_fast)), 0),
            min(math.ceil(at(s.beta_slow)), dim - 1))


def yarn_freqs(dim: int, theta: float, s: YarnScaling) -> jnp.ndarray:
    """YaRN inverse frequencies: the original ones below the correction
    range, those divided by ``factor`` above it, a linear ramp between."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    low, high = yarn_correction_range(dim, theta, s)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    keep = 1.0 - ramp
    return jnp.asarray(extra / s.factor * (1 - keep) + extra * keep, jnp.float32)


def yarn_softmax_scale(s: YarnScaling) -> float:
    """YaRN's factor on the attention softmax scale: mscale(mscale_all_dim)^2."""
    return yarn_mscale(s.factor, s.mscale_all_dim) ** 2


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               scaling: Optional[YarnScaling] = None) -> jnp.ndarray:
    """x: (B, T, H, hd); positions: (B, T) -> rotated x (same dtype)."""
    hd = x.shape[-1]
    if scaling is None:
        freqs = rope_freqs(hd, theta)                   # (hd/2,)
    else:
        freqs = yarn_freqs(hd, theta, scaling)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, T, hd/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions3: jnp.ndarray, theta: float,
                sections: Tuple[int, int, int]) -> jnp.ndarray:
    """Multimodal RoPE (Qwen2-VL): positions3 (3, B, T) = (t, h, w) ids.

    Frequency dims are split into 3 sections, each rotated by its own position
    stream.  ``sections`` counts frequency *pairs* per section and must sum to
    head_dim // 2.
    """
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    # per-frequency position selection
    sec_id = jnp.repeat(jnp.arange(3), jnp.array(sections), total_repeat_length=hd // 2)
    pos = positions3.astype(jnp.float32)                # (3, B, T)
    pos_per_freq = pos[sec_id]                          # (hd/2, B, T)
    ang = jnp.einsum("fbt,f->btf", pos_per_freq, freqs)  # (B, T, hd/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- attention
class KVCache(NamedTuple):
    k: jnp.ndarray   # (B, S, Hkv, hd)
    v: jnp.ndarray   # (B, S, Hkv, hd)
    # cache write index is carried by the caller (same for all layers)


def causal_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                window: Optional[int] = None) -> jnp.ndarray:
    """(B, Tq, Tk) boolean mask: True = attend."""
    m = q_pos[:, :, None] >= k_pos[:, None, :]
    if window is not None:
        m &= (q_pos[:, :, None] - k_pos[:, None, :]) < window
    return m


# Query-chunk size for the scan-based attention path.  Chosen so the live
# (B/dp, H, CHUNK_Q, S) f32 logits block stays O(1 GB) per device for the
# assigned shapes.  This path is what every model runs, on a TPU too: no
# model calls the Pallas flash kernel.
CHUNK_Q = 128
_CHUNK_THRESHOLD = 1 << 22


def _sdpa_block(q, k, v, q_pos, k_pos, window, valid, scale) -> jnp.ndarray:
    """One (possibly full) query block.  q: (B,T,H,hd); k/v: (B,S,Hkv,hd)."""
    B, T, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    qf = q.astype(jnp.float32) * scale
    qg = qf.reshape(B, T, Hkv, g, hd)
    logits = jnp.einsum("btkgh,bskh->bkgts", qg, k.astype(jnp.float32))
    mask = causal_mask(q_pos, k_pos, window)
    if valid is not None:
        mask = mask & valid[:, None, :]
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", w, v.astype(jnp.float32))
    return out.reshape(B, T, H, hd).astype(q.dtype)


def sdpa(q, k, v, q_pos, k_pos, window: Optional[int] = None,
         valid: Optional[jnp.ndarray] = None,
         scale: Optional[float] = None) -> jnp.ndarray:
    """Causal attention; scans over query chunks when T*S is large so the
    lowered HLO never materializes the full (T, S) score tensor.

    q: (B,T,H,hd); k/v: (B,S,Hkv,hd); q_pos: (B,T); k_pos: (B,S);
    valid: (B,S) cache-slot validity (decode/prefill-into-cache).
    """
    B, T, H, hd = q.shape
    S = k.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    if T * S <= _CHUNK_THRESHOLD or T % CHUNK_Q or T <= CHUNK_Q:
        return _sdpa_block(q, k, v, q_pos, k_pos, window, valid, scale)
    nc = T // CHUNK_Q

    # remat each chunk: backward recomputes the chunk's scores instead of
    # keeping nc stacked (B, H, CHUNK_Q, S) softmax residuals alive
    blk = jax.checkpoint(
        lambda qc, qpc: _sdpa_block(qc, k, v, qpc, k_pos, window, valid, scale),
        policy=jax.checkpoint_policies.nothing_saveable)

    def body(_, xs):
        qc, qpc = xs
        return None, blk(qc, qpc)

    q_c = q.reshape(B, nc, CHUNK_Q, H, hd).transpose(1, 0, 2, 3, 4)
    qp_c = q_pos.reshape(B, nc, CHUNK_Q).transpose(1, 0, 2)
    _, outs = jax.lax.scan(body, None, (q_c, qp_c))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, T, H, hd)


def init_attention(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 8)
    p = {
        "wq": _dense_init(ks[0], (d, H * hd), dtype),
        "wk": _dense_init(ks[1], (d, Hkv * hd), dtype),
        "wv": _dense_init(ks[2], (d, Hkv * hd), dtype),
        "wo": _dense_init(ks[3], (H * hd, d), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, dtype)
        p["k_norm"] = init_rmsnorm(hd, dtype)
    return p


def attention(p: Params, cfg: ModelConfig, x: jnp.ndarray,
              positions: jnp.ndarray,
              cache: Optional[KVCache] = None,
              cache_index: Optional[jnp.ndarray] = None,
              window: Optional[int] = None,
              positions3: Optional[jnp.ndarray] = None,
              ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """GQA attention.  Training: cache=None.  Decode: cache + cache_index.

    positions: (B, T) absolute positions of the query tokens.  Windowed layers
    use ring-buffer caches (cache length == window): slot = pos % W; stored keys
    carry RoPE at their absolute positions.
    """
    B, T, D = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    # Megatron TP: heads stay sharded through rope/norm/attention; only wo's
    # row-parallel contraction reduces over 'model' (rules drop the axis when
    # head counts don't divide the TP axis).  Single-token decode skips the
    # constraints: there the layout must follow the donated cache, and the
    # extra reshard copies cost +9 GB/device (musicgen decode_32k, measured).
    def _maybe(t, names):
        return constrain(t, names) if T > 1 else t
    q = _maybe((x @ p["wq"]).reshape(B, T, H, hd),
               ("batch", "seq", "heads", None))
    k = _maybe((x @ p["wk"]).reshape(B, T, Hkv, hd),
               ("batch", "seq", "kv", None))
    v = _maybe((x @ p["wv"]).reshape(B, T, Hkv, hd),
               ("batch", "seq", "kv", None))
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if cfg.mrope and positions3 is not None:
        q = apply_mrope(q, positions3, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions3, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None:
        out = sdpa(q, k, v, positions, positions, window)
        new_cache = None
        y = out.reshape(B, T, H * hd) @ p["wo"]
        return y, new_cache

    S = cache.k.shape[1]
    ring = window is not None and S <= window
    if ring and T > 1:
        # prefill into a ring buffer: attend full-sequence with window mask,
        # then store the last min(T, S) k/v at slots pos % S.
        out = sdpa(q, k, v, positions, positions, window)
        W = min(T, S)
        import numpy as _np
        slots = _np.arange(T - W, T) % S                  # static permutation
        ck = cache.k.at[:, slots].set(k[:, -W:].astype(cache.k.dtype))
        cv = cache.v.at[:, slots].set(v[:, -W:].astype(cache.v.dtype))
        new_cache = KVCache(ck, cv)
    elif ring:
        # decode with ring buffer
        slot = jnp.mod(cache_index, S)
        ck = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype),
                                          (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype),
                                          (0, slot, 0, 0))
        j = jnp.arange(S, dtype=jnp.int32)
        t_now = positions[:, -1:]                          # (B, 1)
        k_pos = t_now - jnp.mod(t_now - j[None, :], S)     # (B, S) abs pos of slot
        out = sdpa(q, ck, cv, positions, k_pos, window,
                   valid=(k_pos >= 0))
        new_cache = KVCache(ck, cv)
    else:
        # full cache: write new k/v at cache_index, attend over filled slots
        ck = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype),
                                          (0, cache_index, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype),
                                          (0, cache_index, 0, 0))
        k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        valid = k_pos <= positions[:, -1:]  # (B, S): only filled slots
        out = sdpa(q, ck, cv, positions, k_pos, window, valid=valid)
        new_cache = KVCache(ck, cv)
    y = out.reshape(B, T, H * hd) @ p["wo"]
    return y, new_cache


# --------------------------------------------------------------------------- MLA
def init_mla(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    m: MLAConfig = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq": _dense_init(ks[0], (d, H * qk_hd), dtype),
        "w_dkv": _dense_init(ks[1], (d, m.kv_lora_rank), dtype),
        "w_krope": _dense_init(ks[2], (d, m.qk_rope_head_dim), dtype),
        "kv_norm": init_rmsnorm(m.kv_lora_rank, dtype),
        "w_uk": _dense_init(ks[3], (m.kv_lora_rank, H * m.qk_nope_head_dim), dtype),
        "w_uv": _dense_init(ks[4], (m.kv_lora_rank, H * m.v_head_dim), dtype),
        "wo": _dense_init(ks[5], (H * m.v_head_dim, d), dtype),
    }


class MLACache(NamedTuple):
    c_kv: jnp.ndarray    # (B, S, kv_lora_rank) — compressed latent
    k_rope: jnp.ndarray  # (B, S, rope_dim) — shared rope key


def mla_attention(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                  positions: jnp.ndarray,
                  cache: Optional[MLACache] = None,
                  cache_index: Optional[jnp.ndarray] = None,
                  ) -> Tuple[jnp.ndarray, Optional[MLACache]]:
    """Multi-head Latent Attention (DeepSeek-V2).  Caches the 512-d latent
    + shared rope key instead of per-head K/V (the paper's KV-cache saving)."""
    with jax.named_scope("mla.attention"):
        return _mla_attention(p, cfg, x, positions, cache, cache_index)


def _mla_attention(p, cfg, x, positions, cache, cache_index):
    m: MLAConfig = cfg.mla
    B, T, D = x.shape
    H = cfg.n_heads
    nope, rope_d, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    yarn = cfg.rope_scaling

    q = constrain((x @ p["wq"]).reshape(B, T, H, nope + rope_d),
                  ("batch", "seq", "heads", None))
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, yarn)

    c_kv = rmsnorm(p["kv_norm"], x @ p["w_dkv"], cfg.norm_eps)   # (B,T,r)
    k_rope_new = apply_rope((x @ p["w_krope"])[:, :, None, :],
                            positions, cfg.rope_theta, yarn)[:, :, 0, :]

    if cache is not None:
        c_kv_full = jax.lax.dynamic_update_slice(
            cache.c_kv, c_kv.astype(cache.c_kv.dtype), (0, cache_index, 0))
        k_rope_full = jax.lax.dynamic_update_slice(
            cache.k_rope, k_rope_new.astype(cache.k_rope.dtype), (0, cache_index, 0))
        new_cache = MLACache(c_kv_full, k_rope_full)
        S = c_kv_full.shape[1]
        k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        valid = k_pos <= positions[:, -1:]
    else:
        c_kv_full, k_rope_full = c_kv, k_rope_new
        new_cache = None
        S = T
        k_pos = positions
        valid = None

    # expand latent to per-head K (nope part) and V
    k_nope = constrain((c_kv_full @ p["w_uk"]).reshape(B, S, H, nope),
                       ("batch", "seq", "heads", None))
    vv = constrain((c_kv_full @ p["w_uv"]).reshape(B, S, H, vd),
                   ("batch", "seq", "heads", None))
    scale = (nope + rope_d) ** -0.5
    if yarn is not None:
        scale *= yarn_softmax_scale(yarn)

    def mla_block(qn, qr, qp):
        Tq = qn.shape[1]
        lg = jnp.einsum("bthn,bshn->bhts", qn.astype(jnp.float32),
                        k_nope.astype(jnp.float32))
        lg += jnp.einsum("bthr,bsr->bhts", qr.astype(jnp.float32),
                         k_rope_full.astype(jnp.float32))
        mask = causal_mask(qp, k_pos)
        if valid is not None:
            mask = mask & valid[:, None, :]
        lg = jnp.where(mask[:, None, :, :], lg * scale, NEG_INF)
        w = jax.nn.softmax(lg, axis=-1)
        return jnp.einsum("bhts,bshv->bthv", w, vv.astype(jnp.float32))

    if T * S <= _CHUNK_THRESHOLD or T % CHUNK_Q or T <= CHUNK_Q:
        out = mla_block(q_nope, q_rope, positions)
    else:
        nc = T // CHUNK_Q
        blk = jax.checkpoint(mla_block,
                             policy=jax.checkpoint_policies.nothing_saveable)

        def body(_, xs):
            qn, qr, qp = xs
            return None, blk(qn, qr, qp)

        qn_c = q_nope.reshape(B, nc, CHUNK_Q, H, nope).transpose(1, 0, 2, 3, 4)
        qr_c = q_rope.reshape(B, nc, CHUNK_Q, H, rope_d).transpose(1, 0, 2, 3, 4)
        qp_c = positions.reshape(B, nc, CHUNK_Q).transpose(1, 0, 2)
        _, outs = jax.lax.scan(body, None, (qn_c, qr_c, qp_c))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(B, T, H, vd)
    y = out.reshape(B, T, H * vd).astype(x.dtype) @ p["wo"]
    return y, new_cache


# --------------------------------------------------------------------------- mlp
def init_mlp(key, d: int, ff: int, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(ks[0], (d, ff), dtype),
        "w_up": _dense_init(ks[1], (d, ff), dtype),
        "w_down": _dense_init(ks[2], (ff, d), dtype),
    }


def mlp(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    # Megatron TP: the hidden (ff) dim stays sharded through the elementwise
    # silu — only w_down's row-parallel contraction reduces over 'model'
    g = constrain(x @ p["w_gate"], ("batch", "seq", "ff"))
    u = constrain(x @ p["w_up"], ("batch", "seq", "ff"))
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32))
    return (h.astype(x.dtype)) @ p["w_down"]
