"""Public op: GQA-aware flash attention wrapper.

Maps (B, T, H, hd) GQA layouts onto the (B, H, T, hd) kernel, repeating KV
heads per group.  ``use_pallas=False`` routes to the jnp oracle.  No model calls this op;
the models run their own chunked attention in ``models/layers.py``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    window: Optional[int] = None,
                    use_pallas: bool = True,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, T, H, hd); k, v: (B, T, Hkv, hd) with H % Hkv == 0 -> (B,T,H,hd)."""
    B, T, H, hd = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qt = q.transpose(0, 2, 1, 3)
    kt = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1)
    vt = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1)
    if use_pallas:
        out = flash_attention_pallas(qt, kt, vt, window=window,
                                     interpret=interpret)
    else:
        out = attention_ref(qt, kt, vt, window=window)
    return out.transpose(0, 2, 1, 3)
