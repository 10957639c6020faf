"""Pallas TPU kernel: blocked causal flash attention with online softmax.

Tiling: grid (B*H, Tq/BQ, Tk/BK) with the key axis innermost.  Each grid step
loads a (BQ, d) query block and a (BK, d) key/value block into VMEM, updates
the running max/denominator (online softmax) and the (BQ, d) accumulator held
in VMEM scratch.  The causal structure is exploited two ways:

  * blocks strictly above the diagonal contribute nothing — ``pl.when``
    skips their compute entirely (half the FLOPs of a naive masked kernel);
  * the diagonal blocks apply the elementwise causal (and optional sliding
    window) mask.

BQ = BK = 128 aligns with the MXU (128×128) and lane width.  bf16 inputs are
upcast to f32 for the softmax math, matching the jnp reference.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

BQ = 128
BK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, scale: float, window: Optional[int], bq: int, bk: int,
                  n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: key block strictly above the diagonal is dead
    q_start = qi * bq
    k_start = ki * bk

    @pl.when(k_start <= q_start + bq - 1)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale           # (BQ, d)
        k = k_ref[0].astype(jnp.float32)                   # (BK, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (BQ, BK)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos <= qpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                # (BQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                             # (BQ, BK)
        alpha = jnp.exp(m_prev - m_new)                    # (BQ, 1)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * alpha
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
        m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           window: Optional[int] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """q, k, v: (B, H, T, d), T % 128 == 0.  Causal; optional sliding window.
    ``interpret=None`` interprets on the CPU backend only."""
    B, H, T, d = q.shape
    bq, bk = min(BQ, T), min(BK, T)
    assert T % bq == 0 and T % bk == 0, (T, bq, bk)
    scale = d ** -0.5
    qf = q.reshape(B * H, T, d)
    kf = k.reshape(B * H, T, d)
    vf = v.reshape(B * H, T, d)
    n_k = T // bk
    kern = functools.partial(_flash_kernel, scale=scale, window=window,
                             bq=bq, bk=bk, n_k=n_k)
    out = pl.pallas_call(
        kern,
        grid=(B * H, T // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(B, H, T, d)
