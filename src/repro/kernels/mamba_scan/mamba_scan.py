"""Pallas TPU kernel: chunked selective scan (Mamba1 S6).

TPU adaptation of the CUDA selective-scan kernel (DESIGN.md §2): instead of a
warp-level scan, we tile (T × D) into (CHUNK_T × BLOCK_D) VMEM blocks.  The
grid is (batch, D-blocks, T-chunks) with the T axis innermost: TPU grid steps
execute sequentially, so the carried state ``h`` lives in a VMEM scratch
accumulator across T-chunks of the same (batch, D-block) and is re-initialized
from ``h0`` whenever a new (batch, D-block) begins.  Within a chunk the
recurrence is a ``lax.fori_loop`` over rows — VPU elementwise work over
(BLOCK_D, N) lanes, which is MXU-free and bandwidth-bound, matching the op's
roofline.

Block sizes: BLOCK_D a multiple of 128 (lane width), CHUNK_T sized so
u/dt/B/C blocks (~4 × CHUNK_T × BLOCK_D × 4B) fit comfortably in VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

CHUNK_T = 128
BLOCK_D = 256


def _scan_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref,
                 y_ref, hT_ref, h_scr):
    tc = pl.program_id(2)

    @pl.when(tc == 0)
    def _init():
        h_scr[...] = h0_ref[0]                        # (BLOCK_D, N)

    A = a_ref[...]                                    # (BLOCK_D, N)
    h = h_scr[...]

    def row(t, h):
        dt_t = dt_ref[0, t, :]                        # (BLOCK_D,)
        u_t = u_ref[0, t, :]
        b_t = b_ref[0, t, :]                          # (N,)
        c_t = c_ref[0, t, :]
        a = jnp.exp(dt_t[:, None] * A)                # (BLOCK_D, N)
        h = a * h + (dt_t * u_t)[:, None] * b_t[None, :]
        y_ref[0, t, :] = jnp.sum(h * c_t[None, :], axis=1)
        return h

    h = jax.lax.fori_loop(0, u_ref.shape[1], row, h)
    h_scr[...] = h
    hT_ref[0] = h


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_pallas(u: jax.Array, dt: jax.Array, Bm: jax.Array,
                          Cm: jax.Array, A: jax.Array, h0: jax.Array,
                          interpret: Optional[bool] = None
                          ) -> Tuple[jax.Array, jax.Array]:
    """Same contract as ``ref.selective_scan_ref`` (all f32);
    ``interpret=None`` interprets on the CPU backend only.

    Requires T % CHUNK_T == 0 and D % BLOCK_D == 0 when larger than the block
    (callers pad; the assigned arch shapes satisfy this natively:
    falcon-mamba D=8192, T ∈ {4096, 32768}).
    """
    B, T, D = u.shape
    N = A.shape[1]
    ct = min(CHUNK_T, T)
    bd = min(BLOCK_D, D)
    assert T % ct == 0 and D % bd == 0, (T, D, ct, bd)
    grid = (B, D // bd, T // ct)

    y, hT = pl.pallas_call(
        _scan_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ct, bd), lambda b, d, t: (b, t, d)),   # u
            pl.BlockSpec((1, ct, bd), lambda b, d, t: (b, t, d)),   # dt
            pl.BlockSpec((1, ct, N), lambda b, d, t: (b, t, 0)),    # B
            pl.BlockSpec((1, ct, N), lambda b, d, t: (b, t, 0)),    # C
            pl.BlockSpec((bd, N), lambda b, d, t: (d, 0)),          # A
            pl.BlockSpec((1, bd, N), lambda b, d, t: (b, d, 0)),    # h0
        ],
        out_specs=[
            pl.BlockSpec((1, ct, bd), lambda b, d, t: (b, t, d)),   # y
            pl.BlockSpec((1, bd, N), lambda b, d, t: (b, d, 0)),    # hT
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, D), jnp.float32),
            jax.ShapeDtypeStruct((B, D, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(u, dt, Bm, Cm, A, h0)
    return y, hT
