"""Pallas TPU kernel: streaming integrity hash over uint32 words.

Tiling: input reshaped to (R, 512) words (4 sublanes × 128 lanes per row).
The grid walks row-blocks sequentially; each step XOR-accumulates its block's
mixed words into a (8, 512) VMEM accumulator (the output block, revisited at
every grid step — TPU grid steps execute in order, so accumulation is safe).
Position mixing uses the global word index derived from the grid coordinate,
so the result is bit-identical to ``ref.checksum_words_np`` for any tiling.

This is the DTN-checksum hot loop of the paper mapped to TPU: bandwidth-bound
streaming over HBM with a tiny VMEM-resident state.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret
from repro.kernels.checksum.ref import PHI, ROW

BLOCK_ROWS = 256          # rows of 512 words per grid step (512 KB per block)
ACC_ROWS = 8


def _mix32(x):
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    return x


def _checksum_kernel(nw_ref, x_ref, acc_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    blk = x_ref[...].astype(jnp.uint32)                  # (BLOCK_ROWS, ROW)
    r, c = blk.shape
    row_ids = jax.lax.broadcasted_iota(jnp.uint32, (r, c), 0)
    col_ids = jax.lax.broadcasted_iota(jnp.uint32, (r, c), 1)
    base = (step * BLOCK_ROWS).astype(jnp.uint32) * jnp.uint32(ROW)
    idx = base + row_ids * jnp.uint32(ROW) + col_ids     # global word index
    g = _mix32(blk ^ (idx * jnp.uint32(PHI)))
    # zero-padding beyond the true word count must not contribute
    nw = nw_ref[0, 0]
    g = jnp.where(idx < nw, g, jnp.uint32(0))
    # fold BLOCK_ROWS -> ACC_ROWS with XORs of whole (8, 512) tiles, so the
    # accumulator stays tiny; the TPU lowering has no in-kernel reduce
    part = g[:ACC_ROWS]
    for k in range(ACC_ROWS, r, ACC_ROWS):
        part = part ^ g[k:k + ACC_ROWS]
    acc_ref[...] ^= part


@functools.partial(jax.jit, static_argnames=("interpret",))
def checksum_words_pallas(words: jax.Array, n_words: jax.Array,
                          nbytes: jax.Array,
                          interpret: Optional[bool] = None) -> jax.Array:
    """words: uint32[N] with N % (BLOCK_ROWS*ROW) == 0 (pre-padded by ops.py);
    n_words: true (unpadded) word count; nbytes: true byte length.
    ``interpret=None`` interprets on the CPU backend only.

    Returns the uint32 scalar hash (bit-identical to the numpy reference).
    """
    n = words.size
    rows = n // ROW
    grid = rows // BLOCK_ROWS
    x2 = words.reshape(rows, ROW)
    nw = jnp.reshape(n_words.astype(jnp.uint32), (1, 1))
    acc = pl.pallas_call(
        _checksum_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  pl.BlockSpec((BLOCK_ROWS, ROW), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ACC_ROWS, ROW), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((ACC_ROWS, ROW), jnp.uint32),
        interpret=resolve_interpret(interpret),
    )(nw, x2)
    h = jax.lax.reduce(acc.reshape(-1), jnp.uint32(0),
                       jax.lax.bitwise_xor, (0,))
    h = h ^ nbytes.astype(jnp.uint32)
    return _mix32(h)
