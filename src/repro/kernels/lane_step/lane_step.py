"""Pallas kernel: the batched ensemble segment step over [lane, row].

One fused elementwise pass computes, for every (lane, row) mover slot, the
walk's branch-free first iteration: seconds to the next byte boundary at the
row's fair-share rate, whether the boundary lands inside the tick (``hit``),
and the resulting byte/active-time/flow updates.  This is the inner loop of
the ensemble engine's lockstep tick — thousands of perturbed worlds advance
through this one kernel call.

Shapes are pre-padded by ``ops.py`` to (8, 128) tile multiples; the grid
walks 8-lane blocks.  Padding rows carry ``rate = 0`` and ``bound =
bytes_done``, which the engine masks out anyway (``hit`` on a PAD row is
never read).

The trajectory contract is float64, and compiled Pallas on a TPU has no
float64: the TPU compiler refuses this kernel in f64.  So it runs only in
interpret mode on the CPU backend, and ``ops.lane_segment_step`` refuses
any other backend before compiling; the ensemble's ``jax`` backend is the
device path (XLA rewrites its f64).  Running compiled on a chip would need
an f32 hi/lo formulation of the step."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

LANE_BLOCK = 8          # sublane tile
ROW_TILE = 128          # last-dim tile


def _lane_step_kernel(t_ref, bd_ref, rate_ref, bound_ref,
                      tl_ref, nb_ref, adv_ref, mv_ref, hit_ref):
    t = t_ref[...]
    bd = bd_ref[...]
    rate = rate_ref[...]
    bound = bound_ref[...]
    pos = rate > 0
    need = jnp.where(pos,
                     jnp.maximum(0.0, bound - bd)
                     / jnp.where(pos, rate, 1.0),
                     jnp.inf)
    hit = need <= t
    adv = jnp.where(hit, need, t)
    tl_ref[...] = jnp.where(hit, t - need, 0.0)
    nb_ref[...] = jnp.where(hit, bound, bd + rate * t)
    adv_ref[...] = adv
    mv_ref[...] = rate * adv
    hit_ref[...] = hit


@functools.partial(jax.jit, static_argnames=("interpret",))
def lane_step_pallas(t: jax.Array, bytes_done: jax.Array, rate: jax.Array,
                     bound: jax.Array, interpret: Optional[bool] = None):
    """All inputs one float dtype, [L, R] with L % 8 == 0 and R % 128 == 0
    (pre-padded by ops.py).  Returns (t_left, new_bytes, adv, moved,
    hit[bool]); ``interpret=None`` interprets on the CPU backend only."""
    L, R = bytes_done.shape
    grid = (L // LANE_BLOCK,)
    spec = pl.BlockSpec((LANE_BLOCK, R), lambda i: (i, 0))
    fl = jax.ShapeDtypeStruct((L, R), bytes_done.dtype)
    return pl.pallas_call(
        _lane_step_kernel,
        grid=grid,
        in_specs=[spec, spec, spec, spec],
        out_specs=[spec, spec, spec, spec, spec],
        out_shape=[fl, fl, fl, fl,
                   jax.ShapeDtypeStruct((L, R), jnp.bool_)],
        interpret=resolve_interpret(interpret),
    )(t, bytes_done, rate, bound)
