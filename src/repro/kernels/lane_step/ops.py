"""Public op: batched segment step with shape padding + x64 scoping.

Pads [L, R] inputs to the kernel's (8, 128) tile granularity, runs the
Pallas kernel in interpret mode under a scoped x64 context (the global flag
is never touched), and slices the padding back off.  Pad slots get
``rate = 0`` and ``bound = bytes_done = 0`` so they compute ``hit = False``
harmlessly.  CPU backend only: see ``require_cpu_backend``."""
from __future__ import annotations

import numpy as np

from repro.kernels.lane_step.lane_step import (LANE_BLOCK, ROW_TILE,
                                               lane_step_pallas)


def require_cpu_backend() -> None:
    """Refuse, before anything compiles, to run the float64 kernel where it
    would have to compile: compiled Pallas on a TPU has no float64."""
    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"the lane-step Pallas kernel computes in float64, which compiled "
            f"Pallas on {backend!r} does not support; it runs only in "
            f"interpret mode on the CPU backend. Use the ensemble's 'jax' "
            f"backend on an accelerator.")


def _pad2(x: np.ndarray, Lp: int, Rp: int) -> np.ndarray:
    L, R = x.shape
    if (L, R) == (Lp, Rp):
        return x
    out = np.zeros((Lp, Rp), dtype=np.float64)
    out[:L, :R] = x
    return out


def lane_segment_step(t, bytes_done, rate, bound):
    """(t_left, new_bytes, adv, moved, hit) over [lane, row] float64 host
    arrays — the Pallas-backed ensemble segment step."""
    require_cpu_backend()
    import jax
    t = np.asarray(t, np.float64)
    bytes_done = np.asarray(bytes_done, np.float64)
    rate = np.asarray(rate, np.float64)
    bound = np.asarray(bound, np.float64)
    L, R = bytes_done.shape
    Lp = ((L + LANE_BLOCK - 1) // LANE_BLOCK) * LANE_BLOCK
    Rp = ((R + ROW_TILE - 1) // ROW_TILE) * ROW_TILE
    with jax.enable_x64(True):
        out = lane_step_pallas(
            *(jax.numpy.asarray(_pad2(a, Lp, Rp), jax.numpy.float64)
              for a in (t, bytes_done, rate, bound)),
            interpret=True)
        t_left, new_bytes, adv, moved, hit = (np.asarray(o)[:L, :R]
                                              for o in out)
    return t_left, new_bytes, adv, moved, hit
