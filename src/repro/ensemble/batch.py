"""Batched backends for the lanes engine's segment step, plus batched fault
draws.

The lanes engine's hot inner operation is ``advance_segment`` over
``[lane, row]`` float64 arrays.  Two interchangeable implementations:

* ``numpy`` — the bit-exact reference (``repro.core.transport``'s own
  module function; the scalar engine runs the same expressions).
* ``jax``   — ``jax.jit(jax.vmap(...))`` of an elementwise per-lane step,
  run under a scoped x64 context (``with jax.enable_x64(True)`` — the
  global flag is never touched, so f32 model code elsewhere is unaffected).
  This is the device path: on a TPU, XLA emulates the float64.

The jax backend agrees with numpy to float64 round-off but NOT necessarily
bit-for-bit: XLA may contract ``bytes_done + rate * t`` into an FMA.  On a
TPU the jax backend's float64 is emulated, and the quantities that pass
through the division (``adv``, ``moved``, ``t_left``) land about 1e-10
relative from numpy on a v5e, so trajectories drift further there.
The determinism contract therefore names numpy the reference backend
— the lane-0 bit-identity gate always runs it — while the jax backend is
validated by ``tests/test_ensemble.py`` elementwise against the reference,
and on the chip by ``chip_smoke.py`` lane for lane.

``BatchedFaultInjector`` wraps N independent per-lane ``FaultInjector``
streams behind one dense-array call.  This is deliberately NOT a vmapped
RNG: the scalar engine's stream is a stateful ``numpy.random.Generator``
whose consumption order is part of the trajectory, so the batch must be N
real streams — the property test asserts draw-for-draw equality with N
solo injectors."""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

from repro.core.faults import FaultInjector
from repro.core.transport import advance_segment


def numpy_segment_fn(t, bytes_done, rate, bound):
    return advance_segment(t, bytes_done, rate, bound)


def _lane_segment_jnp(t, bytes_done, rate, bound):
    """One lane's segment step in jax.numpy — the same expression tree as
    ``transport.advance_segment`` (vmapped over the lane axis by the
    caller)."""
    import jax.numpy as jnp
    inf = jnp.inf
    need = jnp.where(rate > 0,
                     jnp.maximum(0.0, bound - bytes_done)
                     / jnp.where(rate > 0, rate, 1.0), inf)
    hit = need <= t
    adv = jnp.where(hit, need, t)
    new_bytes = jnp.where(hit, bound, bytes_done + rate * t)
    moved = rate * adv
    t_left = jnp.where(hit, t - need, 0.0)
    return t_left, new_bytes, adv, moved, hit


_JAX_FN = None


def jax_segment_device(t, bytes_done, rate, bound):
    """The jit(vmap) step on the default device; returns device arrays.
    Call it inside ``jax.enable_x64(True)``."""
    global _JAX_FN
    import jax
    if _JAX_FN is None:
        _JAX_FN = jax.jit(jax.vmap(_lane_segment_jnp))
    t = np.broadcast_to(np.asarray(t, np.float64), np.shape(bytes_done))
    return _JAX_FN(jnp_f64(t), jnp_f64(bytes_done), jnp_f64(rate),
                   jnp_f64(bound))


def jax_segment_fn(t, bytes_done, rate, bound):
    """jit(vmap) backend.  Inputs/outputs are host numpy float64; x64 is
    enabled only inside this call."""
    import jax
    with jax.enable_x64(True):
        out = jax_segment_device(t, bytes_done, rate, bound)
        t_left, new_bytes, adv, moved, hit = (np.asarray(o) for o in out)
    return t_left, new_bytes, adv, moved, hit


def jnp_f64(x):
    import jax.numpy as jnp
    return jnp.asarray(x, jnp.float64)


def make_segment_fn(backend: str):
    if backend == "numpy":
        return numpy_segment_fn
    if backend == "jax":
        return jax_segment_fn
    raise ValueError(f"unknown segment backend {backend!r}")


class BatchedFaultInjector:
    """N per-lane fault streams behind one dense-array draw.

    ``transient_marks(paths, nbytes)`` performs exactly one scalar
    ``FaultInjector.transient_marks`` call per lane — same draw order, same
    stream — and packs the jagged results into ``(marks[L, M], len[L])``
    with ``inf`` padding (``inf`` never matches a byte boundary)."""

    def __init__(self, seeds: Sequence[int], transient_per_tb: float = 0.15,
                 fragility_tail: float = 2.5):
        self.injectors = [FaultInjector(int(s),
                                        transient_per_tb=transient_per_tb,
                                        fragility_tail=fragility_tail)
                          for s in seeds]

    def __len__(self) -> int:
        return len(self.injectors)

    def transient_marks(self, paths: Sequence[str], nbytes: Sequence[int]
                        ) -> Tuple[np.ndarray, np.ndarray]:
        draws: List[List[float]] = [
            inj.transient_marks(p, int(b))
            for inj, p, b in zip(self.injectors, paths, nbytes)]
        lens = np.array([len(d) for d in draws], dtype=np.int64)
        m = int(lens.max()) if len(lens) else 0
        out = np.full((len(draws), max(1, m)), np.inf)
        for i, d in enumerate(draws):
            out[i, :len(d)] = d
        return out, lens
