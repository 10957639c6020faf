"""Public op: selective scan with automatic padding to kernel granularity."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.mamba_scan.mamba_scan import (BLOCK_D, CHUNK_T,
                                                 selective_scan_pallas)
from repro.kernels.mamba_scan.ref import selective_scan_ref


def selective_scan(u, dt, Bm, Cm, A, h0, use_pallas: bool = True,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """u, dt: (B,T,D); Bm, Cm: (B,T,N); A: (D,N); h0: (B,D,N)."""
    if not use_pallas:
        return selective_scan_ref(u, dt, Bm, Cm, A, h0)
    B, T, D = u.shape
    ct = min(CHUNK_T, T)
    bd = min(BLOCK_D, D)
    pt = (-T) % ct
    pd = (-D) % bd
    if pt or pd:
        padT = lambda x: jnp.pad(x, ((0, 0), (0, pt), (0, 0)))
        u2, dt2 = padT(u), padT(dt)
        Bm2, Cm2 = padT(Bm), padT(Cm)
        if pd:
            u2 = jnp.pad(u2, ((0, 0), (0, 0), (0, pd)))
            dt2 = jnp.pad(dt2, ((0, 0), (0, 0), (0, pd)))
            A = jnp.pad(A, ((0, pd), (0, 0)))
            h0 = jnp.pad(h0, ((0, 0), (0, pd), (0, 0)))
        y, hT = selective_scan_pallas(u2, dt2, Bm2, Cm2, A, h0,
                                      interpret=interpret)
        return y[:, :T, :D], hT[:, :D]
    return selective_scan_pallas(u, dt, Bm, Cm, A, h0, interpret=interpret)
