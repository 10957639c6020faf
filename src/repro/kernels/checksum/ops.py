"""Public op: checksum arbitrary-size byte/array payloads.

Handles padding to the kernel's (BLOCK_ROWS × 512)-word granularity.  Padding
with zero words is safe because each word's hash is position-mixed and the
true byte length is folded into the finalizer — identical to the reference.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.checksum.checksum import BLOCK_ROWS, checksum_words_pallas
from repro.kernels.checksum.ref import ROW, bytes_to_words, checksum_bytes_np


def _pad_words(words: jnp.ndarray) -> jnp.ndarray:
    gran = BLOCK_ROWS * ROW
    n = words.size
    padded = max(gran, ((n + gran - 1) // gran) * gran)
    if padded != n:
        words = jnp.concatenate(
            [words, jnp.zeros((padded - n,), jnp.uint32)])
    return words


def checksum_array(x: jax.Array,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Hash a jax array's raw contents (uint32 view, zero-padded)."""
    raw = jnp.asarray(x).reshape(-1)
    if raw.dtype != jnp.uint32:
        b = np.asarray(raw).tobytes()
        nbytes = len(b)
        words = jnp.asarray(bytes_to_words(b))
    else:
        nbytes = raw.size * 4
        words = raw
    n_words = words.size
    words = _pad_words(words)
    return checksum_words_pallas(words, jnp.uint32(n_words),
                                 jnp.uint32(nbytes & 0xFFFFFFFF),
                                 interpret=interpret)


def checksum_bytes(data: bytes, interpret: Optional[bool] = None) -> int:
    words = jnp.asarray(bytes_to_words(data))
    n_words = words.size
    words = _pad_words(words)
    return int(checksum_words_pallas(
        words, jnp.uint32(n_words), jnp.uint32(len(data) & 0xFFFFFFFF),
        interpret=interpret))
