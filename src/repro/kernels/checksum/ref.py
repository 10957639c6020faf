"""Reference (oracle) implementation of the streaming integrity hash.

Construction (exact uint32 arithmetic, order-sensitive, fully parallel):

    g[i] = mix32(word[i] ^ (i * PHI))        # position baked into each word
    H    = finalize32( XOR_i g[i]  ^  nbytes )

``mix32``/``finalize32`` are xorshift-multiply avalanches.  XOR-reduction is
associative+commutative, so the hash can be computed in any tiling/order —
ideal for a Pallas grid accumulating lane partials in VMEM — while position
mixing keeps it order-*sensitive* over the data.

Three implementations, all bit-identical:
  * ``checksum_bytes_np``  — numpy, used by core.integrity on real files;
  * ``checksum_words_jnp`` — pure-jnp oracle for kernel tests;
  * Pallas kernel in ``checksum.py`` (tiled, VMEM-resident blocks).
"""
from __future__ import annotations

import numpy as np

PHI = np.uint32(0x9E3779B1)
LANES = 128
ROW = 512          # words per kernel row (4 sublanes x 128 lanes)


# ------------------------------------------------------------------- mix/fin
def _mix32_np(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x = (x * np.uint32(0x7FEB352D)).astype(np.uint32)
    x ^= x >> np.uint32(15)
    x = (x * np.uint32(0x846CA68B)).astype(np.uint32)
    x ^= x >> np.uint32(16)
    return x


def finalize32_np(h: int, nbytes: int) -> int:
    x = np.uint32(h) ^ np.uint32(nbytes & 0xFFFFFFFF)
    x = _mix32_np(np.array([x], np.uint32))[0]
    return int(x)


def _mix32_jnp(x):
    import jax.numpy as jnp
    x = x.astype(jnp.uint32)
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    return x


# ------------------------------------------------------------------ word prep
def bytes_to_words(data: bytes) -> np.ndarray:
    pad = (-len(data)) % 4
    if pad:
        data = data + b"\0" * pad
    return np.frombuffer(data, dtype="<u4").astype(np.uint32)


# ------------------------------------------------------------------- hashers
def fold_words_np(words: np.ndarray, start_word: int = 0) -> int:
    """XOR-fold a word slice whose first element sits at global word offset
    ``start_word``.  Because the reduction is associative+commutative and the
    position is baked into each word, partial folds over consecutive slices
    XOR together to the whole-buffer fold — the basis of the streaming
    (chunked) hasher in ``core.integrity``."""
    words = words.astype(np.uint32)
    if not words.size:
        return 0
    idx = np.arange(words.size, dtype=np.uint32) + np.uint32(
        start_word & 0xFFFFFFFF)
    g = _mix32_np(words ^ (idx * PHI))
    return int(np.bitwise_xor.reduce(g))


def checksum_words_np(words: np.ndarray, nbytes: int) -> int:
    return finalize32_np(fold_words_np(words), nbytes)


def checksum_bytes_np(data: bytes) -> int:
    return checksum_words_np(bytes_to_words(data), len(data))


def checksum_words_jnp(words, nbytes: int):
    """Pure-jnp oracle; words: uint32[N] (already padded).  jax is imported
    here, not at module level: host-only simulator workers import this
    module for the numpy hashers and must not load jax."""
    import jax
    import jax.numpy as jnp
    idx = jnp.arange(words.size, dtype=jnp.uint32)
    g = _mix32_jnp(words.astype(jnp.uint32) ^ (idx * jnp.uint32(PHI)))
    h = jax.lax.reduce(g, jnp.uint32(0), jax.lax.bitwise_xor, (0,))
    h = h ^ jnp.uint32(np.uint32(nbytes & 0xFFFFFFFF))
    return _mix32_jnp(h)
