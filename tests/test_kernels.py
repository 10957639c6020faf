"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles.
On the CPU backend every wrapper runs its kernel in interpret mode; the
compiled TPU lowering is checked in tests/test_tpu_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.checksum.ops import checksum_bytes
from repro.kernels.checksum.ref import (bytes_to_words, checksum_bytes_np,
                                        checksum_words_jnp)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.mamba_scan.ops import selective_scan
from repro.kernels.mamba_scan.ref import selective_scan_ref


# ---------------------------------------------------------------- checksum
@pytest.mark.parametrize("size", [0, 1, 3, 4, 7, 100, 4096, 65536,
                                  131072 * 4 + 5, 1_000_003,
                                  # non-word-aligned tails around the lane
                                  # boundary: the scrub path hashes partial
                                  # batches of arbitrary byte length
                                  5, 1021, 65537, 131072 * 4 - 1])
def test_checksum_matches_refs(size):
    data = np.random.default_rng(size).bytes(size)
    ref = checksum_bytes_np(data)
    jref = int(checksum_words_jnp(jnp.asarray(bytes_to_words(data)), size))
    pal = checksum_bytes(data)
    assert ref == jref == pal


def test_interpret_mode_follows_the_backend(monkeypatch):
    """Kernels interpret on the CPU backend only; an explicit choice wins."""
    from repro.kernels import resolve_interpret
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    assert resolve_interpret(True) is True


def test_checksum_order_sensitive():
    a = b"x" * 100 + b"y" * 100
    b = b"y" * 100 + b"x" * 100
    assert checksum_bytes_np(a) != checksum_bytes_np(b)


def test_checksum_length_sensitive():
    # trailing zero bytes must change the hash (length is mixed in)
    a = b"hello"
    assert checksum_bytes_np(a) != checksum_bytes_np(a + b"\0")


# --------------------------------------------------------------- mamba scan
@pytest.mark.parametrize("shape", [
    (1, 32, 64, 8), (2, 64, 128, 16), (2, 128, 256, 16),
    (1, 96, 300, 8),     # non-aligned D (pad path)
    (3, 100, 128, 4),    # non-aligned T
])
def test_selective_scan_matches_ref(shape):
    B, T, D, N = shape
    rng = np.random.default_rng(42)
    u = jnp.asarray(rng.normal(size=(B, T, D)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, T, D)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, T, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, T, N)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (D, N)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(B, D, N)), jnp.float32)
    y_ref, h_ref = selective_scan_ref(u, dt, Bm, Cm, A, h0)
    y, hT = selective_scan(u, dt, Bm, Cm, A, h0, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(h_ref),
                               rtol=1e-4, atol=1e-4)


def test_selective_scan_state_continuity():
    """Scanning [0:T] must equal scanning [0:T/2] then [T/2:T] with carried h."""
    rng = np.random.default_rng(7)
    B, T, D, N = 1, 64, 128, 8
    u = jnp.asarray(rng.normal(size=(B, T, D)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, T, D)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, T, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, T, N)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (D, N)), jnp.float32)
    h0 = jnp.zeros((B, D, N), jnp.float32)
    y_full, h_full = selective_scan(u, dt, Bm, Cm, A, h0)
    h = h0
    ys = []
    for sl in (slice(0, 32), slice(32, 64)):
        y, h = selective_scan(u[:, sl], dt[:, sl], Bm[:, sl], Cm[:, sl], A, h)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_full),
                               rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------- flash attention
@pytest.mark.parametrize("cfg", [
    dict(B=2, T=128, H=4, Hkv=2, hd=64, window=None, dtype=jnp.float32),
    dict(B=1, T=256, H=4, Hkv=1, hd=64, window=None, dtype=jnp.bfloat16),
    dict(B=2, T=256, H=8, Hkv=8, hd=32, window=64, dtype=jnp.float32),
    dict(B=1, T=384, H=2, Hkv=2, hd=128, window=128, dtype=jnp.bfloat16),
])
def test_flash_attention_matches_ref(cfg):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(cfg["B"], cfg["T"], cfg["H"], cfg["hd"])),
                    cfg["dtype"])
    k = jnp.asarray(rng.normal(size=(cfg["B"], cfg["T"], cfg["Hkv"], cfg["hd"])),
                    cfg["dtype"])
    v = jnp.asarray(rng.normal(size=(cfg["B"], cfg["T"], cfg["Hkv"], cfg["hd"])),
                    cfg["dtype"])
    ref = flash_attention(q, k, v, window=cfg["window"], use_pallas=False)
    out = flash_attention(q, k, v, window=cfg["window"], use_pallas=True)
    tol = 2.5e-2 if cfg["dtype"] == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_attention_is_causal():
    """Future tokens must not influence earlier outputs."""
    rng = np.random.default_rng(1)
    B, T, H, hd = 1, 128, 2, 64
    q = jnp.asarray(rng.normal(size=(B, T, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, hd)), jnp.float32)
    o1 = flash_attention(q, k, v, use_pallas=True)
    k2 = k.at[:, -1].add(10.0)
    v2 = v.at[:, -1].add(10.0)
    o2 = flash_attention(q, k2, v2, use_pallas=True)
    np.testing.assert_allclose(np.asarray(o1[:, :-1]), np.asarray(o2[:, :-1]),
                               atol=1e-5)
