"""Per-architecture model tests: smoke (reduced config, one forward/train
step, shape + finiteness), and prefill/decode vs full-forward consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models.config import param_count
from repro.models.frontends import train_batch_stub
from repro.models.model import LM


@pytest.fixture(scope="module")
def rng_key():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch, rng_key):
    cfg = get_config(arch).smoke()
    model = LM(cfg, remat=False)
    params = model.init(rng_key)
    batch = train_batch_stub(cfg, batch=2, seq=64)
    loss, metrics = jax.jit(model.loss_fn)(params, batch)
    assert jnp.isfinite(loss), f"{arch}: loss not finite"
    assert float(loss) > 0
    # gradient step produces finite grads for every leaf
    grads = jax.grad(lambda p: model.loss_fn(p, batch)[0])(params)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))), (arch, path)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_logits_shape(arch, rng_key):
    cfg = get_config(arch).smoke()
    model = LM(cfg, remat=False)
    params = model.init(rng_key)
    B, T = 2, 32
    batch = train_batch_stub(cfg, batch=B, seq=T)
    x = model.embed(params, batch)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    xf, _, _ = model.backbone(params, x, pos,
                              positions3=batch.get("positions3"), mode="train")
    logits = model.unembed(params, xf)
    if cfg.n_codebooks > 1:
        assert logits.shape == (B, T, cfg.n_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (B, T, cfg.vocab_size)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_consistency(arch, rng_key):
    """prefill(T-k) + k decode steps must reproduce the full forward."""
    cfg = get_config(arch).smoke()
    dtype = jnp.bfloat16
    if cfg.moe:
        # drop-free capacity: routing drops depend on co-batch size, which
        # legitimately differs between the two code paths; f32 params because
        # top-k routing is discontinuous — bf16 rounding differences between
        # the scanned and unrolled paths can flip near-tied expert choices
        cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        dtype = jnp.float32
    model = LM(cfg, dtype=dtype, remat=False)
    params = model.init(rng_key)
    B, T, k = 2, 32, 8
    batch = train_batch_stub(cfg, batch=B, seq=T)
    x = model.embed(params, batch)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    xf, _, _ = model.backbone(params, x, pos,
                              positions3=batch.get("positions3"), mode="train")
    full = np.asarray(model.unembed(params, xf), np.float32)

    cache = model.init_cache(B, T + 8)
    if dtype == jnp.float32:
        cache = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
            cache)
    Tp = T - k
    pre = {kk: (v[:, :Tp] if kk != "positions3" else v[:, :, :Tp])
           for kk, v in batch.items() if kk != "labels"}
    logits_p, cache = jax.jit(model.prefill)(params, pre, cache)
    np.testing.assert_allclose(
        np.asarray(logits_p[:, 0], np.float32), full[:, Tp - 1],
        atol=0.12, rtol=0.05)
    if not cfg.embed_inputs:
        return  # vlm stub: decode path uses the token table, not embeds
    dec = jax.jit(model.decode_step)
    for t in range(Tp, T):
        tok = batch["tokens"][:, t:t + 1]
        lg, cache = dec(params, cache, tok, jnp.int32(t))
        # atol covers bf16 rounding: the unrolled decode path and the scanned
        # train forward fuse (and therefore round) differently; in f32 the
        # two paths agree to 2e-5 (verified), and musicgen's summed-codebook
        # logits are O(20), where K summed codebooks amplify per-term
        # rounding — 0.5 abs is ~2% relative at that scale
        np.testing.assert_allclose(
            np.asarray(lg[:, 0], np.float32), full[:, t],
            atol=0.5, rtol=0.03, err_msg=f"{arch} decode t={t}")


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_count_matches_instantiated(arch, rng_key):
    """Analytic param_count (read by the sharding rules) must match the
    actually instantiated smoke model within 2%; an expert model's too when
    it holds a share of its routed experts."""
    cfg = get_config(arch).smoke()
    cfgs = [cfg]
    if cfg.moe is not None:
        cfgs.append(cfg.with_(moe=dataclasses.replace(
            cfg.moe, n_held=cfg.moe.n_routed // 4, first_held=2)))
    for c in cfgs:
        params = LM(c, remat=False).init(rng_key)
        actual = sum(int(np.prod(x.shape))
                     for x in jax.tree_util.tree_leaves(params))
        predicted, _ = param_count(c)
        assert abs(actual - predicted) / actual < 0.02, (arch, actual, predicted)


@pytest.mark.parametrize("window", [None, 256])
def test_sdpa_query_chunks_equal_one_block(window, rng_key):
    """Over ``_CHUNK_THRESHOLD`` scores, ``sdpa`` scans remat'd query chunks
    over all keys; each row's result equals the one-block computation."""
    from repro.models import layers
    B, T, H, Hkv, hd = 1, 2304, 2, 1, 16
    assert T * T > layers._CHUNK_THRESHOLD and T % layers.CHUNK_Q == 0
    kq, kk, kv = jax.random.split(rng_key, 3)
    q = jax.random.normal(kq, (B, T, H, hd), jnp.float32)
    k = jax.random.normal(kk, (B, T, Hkv, hd), jnp.float32)
    v = jax.random.normal(kv, (B, T, Hkv, hd), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def chunked(q, k, v):
        return layers.sdpa(q, k, v, pos, pos, window=window)

    assert "scan" in str(jax.make_jaxpr(chunked)(q, k, v))
    got = jax.jit(chunked)(q, k, v)
    want = jax.jit(lambda q, k, v: layers._sdpa_block(
        q, k, v, pos, pos, window, None, hd ** -0.5))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-6)


def test_sliding_window_masks_history(rng_key):
    """gemma-family local attention must not see beyond its window."""
    cfg = get_config("gemma3-27b").smoke().with_(
        n_layers=1, local_global_ratio=0, sliding_window=4)
    # single local layer via pattern: force all-local by ratio=0 ->
    # uniform_attn with sliding_window applied in serving path only; instead
    # test the layer directly
    from repro.models import layers as L
    p = L.init_attention(rng_key, cfg)
    B, T = 1, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.d_model),
                          jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    out1, _ = L.attention(p, cfg, x, pos, window=4)
    # perturb a token >window in the past of the last query
    x2 = x.at[:, 2].set(x[:, 2] + 5.0)
    out2, _ = L.attention(p, cfg, x2, pos, window=4)
    # last position (15) must be identical: token 2 is outside its window
    np.testing.assert_allclose(np.asarray(out1[:, -1], np.float32),
                               np.asarray(out2[:, -1], np.float32),
                               atol=1e-2)
    # but position 3 must differ (token 2 is within ITS window)
    assert not np.allclose(np.asarray(out1[:, 3], np.float32),
                           np.asarray(out2[:, 3], np.float32), atol=1e-2)


def test_musicgen_multicodebook_loss_counts_all_books(rng_key):
    cfg = get_config("musicgen-large").smoke()
    model = LM(cfg, remat=False)
    params = model.init(rng_key)
    batch = train_batch_stub(cfg, batch=2, seq=16)
    loss, _ = model.loss_fn(params, batch)
    # perturbing only codebook 3's labels must change the loss
    batch2 = dict(batch)
    batch2["labels"] = batch["labels"].at[..., 3].set(
        (batch["labels"][..., 3] + 7) % cfg.vocab_size)
    loss2, _ = model.loss_fn(params, batch2)
    assert float(loss) != float(loss2)
