"""DeepSeek-V2's mechanisms in the program: YaRN rotary frequencies and
softmax scale, the sequence-wise balance loss, one chip's share of the
routed experts, the experts' init, the registry's published settings, and
the expert counters the training loop records."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models.config import MLAConfig, ModelConfig, MoEConfig, YarnScaling
from repro.models.model import LM

PUBLISHED_YARN = YarnScaling(factor=40, original_max_position_embeddings=4096,
                             beta_fast=32, beta_slow=1, mscale=0.707,
                             mscale_all_dim=0.707)


def tiny_deepseek(**moe) -> ModelConfig:
    """d 64, 4 heads, kv_lora 32, qk 16/8, v 16, 16 routed top-2, 2 shared."""
    return ModelConfig(
        name="tiny-deepseek", family="moe", n_layers=3, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=96, vocab_size=256, rope_scaling=PUBLISHED_YARN,
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                      v_head_dim=16),
        moe=MoEConfig(**dict(dict(
            n_routed=16, top_k=2, n_shared=2, d_ff_expert=32,
            first_dense_layers=1, d_ff_dense=96, router_norm_topk=False,
            seq_aux=True, aux_weight=0.001), **moe)),
        norm_eps=1e-6, max_seq_len=256)


# ----------------------------------------------------------------------- YaRN
def test_yarn_matches_the_published_formulas_at_the_published_sizes():
    low, high = L.yarn_correction_range(64, 10000.0, PUBLISHED_YARN)
    assert (low, high) == (10, 23)
    i = np.arange(32)
    extra = 1.0 / 10000.0 ** (2 * i / 64)
    mask = 1 - np.clip((i - 10) / 13, 0, 1)
    want = extra / 40 * (1 - mask) + extra * mask
    np.testing.assert_allclose(np.asarray(L.yarn_freqs(64, 10000.0, PUBLISHED_YARN)),
                               want, rtol=1e-6)
    scale = 192 ** -0.5 * L.yarn_softmax_scale(PUBLISHED_YARN)
    assert L.yarn_softmax_scale(PUBLISHED_YARN) == pytest.approx(
        (1 + 0.1 * 0.707 * math.log(40)) ** 2)
    assert scale == pytest.approx(0.114721, abs=1e-6)
    # cos and sin are not scaled: mscale equals mscale_all_dim
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 64))
    pos = jnp.arange(8, dtype=jnp.int32)[None]
    y = L.apply_rope(x, pos, 10000.0, PUBLISHED_YARN)
    np.testing.assert_allclose(np.linalg.norm(y, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)


def _apply_rope_before(x, positions, theta):
    """``apply_rope`` as it was before YaRN, kept verbatim."""
    hd = x.shape[-1]
    freqs = L.rope_freqs(hd, theta)
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_plain_rope_is_bit_identical_to_before(dtype):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 3, 32)).astype(dtype)
    pos = jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32)[None], (2, 64))
    got = jax.jit(L.apply_rope, static_argnums=2)(x, pos, 10000.0)
    want = jax.jit(_apply_rope_before, static_argnums=2)(x, pos, 10000.0)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def test_mla_softmax_scale_carries_yarns_factor(monkeypatch):
    """Without the factor the attention output changes: the scale is used."""
    cfg = tiny_deepseek()
    p = L.init_mla(jax.random.PRNGKey(2), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, 64))
    pos = jnp.arange(16, dtype=jnp.int32)[None]
    with_factor, _ = L.mla_attention(p, cfg, x, pos)
    monkeypatch.setattr(L, "yarn_softmax_scale", lambda s: 1.0)
    without, _ = L.mla_attention(p, cfg, x, pos)
    assert not np.allclose(with_factor, without, atol=1e-4)


# -------------------------------------------------------------- balance loss
def test_sequence_wise_balance_loss_equals_a_hand_count():
    """Router logits set directly (identity router): 2 sequences of 3 tokens,
    4 experts, top 2."""
    logits = np.array([[2.0, 1.0, 0.0, -1.0], [0.5, 3.0, 0.1, 0.0],
                       [1.0, 0.0, 2.5, 0.2], [0.0, 0.3, 0.2, 4.0],
                       [3.0, 2.9, 0.0, 0.0], [0.1, 0.0, 1.0, 2.0]], np.float32)
    m = MoEConfig(n_routed=4, top_k=2, seq_aux=True)
    _, top_i, aux = MOE._routing({"router": jnp.eye(4)}, m, jnp.asarray(logits),
                                 n_seq=2)
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    top = np.argsort(-probs, -1)[:, :2]
    assert {tuple(sorted(t)) for t in np.asarray(top_i)} == \
        {tuple(sorted(t)) for t in top}
    want = 0.0
    for b in range(2):
        count = np.zeros(4)
        for t in range(3):
            count[top[3 * b + t]] += 1
        f = count * 4 / (2 * 3)
        want += np.sum(f * probs[3 * b: 3 * b + 3].mean(0)) / 2
    assert float(aux) == pytest.approx(want, rel=1e-6)


# --------------------------------------------------------------- the share
def test_expert_init_draws_each_matrix_at_its_own_fan_in():
    cfg = tiny_deepseek(n_routed=64, d_ff_expert=128).with_(d_model=256)
    p = MOE.init_moe(jax.random.PRNGKey(4), cfg)
    std = {k: float(jnp.std(p[k].astype(jnp.float32)))
           for k in ("w_gate", "w_up", "w_down")}
    assert std["w_gate"] == pytest.approx(256 ** -0.5, rel=0.05)
    assert std["w_up"] == pytest.approx(256 ** -0.5, rel=0.05)
    assert std["w_down"] == pytest.approx(128 ** -0.5, rel=0.05)
    # the router is held in the weights' dtype, as AdamW returns it
    assert p["router"].dtype == jnp.bfloat16


def test_a_share_holds_the_whole_layers_experts_at_its_indices():
    whole = MOE.init_moe(jax.random.PRNGKey(5), tiny_deepseek())
    share = MOE.init_moe(jax.random.PRNGKey(5),
                         tiny_deepseek(n_held=4, first_held=8))
    for k in ("w_gate", "w_up", "w_down"):
        assert share[k].shape[0] == 4
        assert np.array_equal(share[k], whole[k][8:12])
    assert np.array_equal(share["router"], whole["router"])
    assert share["router"].shape == (64, 16)


def test_shares_add_up_to_the_whole_layer():
    """Each share of the experts computes its part; the parts, with the
    shared experts once, are the whole layer's output (nothing dropped)."""
    cfg = tiny_deepseek(capacity_factor=16.0)
    p = MOE.init_moe(jax.random.PRNGKey(6), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, 64))
    whole, stats = MOE.moe_forward(p, cfg, x)
    total, routed = L.mlp(p["shared"], x), 0
    for e0 in range(0, 16, 4):
        c = cfg.with_(moe=dataclasses.replace(cfg.moe, n_held=4, first_held=e0,
                                              n_shared=0))
        part = {k: v[e0:e0 + 4] for k, v in p.items() if k.startswith("w_")}
        out, s = MOE.moe_forward(dict(part, router=p["router"]), c, x)
        total, routed = total + out, routed + int(s["routed"])
        assert int(s["dropped"]) == 0
        assert float(s["aux"]) == pytest.approx(float(stats["aux"]))
    np.testing.assert_allclose(total, whole, atol=1e-5)
    assert routed == int(stats["routed"]) == 2 * 32 * 2


def test_counters_count_the_held_assignments_and_drops():
    cfg = tiny_deepseek(n_held=8, first_held=4, capacity_factor=0.5)
    p = MOE.init_moe(jax.random.PRNGKey(8), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 32, 64))
    _, stats = MOE.moe_forward(p, cfg, x)
    _, top_i, _ = MOE._routing(p, cfg.moe, x.reshape(64, 64), 2)
    top = np.asarray(top_i).reshape(-1)
    counts = np.bincount(top, minlength=16)[4:12]
    cap = MOE.moe_capacity(cfg.moe, 64)
    assert int(stats["routed"]) == counts.sum()
    assert int(stats["dropped"]) == np.maximum(counts - cap, 0).sum()
    assert int(stats["load_max"]) == counts.max()


# --------------------------------------------------------------- registry
def test_registry_deepseek_v2_lite_has_the_published_settings():
    cfg = get_config("deepseek-v2-lite-16b")
    assert cfg.norm_eps == 1e-6
    assert cfg.rope_scaling == PUBLISHED_YARN
    m = cfg.moe
    assert (m.n_routed, m.held, m.top_k, m.n_shared) == (64, 64, 6, 2)
    assert not m.router_norm_topk and m.seq_aux and m.aux_weight == 0.001


# ------------------------------------------------------------------ the loop
def test_the_loop_records_the_expert_counters_of_each_step():
    from repro.obs import spans
    from repro.train.loop import TrainConfig, train
    cfg = tiny_deepseek(n_held=8)
    before = {s.id for s in spans.records()}
    train(cfg, TrainConfig(steps=3, batch_size=2, seq_len=32, log_every=0))
    got = [s for s in spans.records()
           if s.name == "train.moe" and s.id not in before]
    assert [s.attrs["step"] for s in got] == [0, 1, 2]
    for s in got:
        assert 0 < s.attrs["routed"] <= 2 * 32 * 2 * 2     # 2 expert layers
        assert 0 <= s.attrs["dropped"] <= s.attrs["routed"]
        assert s.attrs["load_max"] >= s.attrs["routed"] / (2 * 8)


def test_a_dense_models_step_returns_no_expert_counters():
    from repro.optim import adamw
    from repro.train.loop import TrainConfig, make_train_step
    cfg = get_config("smollm-135m").smoke()
    model = LM(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    batch = {k: jnp.zeros((2, 16), jnp.int32) for k in ("tokens", "labels")}
    out = make_train_step(model, adamw.AdamWConfig(), TrainConfig())(
        params, adamw.init(params), batch)
    assert set(out[3]) == {"grad_norm", "clip_scale"}
