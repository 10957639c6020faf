"""Model FLOPs of a DeepSeek-V2 training token on one chip's share of the
experts and the vocabulary, from the configuration's published shapes.

The yardstick of ``moe_train_mfu``: 6 per matrix-product parameter a token
touches on this chip, plus attention's scores and weighted sum over the
whole sequence.  No recompute and no capacity padding are counted.
"""
from __future__ import annotations


def mla_moe_matmul_params(cfg: dict) -> float:
    """Matrix-product parameters per token: every layer's latent-attention
    projections; the dense layers' MLP; each expert layer's router, shared
    experts, and held experts at ``top_k * held / n_routed`` experts a token;
    the head over this chip's slice of the vocabulary (the input lookup is
    no product)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    r, ff_e = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    n_exp = cfg["published"]["n_routed_experts"]
    lead = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - lead
    attn = (d * h * (nope + rope)                # q
            + d * (r + rope)                     # latent and rotary key
            + r * h * (nope + vd)                # keys and values from it
            + h * vd * d)                        # output
    expert = 3 * d * ff_e                        # gate, up, down
    routed = expert * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / n_exp
    moe = d * n_exp + cfg["n_shared_experts"] * expert + routed
    return (cfg["num_hidden_layers"] * attn + lead * 3 * d * cfg["intermediate_size"]
            + n_moe * moe + cfg["vocab_size"] * d)


def mla_moe_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs of one training token (forward and backward): 6 per
    matrix-product parameter, plus per layer and head 2 * seq * (qk + v)
    forward for the scores (qk = nope + rope) and the weighted sum, three
    times that with the backward pass."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = (6 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * (qk + cfg["v_head_dim"]) * seq_len)
    return 6 * mla_moe_matmul_params(cfg) + attn
