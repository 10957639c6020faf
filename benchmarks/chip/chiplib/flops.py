"""Operations and bytes that the benchmark's work needs, from published shapes.

These are the yardstick's counts: a utilization or a roofline share divides
them by a measured time, so they live with the benchmark and not with the
program under test.
"""
from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def llama_matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix product per token: the attention
    projections and the gated MLP of every layer, and the output head (the
    tied embedding counts once, as the head; the input lookup is no product).
    """
    d, hd = cfg["hidden_size"], head_dim(cfg)
    h, kv, ff = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["intermediate_size"])
    attn = d * hd * (2 * h + 2 * kv)          # q, o and k, v projections
    mlp = 3 * d * ff                          # gate, up, down
    return cfg["num_hidden_layers"] * (attn + mlp) + cfg["vocab_size"] * d


def llama_train_flops_per_token(cfg: dict, seq_len: int) -> int:
    """Model FLOPs of one training token (forward and backward, no recompute).

    6 per matmul parameter, plus attention's scores and weighted sum over the
    whole sequence: 2 * 2 * seq * heads * head_dim forward per layer, three
    times that with the backward pass (the PaLM paper's appendix B count).
    """
    attn = (12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * head_dim(cfg) * seq_len)
    return 6 * llama_matmul_params(cfg) + attn


def checksum_bytes(n_words: int) -> int:
    """HBM bytes the integrity checksum kernel reads for ``n_words`` uint32
    words: each word once, 4 bytes.  The kernel does a few integer operations
    per word, so HBM bandwidth bounds it; its roofline time is these bytes
    over the chip's peak bytes per second."""
    return 4 * n_words
