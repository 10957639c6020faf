"""deepseek-v2-lite-16b — MLA + MoE [arXiv:2405.04434; hf].

27L d_model=2048 16H d_ff(expert)=1408 vocab=102400; MLA kv_lora_rank=512,
qk_rope 64 / qk_nope 128 / v 128, YaRN rope scaling (factor 40 over 4096
positions); MoE 64 routed top-6 + 2 shared; first layer dense (d_ff 10944).
(The assignment line also mentions "160 routed" — that is full V2; the Lite
config per the paper is 64 routed.  See DESIGN.md §5.)
"""
from repro.models.config import MLAConfig, ModelConfig, MoEConfig, YarnScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab_size=102400,
    rope_theta=10000.0,
    rope_scaling=YarnScaling(factor=40, original_max_position_embeddings=4096,
                             beta_fast=32, beta_slow=1, mscale=0.707,
                             mscale_all_dim=0.707),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    # norm_topk_prob false, softmax scores, routed_scaling_factor 1; seq_aux
    # with aux_loss_alpha 0.001
    moe=MoEConfig(n_routed=64, top_k=6, n_shared=2, d_ff_expert=1408,
                  first_dense_layers=1, d_ff_dense=10944,
                  router_norm_topk=False, seq_aux=True, aux_weight=0.001),
    norm_eps=1e-6,
    max_seq_len=32768,
)
