"""deepseek-v2-lite — multi-head latent attention (16 heads, latent 512,
no query low-rank, YaRN rope) over a DeepSeekMoE stack: layer 0 a dense
SwiGLU of width 10,944, then 26 layers of 2 shared + 64 routed experts
of width 1,408, top-6 of softmax scores, gates not renormalised
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434].

The config omits ``aux_loss_alpha``: the router aux weight is the
DeepSeek-V2 paper's 0.001 for the Lite model.
"""
from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                YaRNConfig, register)

CONFIG = register(ModelConfig(
    name="deepseek-v2-lite",
    arch_type="moe",
    num_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv=16,
    d_ff=10944,
    vocab=102400,
    groups=((("mla_dense",), 1), (("mla_moe",), 26)),
    rope_theta=10000.0,
    norm_eps=1e-6,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    rope_scaling=YaRNConfig(factor=40.0,
                            original_max_position_embeddings=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    moe=MoEConfig(num_experts=64, top_k=6, expert_ff=1408,
                  num_shared=2, shared_ff=1408, capacity_factor=None,
                  router_aux_weight=0.001, norm_topk=False),
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/"
           "config.json",
))
