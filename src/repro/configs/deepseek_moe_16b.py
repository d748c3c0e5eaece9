"""deepseek-moe-16b — fine-grained MoE: layer 0 a dense SwiGLU of width
10,944, then 27 layers of 2 shared + 64 routed experts of width 1,408,
top-6 with gates that are not renormalised [arXiv:2401.06066;
hf:deepseek-ai/deepseek-moe-16b-base config.json: first_k_dense_replace
1, norm_topk_prob false, aux_loss_alpha 0.001]."""
from repro.configs.base import ModelConfig, MoEConfig, register

CONFIG = register(ModelConfig(
    name="deepseek-moe-16b",
    arch_type="moe",
    num_layers=28,
    d_model=2048,
    n_heads=16,
    n_kv=16,
    d_ff=10944,
    vocab=102400,
    groups=((("attn_dense",), 1), (("attn",), 27)),
    moe=MoEConfig(num_experts=64, top_k=6, expert_ff=1408,
                  num_shared=2, shared_ff=1408, capacity_factor=None,
                  router_aux_weight=0.001, norm_topk=False),
    source="arXiv:2401.06066 (DeepSeekMoE)",
))
