"""Model / input-shape configuration for the repro framework.

Every assigned architecture is expressed as a ``ModelConfig``. A config is
purely declarative; the model code in ``repro.models`` interprets it.

Layers are described by *groups*: ``(pattern, repeat)`` where ``pattern``
is a tuple of block kinds scanned ``repeat`` times with stacked params.
This keeps the lowered HLO O(pattern) instead of O(num_layers) — essential
for the 94-layer MoE / 100-layer VLM dry-runs.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Block kinds understood by repro.models.blocks
#   attn        — GQA self-attention (+ optional sliding window)
#   local_attn  — windowed self-attention (recurrentgemma-style local)
#   cross_attn  — cross-attention to auxiliary embeddings (VLM / decoder)
#   mamba2      — SSD state-space block
#   rglru       — RG-LRU recurrent block
#   attn_dense  — attn followed by the dense MLP (d_ff) even where cfg.moe
#                 is set: the leading dense layers of a DeepSeekMoE stack
#   mla_dense   — multi-head latent attention (cfg.mla) + dense MLP
#   mla_moe     — multi-head latent attention + the MoE (cfg.moe)
# Every block is followed by its MLP unless mlp="none": kinds that name
# none take the MoE where cfg.moe is set, else the dense MLP.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """``num_experts`` is what the router scores over; this chip holds
    ``held`` of them (0: all), experts ``[shard * held, (shard + 1) *
    held)``, and computes their part of each token's routed sum only.
    ``capacity_factor`` None routes every assignment (no token dropped);
    a number drops assignments past that multiple of the mean load.
    ``norm_topk`` renormalises the top-k gates to sum to one.
    ``router_aux_weight`` weighs the load-balance loss in every loss the
    layer enters (the FF block loss included)."""
    num_experts: int
    top_k: int
    expert_ff: int
    num_shared: int = 0
    shared_ff: int = 0
    capacity_factor: Optional[float] = 1.25
    router_aux_weight: float = 0.01
    held: int = 0
    shard: int = 0
    norm_topk: bool = True

    @property
    def held_experts(self) -> int:
        return self.held or self.num_experts


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2), without a query
    low-rank: q = x W_q (nope + rope dims a head); [c_kv, k_pe] =
    x W_kv_a; [k_nope, v] = RMSNorm(c_kv) W_kv_b; k_pe is one rope head
    shared by every head."""
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class YaRNConfig:
    """YaRN rope scaling as DeepSeek-V2 applies it (its
    ``rope_scaling`` of type "yarn")."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk: int = 256


@dataclasses.dataclass(frozen=True)
class RGLRUConfig:
    lru_width: int = 0          # 0 -> d_model
    conv_width: int = 4
    window: int = 2048          # local-attn window used by hybrid attn blocks


@dataclasses.dataclass(frozen=True)
class FFConfig:
    """Forward-Forward training configuration (the paper's technique)."""
    goodness: str = "sumsq"       # sumsq | softmax (Performance-Optimized)
    theta: float = 2.0            # goodness threshold
    neg_mode: str = "random"      # adaptive | fixed | random (LM: corruption)
    peer_norm_weight: float = 0.03
    # layer-local loss is computed on RMS-normalized block outputs


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                       # dense|moe|ssm|hybrid|audio|vlm
    num_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    # layer grouping: tuple of (pattern tuple, repeat)
    groups: Tuple[Tuple[Tuple[str, ...], int], ...] = ()
    head_dim: int = 0                    # 0 -> d_model // n_heads
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    window: Optional[int] = None         # sliding-window size for attn blocks
    norm_eps: float = 1e-6
    act: str = "silu"
    tie_embeddings: bool = False
    logit_softcap: float = 0.0
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    mla: Optional[MLAConfig] = None
    rope_scaling: Optional[YaRNConfig] = None
    ff: FFConfig = dataclasses.field(default_factory=FFConfig)
    # encoder-decoder (audio) ---------------------------------------------
    enc_dec: bool = False
    enc_layers: int = 0
    enc_seq: int = 1024                  # stub frontend frame count
    # vlm ------------------------------------------------------------------
    vision_tokens: int = 0               # stub frontend patch count
    vision_dim: int = 0                  # embedding dim delivered by stub
    # training -------------------------------------------------------------
    dtype: str = "bfloat16"
    remat: bool = True
    seed: int = 0                        # weights and negatives (api.fit)
    source: str = ""                     # citation

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Embedding/unembedding table rows, padded to a multiple of 128
        so the vocab dim shards over any mesh axis (seamless 256206 and
        mamba2 50280 are otherwise indivisible by 16 and force GSPMD to
        replicate full-vocab logits — TBs of all-reduce at 4k batch).
        Padded ids never appear in data; unembed masks their logits."""
        return -(-self.vocab // 128) * 128

    def layers_in_groups(self) -> int:
        return sum(len(p) * r for p, r in self.groups)

    def validate(self) -> None:
        assert self.layers_in_groups() == (
            self.num_layers + (self.enc_layers if self.enc_dec else 0)
        ), (self.name, self.layers_in_groups(), self.num_layers)

    def reduced(self, d_model: int = 256, layers_hint: int = 2) -> "ModelConfig":
        """A tiny same-family variant for CPU smoke tests."""
        n_heads = max(2, min(4, self.n_heads))
        n_kv = max(1, min(self.n_kv, n_heads))
        while n_heads % n_kv:
            n_kv -= 1
        groups = _reduce_groups(self.groups, layers_hint)
        nl = sum(len(p) * r for p, r in groups)
        enc_l = 0
        if self.enc_dec:
            enc_l = nl // 2
            nl = nl - enc_l
        moe = None
        if self.moe is not None:
            moe = dataclasses.replace(
                self.moe, num_experts=4, top_k=2, expert_ff=2 * d_model,
                num_shared=min(self.moe.num_shared, 1),
                shared_ff=2 * d_model if self.moe.num_shared else 0,
                held=2 if self.moe.held else 0, shard=0)
        mla = None
        if self.mla is not None:
            mla = MLAConfig(kv_lora_rank=64, qk_nope_head_dim=32,
                            qk_rope_head_dim=16, v_head_dim=32)
        ssm = None
        if self.ssm is not None:
            ssm = dataclasses.replace(self.ssm, state_dim=16, head_dim=32,
                                      chunk=32)
        rglru = None
        if self.rglru is not None:
            rglru = dataclasses.replace(self.rglru, lru_width=d_model,
                                        window=32)
        return dataclasses.replace(
            self, name=self.name + "-smoke", num_layers=nl, d_model=d_model,
            n_heads=n_heads, n_kv=n_kv, d_ff=2 * d_model,
            vocab=min(self.vocab, 512), head_dim=0, groups=groups,
            window=min(self.window, 64) if self.window else None,
            moe=moe, ssm=ssm, rglru=rglru, mla=mla, enc_layers=enc_l,
            enc_seq=16, vision_tokens=8 if self.vision_tokens else 0,
            vision_dim=d_model if self.vision_dim else 0,
            dtype="float32", remat=False)


def _reduce_groups(groups, layers_hint):
    """Keep one pattern-unit per distinct group, repeat=1."""
    out = []
    seen = set()
    for pattern, _ in groups:
        if pattern in seen:
            continue
        seen.add(pattern)
        out.append((pattern, 1))
    if not out:
        out = [(("attn",), layers_hint)]
    return tuple(out)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str            # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}

# Archs allowed to run long_500k (sub-quadratic sequence mixing).
SUBQUADRATIC = {"mamba2-780m", "recurrentgemma-2b", "h2o-danube-3-4b"}


_REGISTRY = {}


def register(cfg: ModelConfig) -> ModelConfig:
    cfg.validate()
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    if not _REGISTRY:
        load_all()
    return _REGISTRY[name]


def list_configs():
    if not _REGISTRY:
        load_all()
    return sorted(_REGISTRY)


def load_all():
    from repro.configs import (  # noqa: F401
        mamba2_780m, recurrentgemma_2b, seamless_m4t_large_v2,
        qwen3_moe_235b_a22b, tinyllama_1_1b, llama_3_2_vision_90b,
        qwen2_0_5b, qwen3_8b, h2o_danube_3_4b, deepseek_moe_16b,
        deepseek_v2_lite, ff_mlp)
