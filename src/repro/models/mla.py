"""Multi-head latent attention (MLA, DeepSeek-V2), without a query
low-rank (``q_lora_rank`` null, as in DeepSeek-V2-Lite).

For H heads, nope + rope query/key dims and v value dims a head, and a
latent of rank r:

    q             = x W_q                       (H, nope + rope)
    [c_kv, k_pe]  = x W_kv_a                    (r + rope)
    c_kv          = RMSNorm(c_kv)
    [k_nope, v]   = c_kv W_kv_b                 (H, nope + v)
    q_pe, k_pe    = rope(q_pe), rope(k_pe)      k_pe: one head shared by all
    out           = softmax(scale [q_nope, q_pe] . [k_nope, k_pe]) v W_o

with YaRN frequencies and scale (``common.rope_freqs`` /
``common.attn_scale_factor``) where ``cfg.rope_scaling`` is set. The
rope pairs dimension i with i + rope/2 (``common.apply_rope``); the
published code pairs neighbours, which is a fixed permutation of the
rope columns of W_q and W_kv_a.

The decode cache holds the latent (``c_kv`` after its norm, and the
roped ``k_pe``), r + rope numbers a position, and expands it through
W_kv_b at every step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention, common


def init(key, cfg):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    dtype = common.dtype_of(cfg)
    ks = jax.random.split(key, 4)
    return {
        "wq": common.dense_init(ks[0], (d, H, m.qk_head_dim), dtype,
                                fan_in=d),
        "wkv_a": common.dense_init(
            ks[1], (d, m.kv_lora_rank + m.qk_rope_head_dim), dtype),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), jnp.float32),
        "wkv_b": common.dense_init(
            ks[2], (m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim),
            dtype, fan_in=m.kv_lora_rank),
        "wo": common.dense_init(ks[3], (H, m.v_head_dim, d), dtype,
                                fan_in=H * m.v_head_dim),
    }


def softmax_scale(cfg):
    return (cfg.mla.qk_head_dim ** -0.5
            * common.attn_scale_factor(cfg.rope_scaling))


def _rope(cfg, x, pos):
    return common.apply_rope(x, pos, cfg.rope_theta, cfg.rope_scaling)


def _latent(p, cfg, x, pos):
    """(c_kv normed, k_pe roped) of x: (B, S, d) at positions pos (1, S)."""
    m = cfg.mla
    kv = x @ p["wkv_a"]
    c_kv = common.rms_norm(kv[..., :m.kv_lora_rank], p["kv_norm"],
                           cfg.norm_eps)
    k_pe = _rope(cfg, kv[..., None, m.kv_lora_rank:], pos)[..., 0, :]
    return c_kv, k_pe


def _query(p, cfg, x, pos):
    m = cfg.mla
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_pe = _rope(cfg, q[..., m.qk_nope_head_dim:], pos)
    return jnp.concatenate([q[..., :m.qk_nope_head_dim], q_pe], axis=-1)


def _keys_values(p, cfg, c_kv, k_pe):
    """Expand the latent: k (B, T, H, nope + rope), v (B, T, H, v)."""
    m = cfg.mla
    kvb = jnp.einsum("btc,chk->bthk", c_kv, p["wkv_b"])
    k_nope, v = kvb[..., :m.qk_nope_head_dim], kvb[..., m.qk_nope_head_dim:]
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :],
                            k_nope.shape[:-1] + k_pe.shape[-1:])
    return jnp.concatenate([k_nope, k_pe], axis=-1), v


def apply(p, cfg, x, *, causal=True, q_offset=0, return_cache=False,
          max_len=None):
    """x: (B, S, d). Returns y, or (y, cache) with ``return_cache``."""
    S = x.shape[1]
    pos = (q_offset + jnp.arange(S, dtype=jnp.int32))[None]
    q = _query(p, cfg, x, pos)
    c_kv, k_pe = _latent(p, cfg, x, pos)
    k, v = _keys_values(p, cfg, c_kv, k_pe)
    out = attention.chunked_attention(q, k, v, causal=causal,
                                      q_offset=q_offset,
                                      scale=softmax_scale(cfg))
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if not return_cache:
        return y
    pad = (max_len or S) - S
    cache = {"c_kv": jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0))),
             "k_pe": jnp.pad(k_pe, ((0, 0), (0, pad), (0, 0))),
             "kpos": jnp.concatenate([pos[0],
                                      jnp.full((pad,), -1, jnp.int32)])}
    return y, cache


def cache_init(cfg, batch, max_len, dtype):
    m = cfg.mla
    return {"c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "k_pe": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
            "kpos": jnp.full((max_len,), -1, jnp.int32)}


def decode(p, cfg, cache, x, pos):
    """x: (B, d) one token at position ``pos``. Returns (y, cache)."""
    posv = jnp.full((1, 1), pos, jnp.int32)
    q = _query(p, cfg, x[:, None], posv)[:, 0]
    c_kv, k_pe = _latent(p, cfg, x[:, None], posv)
    cache = {
        "c_kv": jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv,
                                                    pos, axis=1),
        "k_pe": jax.lax.dynamic_update_slice_in_dim(cache["k_pe"], k_pe,
                                                    pos, axis=1),
        "kpos": jax.lax.dynamic_update_slice_in_dim(
            cache["kpos"], jnp.asarray([pos], jnp.int32), pos, axis=0)}
    k, v = _keys_values(p, cfg, cache["c_kv"], cache["k_pe"])
    y = attention.decode_attention(q, k, v, cache["kpos"], pos,
                                   scale=softmax_scale(cfg))
    return jnp.einsum("bhk,hkd->bd", y, p["wo"]), cache
