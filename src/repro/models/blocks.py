"""Block-level init/apply/decode dispatch.

Block kinds: attn | attn_dense | local_attn | cross_attn | mamba2 |
rglru | xdec | mla_dense | mla_moe. Every block is pre-norm residual;
attn/rglru/xdec blocks are followed by an MLP sub-layer (the MoE where
``cfg.moe`` is set, else dense); mamba2 blocks have none when d_ff == 0.
A kind that names its MLP (``*_dense``: the dense MLP of width d_ff,
``*_moe``: the MoE) takes that one whatever ``cfg.moe`` says, so a
DeepSeekMoE stack's leading dense layers are blocks of their own kind.
The mla kinds attend by multi-head latent attention (``models.mla``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention, common, mla, mlp, rglru, ssm
from repro.models.mlp import NO_DIST


# ---------------------------------------------------------------------------
# Attention sub-layer
# ---------------------------------------------------------------------------

def attn_init(key, cfg, cross=False):
    d, H, KV = cfg.d_model, cfg.n_heads, cfg.n_kv
    hd = cfg.resolved_head_dim
    dtype = common.dtype_of(cfg)
    ks = jax.random.split(key, 5)
    p = {
        "wq": common.dense_init(ks[0], (d, H, hd), dtype, fan_in=d),
        "wk": common.dense_init(ks[1], (d, KV, hd), dtype, fan_in=d),
        "wv": common.dense_init(ks[2], (d, KV, hd), dtype, fan_in=d),
        "wo": common.dense_init(ks[3], (H, hd, d), dtype, fan_in=H * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dtype)
        p["bk"] = jnp.zeros((KV, hd), dtype)
        p["bv"] = jnp.zeros((KV, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    if cross:
        p["gate"] = jnp.zeros((), jnp.float32)
    return p


def _qkv(p, cfg, x, kv_src):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", kv_src, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", kv_src, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = common.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = common.rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def attn_apply(p, cfg, x, *, kv_src=None, causal=True, window=None,
               use_rope=True, q_offset=0):
    cross = kv_src is not None
    q, k, v = _qkv(p, cfg, x, x if kv_src is None else kv_src)
    if use_rope and not cross:
        pos_q = q_offset + jnp.arange(x.shape[1], dtype=jnp.int32)
        q = common.apply_rope(q, pos_q[None], cfg.rope_theta)
        k = common.apply_rope(k, pos_q[None], cfg.rope_theta)
    out = attention.chunked_attention(
        q, k, v, causal=causal and not cross, window=window,
        q_offset=q_offset,
        causal_skip=attention.DEFAULT_CAUSAL_SKIP and causal and not cross)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if cross:
        y = jnp.tanh(p["gate"]).astype(y.dtype) * y
    return y


def attn_decode(p, cfg, cache, x, pos, *, window=None, use_rope=True):
    """x: (B, d) one token; cache: {k, v, kpos}. Returns (y, cache)."""
    q, k, v = _qkv(p, cfg, x[:, None], x[:, None])
    if use_rope:
        posv = jnp.full((1, 1), pos, jnp.int32)
        q = common.apply_rope(q, posv, cfg.rope_theta)
        k = common.apply_rope(k, posv, cfg.rope_theta)
    S = cache["k"].shape[1]
    slot = pos % S if window is not None else pos
    kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    kpos = jax.lax.dynamic_update_slice_in_dim(
        cache["kpos"], jnp.asarray([pos], jnp.int32), slot, axis=0)
    y = attention.decode_attention(q[:, 0], kc, vc, kpos, pos, window=window)
    y = jnp.einsum("bhk,hkd->bd", y, p["wo"])
    return y, {"k": kc, "v": vc, "kpos": kpos}


def cross_decode(p, cfg, cross_kv, x):
    """Cross-attention for one decode token against precomputed enc/vision KV."""
    k, v, kpos = cross_kv["k"], cross_kv["v"], cross_kv["kpos"]
    q = jnp.einsum("bd,dhk->bhk", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    if cfg.qk_norm:
        q = common.rms_norm(q, p["q_norm"], cfg.norm_eps)
    y = attention.decode_attention(q, k, v, kpos, jnp.int32(2 ** 30))
    y = jnp.einsum("bhk,hkd->bd", y, p["wo"])
    if "gate" in p:
        y = jnp.tanh(p["gate"]).astype(y.dtype) * y
    return y


def precompute_cross_kv(p, cfg, aux):
    """aux: (B, T, d) encoder/vision embeddings -> cache-side KV."""
    k = jnp.einsum("btd,dhk->bthk", aux, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", aux, p["wv"])
    if cfg.qkv_bias:
        k, v = k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        k = common.rms_norm(k, p["k_norm"], cfg.norm_eps)
    return {"k": k, "v": v,
            "kpos": jnp.arange(aux.shape[1], dtype=jnp.int32)}


# ---------------------------------------------------------------------------
# MLP sub-layer dispatch
# ---------------------------------------------------------------------------

DENSE_KINDS = ("attn_dense", "mla_dense")
MLA_KINDS = ("mla_dense", "mla_moe")


def mlp_init(key, cfg, kind="attn"):
    if kind == "mla_moe" or (cfg.moe is not None
                             and kind not in DENSE_KINDS):
        return mlp.moe_init(key, cfg.moe, cfg.d_model, common.dtype_of(cfg))
    if cfg.d_ff == 0:
        return None
    return mlp.dense_mlp_init(key, cfg.d_model, cfg.d_ff,
                              common.dtype_of(cfg))


def mlp_apply(p, cfg, x, dist=NO_DIST, stats=None):
    """Returns (y, aux_loss). ``stats``: see ``mlp.moe_apply``."""
    if p is None:
        return jnp.zeros_like(x), jnp.zeros((), jnp.float32)
    if "router" in p:
        return mlp.moe_apply(p, x, cfg.moe, cfg.act, dist, stats)
    return mlp.dense_mlp_apply(p, x, cfg.act), jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Block init / apply / decode
# ---------------------------------------------------------------------------

def block_init(key, cfg, kind):
    dnorm = jnp.zeros((cfg.d_model,), jnp.float32)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if kind in MLA_KINDS:
        return {"norm1": dnorm, "attn": mla.init(k1, cfg),
                "norm2": dnorm, "mlp": mlp_init(k2, cfg, kind)}
    if kind in ("attn", "attn_dense", "local_attn"):
        p = {"norm1": dnorm, "attn": attn_init(k1, cfg)}
        m = mlp_init(k2, cfg, kind)
        if m is not None:
            p["norm2"] = dnorm
            p["mlp"] = m
        return p
    if kind == "cross_attn":
        p = {"norm1": dnorm, "attn": attn_init(k1, cfg, cross=True)}
        m = mlp_init(k2, cfg)
        if m is not None:
            p["norm2"] = dnorm
            p["mlp"] = m
        return p
    if kind == "xdec":
        return {"norm1": dnorm, "attn": attn_init(k1, cfg),
                "norm_x": dnorm, "xattn": attn_init(k2, cfg, cross=True),
                "norm2": dnorm, "mlp": mlp_init(k3, cfg)}
    if kind == "mamba2":
        p = {"norm1": dnorm, "ssm": ssm.init(k1, cfg)}
        if cfg.d_ff:
            p["norm2"] = dnorm
            p["mlp"] = mlp_init(k2, cfg)
        return p
    if kind == "rglru":
        p = {"norm1": dnorm, "rec": rglru.init(k1, cfg)}
        m = mlp_init(k2, cfg)
        if m is not None:
            p["norm2"] = dnorm
            p["mlp"] = m
        return p
    raise ValueError(kind)


def _window_for(cfg, kind):
    if kind == "local_attn":
        return cfg.rglru.window if cfg.rglru else (cfg.window or 2048)
    return cfg.window


def block_apply(p, cfg, kind, x, ctx):
    """x: (B, S, d). ctx: dict(causal, aux, dist, q_offset, moe_stats).
    Returns (x_out, aux_loss). A list under ``moe_stats`` receives each
    dropless MoE layer's held-expert assignment counts (outside a scan
    only: they are values of the trace that applies the block)."""
    dist = ctx.get("dist", NO_DIST)
    aux_loss = jnp.zeros((), jnp.float32)
    causal = ctx.get("causal", True)
    q_off = ctx.get("q_offset", 0)
    if kind in MLA_KINDS:
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        x = x + mla.apply(p["attn"], cfg, h, causal=causal, q_offset=q_off)
    elif kind in ("attn", "attn_dense", "local_attn"):
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        x = x + attn_apply(p["attn"], cfg, h, causal=causal,
                           window=_window_for(cfg, kind), q_offset=q_off)
    elif kind == "cross_attn":
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        x = x + attn_apply(p["attn"], cfg, h, kv_src=ctx["aux"],
                           use_rope=False)
    elif kind == "xdec":
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        x = x + attn_apply(p["attn"], cfg, h, causal=True, q_offset=q_off)
        h = common.rms_norm(x, p["norm_x"], cfg.norm_eps)
        x = x + attn_apply(p["xattn"], cfg, h, kv_src=ctx["aux"],
                           use_rope=False)
    elif kind == "mamba2":
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        x = x + ssm.forward(p["ssm"], cfg, h)
    elif kind == "rglru":
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        y, _, _ = rglru.forward(p["rec"], cfg, h)
        x = x + y
    else:
        raise ValueError(kind)
    if "mlp" in p:
        h = common.rms_norm(x, p["norm2"], cfg.norm_eps)
        y, aux_loss = mlp_apply(p["mlp"], cfg, h, dist,
                                ctx.get("moe_stats"))
        x = x + y
    return x, aux_loss


# ---------------------------------------------------------------------------
# Prefill: apply block AND produce a decode-ready cache
# ---------------------------------------------------------------------------

def _attn_prefill(p, cfg, x, *, window, max_len, causal=True):
    """Like attn_apply but also returns the KV cache after S tokens."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, cfg, x, x)
    pos = jnp.arange(S, dtype=jnp.int32)
    q = common.apply_rope(q, pos[None], cfg.rope_theta)
    k = common.apply_rope(k, pos[None], cfg.rope_theta)
    out = attention.chunked_attention(
        q, k, v, causal=causal, window=window,
        causal_skip=attention.DEFAULT_CAUSAL_SKIP and causal)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if window is not None and window < max_len:
        Wc = min(window, max_len)
        keep = min(S, Wc)
        # ring layout: position p lives in slot p % Wc
        slots = jnp.asarray([p % Wc for p in range(S - keep, S)], jnp.int32)
        B_, _, KV, hd = k.shape
        ck = jnp.zeros((B_, Wc, KV, hd), k.dtype).at[:, slots].set(
            k[:, S - keep:])
        cv = jnp.zeros((B_, Wc, KV, hd), v.dtype).at[:, slots].set(
            v[:, S - keep:])
        kpos = jnp.full((Wc,), -1, jnp.int32).at[slots].set(
            jnp.arange(S - keep, S, dtype=jnp.int32))
        cache = {"k": ck, "v": cv, "kpos": kpos}
    else:
        L = max_len
        pad = L - S
        cache = {
            "k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
            "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))),
            "kpos": jnp.concatenate(
                [pos, jnp.full((pad,), -1, jnp.int32)]),
        }
    return y, cache


def block_prefill(p, cfg, kind, x, ctx):
    """x: (B, S, d). Returns (x_out, cache) — cache matches block_decode."""
    max_len = ctx.get("max_len", x.shape[1])
    dist = ctx.get("dist", NO_DIST)
    if kind in MLA_KINDS:
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        y, cache = mla.apply(p["attn"], cfg, h, return_cache=True,
                             max_len=max_len)
        x = x + y
    elif kind in ("attn", "attn_dense", "local_attn"):
        window = _window_for(cfg, kind)
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        y, cache = _attn_prefill(p["attn"], cfg, h, window=window,
                                 max_len=max_len)
        x = x + y
    elif kind == "cross_attn":
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        x = x + attn_apply(p["attn"], cfg, h, kv_src=ctx["aux"],
                           use_rope=False)
        cache = precompute_cross_kv(p["attn"], cfg, ctx["aux"])
    elif kind == "xdec":
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        y, self_c = _attn_prefill(p["attn"], cfg, h, window=None,
                                  max_len=max_len)
        x = x + y
        h = common.rms_norm(x, p["norm_x"], cfg.norm_eps)
        x = x + attn_apply(p["xattn"], cfg, h, kv_src=ctx["aux"],
                           use_rope=False)
        cache = {"self": self_c,
                 "cross": precompute_cross_kv(p["xattn"], cfg, ctx["aux"])}
    elif kind == "mamba2":
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        y, cache = ssm.forward(p["ssm"], cfg, h, return_cache=True)
        x = x + y
    elif kind == "rglru":
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        y, hT, conv_tail = rglru.forward(p["rec"], cfg, h)
        cache = {"h": hT, "conv": conv_tail}
        x = x + y
    else:
        raise ValueError(kind)
    if "mlp" in p:
        h = common.rms_norm(x, p["norm2"], cfg.norm_eps)
        y, _ = mlp_apply(p["mlp"], cfg, h, dist)
        x = x + y
    return x, cache


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------

def block_cache_init(cfg, kind, batch, max_len, dtype):
    KV, hd = cfg.n_kv, cfg.resolved_head_dim
    window = _window_for(cfg, kind)

    def kv_cache(length):
        return {"k": jnp.zeros((batch, length, KV, hd), dtype),
                "v": jnp.zeros((batch, length, KV, hd), dtype),
                "kpos": jnp.full((length,), -1, jnp.int32)}

    if kind in MLA_KINDS:
        return mla.cache_init(cfg, batch, max_len, dtype)
    if kind in ("attn", "attn_dense"):
        return kv_cache(max_len if cfg.window is None
                        else min(cfg.window, max_len))
    if kind == "local_attn":
        return kv_cache(min(window, max_len))
    if kind == "cross_attn":
        # filled by precompute_cross_kv at prefill time
        t = cfg.vision_tokens or cfg.enc_seq
        return kv_cache(t)
    if kind == "xdec":
        return {"self": kv_cache(max_len),
                "cross": kv_cache(cfg.enc_seq)}
    if kind == "mamba2":
        return ssm.init_cache(cfg, batch, dtype)
    if kind == "rglru":
        return rglru.init_cache(cfg, batch, dtype)
    raise ValueError(kind)


def block_decode(p, cfg, kind, cache, x, pos, ctx):
    """x: (B, d) one token. Returns (x_out, new_cache)."""
    if kind in MLA_KINDS:
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        y, cache = mla.decode(p["attn"], cfg, cache, h, pos)
        x = x + y
    elif kind in ("attn", "attn_dense", "local_attn"):
        window = _window_for(cfg, kind)
        ring = window is not None
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        y, cache = attn_decode(p["attn"], cfg, cache, h, pos,
                               window=window if ring else None)
        x = x + y
    elif kind == "cross_attn":
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        x = x + cross_decode(p["attn"], cfg, cache, h)
    elif kind == "xdec":
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        y, self_c = attn_decode(p["attn"], cfg, cache["self"], h, pos)
        x = x + y
        h = common.rms_norm(x, p["norm_x"], cfg.norm_eps)
        x = x + cross_decode(p["xattn"], cfg, cache["cross"], h)
        cache = {"self": self_c, "cross": cache["cross"]}
    elif kind == "mamba2":
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        y, cache = ssm.decode_step(p["ssm"], cfg, cache, h)
        x = x + y
    elif kind == "rglru":
        h = common.rms_norm(x, p["norm1"], cfg.norm_eps)
        y, cache = rglru.decode_step(p["rec"], cfg, cache, h)
        x = x + y
    else:
        raise ValueError(kind)
    if "mlp" in p:
        h = common.rms_norm(x, p["norm2"], cfg.norm_eps)
        y, _ = mlp_apply(p["mlp"], cfg, h[:, None], ctx.get("dist", NO_DIST))
        x = x + y[:, 0]
    return x, cache
