"""Shared numerics: norms, RoPE, initializers, activations."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(cfg):
    return jnp.dtype(cfg.dtype)


def rms_norm(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def rms_normalize(x, eps=1e-6):
    """Scale-free RMS normalization (used for FF goodness locality)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype)


def activation(name):
    return {
        "silu": jax.nn.silu,
        "gelu": lambda x: jax.nn.gelu(x, approximate=True),
        "relu": jax.nn.relu,
    }[name]


def softcap(x, cap):
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim, theta, scaling=None):
    """Inverse frequencies of the rotary pairs; with ``scaling`` (a
    ``YaRNConfig``) YaRN's blend of interpolated and original
    frequencies (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``)."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    if scaling is None:
        return inv

    def corr_dim(rotations):
        return (head_dim * math.log(
            scaling.original_max_position_embeddings
            / (rotations * 2 * math.pi))) / (2 * math.log(theta))

    lo = max(math.floor(corr_dim(scaling.beta_fast)), 0)
    hi = min(math.ceil(corr_dim(scaling.beta_slow)), head_dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float32) - lo) / (hi - lo),
                   0.0, 1.0)
    extra_mask = 1.0 - ramp
    return ((inv / scaling.factor) * (1.0 - extra_mask)
            + inv * extra_mask).astype(np.float32)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_mscale(scaling):
    """YaRN's factor on cos and sin (1 where mscale == mscale_all_dim,
    as in DeepSeek-V2)."""
    if scaling is None:
        return 1.0
    return (yarn_mscale(scaling.factor, scaling.mscale)
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim))


def attn_scale_factor(scaling):
    """YaRN's factor on the softmax scale: mscale(factor,
    mscale_all_dim) squared (1 without scaling)."""
    if scaling is None or not scaling.mscale_all_dim:
        return 1.0
    return yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2


def apply_rope(x, positions, theta, scaling=None):
    """x: (..., S, H, hd); positions: (..., S) int32. Pairs dimension i
    with i + hd/2 (split halves)."""
    hd = x.shape[-1]
    inv = jnp.asarray(rope_freqs(hd, theta, scaling))          # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * inv       # (..., S, hd/2)
    m = rope_mscale(scaling)
    cos = (jnp.cos(ang) * m)[..., None, :]                     # (..., S, 1, hd/2)
    sin = (jnp.sin(ang) * m)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Initializers (all take an explicit key; scaled-normal like llama)
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def zeros_init(_key, shape, dtype):
    return jnp.zeros(shape, dtype)


def stack_init(key, repeat, init_fn):
    """Initialize `repeat` copies of a param tree, stacked on axis 0."""
    keys = jax.random.split(key, repeat)
    return jax.vmap(init_fn)(keys)
