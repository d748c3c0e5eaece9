"""Ring attention: context parallelism over a mesh axis (beyond-paper).

Motivation (from the roofline table): prefill_32k for small dense archs
(qwen2-0.5b: batch 32, 14 heads) cannot fill a 256-chip pod with batch
and head parallelism alone — batch x heads < chips — so attention work
replicates. Sharding the SEQUENCE dimension is the missing axis.

Scheme (Liu et al. ring attention, TPU-adapted):
  * q, k, v sharded on the sequence dim over the `axis` (each device
    owns a contiguous S/P-token segment; segment order = device order).
  * P steps: each device holds its q segment, and the k/v segments
    ROTATE around the ring via collective_permute. Online softmax merges
    each incoming block, exactly like the flash kernel's inner loop but
    at inter-chip granularity.
  * causal masking is by global position, computed from the step index;
    fully-masked incoming blocks still rotate (the ring must stay in
    lockstep) but skip their matmuls' contribution via masking.

Communication: each step moves the local K/V (2 * S/P * kv_heads * hd
bytes) to the next neighbor — total = 2 * S * kv * hd per device per
layer, independent of P; compare an all-gather of K/V which needs the
same bytes but peaks memory at full-S K/V per device. Ring keeps peak
at 2 segments.

Used via ``ring_attention(q, k, v, axis="model", mesh=...)`` inside
shard_map (see ops in repro/core/train.py is NOT wired by default —
this is an opt-in building block exercised by tests and the context-
parallel §Perf experiment).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.sharding import shard_map as _shard_map


NEG_INF = -1e30


def _merge(m, l, acc, s, v):
    """Online-softmax merge of one incoming score block."""
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))
    acc_new = acc * corr[..., None] + pv
    return m_new, l_new, acc_new


def ring_attention_local(q, k, v, *, axis, causal=True):
    """Body to run INSIDE shard_map. q: (B, Sq_local, H, hd); k, v:
    (B, Sk_local, KV, hd), sequence sharded over `axis` in device
    order. Returns (B, Sq_local, H, hd).
    """
    P = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    scale = hd ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, KV, G, hd)
    q_pos = idx * Sq + jnp.arange(Sq, dtype=jnp.int32)

    perm = [(i, (i + 1) % P) for i in range(P)]

    def step(carry, t):
        m, l, acc, kc, vc = carry
        # segment currently held arrived from device (idx - t) % P
        src = jax.lax.rem(idx - t + P, P)
        k_pos = src * Sk + jnp.arange(Sk, dtype=jnp.int32)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf, kc.astype(jnp.float32))
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        m, l, acc = _merge(m, l, acc, s, vc)
        # rotate k/v to the next device (skip after the last step)
        kc = jax.lax.ppermute(kc, axis, perm)
        vc = jax.lax.ppermute(vc, axis, perm)
        return (m, l, acc, kc, vc), None

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, hd), jnp.float32)
    (m, l, acc, _, _), _ = jax.lax.scan(
        step, (m0, l0, a0, k, v), jnp.arange(P))
    l = jnp.maximum(l, 1e-30)
    out = (acc / l[..., None]).astype(q.dtype)     # (B, KV, G, Sq, hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)


def ring_attention(q, k, v, *, mesh, axis="model", causal=True,
                   batch_axis=None):
    """Convenience wrapper: shard q/k/v on the sequence dim over `axis`
    (and optionally batch over `batch_axis`), run the ring body.

    q: (B, S, H, hd) GLOBAL arrays (pjit-land).
    """
    Pspec = jax.sharding.PartitionSpec
    seq_spec = Pspec(batch_axis, axis, None, None)

    fn = _shard_map(
        functools.partial(ring_attention_local, axis=axis, causal=causal),
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec),
        out_specs=seq_spec)
    return fn(q, k, v)
