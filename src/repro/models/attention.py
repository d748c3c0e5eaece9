"""Attention: chunked (flash-style, O(S·chunk) memory) full-sequence path
for train/prefill and a cache-based decode path. Supports GQA/MQA, causal,
sliding-window, bidirectional (encoder) and cross-attention.

The chunked path is pure JAX (double ``lax.scan`` with online softmax) so
that the 32k-sequence dry-runs lower with sane memory; the TPU-optimized
kernel lives in ``repro.kernels.flash_attention`` and is numerically
validated against this path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# §Perf toggles (set by launch/dryrun.py opts; read at trace time).
DEFAULT_CAUSAL_SKIP = False
PV_BF16 = False       # cast the post-softmax P matrix to bf16 for the
                      # PV matmul (f32 accumulation via MXU) — halves the
                      # largest attention buffer's traffic


def _pv(p, v):
    """P @ V with optional bf16 P (f32 accumulate)."""
    if PV_BF16:
        return jnp.einsum("bkgqs,bskd->bkgqd", p.astype(jnp.bfloat16),
                          v.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))


def _varying_like(x, *refs):
    """``x`` marked as varying over every manual mesh axis any of
    ``refs`` varies over. Under a ``check_vma`` shard_map a scan's
    carry must keep one type; an online-softmax carry initialised from
    constants is invariant while the step that mixes in q/k/v makes it
    varying, so the initial carry is cast up front. Outside shard_map
    no axis varies and ``x`` is returned unchanged."""
    axes = frozenset().union(*(jax.typeof(r).vma for r in refs))
    if not axes:
        return x
    return jax.lax.pcast(x, tuple(sorted(axes)), to="varying")


def _mask(q_pos, k_pos, causal, window):
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), jnp.bool_)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return m


def chunked_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                      k_offset=0, q_chunk=512, k_chunk=1024,
                      causal_skip=False, scale=None):
    """q: (B, Sq, H, hd); k: (B, Sk, KV, hd); v: (B, Sk, KV, dv).
    Returns (B, Sq, H, dv). ``scale`` defaults to hd ** -0.5.

    Online-softmax over kv chunks, scanned over q chunks: peak score
    buffer is (B, H, q_chunk, k_chunk) regardless of sequence length.
    Each q chunk is rematerialised in the backward pass: kept, its
    chunk pairs' scores would hold the whole (B, H, Sq, Sk) matrix.

    ``causal_skip`` (a §Perf optimization, off by default): instead of
    the dense nq x nk double scan, enumerate only the VISIBLE (q, k)
    chunk pairs (causal upper triangle, window band) statically and
    scan that flat list — ~2x fewer matmuls and ~2x less chunk IO for
    causal self-attention at equal numerics.
    """
    if (causal_skip and isinstance(q_offset, int) and q_offset == 0
            and isinstance(k_offset, int) and k_offset == 0):
        return _triangle_attention(q, k, v, causal=causal, window=window,
                                   q_chunk=q_chunk, k_chunk=k_chunk,
                                   scale=scale)
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    dv = v.shape[-1]
    G = H // KV
    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    if Sq % q_chunk:
        q_chunk = Sq
    if Sk % k_chunk:
        k_chunk = Sk
    nq, nk = Sq // q_chunk, Sk // k_chunk
    scale = hd ** -0.5 if scale is None else scale

    # (nq, B, qc, KV, G, hd)
    qs = q.reshape(B, nq, q_chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(B, nk, k_chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, k_chunk, KV, dv).transpose(1, 0, 2, 3, 4)
    q_positions = q_offset + jnp.arange(Sq, dtype=jnp.int32)
    k_positions = k_offset + jnp.arange(Sk, dtype=jnp.int32)

    def q_body(_, qi):
        qc, q_pos = qi                       # (B, qc, KV, G, hd), (qc,)
        qcf = qc.astype(jnp.float32) * scale

        def k_body(carry, ki):
            m_run, l_run, acc = carry
            kc, vc, k_pos = ki               # (B, kc, KV, hd)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qcf,
                           kc.astype(jnp.float32))     # (B, KV, G, qc, kc)
            msk = _mask(q_pos, k_pos, causal, window)  # (qc, kc)
            s = jnp.where(msk[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(axis=-1)
            pv = _pv(p, vc)
            acc = acc * corr[..., None] + pv
            return (m_new, l_new, acc), None

        m0, l0, a0 = (_varying_like(c, qc, ks, vs) for c in (
            jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32),
            jnp.zeros((B, KV, G, q_chunk), jnp.float32),
            jnp.zeros((B, KV, G, q_chunk, dv), jnp.float32)))
        (m, l, acc), _ = jax.lax.scan(
            k_body, (m0, l0, a0), (ks, vs, k_positions.reshape(nk, k_chunk)))
        l = jnp.maximum(l, 1e-30)
        out = (acc / l[..., None]).astype(q.dtype)     # (B, KV, G, qc, dv)
        return None, out.transpose(0, 3, 1, 2, 4)      # (B, qc, KV, G, dv)

    _, outs = jax.lax.scan(
        jax.checkpoint(q_body), None, (qs, q_positions.reshape(nq, q_chunk)))
    # (nq, B, qc, KV, G, dv) -> (B, Sq, H, dv)
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, dv)


def _triangle_attention(q, k, v, *, causal, window, q_chunk, k_chunk,
                        scale=None):
    """Visible-chunk-pair enumeration (static) + flat scan.

    Carries full (nq, ...) online-softmax tables; each step updates one
    q-chunk's row via dynamic indexing. Invisible pairs never execute.
    """
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    dv = v.shape[-1]
    G = H // KV
    qc = min(q_chunk, Sq)
    kc = min(k_chunk, Sk)
    if Sq % qc:
        qc = Sq
    if Sk % kc:
        kc = Sk
    nq, nk = Sq // qc, Sk // kc
    scale = hd ** -0.5 if scale is None else scale

    pairs = []
    for i in range(nq):
        q_lo, q_hi = i * qc, i * qc + qc - 1
        for j in range(nk):
            k_lo, k_hi = j * kc, j * kc + kc - 1
            if causal and k_lo > q_hi:
                continue                       # strictly above diagonal
            if window is not None and k_hi <= q_lo - window:
                continue                       # entirely below the band
            pairs.append((i, j))
    pairs_arr = jnp.asarray(pairs, jnp.int32)   # (P, 2)

    qs = q.reshape(B, nq, qc, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(B, nk, kc, KV, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kc, KV, dv).transpose(1, 0, 2, 3, 4)

    def body(carry, pair):
        m_t, l_t, acc_t = carry                 # (nq, B, KV, G, qc[, hd])
        i, j = pair[0], pair[1]
        qb = jax.lax.dynamic_index_in_dim(qs, i, 0, keepdims=False)
        kb = jax.lax.dynamic_index_in_dim(ks, j, 0, keepdims=False)
        vb = jax.lax.dynamic_index_in_dim(vs, j, 0, keepdims=False)
        qf = qb.astype(jnp.float32) * scale
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf, kb.astype(jnp.float32))
        q_pos = i * qc + jnp.arange(qc, dtype=jnp.int32)
        k_pos = j * kc + jnp.arange(kc, dtype=jnp.int32)
        msk = _mask(q_pos, k_pos, causal, window)
        s = jnp.where(msk[None, None, None], s, NEG_INF)

        m_run = jax.lax.dynamic_index_in_dim(m_t, i, 0, keepdims=False)
        l_run = jax.lax.dynamic_index_in_dim(l_t, i, 0, keepdims=False)
        acc = jax.lax.dynamic_index_in_dim(acc_t, i, 0, keepdims=False)
        m_new = jnp.maximum(m_run, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + p.sum(axis=-1)
        pv = _pv(p, vb)
        acc = acc * corr[..., None] + pv
        m_t = jax.lax.dynamic_update_index_in_dim(m_t, m_new, i, 0)
        l_t = jax.lax.dynamic_update_index_in_dim(l_t, l_new, i, 0)
        acc_t = jax.lax.dynamic_update_index_in_dim(acc_t, acc, i, 0)
        return (m_t, l_t, acc_t), None

    m0, l0, a0 = (_varying_like(c, qs, ks, vs) for c in (
        jnp.full((nq, B, KV, G, qc), NEG_INF, jnp.float32),
        jnp.zeros((nq, B, KV, G, qc), jnp.float32),
        jnp.zeros((nq, B, KV, G, qc, dv), jnp.float32)))
    (m_t, l_t, acc_t), _ = jax.lax.scan(body, (m0, l0, a0), pairs_arr)
    l_t = jnp.maximum(l_t, 1e-30)
    out = (acc_t / l_t[..., None]).astype(q.dtype)  # (nq, B, KV, G, qc, dv)
    return out.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, dv)


def decode_attention(q, k_cache, v_cache, k_pos, cur_pos, *, window=None,
                     scale=None):
    """One-token attention against a cache.

    q: (B, H, hd); k_cache: (B, S, KV, hd); v_cache: (B, S, KV, dv);
    k_pos: (S,) int32 positions held in each cache slot (-1 = empty);
    cur_pos: scalar int32 — position of the query token.
    """
    B, H, hd = q.shape
    _, S, KV, _ = k_cache.shape
    dv = v_cache.shape[-1]
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale
    qf = q.reshape(B, KV, G, hd).astype(jnp.float32) * scale
    s = jnp.einsum("bkgd,bskd->bkgs", qf, k_cache.astype(jnp.float32))
    valid = (k_pos >= 0) & (k_pos <= cur_pos)
    if window is not None:
        valid &= (cur_pos - k_pos) < window
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, H, dv).astype(q.dtype)
