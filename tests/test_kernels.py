"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.ff_dense import ff_dense
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba2_ssd import mamba2_ssd


@pytest.mark.parametrize("M,K,N", [(64, 784, 512), (128, 256, 2000),
                                   (100, 333, 257), (16, 64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ff_dense(M, K, N, dtype, key):
    x = jax.random.normal(key, (M, K), dtype)
    w = (jax.random.normal(key, (K, N), jnp.float32) * K ** -0.5).astype(
        dtype)
    b = jnp.zeros((N,), dtype)
    y, g = ff_dense(x, w, b)
    yr, gr = ref.ff_dense_ref(x, w, b)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(g, gr, rtol=5 * tol, atol=5 * tol)


@pytest.mark.parametrize("B,S,H,KV,hd", [(2, 256, 4, 2, 64),
                                         (1, 128, 8, 1, 32),
                                         (2, 128, 4, 4, 128)])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64),
                                           (False, None)])
def test_flash_attention(B, S, H, KV, hd, causal, window, key):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    o = flash_attention(q, k, v, causal=causal, window=window,
                        bq=64, bk=64)
    orf = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(o, orf, rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16(key):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 128, 2, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 128, 2, 64), jnp.bfloat16)
    o = flash_attention(q, k, v, bq=64, bk=64)
    orf = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(orf, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("B,S,H,hd,N,chunk", [(2, 128, 4, 32, 16, 32),
                                              (1, 256, 8, 16, 64, 64),
                                              (2, 64, 2, 64, 128, 64)])
def test_mamba2_ssd(B, S, H, hd, N, chunk, key):
    ks = jax.random.split(key, 4)
    xbar = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    dA = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    b = jax.random.normal(ks[2], (B, S, N), jnp.float32)
    c = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    y, hT = mamba2_ssd(xbar, dA, b, c, chunk=chunk)
    yr, hTr = ref.mamba2_ssd_ref(xbar, dA, b, c)
    np.testing.assert_allclose(y, yr, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(hT, hTr, rtol=2e-4, atol=2e-4)


def test_ssd_kernel_matches_model_path(key):
    """The Pallas SSD kernel must agree with the model's streaming scan
    (repro.models.ssm.ssd_chunked) — same chunking math, two codepaths."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(key, 4)
    B, S, H, hd, N = 2, 128, 4, 32, 16
    xh = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.2)
    b = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    c = jax.random.normal(ks[0], (B, S, N), jnp.float32)
    y_model, h_model = ssd_chunked(xh, dt, A, b, c, 32)
    xbar = xh * dt[..., None]
    dA = dt * A
    y_kern, h_kern = mamba2_ssd(xbar, dA, b, c, chunk=32)
    np.testing.assert_allclose(y_model, y_kern, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h_model, h_kern, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# ops dispatch contract: all three ops share the registry-backed impl=
# interface (helpful unknown-impl error, forced ref == direct oracle,
# force_pallas deprecation shim).
# ---------------------------------------------------------------------------

def _op_args(op, key):
    if op == "ff_dense":
        return (jax.random.normal(key, (16, 64)),
                jax.random.normal(key, (64, 128)) * 0.1,
                jnp.zeros((128,)))
    if op == "flash_attention":
        ks = jax.random.split(key, 3)
        return (jax.random.normal(ks[0], (1, 128, 4, 32)),
                jax.random.normal(ks[1], (1, 128, 2, 32)),
                jax.random.normal(ks[2], (1, 128, 2, 32)))
    ks = jax.random.split(key, 4)
    return (jax.random.normal(ks[0], (1, 128, 2, 16)),
            -jax.nn.softplus(jax.random.normal(ks[1], (1, 128, 2))),
            jax.random.normal(ks[2], (1, 128, 16)),
            jax.random.normal(ks[3], (1, 128, 16)))


@pytest.mark.parametrize("op", ["ff_dense", "flash_attention",
                                "mamba2_ssd"])
def test_ops_unknown_impl_lists_choices(op, key):
    fn = getattr(ops, op)
    with pytest.raises(ValueError, match="auto | pallas | ref"):
        fn(*_op_args(op, key), impl="nope")


@pytest.mark.parametrize("op,ref_fn", [
    ("ff_dense", ref.ff_dense_ref),
    ("flash_attention", ref.flash_attention_ref),
    ("mamba2_ssd", ref.mamba2_ssd_ref)])
def test_ops_forced_ref_is_the_oracle(op, ref_fn, key):
    args = _op_args(op, key)
    got = getattr(ops, op)(*args, impl="ref")
    want = ref_fn(*args)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.array_equal(a, b))


@pytest.mark.parametrize("op", ["ff_dense", "flash_attention",
                                "mamba2_ssd"])
def test_ops_force_pallas_warns_and_delegates(op, key):
    """The legacy boolean must warn DeprecationWarning on every op and
    produce the impl='pallas' result."""
    args = _op_args(op, key)
    fn = getattr(ops, op)
    with pytest.warns(DeprecationWarning, match="impl='pallas'"):
        got = fn(*args, force_pallas=True)
    want = fn(*args, impl="pallas")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.array_equal(a, b))
    # force_pallas=False defers to the impl argument unchanged
    with pytest.warns(DeprecationWarning):
        got = fn(*args, force_pallas=False, impl="ref")
    for a, b in zip(jax.tree.leaves(got),
                    jax.tree.leaves(fn(*args, impl="ref"))):
        assert bool(jnp.array_equal(a, b))


def test_ops_impls_tuples_are_live_registry_views():
    assert ops.FF_DENSE_IMPLS[0] == "auto"
    assert set(ops.FF_DENSE_IMPLS) >= {"auto", "pallas", "ref"}
    assert set(ops.FLASH_ATTENTION_IMPLS) >= {"auto", "pallas", "ref"}
    assert set(ops.MAMBA2_SSD_IMPLS) >= {"auto", "pallas", "ref"}


def test_chunked_attention_matches_ref(key):
    """The model's pure-JAX chunked attention vs the dense oracle."""
    from repro.models.attention import chunked_attention
    ks = jax.random.split(key, 3)
    for causal, window in [(True, None), (True, 32), (False, None)]:
        q = jax.random.normal(ks[0], (2, 128, 4, 32), jnp.float32)
        k = jax.random.normal(ks[1], (2, 128, 2, 32), jnp.float32)
        v = jax.random.normal(ks[2], (2, 128, 2, 32), jnp.float32)
        o = chunked_attention(q, k, v, causal=causal, window=window,
                              q_chunk=32, k_chunk=64)
        orf = ref.flash_attention_ref(q, k, v, causal=causal,
                                      window=window)
        np.testing.assert_allclose(o, orf, rtol=2e-5, atol=2e-5)


def _dense_double_scan(q, k, v, *, causal=True, window=None, q_offset=0,
                       k_offset=0, q_chunk=512, k_chunk=1024):
    """The chunked scan that computes every (q chunk, k chunk) pair,
    hidden or not: the oracle the causal skip must equal to the bit."""
    from repro.models.attention import NEG_INF, _mask, _pv
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    dv = v.shape[-1]
    G = H // KV
    q_chunk, k_chunk = min(q_chunk, Sq), min(k_chunk, Sk)
    q_chunk = Sq if Sq % q_chunk else q_chunk
    k_chunk = Sk if Sk % k_chunk else k_chunk
    nq, nk = Sq // q_chunk, Sk // k_chunk
    scale = hd ** -0.5
    qs = q.reshape(B, nq, q_chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(B, nk, k_chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, k_chunk, KV, dv).transpose(1, 0, 2, 3, 4)
    q_positions = q_offset + jnp.arange(Sq, dtype=jnp.int32)
    k_positions = k_offset + jnp.arange(Sk, dtype=jnp.int32)

    def q_body(_, qi):
        qc, q_pos = qi
        qcf = qc.astype(jnp.float32) * scale

        def k_body(carry, ki):
            m_run, l_run, acc = carry
            kc, vc, k_pos = ki
            s = jnp.einsum("bqkgd,bskd->bkgqs", qcf, kc.astype(jnp.float32))
            msk = _mask(q_pos, k_pos, causal, window)
            s = jnp.where(msk[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + _pv(p, vc)
            return (m_new, l_new, acc), None

        c0 = (jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32),
              jnp.zeros((B, KV, G, q_chunk), jnp.float32),
              jnp.zeros((B, KV, G, q_chunk, dv), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            k_body, c0, (ks, vs, k_positions.reshape(nk, k_chunk)))
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        return None, out.transpose(0, 3, 1, 2, 4)

    _, outs = jax.lax.scan(jax.checkpoint(q_body), None,
                           (qs, q_positions.reshape(nq, q_chunk)))
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, dv)


def _out_and_grads(fn, q, k, v, ct, **kw):
    """fn's output and the gradients of <fn(q, k, v), ct> for q, k, v."""
    f = jax.jit(lambda q, k, v: fn(q, k, v, **kw))
    g = jax.jit(jax.grad(lambda q, k, v: (fn(q, k, v, **kw) * ct).sum(),
                         argnums=(0, 1, 2)))
    return (f(q, k, v), *g(q, k, v))


def _qkv(key, B, Sq, Sk, H, KV, hd, dv):
    ks = jax.random.split(key, 4)
    return (jax.random.normal(ks[0], (B, Sq, H, hd), jnp.float32),
            jax.random.normal(ks[1], (B, Sk, KV, hd), jnp.float32),
            jax.random.normal(ks[2], (B, Sk, KV, dv), jnp.float32),
            jax.random.normal(ks[3], (B, Sq, H, dv), jnp.float32))


# (B, Sq, Sk, H, KV, qk head, v head, q_chunk, k_chunk, q_offset)
_SKIP_CASES = {
    "q_chunk_below_k_chunk": (2, 256, 256, 4, 4, 24, 24, 32, 64, 0),
    "q_chunk_equal_k_chunk": (2, 256, 256, 4, 4, 24, 24, 64, 64, 0),
    "q_chunk_above_k_chunk": (2, 256, 256, 4, 4, 24, 24, 64, 32, 0),
    "mla_head_dims": (2, 256, 256, 4, 4, 48, 32, 32, 64, 0),
    "gqa": (2, 256, 256, 8, 2, 24, 24, 32, 64, 0),
    "sq_not_a_chunk_multiple": (2, 96, 192, 4, 2, 24, 16, 64, 64, 32),
}


@pytest.mark.parametrize("case", sorted(_SKIP_CASES))
def test_causal_skip_is_bitwise_the_dense_scan(case, key):
    """Causal self-attention skips the chunk pairs the mask hides and
    folds the visible ones in the same order: output and the gradients
    of q, k and v equal the every-pair scan's to the bit."""
    from repro.models.attention import chunk_pairs, chunked_attention
    B, Sq, Sk, H, KV, hd, dv, qc, kc, q_off = _SKIP_CASES[case]
    q, k, v, ct = _qkv(key, B, Sq, Sk, H, KV, hd, dv)
    run, whole = chunk_pairs(Sq, Sk, qc, kc, q_offset=q_off)
    assert run < whole                       # the skip engages
    kw = dict(q_chunk=qc, k_chunk=kc, q_offset=q_off)
    got = _out_and_grads(chunked_attention, q, k, v, ct, **kw)
    want = _out_and_grads(_dense_double_scan, q, k, v, ct, **kw)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_array_equal(a, b, err_msg=name)


_DENSE_CASES = {"non_causal": dict(causal=False),
                "window": dict(window=48),
                "keys_after_queries": dict(k_offset=16),
                "traced_q_offset": dict(q_offset=64)}


@pytest.mark.parametrize("case", sorted(_DENSE_CASES))
def test_chunked_attention_keeps_every_pair_elsewhere(case, key):
    """Non-causal, windowed, keys that start after the queries, and a
    traced ``q_offset`` run every chunk pair: the same numbers as the
    every-pair scan, and the dense oracle's where it can express the
    positions (the queries the last of the keys' positions)."""
    from repro.models.attention import chunk_pairs, chunked_attention
    kw = dict(_DENSE_CASES[case], q_chunk=32, k_chunk=64)
    Sq = 64 if case == "traced_q_offset" else 128
    qf, k, v, ct = _qkv(key, 2, 128, 128, 4, 2, 32, 32)
    q, ct = qf[:, 128 - Sq:], ct[:, 128 - Sq:]
    if case == "traced_q_offset":
        q_off = kw.pop("q_offset")
        fn = jax.jit(lambda q, k, v, o: chunked_attention(q, k, v, q_offset=o,
                                                          **kw))
        attend = lambda q, k, v, **_: fn(q, k, v, q_off)
        assert chunk_pairs(Sq, 128, 32, 64, q_offset=jnp.int32(q_off)) \
            == (4, 4)
    else:
        attend = chunked_attention
        run, whole = chunk_pairs(128, 128, 32, 64, **{
            n: kw[n] for n in ("causal", "window", "k_offset") if n in kw})
        assert run == whole
    got = _out_and_grads(attend, q, k, v, ct, **kw)
    want = _out_and_grads(_dense_double_scan, q, k, v, ct,
                          **dict(_DENSE_CASES[case], q_chunk=32, k_chunk=64))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_array_equal(a, b, err_msg=name)
    if case != "keys_after_queries":
        orf = ref.flash_attention_ref(qf, k, v,
                                      causal=kw.get("causal", True),
                                      window=kw.get("window"))
        np.testing.assert_allclose(got[0], orf[:, 128 - Sq:], rtol=2e-5,
                                   atol=2e-5)


def test_chunk_pairs_counts_the_pairs_the_scan_runs():
    """Causal self-attention at 4,096 runs 20 of 32 pairs with 512/1024
    chunks and 36 of 64 with 512/512; without the causal mask, with a
    window, or with keys after the queries, every pair; a ``q_offset``
    that is no Python int (traced) keeps every pair too."""
    from repro.models.attention import chunk_pairs
    assert chunk_pairs(4096, 4096) == (20, 32)
    assert chunk_pairs(4096, 4096, 512, 1024) == (20, 32)
    assert chunk_pairs(4096, 4096, 512, 512) == (36, 64)
    assert chunk_pairs(4096, 4096, 1024, 512) == (20, 32)
    assert chunk_pairs(4096, 4096, causal=False) == (32, 32)
    assert chunk_pairs(4096, 4096, 512, 512, causal=False) == (64, 64)
    assert chunk_pairs(4096, 4096, window=1024) == (32, 32)
    assert chunk_pairs(4096, 4096, k_offset=1) == (32, 32)
    assert chunk_pairs(1024, 4096, q_offset=3072) == (8, 8)
    assert chunk_pairs(1024, 4096, q_offset=1024) == (4, 8)
    seen = []
    jax.make_jaxpr(lambda o: seen.append(chunk_pairs(4096, 4096, q_offset=o))
                   or o)(0)
    assert seen == [(32, 32)]


def test_causal_skip_keeps_the_per_q_chunk_remat():
    """The compiled grad of causal attention with the skip engaged
    needs no more temporary bytes than the same causal call with the
    skip disengaged (a traced ``q_offset``, so every pair runs), and no
    more than the call without the mask (``causal=False``) but for one
    gradient buffer of K and of V (what the switch over k-chunk counts
    returns per q chunk). Kept across q chunks, the pairs' scores would
    add the whole (B, H, Sq, Sk) score matrix, twelve times that
    allowance here."""
    from repro.models.attention import chunk_pairs, chunked_attention
    B, S, H, hd, dv = 2, 1024, 4, 48, 32
    assert chunk_pairs(S, S, 128, 256) == (20, 32)
    q = k = jnp.zeros((B, S, H, hd), jnp.float32)
    v = jnp.zeros((B, S, H, dv), jnp.float32)

    def temp_bytes(causal, traced_offset=False):
        def f(q, k, v, o):
            return chunked_attention(q, k, v, causal=causal, q_offset=o,
                                     q_chunk=128, k_chunk=256).sum()
        if traced_offset:
            g = jax.jit(jax.grad(f, argnums=(0, 1, 2)))
            lowered = g.lower(q, k, v, 0)
        else:
            g = jax.jit(jax.grad(lambda q, k, v: f(q, k, v, 0),
                                 argnums=(0, 1, 2)))
            lowered = g.lower(q, k, v)
        return lowered.compile().memory_analysis().temp_size_in_bytes

    kv_grads = k.nbytes + v.nbytes
    assert B * H * S * S * 4 > 12 * kv_grads
    skip = temp_bytes(True)
    assert skip <= temp_bytes(True, traced_offset=True)
    assert skip <= temp_bytes(False) + kv_grads
