"""Gradient correctness of the fused FF custom_vjp (deliverable of the
hot-loop PR): the Pallas backward kernel vs jax.grad through the jnp
oracle, and ref-vs-pallas weight-stream equality of the chapter trainer.
Also covers the in-kernel norm epilogue (``norm=True``): value and
gradient parity vs the composed oracle on non-tile-aligned shapes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import ff, ff_mlp
from repro.kernels import ref
from repro.kernels.ff_dense import NORM_EPS
from repro.kernels.ff_dense_vjp import ff_dense_norm_vjp, ff_dense_vjp


def _stacked_ff_loss(apply_fn):
    """Fused pos/neg FF loss over a stacked (2B, K) batch, built on
    either the custom_vjp kernel or the oracle."""
    def loss(lp, xb, theta, peer_w):
        y, g = apply_fn(xb, lp["w"], lp["b"])
        g = g / y.shape[-1]
        half = xb.shape[0] // 2
        out = ff.ff_loss(g[:half], g[half:], theta)
        return out + peer_w * ff.peer_norm_loss(y[:half])
    return loss


_FUSED = _stacked_ff_loss(lambda x, w, b: ff_dense_vjp(x, w, b, True))
_ORACLE = _stacked_ff_loss(ref.ff_dense_ref)


@pytest.mark.parametrize("M,K,N", [(100, 333, 257), (64, 784, 512),
                                   (100, 784, 2000), (16, 64, 64)])
@pytest.mark.parametrize("peer_w", [0.0, 0.3])
def test_fused_grad_matches_oracle(M, K, N, peer_w, key):
    """Non-tile-aligned shapes exercise the padded backward path; the
    peer term exercises the dy cotangent, the FF loss the dg one."""
    kx, kw = jax.random.split(jax.random.fold_in(key, M * N + K))
    x = jax.random.normal(kx, (M, K), jnp.float32)
    lp = {"w": jax.random.normal(kw, (K, N), jnp.float32) * K ** -0.5,
          "b": jnp.full((N,), 0.1, jnp.float32)}
    gf, gxf = jax.grad(_FUSED, argnums=(0, 1))(lp, x, 2.0, peer_w)
    gr, gxr = jax.grad(_ORACLE, argnums=(0, 1))(lp, x, 2.0, peer_w)
    np.testing.assert_allclose(gf["w"], gr["w"], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(gf["b"], gr["b"], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(gxf, gxr, rtol=1e-4, atol=1e-6)


def test_fused_value_matches_oracle(key):
    x = jax.random.normal(key, (100, 333), jnp.float32)
    w = jax.random.normal(key, (333, 257), jnp.float32) * 333 ** -0.5
    b = jnp.full((257,), 0.05, jnp.float32)
    for peer_w in (0.0, 0.3):
        lf = _FUSED({"w": w, "b": b}, x, 2.0, peer_w)
        lr = _ORACLE({"w": w, "b": b}, x, 2.0, peer_w)
        np.testing.assert_allclose(lf, lr, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# In-kernel norm epilogue (norm=True): the inter-layer divide fused into
# the Pallas kernel, vs the composed jnp oracle.
# ---------------------------------------------------------------------------

def _normed_loss(apply_fn):
    """A loss exercising BOTH outputs of the normed kernel: the
    normalized activation (dyn cotangent, through a §4.4-style head
    matmul) and the raw goodness (dg cotangent)."""
    def loss(lp, xb, v):
        yn, g = apply_fn(xb, lp["w"], lp["b"])
        return jnp.mean((yn @ v) ** 2) + jnp.mean(jnp.tanh(g))
    return loss


_NORM_FUSED = _normed_loss(lambda x, w, b: ff_dense_norm_vjp(x, w, b, True))
_NORM_ORACLE = _normed_loss(ref.ff_dense_norm_ref)


@pytest.mark.parametrize("M,K,N", [(100, 333, 257), (90, 784, 200),
                                   (16, 64, 64), (128, 100, 384)])
def test_norm_epilogue_value_matches_oracle(M, K, N, key):
    """Non-tile-aligned shapes exercise the padded row-resident block:
    the zero-padded N columns must not perturb the in-kernel
    normalizer."""
    kx, kw = jax.random.split(jax.random.fold_in(key, M + N))
    x = jax.random.normal(kx, (M, K), jnp.float32)
    w = jax.random.normal(kw, (K, N), jnp.float32) * K ** -0.5
    b = jnp.full((N,), 0.1, jnp.float32)
    yn, g = ff_dense_norm_vjp(x, w, b, True)
    yr, gr = ref.ff_dense_norm_ref(x, w, b)
    np.testing.assert_allclose(yn, yr, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(g, gr, rtol=1e-5, atol=1e-5)
    # the normalized rows must have (near-)unit length wherever any
    # unit fired — the epilogue divided by the right normalizer
    lengths = jnp.linalg.norm(yn, axis=-1)
    fired = g > 1e-6
    np.testing.assert_allclose(np.asarray(lengths)[np.asarray(fired)],
                               1.0, rtol=1e-4)


@pytest.mark.parametrize("M,K,N", [(100, 333, 257), (16, 64, 64)])
def test_norm_epilogue_grad_matches_oracle(M, K, N, key):
    """The folded-cotangent backward (norm chain rule delegated to the
    fused bwd kernel) vs jax.grad through the composed oracle."""
    kx, kw, kv = jax.random.split(jax.random.fold_in(key, M + N), 3)
    x = jax.random.normal(kx, (M, K), jnp.float32)
    lp = {"w": jax.random.normal(kw, (K, N), jnp.float32) * K ** -0.5,
          "b": jnp.full((N,), 0.1, jnp.float32)}
    v = jax.random.normal(kv, (N,), jnp.float32)
    gf, gxf = jax.grad(_NORM_FUSED, argnums=(0, 1))(lp, x, v)
    gr, gxr = jax.grad(_NORM_ORACLE, argnums=(0, 1))(lp, x, v)
    np.testing.assert_allclose(gf["w"], gr["w"], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(gf["b"], gr["b"], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(gxf, gxr, rtol=1e-4, atol=1e-6)


def _ragged_case(path, key):
    """(loss, args) of the plain or the norm path on a shape whose M, K
    and N are no tile multiples."""
    M, K, N = 100, 333, 257
    kx, kw, kv = jax.random.split(key, 3)
    x = jax.random.normal(kx, (M, K), jnp.float32)
    lp = {"w": jax.random.normal(kw, (K, N), jnp.float32) * K ** -0.5,
          "b": jnp.full((N,), 0.1, jnp.float32)}
    if path == "plain":
        return _FUSED, (lp, x, 2.0, 0.3)
    return _NORM_FUSED, (lp, x, jax.random.normal(kv, (N,), jnp.float32))


@pytest.mark.parametrize("path", ["plain", "norm"])
def test_param_grad_without_dx_is_bit_identical(path, key):
    """A gradient of the parameters alone, as the trainers take it,
    drops dx and with it the dx kernel; its dw and db must be the bits
    of the gradient that keeps dx."""
    loss, args = _ragged_case(path, key)
    alone = jax.jit(jax.grad(loss, argnums=0))(*args)
    with_dx, _ = jax.jit(jax.grad(loss, argnums=(0, 1)))(*args)
    for name in ("w", "b"):
        np.testing.assert_array_equal(alone[name], with_dx[name])


# ---------------------------------------------------------------------------
# Tuned block shapes through the custom_vjp: the autotuner hands
# (bm, bn, bk) tuples down both fused paths — gradients must match the
# oracle for ANY legal blocks, not just the defaults, on
# non-tile-aligned shapes (the padded row/column edge cases).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("blocks", [None, (32, 128, 64), (64, 256, 128),
                                    (16, 128, 256)])
def test_tuned_blocks_grad_matches_oracle(blocks, key):
    M, K, N = 100, 333, 257          # deliberately not tile-aligned
    fused = _stacked_ff_loss(
        lambda x, w, b: ff_dense_vjp(x, w, b, True, blocks))
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (M, K), jnp.float32)
    lp = {"w": jax.random.normal(kw, (K, N), jnp.float32) * K ** -0.5,
          "b": jnp.full((N,), 0.1, jnp.float32)}
    gf, gxf = jax.grad(fused, argnums=(0, 1))(lp, x, 2.0, 0.3)
    gr, gxr = jax.grad(_ORACLE, argnums=(0, 1))(lp, x, 2.0, 0.3)
    np.testing.assert_allclose(gf["w"], gr["w"], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(gf["b"], gr["b"], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(gxf, gxr, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("blocks", [None, (32, 128, 64), (16, 128, 256)])
def test_tuned_blocks_norm_grad_matches_oracle(blocks, key):
    """Same sweep through the norm-epilogue vjp — the whole-row
    residency path must stay grad-exact under tuned blocks too."""
    M, K, N = 90, 333, 257
    fused = _normed_loss(
        lambda x, w, b: ff_dense_norm_vjp(x, w, b, True, blocks))
    kx, kw, kv = jax.random.split(key, 3)
    x = jax.random.normal(kx, (M, K), jnp.float32)
    lp = {"w": jax.random.normal(kw, (K, N), jnp.float32) * K ** -0.5,
          "b": jnp.full((N,), 0.1, jnp.float32)}
    v = jax.random.normal(kv, (N,), jnp.float32)
    yn, g = ff_dense_norm_vjp(x, lp["w"], lp["b"], True, blocks)
    yr, gr_ = ref.ff_dense_norm_ref(x, lp["w"], lp["b"])
    np.testing.assert_allclose(yn, yr, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(g, gr_, rtol=1e-5, atol=1e-5)
    gf, gxf = jax.grad(fused, argnums=(0, 1))(lp, x, v)
    gr, gxr = jax.grad(_NORM_ORACLE, argnums=(0, 1))(lp, x, v)
    np.testing.assert_allclose(gf["w"], gr["w"], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(gf["b"], gr["b"], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(gxf, gxr, rtol=1e-4, atol=1e-6)


def test_fwd_norm_ref_is_bit_identical_to_composed_norm(key):
    """The ref path of the fused hand-off must reproduce the historical
    ``_norm(layer_apply(...))`` weight-stream bit-for-bit — that is what
    keeps every pre-existing sequential/executor oracle unchanged."""
    x = jax.random.normal(key, (100, 333), jnp.float32)
    lp = {"w": jax.random.normal(key, (333, 257), jnp.float32) * 0.05,
          "b": jnp.full((257,), 0.1, jnp.float32)}
    a = ff_mlp.fwd_norm(lp, x, impl="ref")
    old = ff_mlp._norm(ff_mlp.layer_apply(lp, x))
    assert bool(jnp.array_equal(a, old))


def test_norm_epilogue_dead_rows_no_nan():
    """An all-ReLU-dead row (g = 0) must normalize to zeros, not NaN —
    in the FORWARD and in the GRADIENT. The backward's dg' is 0/0 = NaN
    on such rows and is discarded only because the bwd kernel masks dy
    with jnp.where(y > 0, ..., 0); this pins that invariant (jax.grad
    of the composed oracle NaNs here — the fused path must not)."""
    x = jnp.zeros((4, 64), jnp.float32)
    w = jnp.zeros((64, 128), jnp.float32)
    b = jnp.full((128,), -1.0, jnp.float32)     # relu kills every unit
    yn, g = ff_dense_norm_vjp(x, w, b, True)
    assert bool(jnp.all(yn == 0.0)) and bool(jnp.all(g == 0.0))
    assert NORM_EPS > 0.0
    v = jnp.ones((128,), jnp.float32)
    gw, gx = jax.grad(_NORM_FUSED, argnums=(0, 1))(
        {"w": w, "b": b}, x, v)
    for leaf in (gw["w"], gw["b"], gx):
        assert bool(jnp.all(jnp.isfinite(leaf))), "NaN leaked through " \
            "the dead-row backward (dy must be masked via jnp.where)"


def _run_chapter(impl, key, K, N, n, batch, epochs):
    kx, kn, kw, kt = jax.random.split(key, 4)
    # fresh buffers per run: the chapter trainer donates lp/opt
    x_pos = jax.random.normal(kx, (n, K), jnp.float32)
    x_neg = jax.random.normal(kn, (n, K), jnp.float32)
    lp = {"w": jax.random.normal(kw, (K, N), jnp.float32) * K ** -0.5,
          "b": jnp.zeros((N,), jnp.float32)}
    opt = optim.adam_init(lp)
    lrs = jnp.full((epochs,), 0.01, jnp.float32)
    stream = []
    for chapter in range(2):
        lp, opt = ff_mlp.train_layer_chapter(
            lp, opt, x_pos, x_neg, lrs, jax.random.fold_in(kt, chapter),
            batch=batch, epochs=epochs, theta=2.0, peer_w=0.0, impl=impl)
        stream.append(jax.tree.map(np.asarray, lp))
    return stream


def test_train_layer_chapter_ref_vs_pallas_weight_stream(key):
    """kernel_impl=ref and kernel_impl=pallas (interpret) must produce
    the same weight stream to <= 1e-4 max-abs across chapters."""
    K, N = 333, 257          # deliberately not tile-aligned
    ref_stream = _run_chapter("ref", key, K, N, n=256, batch=64, epochs=2)
    pal_stream = _run_chapter("pallas", key, K, N, n=256, batch=64,
                              epochs=2)
    for lr_, lp_ in zip(ref_stream, pal_stream):
        for name in ("w", "b"):
            max_err = float(np.abs(lr_[name] - lp_[name]).max())
            assert max_err <= 1e-4, (name, max_err)


def _run_perf_opt_chapter(impl, key, K, N, n, batch, epochs):
    kx, kw, kh, kt = jax.random.split(key, 4)
    x = jax.random.normal(kx, (n, K), jnp.float32)
    y = jax.random.randint(kt, (n,), 0, 10)
    # fresh buffers per run: the trainer donates everything
    lp = {"w": jax.random.normal(kw, (K, N), jnp.float32) * K ** -0.5,
          "b": jnp.zeros((N,), jnp.float32)}
    head = {"w": jax.random.normal(kh, (N, 10), jnp.float32) * N ** -0.5,
            "b": jnp.zeros((10,), jnp.float32)}
    opt, opt_h = optim.adam_init(lp), optim.adam_init(head)
    lrs = jnp.full((epochs,), 0.01, jnp.float32)
    stream = []
    for chapter in range(2):
        lp, head, opt, opt_h = ff_mlp.train_layer_chapter_perf_opt(
            lp, head, opt, opt_h, x, y, lrs,
            jax.random.fold_in(kt, chapter), batch=batch, epochs=epochs,
            impl=impl)
        stream.append(jax.tree.map(np.asarray, (lp, head)))
    return stream


def test_perf_opt_chapter_ref_vs_pallas_weight_stream(key):
    """The §4.4 trainer drives the normed custom_vjp inside its hot
    loop — its ref and pallas weight streams must agree on a
    non-tile-aligned layer."""
    ref_stream = _run_perf_opt_chapter("ref", key, 333, 257, n=256,
                                       batch=64, epochs=2)
    pal_stream = _run_perf_opt_chapter("pallas", key, 333, 257, n=256,
                                       batch=64, epochs=2)
    for (lr_, hr_), (lp_, hp_) in zip(ref_stream, pal_stream):
        for a, b in ((lr_, lp_), (hr_, hp_)):
            for name in ("w", "b"):
                max_err = float(np.abs(a[name] - b[name]).max())
                assert max_err <= 1e-4, (name, max_err)
