"""Ahead-of-time compiles of the FF-MLP hot path for a described TPU v5e.

Interpret-mode Pallas (what every other test runs) accepts block shapes
that the TPU lowering refuses, so these tests hand the paper-width
shapes of the ``ff_dense`` kernels, their custom_vjp gradients and one
``train_layer_chapter`` step to the TPU compiler for one chip of a
described ``v5e:2x2`` topology. Nothing runs: a pass means Mosaic and
XLA accepted the program, and each compiled text must hold the kernel
as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers import
every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import optim
from repro.core import ff_mlp
from repro.kernels import ff_dense as ff_dense_mod, ops
from repro.kernels.ff_dense_vjp import (
    ff_dense_bwd, ff_dense_norm_vjp, ff_dense_vjp,
)

# the paper's layer widths: a stacked [pos; neg] batch of 2 x 64 rows
# through the 784 -> 2000 first layer and a 2000 -> 2000 hidden layer
M = 128
LAYERS = [(784, 2000), (2000, 2000)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # described-chip compiles are written to a persistent cache but can
    # never be read back without the chip; keep them out of it
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("K,N", LAYERS)
@pytest.mark.parametrize("norm", [False, True])
def test_ff_dense_forward_compiles(one_chip, K, N, norm):
    def fwd(x, w, b):
        return ff_dense_mod.ff_dense(x, w, b, interpret=False, norm=norm)

    text = _compiled_text(fwd, _spec(one_chip, (M, K)),
                          _spec(one_chip, (K, N)), _spec(one_chip, (N,)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("K,N", LAYERS)
def test_ff_dense_backward_compiles(one_chip, K, N):
    def bwd(x, w, y, dy, dg):
        return ff_dense_bwd(x, w, y, dy, dg, interpret=False)

    text = _compiled_text(bwd, _spec(one_chip, (M, K)),
                          _spec(one_chip, (K, N)), _spec(one_chip, (M, N)),
                          _spec(one_chip, (M, N)), _spec(one_chip, (M,)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("K,N", LAYERS)
@pytest.mark.parametrize("fused", [ff_dense_vjp, ff_dense_norm_vjp],
                         ids=["plain", "norm"])
def test_ff_dense_grad_compiles(one_chip, K, N, fused):
    def loss(w, x, b):
        y, g = fused(x, w, b, False, None)
        return jnp.sum(y) + jnp.sum(g)

    text = _compiled_text(jax.grad(loss), _spec(one_chip, (K, N)),
                          _spec(one_chip, (M, K)), _spec(one_chip, (N,)))
    # the fused forward AND the fused backward kernel
    assert text.count("tpu_custom_call") >= 2


def test_train_layer_chapter_compiles(one_chip, monkeypatch):
    """One paper-width chapter of a 2000 -> 2000 layer (n=6000, batch
    64) through ``impl="auto"``, resolved as it is on a TPU."""
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")
    assert not ops._interpret()
    K = N = 2000
    n, epochs = 6000, 1
    lp = {"w": _spec(one_chip, (K, N)), "b": _spec(one_chip, (N,))}
    opt = jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype),
                       jax.eval_shape(optim.adam_init, lp))
    compiled = ff_mlp.train_layer_chapter.lower(
        lp, opt, _spec(one_chip, (n, K)), _spec(one_chip, (n, K)),
        _spec(one_chip, (epochs,)), _spec(one_chip, (2,), jnp.uint32),
        batch=64, epochs=epochs, theta=2.0, impl="auto").compile()
    assert "tpu_custom_call" in compiled.as_text()
