"""Fused FF layer kernel: y = relu(x @ w + b), g = sum(y^2, axis=-1).

The Forward-Forward hot loop evaluates a dense layer AND its goodness for
both the positive and negative batch every step. Fusing the goodness
reduction into the matmul epilogue saves one full HBM round-trip of the
(M, N) activations — on TPU the (bm, bn) tile is reduced to a (bm, 1)
partial in VMEM right after the MXU matmul, while the tile is still hot.

Grid: (M/bm, N/bn), N innermost so the goodness partials for a row-block
accumulate across the j steps in the same VMEM scratch-free output block
(revisited blocks are legal because the TPU grid is executed
sequentially minor-to-major).

``norm=True`` additionally fuses Hinton's inter-layer length
normalization into the kernel epilogue: the goodness output IS the
squared norm, so once a row-block's g is fully accumulated the kernel
divides the activations by ``sqrt(g) + NORM_EPS`` in place. To stay
inside Pallas TPU's documented residency guarantee (an output block is
only preserved across CONSECUTIVE grid steps — the same rule the g
accumulation relies on; a revisit after eviction is undefined), the
normed kernel widens the y output block to the whole row (bm, N) with
a j-constant index map: the row block stays resident in VMEM for the
entire inner j sweep (~1 MB at the paper's N=2000), each step stores
its (bm, bn) column slice, and the j == nj-1 step normalizes the
resident block before it is written out. The epilogue therefore costs
ZERO extra HBM traffic — y goes out exactly once, already normalized,
and the separate norm reduction, sqrt, divide (and g's round-trip)
all disappear as XLA dispatches.

Tile defaults are MXU-aligned (128x128); K is streamed whole per tile —
for the paper's [784, 2000] layers x(bm, K) + w(K, bn) comfortably fit
VMEM (784*128*4 + 784*128*4 ~= 0.8 MB).

Every block is 2-D: the bias enters as a (1, bn) block of b.reshape(1, N)
and the goodness leaves as a (bm, 1) block of an (M, 1) output. Mosaic
(the TPU lowering) refuses 1-D (bn,) / (bm,) blocks of these operands:
the layout XLA gives a 1-D f32 array does not match the one the kernel
expects. The wrapper reshapes, so callers still see b (N,) and g (M,).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# Hinton's inter-layer normalization epsilon — the ONE constant shared
# by the fused epilogue, the jnp oracle (ref.ff_dense_norm_ref) and
# ff_mlp._norm, so the kernel and XLA paths divide by the same number.
NORM_EPS = 1e-8

# Per-grid-step VMEM budget the autotuner's candidate filter enforces:
# half of a v5e core's ~16 MB, leaving headroom for Pallas's automatic
# input double-buffering. A candidate (bm, bn) whose resident blocks
# exceed this is never benchmarked — in particular the norm=True path,
# whose j-constant index map keeps the whole (bm, N) y row block
# resident across the inner sweep (the documented consecutive-revisit
# guarantee; an evicted block would make the epilogue divide undefined).
VMEM_BUDGET_BYTES = 8 * 2 ** 20

# Precision of every in-kernel product (this kernel's and the fused
# backward's). Mosaic's default f32 contraction on a TPU is not full f32:
# on a TPU v5e the default-precision kernel missed the f32 oracle by
# 2.4e-3 (value) and 8.7e-2 (grad), scale-normalized, and HIGHEST met it
# at 3e-7 with no slower a step at the paper's widths. The FF objective
# is defined in f32, so the kernels ask for it. Interpret mode on the CPU
# computes f32 products either way.
MATMUL_PRECISION = jax.lax.Precision.HIGHEST


def vmem_block_bytes(K, N, bm, bn, *, norm=False, dtype_bytes=4):
    """Resident VMEM bytes of one forward grid step for blocks (bm, bn).

    The single source of truth for the autotuner's candidate filter:
    x (bm, K) + w (K, bn) + the (1, bn) bias block (padded to 8
    sublanes) + the y output block + the (bm, 1) goodness accumulator
    (padded to 128 lanes). With ``norm=True`` the y block is the WHOLE
    (bm, Np) row (j-constant index map, see module docstring) — this is
    the VMEM row-residency invariant every tuned candidate must honor.
    """
    np_ = -(-N // bn) * bn if bn else N          # padded N
    y_cols = np_ if norm else bn
    return ((bm * K + K * bn + 8 * bn + bm * y_cols) * dtype_bytes
            + bm * 128 * 4)


def _tile_y_g(x_ref, w_ref, b_ref, g_ref, j):
    """The shared per-(i, j) compute: (bm, bn) activation tile plus the
    row-block goodness accumulation into the resident g block."""
    h = jnp.dot(x_ref[...], w_ref[...], precision=MATMUL_PRECISION,
                preferred_element_type=jnp.float32)
    h = h + b_ref[...]
    y = jnp.maximum(h, 0.0)
    g_part = jnp.sum(y * y, axis=1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        g_ref[...] = g_part

    @pl.when(j != 0)
    def _acc():
        g_ref[...] = g_ref[...] + g_part

    return y


def _kernel(x_ref, w_ref, b_ref, y_ref, g_ref):
    j = pl.program_id(1)
    y = _tile_y_g(x_ref, w_ref, b_ref, g_ref, j)
    y_ref[...] = y.astype(y_ref.dtype)


def _kernel_norm(x_ref, w_ref, b_ref, y_ref, g_ref, *, bn, nj):
    # y_ref is the whole (bm, N) row block, resident across the j sweep
    # (j-constant index map — the consecutive-revisit accumulation
    # guarantee, same as g_ref); each step fills its column slice.
    j = pl.program_id(1)
    y = _tile_y_g(x_ref, w_ref, b_ref, g_ref, j)
    y_ref[:, pl.ds(j * bn, bn)] = y.astype(y_ref.dtype)

    @pl.when(j == nj - 1)
    def _normalize():
        # g is now fully accumulated; divide the still-resident row
        # block in place before it is written out — the fused epilogue.
        yy = y_ref[...].astype(jnp.float32)
        scale = jnp.sqrt(g_ref[...]) + NORM_EPS
        y_ref[...] = (yy / scale).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret",
                                             "norm"))
def ff_dense(x, w, b, *, bm=128, bn=128, interpret=True, norm=False):
    """x: (M, K), w: (K, N), b: (N,) -> (y (M, N), goodness (M,) f32).

    norm=True: y is length-normalized in the kernel epilogue
    (``y / (sqrt(g) + NORM_EPS)``); g stays the RAW pre-norm goodness.
    """
    M, K = x.shape
    _, N = w.shape
    bm = min(bm, M)
    bn = min(bn, N)
    if M % bm or N % bn:          # pad to tile multiples
        Mp = -(-M // bm) * bm
        Np = -(-N // bn) * bn
        xp = jnp.pad(x, ((0, Mp - M), (0, 0)))
        wp = jnp.pad(w, ((0, 0), (0, Np - N)))
        bp = jnp.pad(b, (0, Np - N))
        # padded N columns are zero (w and b both padded with zeros), so
        # they contribute nothing to g — the in-kernel normalizer of the
        # real columns is exact.
        y, g = ff_dense(xp, wp, bp, bm=bm, bn=bn, interpret=interpret,
                        norm=norm)
        return y[:M, :N], g[:M]

    nj = N // bn
    grid = (M // bm, nj)
    in_specs = [
        pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
        pl.BlockSpec((K, bn), lambda i, j: (0, j)),
        pl.BlockSpec((1, bn), lambda i, j: (0, j)),
    ]
    if norm:
        kernel = functools.partial(_kernel_norm, bn=bn, nj=nj)
        # whole-row y block, resident across the inner j sweep
        y_spec = pl.BlockSpec((bm, N), lambda i, j: (i, 0))
    else:
        kernel = _kernel
        y_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    out_specs = [y_spec, pl.BlockSpec((bm, 1), lambda i, j: (i, 0))]
    y, g = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((M, N), x.dtype),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, w, b.reshape(1, N))
    return y, g[:, 0]
