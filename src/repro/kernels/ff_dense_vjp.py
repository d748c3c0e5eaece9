"""Trainable fused FF layer: custom_vjp around the ff_dense Pallas kernel.

Forward is the existing fused matmul -> ReLU -> goodness kernel
(``ff_dense.py``); this module adds the missing piece that makes it the
*training-time* engine rather than a benchmark curiosity: fused Pallas
backward kernels, so ``jax.grad`` of the FF objective runs entirely on
the fused path.

Math. With y = relu(x @ w + b) and g = sum(y^2, axis=-1), the cotangents
(dy_out, dg) of (y, g) combine into a single post-activation gradient

    dy = (dy_out + 2 * y * dg[:, None]) * 1[y > 0]

(1[y > 0] is the ReLU mask — y > 0 iff the pre-activation was > 0), and

    dw = x^T @ dy      db = sum_rows(dy)      dx = dy @ w^T.

The backward is two ``pallas_call``s, each rebuilding dy per block from
the y/dy_out/dg row blocks (cheap VPU work traded for the HBM traffic
of materializing the (M, N) dy):

* ``_dw_kernel`` (x, y, dy_out, dg) -> (dw, db). Grid (K/bk, M/bm) with
  M innermost: dw accumulates across the inner M steps into the same
  resident (bk, N) block; db accumulates on the kb == 0 passes. VMEM
  blocks: x (bm, bk), y and dy_out (bm, N), dg (bm,), dw (bk, N),
  db (N,). It never reads w.
* ``_dx_kernel`` (w, y, dy_out, dg) -> dx. Same grid; each step writes
  its own (bm, bk) dx block from the (bk, N) w block, which stays
  resident across the inner M steps. VMEM blocks: w (bk, N), y and
  dy_out (bm, N), dg (bm,), dx (bm, bk).

dx is a call of its own because no FF objective uses it: every FF loss
is layer-local and the layer's input is data, so the trainers
differentiate the parameters only. XLA then removes the dx call, and
the w pad that only it reads, as dead code, and a trainer's step runs
the dw/db products alone — half the backward's matmul work. A caller
that differentiates x keeps the dx call; nothing else selects it.

N is streamed whole per block (padded to a lane multiple) — for the
paper's 2000-wide layers a (128, 2048) f32 block is ~1 MB.
Non-tile-aligned shapes are zero-padded exactly like the forward kernel;
zero rows/cols of x/w/y/dy contribute zero to every product, so slicing
the outputs back is exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ff_dense import (
    MATMUL_PRECISION, NORM_EPS, ff_dense as _ff_dense_fwd,
)


def _dy(y_ref, dyo_ref, dg_ref):
    """The post-activation gradient of one (bm, N) row block."""
    y = y_ref[...].astype(jnp.float32)
    dy = dyo_ref[...].astype(jnp.float32) + 2.0 * y * dg_ref[...][:, None]
    return jnp.where(y > 0.0, dy, 0.0)


def _dw_kernel(x_ref, y_ref, dyo_ref, dg_ref, dw_ref, db_ref):
    kb = pl.program_id(0)
    i = pl.program_id(1)
    dy = _dy(y_ref, dyo_ref, dg_ref)                      # (bm, N)

    dw_part = jnp.dot(x_ref[...].astype(jnp.float32).T, dy,
                      precision=MATMUL_PRECISION,
                      preferred_element_type=jnp.float32)  # (bk, N)

    @pl.when(i == 0)
    def _init_dw():
        dw_ref[...] = dw_part.astype(dw_ref.dtype)

    @pl.when(i != 0)
    def _acc_dw():
        dw_ref[...] = dw_ref[...] + dw_part.astype(dw_ref.dtype)

    db_part = jnp.sum(dy, axis=0)

    @pl.when((kb == 0) & (i == 0))
    def _init_db():
        db_ref[...] = db_part

    @pl.when((kb == 0) & (i != 0))
    def _acc_db():
        db_ref[...] = db_ref[...] + db_part


def _dx_kernel(w_ref, y_ref, dyo_ref, dg_ref, dx_ref):
    dy = _dy(y_ref, dyo_ref, dg_ref)
    dx_ref[...] = jnp.dot(
        dy, w_ref[...].astype(jnp.float32).T, precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32).astype(dx_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def ff_dense_bwd(x, w, y, dy_out, dg, *, bm=128, bk=256, interpret=True):
    """Fused backward: (x, w, y, dL/dy, dL/dg) -> (dx, dw, db).

    dx comes from a separate kernel, so a caller that drops it (every
    FF trainer) compiles to the dw/db kernel alone."""
    M, K = x.shape
    N = w.shape[1]
    bm = min(bm, M)
    bk = min(bk, K)
    Mp = -(-M // bm) * bm
    Kp = -(-K // bk) * bk
    Np = -(-N // 128) * 128
    if Mp != M or Kp != K or Np != N:
        x = jnp.pad(x, ((0, Mp - M), (0, Kp - K)))
        y = jnp.pad(y, ((0, Mp - M), (0, Np - N)))
        dy_out = jnp.pad(dy_out, ((0, Mp - M), (0, Np - N)))
        dg = jnp.pad(dg, (0, Mp - M))

    grid = (Kp // bk, Mp // bm)          # M innermost: dw, w stay resident
    rows = [
        pl.BlockSpec((bm, Np), lambda kb, i: (i, 0)),    # y
        pl.BlockSpec((bm, Np), lambda kb, i: (i, 0)),    # dy_out
        pl.BlockSpec((bm,), lambda kb, i: (i,)),         # dg
    ]
    dw, db = pl.pallas_call(
        _dw_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda kb, i: (i, kb))] + rows,
        out_specs=[
            pl.BlockSpec((bk, Np), lambda kb, i: (kb, 0)),   # dw
            pl.BlockSpec((Np,), lambda kb, i: (0,)),         # db
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Kp, Np), w.dtype),
            jax.ShapeDtypeStruct((Np,), jnp.float32),
        ],
        interpret=interpret,
    )(x, y, dy_out, dg)
    # only the dx kernel reads w: where dx is dead, so is this pad
    w = jnp.pad(w, ((0, Kp - K), (0, Np - N)))
    dx = pl.pallas_call(
        _dx_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bk, Np), lambda kb, i: (kb, 0))] + rows,
        out_specs=pl.BlockSpec((bm, bk), lambda kb, i: (i, kb)),
        out_shape=jax.ShapeDtypeStruct((Mp, Kp), x.dtype),
        interpret=interpret,
    )(w, y, dy_out, dg)
    return dx[:M, :K], dw[:K, :N], db[:N]


def _split_blocks(blocks):
    """Tuned block shapes -> (forward kwargs, backward kwargs).

    ``blocks`` is None (kernel defaults) or an autotuner-shaped
    ``(bm, bn, bk)`` tuple with None holes meaning "default": bm/bn tile
    the forward grid, bm/bk the backward one (the backward streams N
    whole, so bn never reaches it; the forward streams K whole, so bk
    never reaches it — see each kernel's docstring).
    """
    if blocks is None:
        return {}, {}
    bm, bn, bk = blocks
    fwd = {k: v for k, v in (("bm", bm), ("bn", bn)) if v}
    bwd = {k: v for k, v in (("bm", bm), ("bk", bk)) if v}
    return fwd, bwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def ff_dense_vjp(x, w, b, interpret=True, blocks=None):
    """Differentiable fused FF layer. Returns (y (M, N), goodness (M,)).

    ``interpret`` and ``blocks`` must be passed positionally (custom_vjp
    nondiff args); use interpret=True everywhere except on a real TPU.
    ``blocks`` is an optional autotuned ``(bm, bn, bk)`` tuple (from
    ``kernels.autotune``) applied to BOTH the forward and the fused
    backward kernel; None means the MXU-aligned defaults.
    """
    fwd_kw, _ = _split_blocks(blocks)
    return _ff_dense_fwd(x, w, b, interpret=interpret, **fwd_kw)


def _ff_dense_vjp_fwd(x, w, b, interpret, blocks):
    fwd_kw, _ = _split_blocks(blocks)
    y, g = _ff_dense_fwd(x, w, b, interpret=interpret, **fwd_kw)
    return (y, g), (x, w, b, y)


def _ff_dense_vjp_bwd(interpret, blocks, res, cts):
    x, w, b, y = res
    dy_out, dg = cts
    _, bwd_kw = _split_blocks(blocks)
    dx, dw, db = ff_dense_bwd(x, w, y, dy_out, dg, interpret=interpret,
                              **bwd_kw)
    return dx, dw, db.astype(b.dtype)


ff_dense_vjp.defvjp(_ff_dense_vjp_fwd, _ff_dense_vjp_bwd)


# ---------------------------------------------------------------------------
# Normed variant: the kernel's fused inter-layer norm epilogue,
# differentiable. yn = y / (sqrt(g) + eps) with g = sum(y^2, -1).
#
# Backward math. Write s = sqrt(g), u = 1 / (s + eps), so yn = y * u and
# u depends on y only through g. For cotangents (dyn, dg_ct) the chain
# rule through the normalizer gives the POST-ReLU gradient
#
#     dy = dyn * u  +  (2 * dg_ct  -  (dyn . y) * u^2 / s) * y
#
# ((dyn . y) is the row dot product; the u^2/s term is d(1/(s+eps))/dg
# = -u^2 / (2s) times dg/dy = 2y). That is exactly the
# ``dy_out + 2 * y * dg`` form the existing fused backward kernel
# rebuilds per tile, so the normed backward delegates to the SAME
# ``ff_dense_bwd`` Pallas kernel with folded cotangents
#
#     dy_out' = dyn * u        dg' = dg_ct - (dyn . y) * u^2 / (2s)
#
# — only O(M) / O(M*N) element-wise prep runs outside the kernel, never
# an extra matmul. Raw y is rebuilt from the residuals as yn * (s + eps)
# (same sign as y, so the kernel's ReLU mask is unchanged). All-ReLU-dead
# rows (g = 0) get an EXACT zero gradient here: dg' is 0/0 = NaN for
# them, but the bwd kernel multiplies it by y = 0 and then applies the
# y > 0 mask via jnp.where, which discards the NaN. jax.grad of the
# composed oracle instead propagates NaN on such rows (d sqrt(g) at
# g = 0 is inf) — the fused path is the well-defined one, and the two
# only differ on rows where the oracle has no usable gradient at all.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def ff_dense_norm_vjp(x, w, b, interpret=True, blocks=None):
    """Differentiable fused FF layer WITH the in-kernel norm epilogue.
    Returns (yn (M, N) length-normalized, RAW goodness (M,)).

    ``interpret`` and ``blocks`` must be passed positionally (custom_vjp
    nondiff args); use interpret=True everywhere except on a real TPU.
    ``blocks`` as in ``ff_dense_vjp`` — every candidate the autotuner
    offers here already passed the VMEM row-residency filter
    (``ff_dense.vmem_block_bytes``), since norm=True keeps the whole
    (bm, N) row block resident across the inner sweep.
    """
    fwd_kw, _ = _split_blocks(blocks)
    return _ff_dense_fwd(x, w, b, interpret=interpret, norm=True,
                         **fwd_kw)


def _ff_dense_norm_vjp_fwd(x, w, b, interpret, blocks):
    fwd_kw, _ = _split_blocks(blocks)
    yn, g = _ff_dense_fwd(x, w, b, interpret=interpret, norm=True,
                          **fwd_kw)
    return (yn, g), (x, w, b, yn, g)


def _ff_dense_norm_vjp_bwd(interpret, blocks, res, cts):
    x, w, b, yn, g = res
    dyn, dg_ct = cts
    s = jnp.sqrt(g)
    u = 1.0 / (s + NORM_EPS)
    scale = s + NORM_EPS
    y = yn * scale[:, None]
    rowdot = jnp.sum(dyn * yn, axis=-1) * scale      # = dyn . y
    dg_eff = dg_ct - rowdot * u * u / (2.0 * s)
    dy_out_eff = dyn * u[:, None]
    _, bwd_kw = _split_blocks(blocks)
    dx, dw, db = ff_dense_bwd(x, w, y, dy_out_eff, dg_eff,
                              interpret=interpret, **bwd_kw)
    return dx, dw, db.astype(b.dtype)


ff_dense_norm_vjp.defvjp(_ff_dense_norm_vjp_fwd, _ff_dense_norm_vjp_bwd)
