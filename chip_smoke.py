"""Bring-up smoke: the paper's FF-MLP on one TPU chip, end to end.

Drives the main path once through the entry points a user calls, at the
paper's full widths (``PAPER_MLP``: 784-2000-2000-2000-2000, batch 64,
``kernel_impl="auto"``), with random weights made from the config seed
and the seeded MNIST stand-in (``data.mnist_like``):

  device    the first JAX device must be a TPU. There is no CPU fallback.
  kernel    one paper-width layer chapter (``ff_mlp.train_layer_chapter``
            through ``ops.ff_dense(impl="auto")``) is compiled; "auto"
            must resolve to the Pallas kernel, compiled and not
            interpreted, and the program must hold a ``tpu_custom_call``.
  numerics  on one (128, 784) x (784, 2000) layer the fused kernel's
            value and ``jax.grad`` agree with the ``ref`` oracle, run at
            "highest" matmul precision, within 1e-4 (scale-normalized),
            with the norm epilogue off and on.
  train     ``api.fit(..., backend="sequential")`` of ``PAPER_MLP`` cut
            to a few chapters: weights and per-layer FF loss finite,
            every layer moved, test accuracy at or above ``ACC_BAR``.

``--four-chips`` runs only the multi-chip path: the executor under the
all_layers and single_layer schedules on 4 chips, each gated bit-equal
against one sequential run and checked to have trained its layers on 4
distinct devices.

    python chip_smoke.py [--four-chips]

Facts of the run go to stdout; its last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``. Any failure
raises, so the script exits non-zero without that line. No tuning table
steers it, and it keeps its compile cache where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro import api, data as data_lib, optim
from repro.configs.ff_mlp import PAPER_MLP
from repro.core import ff, ff_mlp, pff_exec
from repro.kernels import autotune, ops, registry
from repro.launch import compile_cache
from repro.obs import trace as obs_trace

ERR_BUDGET = 1e-4
# PAPER_MLP at full widths, cut from 100 chapters x 1 mini-epoch to 4
# chapters x 15 mini-epochs
SMOKE_CFG = dataclasses.replace(PAPER_MLP, epochs=60, splits=4)
N_TRAIN, N_TEST = 6000, 1000
# SMOKE_CFG with kernel_impl="ref" reaches 0.963 test accuracy on the CPU
# (0.691, 0.934, 0.959, 0.963 after chapters 1-4); the bar leaves 0.05
# for the fused kernel's different float summation order
ACC_BAR = 0.91

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(msg):
    print(msg, flush=True)


class SmokeFailure(RuntimeError):
    """A gate of the smoke failed."""


def require(ok, msg):
    """A gate: raise unless ``ok`` (unlike ``assert``, kept under -O)."""
    if not ok:
        raise SmokeFailure(msg)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, as JAX's
    monitoring events report them (a cache hit's retrieval time counts
    as its compile time)."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def _event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.count, self.cache_hits


def check_kernel(meter, *, n=N_TRAIN, width=SMOKE_CFG.layer_sizes[1],
                 batch=SMOKE_CFG.batch_size,
                 epochs=SMOKE_CFG.epochs // SMOKE_CFG.splits):
    """Compile one layer chapter through ``impl="auto"`` and prove the
    compiled Pallas kernel is in it."""
    platform = ops._platform()
    rows = 2 * batch                          # stacked [pos; neg] batch
    entry = autotune.lookup("ff_dense", rows, width, width, jnp.float32,
                            platform)
    require(entry is None, f"a tuning table steered impl='auto': {entry}")
    impl = registry.ff_dense.resolve(platform).name
    interpret = ops._interpret()
    log(f"kernel: impl='auto' on {platform!r} resolves to {impl!r}, "
        f"interpret={interpret}")
    require(impl == "pallas" and not interpret,
            f"impl='auto' resolved to {impl!r} with interpret={interpret}")

    f32 = jnp.float32
    lp = {"w": jax.ShapeDtypeStruct((width, width), f32),
          "b": jax.ShapeDtypeStruct((width,), f32)}
    opt = jax.eval_shape(optim.adam_init, lp)
    x = jax.ShapeDtypeStruct((n, width), f32)
    s0 = meter.seconds
    t0 = time.perf_counter()
    compiled = ff_mlp.train_layer_chapter.lower(
        lp, opt, x, x, jax.ShapeDtypeStruct((epochs,), f32),
        jax.ShapeDtypeStruct((2,), jnp.uint32), batch=batch, epochs=epochs,
        theta=SMOKE_CFG.theta, impl="auto").compile()
    wall = time.perf_counter() - t0
    calls = compiled.as_text().count("tpu_custom_call")
    log(f"kernel: train_layer_chapter ({width}->{width}, n={n}, batch "
        f"{batch}) lowered+compiled in {wall:.3f}s (backend compile "
        f"{meter.seconds - s0:.3f}s); tpu_custom_call x{calls}")
    # the fused forward and the fused backward kernel
    require(calls >= 2, "no compiled ff_dense kernel in the layer step")


def _scale_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-9))


def check_numerics(*, M=128, K=784, N=2000, seed=0):
    """Fused kernel vs the jnp oracle: value and grad, norm off and on."""
    kx, kw, kb, ky, kg = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(kx, (M, K), jnp.float32)
    w = jax.random.normal(kw, (K, N), jnp.float32) * K ** -0.5
    b = jax.random.normal(kb, (N,), jnp.float32) * 0.1
    cy = jax.random.normal(ky, (M, N), jnp.float32) * 0.01
    cg = jax.random.normal(kg, (M,), jnp.float32) * 0.01

    def value_and_grads(impl, norm):
        def loss(x, w, b):
            y, g = ops.ff_dense(x, w, b, impl=impl, norm=norm)
            return jnp.vdot(y, cy) + jnp.vdot(g, cg), (y, g)
        (_, yg), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(x, w, b)
        return yg, grads

    worst = 0.0
    for norm in (False, True):
        out = value_and_grads("pallas", norm)
        with jax.default_matmul_precision("highest"):
            out_r = value_and_grads("ref", norm)
        errs = {name: _scale_err(a, r) for name, a, r in zip(
            ("y", "g", "dx", "dw", "db"), jax.tree.leaves(out),
            jax.tree.leaves(out_r))}
        err = max(errs["y"], errs["g"])
        grad_err = max(errs["dx"], errs["dw"], errs["db"])
        log(f"numerics: ff_dense ({M}x{K})x({K}x{N}) norm={norm}: "
            f"value err {err:.3e}, grad err {grad_err:.3e} "
            f"(budget {ERR_BUDGET:.0e}; "
            + ", ".join(f"{k} {v:.3e}" for k, v in errs.items()) + ")")
        worst = max(worst, err, grad_err)
    require(worst <= ERR_BUDGET,
            f"fused ff_dense misses the {ERR_BUDGET:.0e} oracle budget on "
            f"the chip: worst scale-normalized error {worst:.3e}")


def _ff_losses(params, task, cfg, n=256):
    """Per-layer FF loss on a fixed [pos; neg] batch of training data."""
    x = jnp.asarray(task.x_train[:n])
    y = jnp.asarray(task.y_train[:n])
    wrong = ff.random_wrong_labels(jax.random.PRNGKey(1), y,
                                   cfg.num_classes)
    hp = ff_mlp._norm(ff.overlay_label(x, y, cfg.num_classes))
    hn = ff_mlp._norm(ff.overlay_label(x, wrong, cfg.num_classes))
    impl = ff_mlp.kernel_impl(cfg)
    losses = []
    for lp in params["layers"]:
        losses.append(float(ff_mlp._ff_layer_loss(
            lp, jnp.concatenate([hp, hn]), cfg.theta, cfg.peer_w, impl)))
        hp = ff_mlp.fwd_norm(lp, hp, impl)
        hn = ff_mlp.fwd_norm(lp, hn, impl)
    return losses


def train(meter, cfg=SMOKE_CFG, task=None, acc_bar=ACC_BAR):
    """The sequential fit at full widths, gated."""
    task = task or data_lib.mnist_like(seed=0, n_train=N_TRAIN,
                                       n_test=N_TEST)
    init = ff_mlp.init(jax.random.PRNGKey(cfg.seed), cfg)
    loss0 = _ff_losses(init, task, cfg)
    s0, c0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    res = api.fit(cfg, task, backend="sequential")
    wall = time.perf_counter() - t0
    s1, c1, h1 = meter.snapshot()
    per_chapter = collections.defaultdict(float)
    for r in res.records:
        per_chapter[r.chapter] += r.duration
    log(f"train: sequential fit of {list(cfg.layer_sizes)} batch "
        f"{cfg.batch_size}, {cfg.splits} chapters x {cfg.epochs // cfg.splits}"
        f" mini-epochs, n_train {len(task.x_train)}: {wall:.3f}s wall, "
        f"backend compile {s1 - s0:.3f}s over {c1 - c0} programs "
        f"({h1 - h0} persistent-cache hits)")
    log("train: per-chapter wall s (tasks blocked until ready): "
        + " ".join(f"{per_chapter[c]:.4f}" for c in sorted(per_chapter)))
    loss1 = _ff_losses(res.params, task, cfg)
    log("train: per-layer FF loss init -> trained: "
        + " ".join(f"{a:.4f}->{b:.4f}" for a, b in zip(loss0, loss1)))
    log(f"train: test accuracy {res.test_acc:.4f} (bar {acc_bar:.2f}), "
        f"train accuracy {res.train_acc:.4f}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"train: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")

    require(all(np.isfinite(v) for v in loss1), f"non-finite loss {loss1}")
    for k, (lp, lp0) in enumerate(zip(res.params["layers"],
                                      init["layers"])):
        for name in ("w", "b"):
            require(bool(jnp.all(jnp.isfinite(lp[name]))),
                    f"layer {k} {name} not finite")
        require(not bool(jnp.array_equal(lp["w"], lp0["w"])),
                f"layer {k} weights never moved")
    require(res.test_acc >= acc_bar,
            f"test accuracy {res.test_acc:.4f} below the bar {acc_bar:.2f}")


def four_chips(cfg=SMOKE_CFG, task=None, nodes=4):
    """Executor on ``nodes`` devices vs one sequential run: bit-equal
    weight streams, node work on distinct devices."""
    devices = jax.devices()[:nodes]
    require(len({d.id for d in devices}) == nodes,
            f"needs {nodes} devices, found {len(jax.devices())}")
    task = task or data_lib.mnist_like(seed=0, n_train=N_TRAIN,
                                       n_test=N_TEST)
    t0 = time.perf_counter()
    ref = api.fit(cfg, task, backend="sequential")
    log(f"four_chips: sequential reference {time.perf_counter() - t0:.3f}s"
        f", test accuracy {ref.test_acc:.4f}")
    for schedule in ("all_layers", "single_layer"):
        tracer = obs_trace.Tracer(block_tasks=False)
        t0 = time.perf_counter()
        res = api.fit(cfg, task, backend="executor", schedule=schedule,
                      num_nodes=nodes, trace=tracer)
        wall = time.perf_counter() - t0
        placed = sorted({s.attrs["device"] for s in tracer.snapshot()
                         if s.name == "task:train"})
        same = pff_exec.params_bit_equal(ref.params, res.params)
        log(f"four_chips: {schedule} on {nodes} nodes: {wall:.3f}s, "
            f"train tasks on devices {placed}, test accuracy "
            f"{res.test_acc:.4f}, weights bit-equal to sequential: {same}")
        require(len(placed) == nodes,
                f"{schedule}: train tasks ran on devices {placed}, not on "
                f"{nodes} distinct devices")
        require(same,
                f"{schedule}: the executor's weights differ from the "
                f"sequential run's (impl {ff_mlp.kernel_impl(cfg)!r} -> "
                f"Pallas on the chip)")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip executor path and its "
                         "sequential comparison")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_dir = compile_cache.use_compile_cache()
    with tempfile.TemporaryDirectory() as td:
        # no tuning table: impl="auto" means the registry's default
        os.environ["REPRO_TUNE_TABLE"] = os.path.join(td, "none.json")
        autotune.invalidate_cache()
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                     f"{dev.platform!r} ({dev.device_kind}); there is no "
                     f"CPU fallback")
        n_dev = len(jax.devices())
        log(f"device: {dev.platform} {dev.device_kind!r} x{n_dev}; "
            f"compile cache {cache_dir}")
        meter = CompileMeter()
        t0 = time.perf_counter()
        if args.four_chips:
            four_chips()
        else:
            check_kernel(meter)
            check_numerics()
            train(meter)
        log(f"total: {time.perf_counter() - t0:.3f}s, backend compile "
            f"{meter.seconds:.3f}s over {meter.count} programs, "
            f"{meter.cache_hits} persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}), flush=True)


if __name__ == "__main__":
    main()
