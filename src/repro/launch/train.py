"""Training launcher.

Two modes:
  * ``--paper-mlp``: the paper's own experiment — FF MLP on the synthetic
    image task with a PFF schedule.
  * ``--arch <id>``: FF-train a (reduced, unless --full) assigned
    architecture on the synthetic LM corpus. On this CPU container the
    reduced configs run for real; the full configs are exercised by
    ``dryrun.py``.

``--arch`` with ``--chapters N`` switches from the joint FF step to the
paper's CHAPTER schedule on the real-text BPE source (``data.
text_source``) — sequentially, or on the real executor across
``--nodes`` devices (``--backend executor``).

Examples:
  PYTHONPATH=src python -m repro.launch.train --paper-mlp \
      --neg-mode random --classifier goodness --epochs 60 --splits 10
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --steps 50 --batch 8 --seq 128
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
      --chapters 4 --backend executor --schedule single_layer --nodes 4
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import api, checkpoint, data as data_lib, optim
from repro.configs import get_config
from repro.configs.ff_mlp import FFMLPConfig
from repro.core import pff_dag, train as train_lib
from repro.kernels import ops
from repro.launch import compile_cache
from repro.models import transformer
from repro.obs import export as obs_export, trace as obs_trace


def run_paper_mlp(args):
    task = (data_lib.cifar_like if args.cifar else data_lib.mnist_like)(
        seed=args.seed, n_train=args.n_train, n_test=args.n_test)
    sizes = (task.dim,) + tuple(args.hidden for _ in range(args.layers))
    cfg = FFMLPConfig(
        layer_sizes=sizes, epochs=args.epochs, splits=args.splits,
        neg_mode=args.neg_mode or FFMLPConfig.neg_mode,
        classifier=args.classifier,
        goodness_fn=args.goodness_fn, batch_size=args.batch,
        kernel_impl=args.kernel_impl, seed=args.seed)
    backend = args.backend
    if backend == "sequential" and args.schedule == "federated":
        backend = "federated"          # pre-facade CLI spelling
    t0 = time.time()
    res = api.fit(cfg, task, backend=backend, schedule=args.schedule,
                  num_nodes=args.nodes, probe_every=args.probe,
                  verbose=True,
                  trace=getattr(args, "tracer", obs_trace.NOOP))
    wall = time.time() - t0
    acc = f"test acc {res.test_acc:.4f}" if res.test_acc is not None else ""
    print(f"\n[{backend}] {acc}  wall {wall:.1f}s")
    if res.makespan is not None:
        speed = (f" speedup={res.speedup:5.2f}x "
                 f"util={res.utilization:.2f}"
                 if res.speedup is not None else "")
        print(f"  {res.schedule} N={res.num_nodes}: "
              f"makespan={res.makespan:8.2f}s{speed}")
    if res.records:
        for sched, n in (("sequential", 1), ("single_layer", args.nodes),
                         ("all_layers", args.nodes)):
            sim = api.simulate(res, sched, n)
            print(f"  {sched:13s} N={n}: time={sim.makespan:8.1f}s "
                  f"speedup={sim.speedup:5.2f}x "
                  f"util={sim.utilization:.2f}  (simulated)")
    return res


def run_lm_chapters(args, cfg):
    """LM chapter schedule on real text (``--chapters N``): per-block
    train tasks + a per-chapter head task, sequentially
    (``--backend sequential``) or on the real executor across
    ``--nodes`` devices (``--backend executor --schedule ...``) —
    the ``api.fit`` invocation the README documents."""
    tracer = getattr(args, "tracer", obs_trace.NOOP)
    source = data_lib.text_source(vocab=cfg.vocab, seq_len=args.seq,
                                  seed=args.seed)
    t0 = time.time()
    res = api.fit(cfg, source, backend=args.backend,
                  schedule=args.schedule, num_nodes=args.nodes,
                  chapters=args.chapters,
                  steps_per_chapter=args.steps_per_chapter,
                  batch=args.batch, seq=args.seq, lr=args.lr,
                  head_lr=args.head_lr,
                  trace=tracer if tracer.enabled else None)
    wall = time.time() - t0
    print(f"\n[{args.backend}] {res.schedule} N={res.num_nodes}: "
          f"chapters={args.chapters} eval_ce={res.eval_ce:.4f} "
          f"makespan={res.makespan:.2f}s wall={wall:.1f}s")
    if args.ckpt:
        checkpoint.save(args.ckpt, res.params,
                        step=args.chapters * args.steps_per_chapter,
                        tracer=tracer)
        print("saved", args.ckpt)
    return res.params


def run_lm(args):
    tracer = getattr(args, "tracer", obs_trace.NOOP)
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    if args.neg_mode:
        cfg = dataclasses.replace(
            cfg, ff=dataclasses.replace(cfg.ff, neg_mode=args.neg_mode))
    if args.chapters:
        return run_lm_chapters(args, cfg)
    key = jax.random.PRNGKey(args.seed)
    params = transformer.init(key, cfg)
    opt = optim.adam_init(params)
    make = (train_lib.make_bp_train_step if args.baseline
            else train_lib.make_ff_train_step)
    step_fn = jax.jit(make(cfg, lr=args.lr))

    aux = None
    if cfg.enc_dec:
        aux = jax.random.normal(key, (args.batch, cfg.enc_seq,
                                      cfg.d_model), cfg.dtype)
    elif cfg.vision_tokens:
        aux = jax.random.normal(key, (args.batch, cfg.vision_tokens,
                                      cfg.d_model), cfg.dtype)

    t0 = time.time()
    with tracer.span("train:lm", arch=args.arch, steps=args.steps):
        for i, tokens in enumerate(data_lib.lm_batches(
                cfg.vocab, args.batch, args.seq, args.steps, args.seed)):
            batch = {"tokens": jnp.asarray(tokens)}
            if aux is not None:
                batch["aux"] = aux
            params, opt, metrics = step_fn(params, opt, batch, i + 1)
            if (i + 1) % args.log_every == 0:
                m = {k: round(float(v), 4) for k, v in metrics.items()}
                print(f"step {i + 1}: {m}  ({time.time() - t0:.1f}s)")
    if args.ckpt:
        checkpoint.save(args.ckpt, params, step=args.steps,
                        tracer=tracer)
        print("saved", args.ckpt)
    return params


def main():
    compile_cache.use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper-mlp", action="store_true")
    ap.add_argument("--cifar", action="store_true")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--baseline", action="store_true",
                    help="backprop baseline instead of FF")
    # choices sourced from the live registries / dispatch tables, so
    # --help stays truthful when strategies are (un)registered
    ap.add_argument("--backend", default="sequential",
                    choices=list(api.BACKENDS),
                    help="api.fit backend (--paper-mlp): sequential "
                         "trainer, event simulator, real multi-device "
                         "executor, federated shards, or pod pipeline")
    ap.add_argument("--schedule", default="all_layers",
                    choices=list(pff_dag.SCHEDULES))
    ap.add_argument("--neg-mode", default=None,
                    choices=[None] + list(api.negatives.names()))
    ap.add_argument("--classifier", default="goodness",
                    choices=list(api.classifier.names()))
    ap.add_argument("--goodness-fn", default="sumsq",
                    choices=list(api.goodness.names()))
    ap.add_argument("--kernel-impl", default="auto",
                    choices=list(ops.FF_DENSE_IMPLS),
                    help="ops.ff_dense impl (choices live from the "
                         "kernel registry): auto = the tuning table's "
                         "measured winner per shape when populated "
                         "(make tune-smoke / REPRO_TUNE_TABLE), else "
                         "the platform default")
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=500)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=60)
    ap.add_argument("--splits", type=int, default=10)
    ap.add_argument("--n-train", type=int, default=4032)
    ap.add_argument("--n-test", type=int, default=1000)
    ap.add_argument("--probe", type=int, default=0)
    ap.add_argument("--chapters", type=int, default=0,
                    help="--arch mode: run the LM CHAPTER schedule for "
                         "this many chapters on the real-text BPE "
                         "source (0 = the joint FF step on the "
                         "synthetic corpus); combine with --backend "
                         "sequential|executor and --schedule/--nodes")
    ap.add_argument("--steps-per-chapter", type=int, default=8,
                    help="per-task step budget of the chapter schedule")
    ap.add_argument("--head-lr", type=float, default=None,
                    help="chapter-head learning rate (default: --lr)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record an execution trace (repro.obs) and "
                         "export it here after the run")
    ap.add_argument("--trace-format", default="chrome",
                    choices=list(obs_export.names()),
                    help="trace exporter (choices live from the "
                         "repro.obs exporter registry); chrome loads "
                         "in Perfetto / chrome://tracing")
    args = ap.parse_args()
    args.tracer = (obs_trace.Tracer(meta={"launcher": "train"})
                   if args.trace else obs_trace.NOOP)
    if args.paper_mlp:
        run_paper_mlp(args)
    elif args.arch:
        run_lm(args)
    else:
        ap.error("need --paper-mlp or --arch")
    if args.tracer.enabled:
        obs_export.export(args.tracer, args.trace,
                          format=args.trace_format)
        print(f"trace: {args.tracer.span_count()} spans -> {args.trace} "
              f"({args.trace_format})")


if __name__ == "__main__":
    main()
