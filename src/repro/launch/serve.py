"""Serving launcher — a thin CLI over the ``repro.api`` facade.

Default mode (``--mode ff``) runs the train-while-serve workload:
``api.serve`` trains the config on the executor while a continuous-
batching replica serves the configured traffic from live hot-swapped
weights, then prints the SLO block and the swap timeline.

  PYTHONPATH=src python -m repro.launch.serve --traffic zipf \
      --schedule all_layers --nodes 4

``--mode lm`` keeps the old transformer prefill+decode demo
(``lm_decode``):

  PYTHONPATH=src python -m repro.launch.serve --mode lm \
      --arch qwen2-0.5b --batch 4 --prompt-len 64 --gen 32

The module-level ``serve(cfg, ...)`` of earlier versions (the LM demo)
is deprecated: call ``lm_decode`` for the demo or ``repro.api.serve``
for the serving subsystem.
"""
from __future__ import annotations

import argparse
import time
import warnings

import jax
import jax.numpy as jnp

from repro import data as data_lib
from repro.configs import get_config
from repro.models import transformer


def lm_decode(cfg, *, batch, prompt_len, gen, seed=0, greedy=True):
    """Prefill a batch of prompts, then batched greedy decode against
    the KV caches — the CPU-scale transformer serving demo."""
    key = jax.random.PRNGKey(seed)
    params = transformer.init(key, cfg)
    prompts = jnp.asarray(next(iter(data_lib.lm_batches(
        cfg.vocab, batch, prompt_len - 1, 1, seed))))

    aux = None
    if cfg.enc_dec:
        aux = jax.random.normal(key, (batch, cfg.enc_seq, cfg.d_model),
                                cfg.dtype)
    elif cfg.vision_tokens:
        aux = jax.random.normal(key, (batch, cfg.vision_tokens,
                                      cfg.d_model), cfg.dtype)

    max_len = prompt_len + gen
    prefill = jax.jit(lambda p, t, a: transformer.prefill(
        p, cfg, t, aux=a, max_len=max_len, last_only=True))
    step = jax.jit(lambda p, c, t, pos: transformer.serve_step(
        p, cfg, c, t, pos))

    t0 = time.time()
    logits, caches = prefill(params, prompts, aux)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits, axis=-1)              # (B,)
    out = [tok]
    t1 = time.time()
    for i in range(gen - 1):
        logits, caches = step(params, caches, tok, prompt_len + i)
        tok = (jnp.argmax(logits, axis=-1) if greedy
               else jax.random.categorical(
                   jax.random.fold_in(key, i), logits))
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t1
    gen_tokens = jnp.stack(out, axis=1)
    return {
        "generated": gen_tokens,
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "decode_tok_per_s": batch * (gen - 1) / max(t_decode, 1e-9),
    }


def serve(cfg, *, batch, prompt_len, gen, seed=0, greedy=True):
    """Deprecated: this was the transformer decode demo — use
    ``lm_decode`` (same signature), or ``repro.api.serve`` for the
    goodness-classifier serving subsystem."""
    warnings.warn("launch.serve.serve is deprecated; use launch.serve."
                  "lm_decode for the transformer demo or repro.api."
                  "serve for the serving subsystem",
                  DeprecationWarning, stacklevel=2)
    return lm_decode(cfg, batch=batch, prompt_len=prompt_len, gen=gen,
                     seed=seed, greedy=greedy)


def _main_ff(args):
    from repro import api
    from repro.configs.ff_mlp import FFMLPConfig
    from repro.obs import export as obs_export, trace as obs_trace

    task = data_lib.mnist_like(n_train=args.n_train, n_test=400)
    cfg = FFMLPConfig(
        layer_sizes=(task.dim,) + (args.width,) * args.layers,
        epochs=args.epochs, splits=args.splits, neg_mode="random",
        classifier="goodness", batch_size=64, seed=args.seed)
    # block_tasks=False: the point of tracing a serve run is the live
    # interleaving of training and scoring — keep the async overlap
    tracer = (obs_trace.Tracer(block_tasks=False,
                               meta={"launcher": "serve"})
              if args.trace else obs_trace.NOOP)
    res = api.serve(cfg, task, traffic=args.traffic,
                    schedule=args.schedule, num_nodes=args.nodes,
                    rate=args.rate, max_batch=args.max_batch,
                    max_wait_s=args.max_wait, queue_cap=args.queue_cap,
                    seed=args.seed, trace=tracer)
    if tracer.enabled:
        obs_export.export(tracer, args.trace, format=args.trace_format)
        print(f"trace: {tracer.span_count()} spans -> {args.trace} "
              f"({args.trace_format})")
    slo = res.slo
    print(f"train-while-serve: schedule={res.schedule} "
          f"nodes={res.num_nodes} traffic={res.traffic}")
    print(f"  train acc={res.fit.test_acc:.4f} "
          f"makespan={res.fit.makespan:.2f}s")
    print(f"  served {slo['requests']} req @ "
          f"{slo['throughput_rps']:.1f} rps  "
          f"p50={slo['latency_p50_ms']:.1f}ms "
          f"p99={slo['latency_p99_ms']:.1f}ms  "
          f"shed={slo['shed_rate']:.3f}")
    print(f"  swaps={slo['swaps']} "
          f"staleness_max={slo['staleness_max_s']:.3f}s "
          f"violations={slo['consistency_violations']}")
    for v, row in res.accuracy_by_version.items():
        print(f"    version {v:3d}: n={row['n']:5d} "
              f"acc={row['accuracy']:.3f}")
    return 1 if slo["consistency_violations"] else 0


def _main_lm(args):
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    res = lm_decode(cfg, batch=args.batch, prompt_len=args.prompt_len,
                    gen=args.gen, seed=args.seed)
    print(f"prefill {res['prefill_s']:.2f}s  decode {res['decode_s']:.2f}s"
          f"  ({res['decode_tok_per_s']:.1f} tok/s)")
    print("first generated rows:", res["generated"][:2, :12])
    return 0


def main(argv=None):
    from repro import api
    from repro.core import pff_dag
    from repro.launch import compile_cache

    compile_cache.use_compile_cache()

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=("ff", "lm"), default="ff",
                    help="ff: train-while-serve via api.serve (default);"
                         " lm: transformer prefill+decode demo")
    g = ap.add_argument_group("ff mode")
    g.add_argument("--traffic", default="uniform",
                   choices=list(api.traffic.names()))
    g.add_argument("--schedule", default="all_layers",
                   choices=list(pff_dag.SCHEDULES))
    g.add_argument("--nodes", type=int, default=4)
    g.add_argument("--rate", type=float, default=300.0)
    g.add_argument("--max-batch", type=int, default=64)
    g.add_argument("--max-wait", type=float, default=0.02)
    g.add_argument("--queue-cap", type=int, default=512)
    g.add_argument("--epochs", type=int, default=100)
    g.add_argument("--splits", type=int, default=4)
    g.add_argument("--layers", type=int, default=2)
    g.add_argument("--width", type=int, default=256)
    g.add_argument("--n-train", type=int, default=2560)
    lm = ap.add_argument_group("lm mode")
    lm.add_argument("--arch", default=None,
                    help="transformer config name (lm mode)")
    lm.add_argument("--full", action="store_true")
    lm.add_argument("--batch", type=int, default=4)
    lm.add_argument("--prompt-len", type=int, default=64)
    lm.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    from repro.obs import export as obs_export
    g.add_argument("--trace", default=None, metavar="PATH",
                   help="record an execution trace (repro.obs; "
                        "non-blocking tracer, overlap intact) and "
                        "export it here after the run")
    g.add_argument("--trace-format", default="chrome",
                   choices=list(obs_export.names()),
                   help="trace exporter (choices live from the "
                        "repro.obs exporter registry)")
    args = ap.parse_args(argv)
    if args.mode == "lm":
        if args.arch is None:
            ap.error("--mode lm requires --arch")
        return _main_lm(args)
    return _main_ff(args)


if __name__ == "__main__":
    raise SystemExit(main())
