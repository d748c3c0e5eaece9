"""``repro.api`` — the one training facade for the paper's FF/PFF system.

The paper's point is that ONE chapter-task DAG can be driven by many
schedules; this module is the one entry point that drives it:

    from repro import api, data
    from repro.configs.ff_mlp import FFMLPConfig

    task = data.mnist_like(n_train=2560, n_test=500)
    cfg = FFMLPConfig(layer_sizes=(784, 400, 400), epochs=60, splits=6)

    res = api.fit(cfg, task)                                # sequential
    res = api.fit(cfg, task, backend="federated", num_nodes=4)
    res = api.fit(cfg, task, backend="executor",            # real devices
                  schedule="all_layers", num_nodes=4)
    res = api.fit(cfg, task, backend="simulate",            # event sim
                  schedule="single_layer", num_nodes=4)

Every backend returns the same ``FitResult`` (params, per-task records,
test accuracy, makespan/speedup/utilization when applicable, profile).
Strategy variation — negatives, goodness objective, classifier — is
config-driven through three registries (``api.negatives``,
``api.goodness``, ``api.classifier``); register your own with
``api.register_negatives`` & co and reference it by name in the config.

Backends
--------
sequential  the canonical chapter-schedule trainer (times every task;
            its records feed the simulator and the paper tables).
federated   the same trainer on node-local shards (Federated PFF §4.3).
executor    the REAL multi-device executor: one ``jax.device`` per paper
            node, async dispatch, ``device_put`` hand-off — bit-exact
            vs ``sequential`` (the CI oracle). Needs ``schedule`` and
            ``num_nodes`` <= len(jax.devices()).
simulate    trains sequentially once, then replays the measured task
            timings through the event-driven schedule simulator.
pod         beyond-paper: the PFF pipeline over a (stage, data, model)
            TPU-style mesh for transformer LM configs
            (``repro.core.pff_pod``); ``num_nodes`` = pipeline stages.

Serving (``api.serve`` — ROADMAP item 2's train-while-serving) is the
fourth registry-driven surface: ``api.traffic`` shapes the request
stream (uniform / zipf / bursty; ``api.register_traffic`` adds more),
``repro.serve`` provides the continuous-batching loop, and the executor
hot-publishes each freshly-trained layer into the serving replica
mid-run:

    res = api.serve(cfg, task, traffic="zipf", num_nodes=4)  # train+serve
    res.slo["latency_p99_ms"], res.slo["consistency_violations"]
    res = api.fit(cfg, task, backend="executor", num_nodes=4,
                  serve=api.ServeConfig(traffic="bursty"))   # same, via fit

Deprecated entry points ``pff.train_ff_mlp``, ``pff.train_federated``
and ``pff_exec.run_pff_exec`` delegate here with a DeprecationWarning;
``launch.serve.serve`` (the old transformer decode demo) warns and
delegates to ``launch.serve.lm_decode``.

``python -m repro.api --selftest`` (= ``make api-smoke``) runs every
registered strategy through the sequential backend on a tiny task and
checks the deprecation shims.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

from repro import data as data_lib
from repro.core import ff_mlp, pff, pff_exec, strategies
from repro.kernels import registry as kernel_registry
from repro.obs import trace as obs_trace
from repro.core.faults import (              # re-exported resilience surface
    FaultPlan, ResilienceConfig,
)
from repro.core.strategies import (          # re-exported registry surface
    classifier, goodness, negatives,
    register_classifier, register_goodness, register_negatives,
)
from repro.serve import engine as serve_engine
from repro.serve.engine import ServeConfig   # re-exported serving surface
from repro.serve.traffic import register_traffic, traffic

__all__ = [
    "fit", "simulate", "serve", "FitResult", "ServeResult", "ServeConfig",
    "BACKENDS",
    "negatives", "goodness", "classifier", "traffic",
    "register_negatives", "register_goodness", "register_classifier",
    "register_traffic",
    "FaultPlan", "ResilienceConfig",
]

BACKENDS = ("sequential", "simulate", "executor", "federated", "pod")


@dataclasses.dataclass
class FitResult:
    """What every backend returns. Fields that a backend cannot measure
    stay None (e.g. ``makespan`` for plain sequential training).
    ``raw`` keeps the backend-native result object (TrainResult /
    ExecResult / SimResult / pod history) for the deprecation shims and
    power users."""
    backend: str
    cfg: object
    params: Optional[dict] = None
    schedule: Optional[str] = None
    num_nodes: int = 1
    records: Optional[List[pff.TaskRecord]] = None
    test_acc: Optional[float] = None
    train_acc: Optional[float] = None
    history: list = dataclasses.field(default_factory=list)
    makespan: Optional[float] = None
    speedup: Optional[float] = None
    utilization: Optional[float] = None
    sim: Optional[pff.SimResult] = None
    profile: Optional[dict] = None
    resilience: Optional[dict] = None
    eval_ce: Optional[float] = None         # LM chapter backends: val CE
    serve: Optional["ServeResult"] = None   # fit(serve=ServeConfig(...))
    trace: Optional[object] = None          # obs.trace.Tracer (trace=...)
    raw: object = None


@dataclasses.dataclass
class ServeResult:
    """What ``api.serve`` returns — same field conventions as
    ``FitResult``: a ``records`` list (per-request lifecycle dicts, the
    serving analog of the per-task ``TaskRecord`` list), per-phase
    ``timings``, and a ``.slo`` stats block shaped like
    ``FitResult.resilience`` (one JSON-ready dict of counters and
    percentiles: p50/p99 latency, throughput, shed rate, swap count,
    staleness, consistency violations)."""
    cfg: object
    traffic: str
    schedule: Optional[str] = None          # None = serve-only (static)
    num_nodes: int = 1
    records: Optional[List[dict]] = None
    swaps: Optional[List[dict]] = None      # hot-swap timeline
    slo: Optional[dict] = None
    timings: Optional[dict] = None          # {"serve_s", ["train_s"]}
    accuracy_by_version: Optional[dict] = None
    test_acc: Optional[float] = None        # accuracy over served requests
    fit: Optional[FitResult] = None         # training side (combined mode)
    trace: Optional[object] = None          # obs.trace.Tracer (trace=...)
    raw: object = None                      # serve.engine.EngineResult


def _validate_strategies(cfg):
    """Fail fast with the registry's helpful errors + pairing checks."""
    good = strategies.goodness.get(cfg.goodness_fn)
    strategies.negatives.get(cfg.neg_mode)
    cls = strategies.classifier.get(cfg.classifier)
    impl = ff_mlp.kernel_impl(cfg)
    if impl != "auto":
        # source-of-truth'd from the kernel impl registry, like the
        # strategy names above — a typo'd kernel_impl fails here, not
        # deep inside the first jitted chapter
        kernel_registry.ff_dense.get(impl)
    if cls.requires_goodness and cfg.goodness_fn != cls.requires_goodness:
        raise ValueError(
            f"classifier {cfg.classifier!r} reads parameters trained by "
            f"goodness_fn={cls.requires_goodness!r}, but the config has "
            f"goodness_fn={cfg.goodness_fn!r}")
    return good


def fit(cfg, task=None, *, backend="sequential", schedule=None,
        num_nodes=1, probe_every=0, verbose=False, profile=False,
        devices=None, overlap=True, resilience=None, resume_from=None,
        serve=None, trace=None, comm_time=0.0, steps=40, batch=8,
        seq=64, lr=1e-3, chapters=4, steps_per_chapter=8,
        head_lr=None) -> FitResult:
    """Train ``cfg`` on ``task`` with the chosen backend. See the module
    docstring for the backend table.

    schedule: PFF schedule for the ``executor``/``simulate`` backends
    (default "all_layers"; "sequential" is forced when num_nodes == 1).
    probe_every/verbose: chapter-level accuracy probes (sequential /
    federated backends).
    profile: executor backend — collect per-task records + node busy
    times (blocks after every task; run again without it for makespan).
    devices: executor backend — explicit device list.
    overlap: executor backend — double-buffer the ``device_put``
    weight/negatives hand-off so transfers overlap compute (the
    default; False restores the serialize-on-demand hand-off for A/B
    runs — the weight stream is bit-identical either way).
    resilience: executor backend — a ``repro.core.faults.
    ResilienceConfig``: chapter-granular checkpointing, retry/backoff +
    dead-node degradation, deterministic fault injection, and the
    elastic federated ``membership`` callback. Stats come back on
    ``FitResult.resilience``.
    resume_from: executor backend — a chapter manifest (or its
    directory) written by a previous resilient run; training replays
    the DAG from the next chapter, bit-exactly.
    serve: executor backend — a ``ServeConfig``: run the combined
    train-while-serve mode (the executor hot-publishes every freshly-
    trained layer into a serving replica, which serves the config's
    traffic concurrently). The serving side comes back on
    ``FitResult.serve``; ``api.serve()`` is the same machinery with the
    serving result on top.
    trace: ``True`` or a ``repro.obs.Tracer`` — record an execution
    trace (spans + events + counters) into ``FitResult.trace``; export
    it with ``repro.obs.export.export`` and analyze the executor DAG
    timeline with ``repro.obs.analyze.analyze``. The default tracer
    blocks after each executor task for accurate per-task durations
    (like ``profile=``); pass ``Tracer(block_tasks=False)`` to observe
    with the async overlap intact.
    comm_time: simulate backend — per-DAG-edge cross-node hand-off cost.
    steps/batch/seq/lr: pod backend — pipeline run length and shapes
    (``task`` may be an iterable of token blocks, or None to use the
    synthetic LM corpus).

    Transformer LM configs (``repro.configs.get_config``) on the
    sequential / executor backends run the CHAPTER schedule
    (``core.pff_lm`` — per-block train tasks + a per-chapter head task)
    instead of the FF-MLP path: ``task`` is a ``data.Source`` of token
    blocks (default: the real-text BPE ``data.text_source``), sized by
    ``chapters`` x ``steps_per_chapter`` x ``batch`` x ``seq``;
    ``head_lr`` overrides ``lr`` for the head task. The executor
    backend drives ``pff_exec.LMExecutor`` across ``num_nodes``
    devices, bit-exact vs sequential; quality comes back on
    ``FitResult.eval_ce`` (held-out CE, scored identically for both).
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{BACKENDS}")
    if (resilience is not None or resume_from is not None) \
            and backend != "executor":
        raise ValueError(
            f"resilience/resume_from are executor-backend features "
            f"(chapter checkpoints, fault injection, elastic "
            f"membership); got backend={backend!r}")
    if serve is not None and backend != "executor":
        raise ValueError(
            f"serve= runs the train-while-serve mode, which needs the "
            f"executor backend's live per-layer publication; got "
            f"backend={backend!r}")
    if serve is not None and not isinstance(serve, ServeConfig):
        raise TypeError(f"serve= expects an api.ServeConfig, got "
                        f"{type(serve).__name__}")
    tracer = obs_trace.as_tracer(trace)
    out_trace = tracer if tracer.enabled else None
    if backend == "pod":
        with tracer.span("fit:pod", num_nodes=num_nodes, steps=steps):
            fres = _fit_pod(cfg, task, num_nodes=num_nodes, steps=steps,
                            batch=batch, seq=seq, lr=lr, verbose=verbose)
        fres.trace = out_trace
        return fres

    if hasattr(cfg, "groups") and backend in ("sequential", "executor"):
        # transformer LM config -> the chapter schedule (core.pff_lm),
        # sequential reference or the real LMExecutor
        if resilience is not None or resume_from is not None \
                or serve is not None:
            raise ValueError(
                "LM chapter schedules do not support resilience/"
                "resume_from/serve yet (ROADMAP: unify with lm_decode "
                "serving)")
        fres = _fit_lm_chapters(
            cfg, task, backend=backend, schedule=schedule,
            num_nodes=num_nodes, chapters=chapters,
            steps_per_chapter=steps_per_chapter, batch=batch, seq=seq,
            lr=lr, head_lr=head_lr, devices=devices, overlap=overlap,
            profile=profile, tracer=tracer)
        fres.trace = fres.trace or out_trace
        return fres

    _validate_strategies(cfg)
    if backend == "sequential":
        with tracer.span("fit:sequential", backend=backend,
                         schedule="sequential"):
            res = pff.run_chapter_schedule(cfg, task,
                                           probe_every=probe_every,
                                           verbose=verbose, tracer=tracer)
        return FitResult(backend=backend, cfg=cfg, params=res.params,
                         schedule="sequential", num_nodes=1,
                         records=res.records, test_acc=res.test_acc,
                         train_acc=res.train_acc, history=res.history,
                         trace=out_trace, raw=res)

    if backend == "federated":
        with tracer.span("fit:federated", num_nodes=num_nodes):
            res = pff.run_federated_schedule(cfg, task, num_nodes,
                                             probe_every=probe_every,
                                             verbose=verbose,
                                             tracer=tracer)
        return FitResult(backend=backend, cfg=cfg, params=res.params,
                         schedule="federated", num_nodes=num_nodes,
                         records=res.records, test_acc=res.test_acc,
                         train_acc=res.train_acc, history=res.history,
                         trace=out_trace, raw=res)

    schedule = schedule or ("sequential" if num_nodes == 1
                            else "all_layers")
    if backend == "executor":
        with tracer.span("fit:executor", backend=backend,
                         schedule=schedule):
            with tracer.span("fit:init"):
                ex = pff_exec.PFFExecutor(cfg, task, schedule, num_nodes,
                                          devices=devices, overlap=overlap,
                                          resilience=resilience)
            if serve is not None:
                return _run_combined(cfg, ex, serve, source=None,
                                     resume_from=resume_from,
                                     schedule=schedule, num_nodes=num_nodes,
                                     tracer=tracer).fit
            res = ex.run(profile=profile, resume_from=resume_from,
                         trace=out_trace)
        return FitResult(backend=backend, cfg=cfg, params=res.params,
                         schedule=schedule, num_nodes=num_nodes,
                         records=res.records, test_acc=res.test_acc,
                         makespan=res.makespan,
                         profile=({"node_busy": res.node_busy}
                                  if res.node_busy is not None
                                  else None),
                         resilience=res.resilience,
                         trace=res.trace, raw=res)

    # backend == "simulate": canonical training once, then replay its
    # measured task timings under the schedule's node assignment
    with tracer.span("fit:simulate", schedule=schedule,
                     num_nodes=num_nodes):
        res = pff.run_chapter_schedule(cfg, task, probe_every=probe_every,
                                       verbose=verbose, tracer=tracer)
        sim = pff.simulate_schedule(res.records, schedule, num_nodes,
                                    comm_time=comm_time)
    return FitResult(backend=backend, cfg=cfg, params=res.params,
                     schedule=schedule, num_nodes=num_nodes,
                     records=res.records, test_acc=res.test_acc,
                     train_acc=res.train_acc, history=res.history,
                     makespan=sim.makespan, speedup=sim.speedup,
                     utilization=sim.utilization, sim=sim,
                     trace=out_trace, raw=res)


# ---------------------------------------------------------------------------
# Serving facade (repro.serve machinery behind api.serve / fit(serve=...))
# ---------------------------------------------------------------------------

def _serve_records(engine_res) -> List[dict]:
    """Per-request lifecycle dicts (JSON-ready) from the engine's
    ``Request`` objects — the ``ServeResult.records`` convention."""
    return [{"id": r.id, "t_arrival": r.t_arrival, "t_admit": r.t_admit,
             "t_done": r.t_done, "latency": r.latency,
             "version": r.version, "pred": r.pred, "label": r.label,
             "correct": (r.pred == r.label) if r.pred is not None
             else None}
            for r in engine_res.requests]


def _serve_result(cfg, sconfig, engine_res, *, schedule=None, num_nodes=1,
                  fit_result=None, tracer=obs_trace.NOOP) -> ServeResult:
    slo = serve_engine.summarize(engine_res)
    return ServeResult(
        cfg=cfg, traffic=sconfig.traffic, schedule=schedule,
        num_nodes=num_nodes, records=_serve_records(engine_res),
        swaps=engine_res.swaps, slo=slo,
        timings=dict(engine_res.timings),
        accuracy_by_version=serve_engine.accuracy_by_version(engine_res),
        test_acc=slo["accuracy"], fit=fit_result,
        trace=tracer if tracer.enabled else None, raw=engine_res)


def _run_combined(cfg, ex, sconfig, *, source, resume_from, schedule,
                  num_nodes, tracer=obs_trace.NOOP) -> ServeResult:
    """Train-while-serve: one executor run with live publication, one
    serve loop, results cross-linked (``ServeResult.fit`` /
    ``FitResult.serve``). One tracer is shared by the serve loop and
    the executor thread, so the trace has a single clock domain."""
    engine_res = serve_engine.train_while_serve(ex, sconfig, source,
                                                resume_from=resume_from,
                                                tracer=tracer)
    res = engine_res.exec_result
    fit_res = FitResult(backend="executor", cfg=cfg, params=res.params,
                        schedule=schedule, num_nodes=num_nodes,
                        records=res.records, test_acc=res.test_acc,
                        makespan=res.makespan, resilience=res.resilience,
                        trace=res.trace, raw=res)
    sres = _serve_result(cfg, sconfig, engine_res, schedule=schedule,
                         num_nodes=num_nodes, fit_result=fit_res,
                         tracer=tracer)
    fit_res.serve = sres
    return sres


def serve(cfg, task=None, *, traffic=None, source=None, params=None,
          schedule=None, num_nodes=1, devices=None, overlap=True,
          resilience=None, resume_from=None, serve_cfg=None, trace=None,
          **knobs) -> ServeResult:
    """Serve the goodness classifier under deterministic open-loop
    traffic — while TRAINING it live on the executor (the default), or
    from a fixed ``params`` snapshot (serve-only replay).

    traffic: a name from the ``api.traffic`` registry (uniform / zipf /
    bursty, or anything added with ``api.register_traffic``).
    source: a ``data.Source`` for request payloads; defaults to the
    task's test split (``data.source_of``).
    params: a trained params dict — serve-only mode: no training
    underneath, one static snapshot at version 0 (``n_requests``
    bounds the run, default 256).
    schedule/num_nodes/devices/overlap/resilience/resume_from: the
    executor knobs, exactly as ``fit(backend="executor")`` takes them
    (combined mode only).
    serve_cfg / **knobs: a ``ServeConfig``, and/or its fields as
    keywords (``rate=...``, ``max_batch=...``, ``max_wait_s=...``,
    ``queue_cap=...``, ``n_requests=...``, ``seed=...``) — keywords win.
    trace: ``True`` or a ``repro.obs.Tracer`` — record admission /
    batch-form / score / swap-install spans (and, in combined mode, the
    executor's task spans on the SAME clock) into ``ServeResult.trace``.
    Combined mode: the default tracer blocks training after every task;
    pass ``Tracer(block_tasks=False)`` to watch serving under the real
    overlapped training load.
    """
    base = serve_cfg if serve_cfg is not None else ServeConfig()
    if traffic is not None:
        knobs["traffic"] = traffic
    valid = {f.name for f in dataclasses.fields(ServeConfig)}
    bad = set(knobs) - valid
    if bad:
        raise TypeError(f"unknown ServeConfig knob(s) {sorted(bad)}; "
                        f"valid: {sorted(valid)}")
    sconfig = dataclasses.replace(base, **knobs)

    good = _validate_strategies(cfg)
    tracer = obs_trace.as_tracer(trace)
    if source is None:
        if task is None:
            raise ValueError("serve needs a task or an explicit "
                             "source= for request payloads")
        source = data_lib.source_of(task)

    if params is not None:
        if sconfig.n_requests is None:
            sconfig = dataclasses.replace(sconfig, n_requests=256)
        engine_res = serve_engine.serve_static(
            params, cfg, source, sconfig,
            eval_mode=good.eval_mode(cfg), impl=ff_mlp.kernel_impl(cfg),
            tracer=tracer)
        return _serve_result(cfg, sconfig, engine_res, tracer=tracer)

    if task is None:
        raise ValueError("train-while-serve needs the training task "
                         "(pass params= for serve-only)")
    schedule = schedule or ("sequential" if num_nodes == 1
                            else "all_layers")
    ex = pff_exec.PFFExecutor(cfg, task, schedule, num_nodes,
                              devices=devices, overlap=overlap,
                              resilience=resilience)
    return _run_combined(cfg, ex, sconfig, source=source,
                         resume_from=resume_from, schedule=schedule,
                         num_nodes=num_nodes, tracer=tracer)


def simulate(result_or_records, schedule, num_nodes,
             **kw) -> pff.SimResult:
    """Replay a training run's task records under another schedule —
    accepts a ``FitResult`` (sequential/federated/simulate backends) or
    a raw record list."""
    records = getattr(result_or_records, "records", result_or_records)
    if records is None:
        raise ValueError(
            "no task records on this result (executor results carry "
            "records only when profiled or traced with a blocking "
            "tracer — fit(..., profile=True) or fit(..., trace=True))")
    return pff.simulate_schedule(records, schedule, num_nodes, **kw)


def _fit_lm_chapters(cfg, source, *, backend, schedule, num_nodes,
                     chapters, steps_per_chapter, batch, seq, lr,
                     head_lr, devices, overlap, profile,
                     tracer=obs_trace.NOOP) -> FitResult:
    """LM chapter-schedule backends (transformer configs): sequential =
    ``pff_lm.train_chapters`` (the oracle), executor =
    ``pff_exec.LMExecutor`` on real devices. Both consume the same
    ``data.Source`` of token blocks through the same
    ``chapter_batches`` stream and are scored by the same held-out
    ``train.eval_ce`` — so the bit-exactness gate extends to the
    reported CE."""
    import time

    import jax.numpy as jnp

    from repro.core import pff_lm
    from repro.core import train as train_lib

    seed = getattr(cfg, "seed", 0) or 0
    if source is None:
        source = data_lib.text_source(vocab=cfg.vocab, seq_len=seq,
                                      seed=seed)
    if backend == "sequential":
        data_iter = pff_lm.chapter_batches(source, batch=batch,
                                           steps=steps_per_chapter)
        with tracer.span("fit:lm_sequential", chapters=chapters):
            t0 = time.perf_counter()
            params, records, losses = pff_lm.train_chapters(
                cfg, data_iter, chapters=chapters,
                steps_per_chapter=steps_per_chapter, lr=lr,
                head_lr=head_lr, seed=seed)
            makespan = time.perf_counter() - t0
        fres = FitResult(backend=backend, cfg=cfg, params=params,
                         schedule="sequential", num_nodes=1,
                         records=records, makespan=makespan,
                         history=[(i + 1, l)
                                  for i, l in enumerate(losses)])
    else:
        schedule = schedule or ("sequential" if num_nodes == 1
                                else "all_layers")
        ex = pff_exec.LMExecutor(
            cfg, source, schedule, num_nodes, chapters=chapters,
            steps_per_chapter=steps_per_chapter, batch=batch, lr=lr,
            head_lr=head_lr, seed=seed, devices=devices, overlap=overlap)
        res = ex.run(profile=profile,
                     trace=tracer if tracer.enabled else None)
        fres = FitResult(backend=backend, cfg=cfg, params=res.params,
                         schedule=schedule, num_nodes=num_nodes,
                         records=res.records, makespan=res.makespan,
                         profile=({"node_busy": res.node_busy}
                                  if res.node_busy is not None
                                  else None),
                         trace=res.trace, raw=res)
    # one eval path for BOTH backends: held-out CE on a fixed val draw
    ev = jnp.asarray(source.blocks("val", 16, seed=321))
    fres.eval_ce = float(train_lib.eval_ce(fres.params, cfg, ev))
    return fres


def _fit_pod(cfg, task, *, num_nodes, steps, batch, seq, lr, verbose):
    """Beyond-paper pod-pipeline backend (transformer LM configs only)."""
    import jax
    import jax.numpy as jnp

    from repro import optim
    from repro.core import pff_pod
    from repro.models import transformer

    if not hasattr(cfg, "groups"):
        raise ValueError(
            "backend=\"pod\" expects a transformer LM config "
            "(repro.configs.get_config(...)); FF-MLP configs run on the "
            "sequential/federated/executor/simulate backends")
    stages = num_nodes
    if stages < 1 or stages > len(jax.devices()):
        raise ValueError(f"pod backend needs 1 <= num_nodes <= "
                         f"{len(jax.devices())} devices, got {stages}")
    mesh = jax.make_mesh((stages, 1, 1), ("stage", "data", "model"))
    key = jax.random.PRNGKey(getattr(cfg, "seed", 0) or 0)
    params = transformer.init(key, cfg)
    opt = optim.adam_init(params)
    inflight = pff_pod.init_inflight(cfg, batch, seq, stages=stages)
    step_fn = pff_pod.make_pff_pod_step(cfg, mesh, lr=lr)
    batches = (task if task is not None
               else data_lib.lm_batches(cfg.vocab, batch, seq, steps))
    history = []
    import time
    t0 = time.perf_counter()
    with mesh:
        for i, tokens in enumerate(batches):
            params, opt, inflight, m = step_fn(
                params, opt, {"tokens": jnp.asarray(tokens)}, inflight,
                i + 1)
            history.append((i + 1, float(m["loss_ff"])))
            if verbose and (i + 1) % 10 == 0:
                print(f"  pod step {i + 1}: FF loss "
                      f"{history[-1][1]:.4f}")
    jax.block_until_ready(params)
    makespan = time.perf_counter() - t0
    return FitResult(backend="pod", cfg=cfg, params=params,
                     schedule="pod_pipeline", num_nodes=stages,
                     history=history, makespan=makespan, raw=history)


# ---------------------------------------------------------------------------
# Selftest: every registered strategy x the sequential backend, plus the
# deprecation shims. ``make api-smoke`` runs this.
# ---------------------------------------------------------------------------

def _selftest_cases():
    """One tiny sequential run per registered strategy (deduplicated)."""
    from repro.configs.ff_mlp import FFMLPConfig

    base = dict(layer_sizes=(784, 64, 64), epochs=2, splits=2,
                batch_size=64, seed=0)
    cases = {}
    for name in strategies.negatives.names():
        cases[f"negatives:{name}"] = FFMLPConfig(
            neg_mode=name, classifier="goodness", goodness_fn="sumsq",
            **base)
    for name in strategies.goodness.names():
        cases[f"goodness:{name}"] = FFMLPConfig(
            neg_mode="random", classifier="goodness", goodness_fn=name,
            **base)
    for name in strategies.classifier.names():
        strat = strategies.classifier.get(name)
        cases[f"classifier:{name}"] = FFMLPConfig(
            neg_mode="random", classifier=name,
            goodness_fn=strat.requires_goodness or "sumsq", **base)
    return cases


def _selftest(argv=None):
    import argparse
    import warnings

    import numpy as np

    p = argparse.ArgumentParser(description="repro.api facade selftest")
    p.add_argument("--selftest", action="store_true",
                   help="accepted for symmetry with `make api-smoke`")
    p.parse_args(argv)

    task = data_lib.mnist_like(n_train=256, n_test=128)
    failures = []
    for label, cfg in _selftest_cases().items():
        try:
            res = fit(cfg, task, backend="sequential")
            acc = res.test_acc
            flat = np.concatenate([np.asarray(lp["w"]).ravel()
                                   for lp in res.params["layers"]])
            if not (0.0 <= acc <= 1.0) or not np.all(np.isfinite(flat)):
                failures.append(f"{label}: degenerate result "
                                f"(acc={acc}, finite={np.all(np.isfinite(flat))})")
            print(f"  {label:24s} acc={acc:.3f} "
                  f"records={len(res.records)} OK")
        except Exception as e:                      # noqa: BLE001
            failures.append(f"{label}: {type(e).__name__}: {e}")
            print(f"  {label:24s} FAILED: {e}")

    # deprecated names must still work AND warn
    from repro.configs.ff_mlp import FFMLPConfig
    shim_cfg = FFMLPConfig(layer_sizes=(784, 32), epochs=2, splits=2,
                           neg_mode="random", classifier="goodness",
                           batch_size=64, seed=0)
    shims = (
        ("pff.train_ff_mlp", lambda: pff.train_ff_mlp(shim_cfg, task)),
        ("pff.train_federated",
         lambda: pff.train_federated(shim_cfg, task, 2)),
        ("pff_exec.run_pff_exec",
         lambda: pff_exec.run_pff_exec(shim_cfg, task, "sequential", 1)),
    )
    for name, call in shims:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            try:
                out = call()
            except Exception as e:                  # noqa: BLE001
                failures.append(f"{name}: {type(e).__name__}: {e}")
                print(f"  shim {name:24s} FAILED: {e}")
                continue
            if not any(issubclass(w.category, DeprecationWarning)
                       for w in caught):
                failures.append(f"{name}: no DeprecationWarning emitted")
            elif out is None:
                failures.append(f"{name}: shim returned None")
            else:
                print(f"  shim {name:24s} warns + delegates OK")

    if failures:
        print("API SELFTEST FAILED:\n  " + "\n  ".join(failures))
        return 1
    print(f"api selftest OK: {len(_selftest_cases())} strategy cases x "
          "sequential backend + deprecation shims")
    return 0


if __name__ == "__main__":
    raise SystemExit(_selftest())
