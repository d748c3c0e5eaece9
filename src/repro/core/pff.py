"""Pipeline Forward-Forward (PFF): the paper's distributed schedules.

The key observation the paper exploits: with splits, FF training is a DAG
of chapter-tasks T(k, c) = "train layer k for C epochs in chapter c" with
forward-only dependencies and NO backward edges — that is what
backpropagation would add, and why GPipe/PipeDream have bubbles that PFF
does not. Because the DAG (not the node assignment) fixes the
weight-update order, Sequential, Single-Layer PFF and All-Layers PFF
produce IDENTICAL weight streams — they differ only in wall-clock.

The PFF machinery is split across three modules:

  * ``repro.core.pff_dag``  — the chapter-task DAG itself (task set,
    dependency edges, per-schedule node assignments). Single source of
    truth consumed by both the simulator and the executor.
  * this module — (a) the canonical sequential trainer
    (``run_chapter_schedule``; drive it via ``repro.api.fit``), which
    executes the chapter schedule once, timing
    every task, and (b) an event-driven simulator
    (``simulate_schedule``) that replays those timings under each
    schedule's node assignment to obtain distributed training time,
    utilization and bubble fraction — the paper's Tables 1-3.
  * ``repro.core.pff_exec`` — the REAL executor: runs the same DAG
    concurrently across an actual ``jax.devices()`` set (one device per
    paper "node") with async dispatch and ``device_put`` hand-off, and
    reproduces this module's weight stream bit-exactly for All-Layers.
    ``benchmarks/pff_exec.py`` records its measured makespan next to
    the simulator's prediction.

Federated PFF additionally changes the data each chapter sees
(node-local shards), so it is always trained for real with per-node data
(``run_federated_schedule`` here, or the executor with
schedule="federated"; both via ``repro.api.fit``).

AdaptiveNEG adds a per-chapter negative-regeneration task; in Single-Layer
the LAST node generates and publishes negatives (serializing), while in
All-Layers/Federated each node regenerates its own (parallel) — this
asymmetry reproduces the paper's observed Single-Layer penalty.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import data as data_lib, optim
from repro.core import ff, ff_mlp, pff_dag, strategies
from repro.obs import trace as obs_trace


# ---------------------------------------------------------------------------
# Canonical chapter-schedule trainer (times every task)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TaskRecord:
    kind: str                  # train | forward | neg_gen | head | publish
    layer: int                 # -1 for non-layer tasks
    chapter: int
    duration: float


@dataclasses.dataclass
class TrainResult:
    params: dict
    records: List[TaskRecord]
    test_acc: float
    train_acc: float
    cfg: object
    history: List[Tuple[int, float]]       # (chapter, test_acc) probes


def run_chapter_schedule(cfg, task: data_lib.ImageTask, *, probe_every=0,
                         node_data: Optional[List[np.ndarray]] = None,
                         num_nodes: int = 1, verbose=False,
                         tracer=obs_trace.NOOP) -> TrainResult:
    """Runs the canonical chapter schedule of the paper (the facade's
    ``sequential`` / ``federated`` backends — call ``repro.api.fit``).

    All strategy variation (negatives, goodness, classifier) comes from
    the ``repro.core.strategies`` registries; this driver only walks the
    chapter x layer task order and times every task.

    node_data: optional list of per-node index arrays (Federated PFF) —
    chapter c uses node (c % num_nodes)'s shard.

    tracer: an ``obs.trace`` tracer. Every layer boundary opens a span,
    a profiler annotation even on the default ``NOOP`` (the executor's
    names, ``pff_exec``): ``fit:init``; per chapter ``chapter``,
    ``chapter:inputs``, ``task:train`` (attrs layer/chapter/steps) with
    ``task:wait`` and ``task:handoff`` inside, ``task:head``,
    ``task:neg_gen`` with ``neg:score`` (attrs chapter/rows) inside;
    ``fit:eval`` (attr rows) for probes and the final accuracies.
    ``task:wait`` and ``task:handoff`` are profiler-only
    (``obs_trace.annotate``).
    """
    good = strategies.goodness.get(cfg.goodness_fn)
    neg = strategies.negatives.get(cfg.neg_mode)
    cls = strategies.classifier.get(cfg.classifier)
    records: List[TaskRecord] = []
    history = []

    S = cfg.splits
    C = max(cfg.epochs // cfg.splits, 1)
    impl = ff_mlp.kernel_impl(cfg)
    has_neg = good.uses_negatives and neg.regenerates
    with tracer.span("fit:init"):
        key = jax.random.PRNGKey(cfg.seed)
        params = ff_mlp.init(key, cfg)
        opt = ff_mlp.opt_init(params)
        n_layers = len(params["layers"])
        x_all = jnp.asarray(task.x_train)
        y_all = jnp.asarray(task.y_train)

        # Hoisted out of the chapter loop: label overlays and the
        # layer-0 length-normalization are chapter-invariant (the
        # positive overlay never changes; the negative one changes only
        # on regeneration), so recomputing them every chapter x layer
        # would be pure waste.
        kneg = jax.random.fold_in(key, 999)
        if good.uses_negatives:
            # only the normalized forms are kept — the raw overlays
            # would be ~190 MB of dead weight each at MNIST scale. The
            # initial negatives pass params=None/scores=None: every
            # strategy degrades to key-only wrong labels before a model
            # exists (the executor does the same, so custom strategies
            # see one uniform contract).
            xp0 = ff_mlp._norm(ff.overlay_label(x_all, y_all,
                                                cfg.num_classes))
            xn0 = ff_mlp._norm(neg.fn(kneg, cfg, None, x_all, y_all, None))
        if not good.uses_negatives or cls.trains_head:
            x_neutral = ff.overlay_neutral(x_all, cfg.num_classes)
            if not good.uses_negatives:
                xk0 = ff_mlp._norm(x_neutral)

    for chapter in range(S):
        with tracer.span("chapter", chapter=chapter):
            with tracer.span("chapter:inputs", chapter=chapter):
                if node_data is not None:
                    idx = jnp.asarray(node_data[chapter % num_nodes])
                else:
                    idx = None
                # learning-rate for this chapter's mini-epochs
                lrs = jnp.asarray([
                    optim.cooldown_lr(cfg.lr_ff, chapter * C + e,
                                      cfg.epochs, cfg.cooldown_after)
                    for e in range(C)], jnp.float32)
                lrs_head = lrs * (cfg.lr_softmax / cfg.lr_ff)
                kc = jax.random.fold_in(key, chapter)

                # per-chapter inputs: activations flow layer-to-layer,
                # extras (labels) do not
                if good.uses_negatives:
                    acts = (xp0 if idx is None else xp0[idx],
                            xn0 if idx is None else xn0[idx])
                    extras = ()
                else:
                    acts = (xk0 if idx is None else xk0[idx],)
                    extras = (y_all if idx is None else y_all[idx],)
            steps = ff_mlp._num_batches(acts[0].shape[0],
                                        cfg.batch_size) * C

            for k in range(n_layers):
                with tracer.span("task:train", layer=k, chapter=chapter,
                                 steps=steps):
                    t0 = time.perf_counter()
                    state = good.train_chapter(
                        good.get_state(params, opt, k), acts, extras, lrs,
                        jax.random.fold_in(kc, k), cfg=cfg, epochs=C)
                    with obs_trace.annotate("task:wait"):
                        jax.block_until_ready(state[0])
                    good.set_state(params, opt, k, state)
                    if k + 1 < n_layers:
                        # propagate data through the freshly-trained layer
                        with obs_trace.annotate(
                                "task:handoff", layer=k,
                                rows=sum(a.shape[0] for a in acts)):
                            acts = tuple(ff_mlp.fwd_norm(state[0], a,
                                                         impl=impl)
                                         for a in acts)
                    records.append(TaskRecord(
                        "train", k, chapter, time.perf_counter() - t0))

            # softmax head (trained alongside, layer-local — paper §3)
            if cls.trains_head:
                with tracer.span("task:head", chapter=chapter):
                    t0 = time.perf_counter()
                    xn_all = x_neutral if idx is None else x_neutral[idx]
                    feats = ff_mlp.softmax_feats(params["layers"], xn_all,
                                                 impl=impl)
                    params["head"], opt["head"] = ff_mlp.train_head_chapter(
                        params["head"], opt["head"], feats,
                        y_all if idx is None else y_all[idx],
                        lrs_head, jax.random.fold_in(kc, 77),
                        batch=cfg.batch_size, epochs=C)
                    with obs_trace.annotate("task:wait"):
                        jax.block_until_ready(params["head"]["w"])
                    records.append(TaskRecord(
                        "head", n_layers, chapter, time.perf_counter() - t0))

            # negative regeneration (UpdateXNEG)
            if has_neg:
                with tracer.span("task:neg_gen", chapter=chapter):
                    t0 = time.perf_counter()
                    # params travel with scores: only needs_scores
                    # strategies see the live model (key-only regen gets
                    # None on the executor's per-node path too — keep
                    # both drivers' contracts identical)
                    scores = None
                    if neg.needs_scores:
                        with tracer.span("neg:score", chapter=chapter,
                                         rows=x_all.shape[0]):
                            scores = _class_scores_chunked(params, x_all,
                                                           cfg)
                    xn0 = ff_mlp._norm(neg.fn(
                        jax.random.fold_in(kneg, chapter), cfg,
                        params if neg.needs_scores else None,
                        x_all, y_all, scores))
                    with obs_trace.annotate("task:wait"):
                        jax.block_until_ready(xn0)
                    records.append(TaskRecord(
                        "neg_gen", -1, chapter, time.perf_counter() - t0))

            if probe_every and (chapter + 1) % probe_every == 0:
                with tracer.span("fit:eval", rows=len(task.x_test)):
                    acc = ff_mlp.accuracy(params, task.x_test, task.y_test,
                                          cfg.num_classes,
                                          good.eval_mode(cfg), impl=impl)
                history.append((chapter + 1, acc))
                if verbose:
                    print(f"  chapter {chapter + 1}/{S}: test acc "
                          f"{acc:.4f}")

    mode = good.eval_mode(cfg)
    n_eval = min(len(task.x_train), 2000)
    with tracer.span("fit:eval", rows=len(task.x_test) + n_eval):
        test_acc = ff_mlp.accuracy(params, task.x_test, task.y_test,
                                   cfg.num_classes, mode, impl=impl)
        train_acc = ff_mlp.accuracy(params, task.x_train[:n_eval],
                                    task.y_train[:n_eval],
                                    cfg.num_classes, mode, impl=impl)
    return TrainResult(params, records, test_acc, train_acc, cfg, history)


def _class_scores_chunked(params, x, cfg, chunk=2000):
    """Full-train-set goodness scores for AdaptiveNEG regeneration —
    one shared chunked loop (``ff_mlp.chunked_scores``) with accuracy()
    and the facade's eval step."""
    impl = ff_mlp.kernel_impl(cfg)
    return ff_mlp.chunked_scores(
        lambda xc: ff_mlp.goodness_class_scores(params, xc,
                                                cfg.num_classes, impl=impl),
        x, chunk=chunk)


# ---------------------------------------------------------------------------
# Event-driven schedule simulator
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SimResult:
    schedule: str
    num_nodes: int
    makespan: float
    sequential_time: float
    speedup: float
    utilization: float
    bubble_fraction: float
    node_busy: List[float]


def task_durations(records: List[TaskRecord], *, reducer=np.median):
    """Duration per (kind, layer), reduced with ``reducer``.

    The default ``np.median`` is robust to jit-compile outliers (the
    first occurrence of every task shape pays compilation). The reducer
    is exposed because these durations are what ``simulate_schedule``
    replays — and what the real executor (``repro.core.pff_exec``) is
    validated against in ``benchmarks/pff_exec.py``.
    """
    acc: Dict[Tuple[str, int], List[float]] = {}
    for r in records:
        acc.setdefault((r.kind, r.layer), []).append(r.duration)
    return {k: float(reducer(v)) for k, v in acc.items()}


def simulate_schedule(records: List[TaskRecord], schedule: str,
                      num_nodes: int, *, comm_time: float = 0.0,
                      forward_frac: float = 0.18,
                      reducer=np.median) -> SimResult:
    """Replays the ``pff_dag`` task DAG under a node assignment.

    forward_frac: cost of re-running the forward pass of ONE layer over
    the train set, as a fraction of one train-task (used by Single-Layer,
    Algorithm 1 lines 3-5; measured ratio fwd/train ≈ C * this).

    Negatives are used at whatever freshness is available
    ("UpdateXNEG(publish=False)", regenerated per node): they do NOT
    gate the next chapter's start (``strict_neg=False`` in the DAG) —
    their cost appears only as node busy time. This matches the paper's
    All-Layers AdaptiveNEG behaviour; the executor's bit-exact mode
    gates instead.
    """
    dur = task_durations(records, reducer=reducer)
    layers = sorted({r.layer for r in records if r.kind == "train"})
    chapters = sorted({r.chapter for r in records if r.kind == "train"})
    L, S = len(layers), len(chapters)
    has_head = any(k == "head" for k, _ in dur)
    has_neg = any(k == "neg_gen" for k, _ in dur)
    has_local = any(k == "local_head" for k, _ in dur)

    t_train = {k: dur[("train", k)] for k in layers}
    t_head = dur.get(("head", L), 0.0)
    t_neg = dur.get(("neg_gen", -1), 0.0)
    t_local = {k: dur.get(("local_head", k), 0.0) for k in layers}
    # fair sequential baseline: same median task costs, one node
    seq_total = S * (sum(t_train.values()) + (t_head if has_head else 0.0)
                     + (t_neg if has_neg else 0.0)
                     + (sum(t_local.values()) if has_local else 0.0))

    def owner(task: pff_dag.Task) -> int:
        if task.kind == "head":
            return pff_dag.head_node_of(schedule, num_nodes, n_layers=L,
                                        chapter=task.chapter)
        if task.kind == "neg_gen":
            return pff_dag.neg_node_of(schedule, num_nodes,
                                       chapter=task.chapter)
        # train / local_head: a local head trains where its layer trains
        return pff_dag.node_of(schedule, num_nodes, layer=task.layer,
                               chapter=task.chapter)

    def cost(task: pff_dag.Task) -> float:
        if task.kind == "head":
            return t_head
        if task.kind == "neg_gen":
            return t_neg
        if task.kind == "local_head":
            return t_local[task.layer]
        extra = 0.0
        if schedule == "single_layer" and task.layer > 0:
            # re-forward layers < k over the train set (Algorithm 1)
            extra = forward_frac * sum(t_train[j]
                                       for j in range(task.layer))
        return extra + t_train[task.layer]

    # ---- event simulation over the shared DAG ------------------------------
    node_free = [0.0] * num_nodes
    node_busy = [0.0] * num_nodes
    done: Dict[pff_dag.Task, float] = {}

    for task in pff_dag.build_tasks(L, S, has_head=has_head,
                                    has_neg=has_neg,
                                    has_local_heads=has_local):
        n = owner(task)
        start = node_free[n]
        for dep in pff_dag.deps(task, L, has_head=has_head,
                                has_neg=has_neg,
                                has_local_heads=has_local):
            start = max(start, done[dep] +
                        (comm_time if owner(dep) != n else 0.0))
        t = cost(task)
        end = start + t
        node_free[n] = end
        node_busy[n] += t
        done[task] = end

    makespan = max(node_free)
    speedup = seq_total / makespan if makespan > 0 else 1.0
    util = sum(node_busy) / (num_nodes * makespan) if makespan else 1.0
    return SimResult(schedule, num_nodes, makespan, seq_total, speedup,
                     util, 1.0 - util, node_busy)


# ---------------------------------------------------------------------------
# Federated PFF (actually trains on node-local shards)
# ---------------------------------------------------------------------------

def federated_shards(cfg, task: data_lib.ImageTask, num_nodes: int):
    """The canonical federated shard split: a seed-deterministic
    permutation dealt round-robin, so every node (and the executor)
    reconstructs the same shards without communication."""
    rng = np.random.default_rng(cfg.seed)
    order = rng.permutation(len(task.x_train))
    return [order[i::num_nodes] for i in range(num_nodes)]


def run_federated_schedule(cfg, task: data_lib.ImageTask, num_nodes: int,
                           **kw) -> TrainResult:
    """Federated PFF's weight stream (the facade's ``federated`` backend)."""
    return run_chapter_schedule(cfg, task,
                                node_data=federated_shards(cfg, task,
                                                           num_nodes),
                                num_nodes=num_nodes, **kw)


# ---------------------------------------------------------------------------
# Elastic Federated PFF: membership-aware rounds + weighted aggregation
# ---------------------------------------------------------------------------

def weighted_average_trees(trees, weights):
    """Leaf-wise weighted average of pytrees — the federated round
    aggregator. ``weights`` are python floats (normalized live-shard
    fractions); accumulation walks ``trees`` in the given order, so two
    callers passing the same trees in the same order get BIT-IDENTICAL
    results (the elastic executor is checked against the sequential
    reference this way). Integer/bool leaves (none today, but e.g. step
    counters) must agree across trees and are taken from the first.
    """
    if len(trees) != len(weights) or not trees:
        raise ValueError(f"{len(trees)} trees vs {len(weights)} weights")

    def avg(*leaves):
        if not jnp.issubdtype(jnp.asarray(leaves[0]).dtype, jnp.floating):
            return leaves[0]
        acc = leaves[0] * weights[0]
        for leaf, w in zip(leaves[1:], weights[1:]):
            acc = acc + leaf * w
        return acc
    return jax.tree_util.tree_map(avg, *trees)


def elastic_node_round(good, cfg, states, head_state, acts, extras, lrs,
                       lrs_head, key_node, *, epochs, impl, y=None,
                       x_neutral=None, train_head=False):
    """One node's shard-local work for one elastic round, starting from
    (already copied/placed) round-start ``states``/``head_state``.

    This is THE round math — the sequential reference
    (``run_elastic_federated``) and the real executor's elastic driver
    both call exactly this function, which is what makes the
    multi-device aggregate bit-checkable against the single-device one.
    NOTE: the chapter trainers donate their inputs — callers pass
    per-node copies, never the round-start globals themselves.
    """
    out_states = []
    for k, st in enumerate(states):
        st = good.train_chapter(st, acts, extras, lrs,
                                jax.random.fold_in(key_node, k),
                                cfg=cfg, epochs=epochs)
        out_states.append(st)
        if k + 1 < len(states):
            acts = tuple(ff_mlp.fwd_norm(st[0], a, impl=impl)
                         for a in acts)
    if train_head:
        feats = ff_mlp.softmax_feats([s[0] for s in out_states],
                                     x_neutral, impl=impl)
        head, oph = ff_mlp.train_head_chapter(
            head_state[0], head_state[1], feats, y, lrs_head,
            jax.random.fold_in(key_node, 77), batch=cfg.batch_size,
            epochs=epochs)
        head_state = (head, oph)
    return out_states, head_state


def _check_membership(live, num_nodes, r):
    live = sorted(set(int(n) for n in live))
    if not live:
        raise ValueError(f"membership callback returned no live nodes "
                         f"for round {r}")
    bad = [n for n in live if not 0 <= n < num_nodes]
    if bad:
        raise ValueError(f"membership round {r}: node ids {bad} outside "
                         f"[0, {num_nodes})")
    return live


def run_elastic_federated(cfg, task: data_lib.ImageTask, num_nodes: int,
                          membership) -> TrainResult:
    """Sequential reference for ELASTIC Federated PFF (the executor's
    ``resilience.membership`` mode is bit-checked against this).

    Per round r (= one chapter's worth of work, cfg.splits rounds):
    ``membership(r)`` names the live nodes; each live node trains a COPY
    of the round-start model on ITS OWN shard for C mini-epochs
    (shard-local training — the property the paper's federated schedule
    already has), and the aggregator replaces the global model with the
    live results averaged by live shard sizes
    (``weighted_average_trees``). Nodes joining or leaving between
    rounds therefore change only which shards contribute and their
    weights — no global state is ever stranded on an absent node.

    Key-only negative strategies only: score-needing (AdaptiveNEG)
    regeneration reads the full global model, which does not exist
    mid-round on any single node.
    """
    good = strategies.goodness.get(cfg.goodness_fn)
    neg = strategies.negatives.get(cfg.neg_mode)
    cls = strategies.classifier.get(cfg.classifier)
    if good.uses_negatives and neg.regenerates and neg.needs_scores:
        raise ValueError(
            f"elastic federated membership supports key-only negative "
            f"strategies; {cfg.neg_mode!r} needs full-model scores")
    key = jax.random.PRNGKey(cfg.seed)
    kneg = jax.random.fold_in(key, 999)
    params = ff_mlp.init(key, cfg)
    opt = ff_mlp.opt_init(params)
    S = cfg.splits
    C = max(cfg.epochs // cfg.splits, 1)
    n_layers = len(params["layers"])
    impl = ff_mlp.kernel_impl(cfg)
    x_all = jnp.asarray(task.x_train)
    y_all = jnp.asarray(task.y_train)
    shards = [jnp.asarray(s)
              for s in federated_shards(cfg, task, num_nodes)]
    train_head = cls.trains_head

    if good.uses_negatives:
        xp0 = ff_mlp._norm(ff.overlay_label(x_all, y_all, cfg.num_classes))
        xn0 = ff_mlp._norm(neg.fn(kneg, cfg, None, x_all, y_all, None))
    else:
        xk0 = ff_mlp._norm(ff.overlay_neutral(x_all, cfg.num_classes))
    if train_head or not good.uses_negatives:
        x_neutral = ff.overlay_neutral(x_all, cfg.num_classes)

    states = [good.get_state(params, opt, k) for k in range(n_layers)]
    head_state = (params["head"], opt["head"])
    history = []
    for r in range(S):
        live = _check_membership(membership(r), num_nodes, r)
        history.append((r, len(live)))
        lrs = jnp.asarray([
            optim.cooldown_lr(cfg.lr_ff, r * C + e, cfg.epochs,
                              cfg.cooldown_after) for e in range(C)],
            jnp.float32)
        lrs_head = lrs * (cfg.lr_softmax / cfg.lr_ff)
        kr = jax.random.fold_in(key, r)
        if good.uses_negatives and neg.regenerates and r > 0:
            xn0 = ff_mlp._norm(neg.fn(jax.random.fold_in(kneg, r - 1),
                                      cfg, None, x_all, y_all, None))
        per_node = {}
        for node in live:
            idx = shards[node]
            if good.uses_negatives:
                acts, extras = (xp0[idx], xn0[idx]), ()
            else:
                acts, extras = (xk0[idx],), (y_all[idx],)
            placed = [jax.tree_util.tree_map(jnp.copy, st)
                      for st in states]
            placed_head = jax.tree_util.tree_map(jnp.copy, head_state)
            per_node[node] = elastic_node_round(
                good, cfg, placed, placed_head, acts, extras, lrs,
                lrs_head, jax.random.fold_in(kr, node), epochs=C,
                impl=impl, y=y_all[idx] if train_head else None,
                x_neutral=x_neutral[idx] if train_head else None,
                train_head=train_head)
        total = float(sum(len(shards[n]) for n in live))
        w = [len(shards[n]) / total for n in live]
        states = [weighted_average_trees(
            [per_node[n][0][k] for n in live], w)
            for k in range(n_layers)]
        if train_head:
            head_state = weighted_average_trees(
                [per_node[n][1] for n in live], w)

    final = {**good.export(states), "head": head_state[0]}
    mode = good.eval_mode(cfg)
    test_acc = ff_mlp.accuracy(final, task.x_test, task.y_test,
                               cfg.num_classes, mode, impl=impl)
    train_acc = ff_mlp.accuracy(final, task.x_train[:2000],
                                task.y_train[:2000], cfg.num_classes,
                                mode, impl=impl)
    return TrainResult(final, [], test_acc, train_acc, cfg, history)


# ---------------------------------------------------------------------------
# Deprecated entry points — the supported surface is ``repro.api.fit``
# ---------------------------------------------------------------------------

def train_ff_mlp(cfg, task: data_lib.ImageTask, *, probe_every=0,
                 node_data: Optional[List[np.ndarray]] = None,
                 num_nodes: int = 1, verbose=False) -> TrainResult:
    """Deprecated: use ``repro.api.fit(cfg, task, backend="sequential")``."""
    warnings.warn("pff.train_ff_mlp is deprecated; use repro.api.fit("
                  "cfg, task, backend=\"sequential\")",
                  DeprecationWarning, stacklevel=2)
    from repro import api
    if node_data is not None:       # pre-facade federated spelling
        return run_chapter_schedule(cfg, task, probe_every=probe_every,
                                    node_data=node_data,
                                    num_nodes=num_nodes, verbose=verbose)
    return api.fit(cfg, task, backend="sequential", probe_every=probe_every,
                   verbose=verbose).raw


def train_federated(cfg, task: data_lib.ImageTask, num_nodes: int,
                    **kw) -> TrainResult:
    """Deprecated: use ``repro.api.fit(cfg, task, backend="federated")``."""
    warnings.warn("pff.train_federated is deprecated; use repro.api.fit("
                  "cfg, task, backend=\"federated\", num_nodes=N)",
                  DeprecationWarning, stacklevel=2)
    from repro import api
    return api.fit(cfg, task, backend="federated", num_nodes=num_nodes,
                   **kw).raw
