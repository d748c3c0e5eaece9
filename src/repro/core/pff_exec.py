"""Real multi-device PFF executor: the paper's schedules on actual devices.

Where ``repro.core.pff`` times the canonical chapter schedule once and
REPLAYS the timings through an event-driven simulator, this module RUNS
the Single-Layer, All-Layers and Federated schedules concurrently across
an actual ``jax.devices()`` set — one device per paper "node"
(``launch.mesh.pff_node_devices``; on CI/CPU export
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before importing
jax). The chapter-task DAG and the per-schedule node assignments come
from ``repro.core.pff_dag`` — the same module the simulator replays.

Execution model: the per-schedule drivers dispatch tasks in the DAG's
canonical topological order (the same order ``pff_dag.build_tasks``
lists; node assignments come from ``pff_dag.node_of`` & co — the
dependency EDGES are realized implicitly as JAX data dependencies, which
``tests/test_pff_exec.py``'s ``test_dag_topological_order`` plus the
bit-exactness oracle keep honest against the DAG module) and never
block. Every task's inputs are ``jax.device_put`` onto
its owning node (activation/weight hand-off along the DAG edges), the
jitted chapter trainers (``ff_mlp.train_layer_chapter`` & co — the fused
Pallas ``ff_dense`` hot loop, with donated param/opt buffers) are
dispatched asynchronously, and JAX's async runtime overlaps nodes: node
i crunches chapter c while node i+1 already trains layer 0 of chapter
c+1. Makespan is wall-clock from first dispatch to the last weight
buffer becoming ready.

Bit-exactness: the DAG fixes the weight-update order, so the executor
reuses the EXACT eager/jitted call sequence of the sequential trainer
per task — same keys, same learning-rate arrays, same kernel path — and
therefore reproduces ``pff.train_ff_mlp``'s weight stream bit-exactly
for All-Layers (and Federated vs ``pff.train_federated``). That is the
correctness oracle enforced by ``tests/test_pff_exec.py``. AdaptiveNEG
negatives are regenerated with "publish" semantics (the DAG's
``strict_neg`` gating: chapter c+1 trains on negatives from the full
chapter-c model), which is exactly what the sequential trainer does;
RandomNEG negatives depend only on the PRNG key, so each node
regenerates its own locally — parallel, and still bit-exact.

Double-buffered hand-off: with ``overlap=True`` (the default) every
cross-node ``device_put`` along a DAG edge is issued the moment its
producing task has been DISPATCHED, not when its consuming task needs
the data — per-(tree, node) transfer slots (``_Handoff``) so the next
chapter's weights/negatives stream onto their destination node while
the current chapter's compute is still in flight. The prefetch targets
come from ``pff_dag.handoff_targets`` / ``chapter_train_nodes`` — the
same DAG edges the dispatch order walks — and every slot is tagged with
the producing chapter (version): a consumer takes the prefetched copy
only when the version matches the state it would have pulled on demand,
so the overlapped weight stream is the bit-exact SAME weight stream
(``device_put`` moves bits, the version gate proves they are the right
ones; the on/off A-B case in ``tests/test_pff_exec.py`` enforces it).
``overlap=False`` restores the serialize-on-demand hand-off for A/B
measurement.

``benchmarks/pff_exec.py`` records this executor's measured makespan
next to the simulator's prediction (``BENCH_pff_exec.json``), with
overlap on and off, plus the hand-off transfer counts.

All strategy variation (negatives / goodness / classifier) comes from
the ``repro.core.strategies`` registries — the same objects the
sequential trainer consumes — including the Performance-Optimized
goodness path (paper §4.4): its per-layer local-head task is a
per-layer dependent of the train task in the DAG
(``pff_dag.build_tasks(has_local_heads=True)``), owned by the same
node, and the executor dispatches it FUSED with its train task (the
§4.4 objective is one two-layer-deep backprop call), which preserves
the DAG order and the bit-exactness oracle.

Resilience (``faults.ResilienceConfig`` via ``api.fit(...,
resilience=...)``): because the DAG has no backward edges, a completed
chapter is a CONSISTENT recovery line (``pff_dag.replay_frontier``) —
the executor exploits that four ways. (1) chapter-granular
checkpointing: after chapter c an atomic manifest (node states + head +
published negatives + hand-off versions, ``repro.checkpoint`` with
``meta``/``strict``) lands in ``checkpoint_dir``; ``run(resume_from=
...)`` replays from the last completed chapter BIT-EXACTLY — the
kill-then-resume gates in ``tests/test_pff_faults.py`` and
``benchmarks/pff_faults.py`` prove the resumed weight stream equals the
uninterrupted one. (2) retry with exponential backoff: an injected
crash (``faults.FaultPlan`` — deterministic, schedule-addressable)
fires at task ENTRY, before the hand-off take / buffer donation, so a
retry re-dispatches the identical task. (3) graceful degradation: on
budget exhaustion the node is declared dead — all_layers/single_layer
remap its logical node to a surviving device (same math, still
bit-exact); federated rolls the chapter back and drops the dead node's
shard for that round. (4) elastic federated membership: a
``membership(round)`` callback names the live nodes each round; every
live node trains a COPY of the round-start model on its own shard and
the aggregator averages weighted by live shard sizes — bit-checked
against the sequential reference ``pff.run_elastic_federated`` (both
call ``pff.elastic_node_round``). ``ExecResult.resilience`` reports
retries, reassignments, checkpoint/restore cost and faults injected.

Observability (``repro.obs``): ``run(trace=...)`` records one
``task:<kind>`` span per DAG task (attrs kind/layer/chapter/node,
and the id of the device its output landed on),
``handoff:*`` events from the transfer slots, the ``resilience:retry``
event and counters from the retry/checkpoint machinery, and a closing
``run`` span carrying the DAG shape — everything ``obs.analyze`` needs
to rebuild the critical path over ``pff_dag.deps`` and attribute
hand-off cost on/off it. The old ``profile=True`` path now rides the
tracer: ``ExecResult.records`` / ``node_busy`` are derived from the
task spans (identical order and semantics), and the untraced default
pays only no-op tracer calls (``obs.trace.NOOP``).

The MLP executor's spans are lexical, so each is also a profiler
annotation (traced or not; ``obs.trace``): ``fit:init`` (set-up),
``chapter`` and ``chapter:inputs``, the task spans with ``task:wait``
(the timeline mode's block) and ``neg:score`` inside, ``task:handoff``
(the next layer's inputs) and ``fit:eval`` — the same names the
sequential trainer (``pff.run_chapter_schedule``) opens.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import checkpoint as checkpoint_lib, data as data_lib, optim
from repro.core import faults as faults_lib
from repro.core import ff, ff_mlp, pff, pff_dag, pff_lm, strategies
from repro.launch import mesh as mesh_lib
from repro.obs import trace as obs_trace


def _device_id(out):
    """Id of the device holding a task's output (its first leaf) — the
    ``device`` attr of task spans, which shows where each node's work
    really ran."""
    leaves = jax.tree_util.tree_leaves(out)
    return next(iter(leaves[0].devices())).id if leaves else None


@dataclasses.dataclass
class ExecResult:
    params: dict
    schedule: str
    num_nodes: int
    makespan: float                        # seconds, first dispatch -> ready
    test_acc: Optional[float]              # None for LM runs (use eval CE)
    records: Optional[List[pff.TaskRecord]]  # per-task durations (traced)
    node_busy: Optional[List[float]]         # per-node busy seconds (traced)
    handoff: Optional[dict] = None           # transfer-slot counters
    resilience: Optional[dict] = None        # retry/checkpoint/fault stats
    trace: Optional[object] = None           # obs.trace.Tracer, if traced


def _records_from_spans(tracer, span0, num_nodes):
    """(records, node_busy) derived from the ``task:*`` spans of one
    run — the traced-profile view both executors share (same order and
    blocked durations the old ``profile=True`` path collected, so
    ``pff.simulate_schedule`` replays traced runs unchanged)."""
    records = []
    node_busy = [0.0] * num_nodes
    for s in tracer.snapshot(start=span0):
        if not s.name.startswith("task:"):
            continue
        a = s.attrs
        records.append(pff.TaskRecord(a["kind"], a["layer"],
                                      a["chapter"], s.duration))
        node_busy[a["node"]] += s.duration
    return records, node_busy


class _ShardDropped(Exception):
    """Raised inside a federated chapter when its owning node dies with
    the retry budget exhausted — the run loop rolls the chapter back and
    drops that node's shard for the round (graceful degradation)."""

    def __init__(self, node):
        super().__init__(f"node {node} dead; shard dropped this round")
        self.node = node


def checkpoint_path(directory: str, chapter: int) -> str:
    """Canonical chapter-manifest filename."""
    return os.path.join(directory, f"pff_chapter_{chapter:04d}.npz")


def latest_checkpoint(directory: str) -> Optional[str]:
    """Newest (highest-chapter) manifest in ``directory``, or None."""
    paths = glob.glob(os.path.join(directory, "pff_chapter_*.npz"))
    if not paths:
        return None
    return max(paths, key=lambda p: int(
        re.search(r"pff_chapter_(\d+)\.npz$", p).group(1)))


class _Handoff:
    """Double-buffered transfer slots for the DAG hand-off.

    ``prefetch`` enqueues an async ``device_put`` of a pytree onto its
    future consumer's device and parks it under ``(name, node)`` tagged
    with the producing chapter. ``take`` returns the parked copy iff the
    version matches what the consumer would have pulled on demand —
    otherwise (or with overlap disabled) it falls back to a synchronous-
    path ``device_put`` exactly like the pre-overlap executor. Slots
    whose trees will be DONATED by the consuming jit are popped on hit
    (``pop=True``) so an invalidated buffer can never be re-served;
    params-only slots stay parked so several same-chapter consumers on
    one node share a single transfer.

    Counters (the dispatch-count measurement in ``BENCH_pff_exec.json``):
    ``prefetch_issued``/``prefetch_hits`` and the fallback pulls, split
    into ``pulls_cross`` (a real inter-node transfer on the consumer's
    critical path — what double-buffering exists to hide) vs
    ``pulls_local`` (same-device no-ops).

    Fault hooks (``fault_cb`` — ``faults.FaultPlan.handoff_action``):
    a "drop" fault loses the transfer (the slot is never parked — the
    consumer's on-demand fallback IS the recovery path, so the weight
    stream cannot change); a "corrupt" fault parks the copy with its
    float leaves NaN-poisoned and the slot's integrity flag set,
    modelling a checksum failure on receive. ``take`` deletes a
    corrupt-flagged slot and re-pulls fresh bits instead of serving it
    (``corrupt_detected``); serving the poisoned tree would NaN the
    weights, so a regression here fails the bit-exactness oracle loudly.
    """

    def __init__(self, devices, enabled: bool, fault_cb=None,
                 tracer=obs_trace.NOOP):
        self.devices = devices
        self.enabled = enabled
        self.fault_cb = fault_cb
        self.tracer = tracer
        self.slots: Dict[tuple, tuple] = {}   # (name, node) -> (ver, tree, corrupt)
        self.stats = {"prefetch_issued": 0, "prefetch_hits": 0,
                      "pulls_cross": 0, "pulls_local": 0,
                      "prefetch_dropped": 0, "corrupt_injected": 0,
                      "corrupt_detected": 0}

    def _event(self, name, slot_name, node, version):
        # the prefetch and pull counters mirror onto the tracer
        # timeline, so the analyzer's on/off-critical-path attribution
        # reconciles with these stats exactly (a trace-smoke gate)
        if self.tracer.enabled:
            self.tracer.event(name, tree=str(slot_name[0]), node=node,
                              version=version)

    @staticmethod
    def _poison(leaf):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
            return jnp.full_like(leaf, jnp.nan)
        return leaf

    def prefetch(self, name, node: int, version: int, tree):
        if not self.enabled:
            return
        corrupt = False
        if self.fault_cb is not None:
            action = self.fault_cb(name, node, version)
            if action == "drop":
                self.stats["prefetch_dropped"] += 1
                return
            if action == "corrupt":
                tree = jax.tree_util.tree_map(self._poison, tree)
                self.stats["corrupt_injected"] += 1
                corrupt = True
        self.slots[(name, node)] = (
            version, jax.device_put(tree, self.devices[node]), corrupt)
        self.stats["prefetch_issued"] += 1
        self._event("handoff:prefetch_issue", name, node, version)

    def _on_device(self, tree, dev) -> bool:
        leaves = jax.tree_util.tree_leaves(tree)
        try:
            return bool(leaves) and leaves[0].devices() == {dev}
        except AttributeError:                      # non-committed leaf
            return False

    def take(self, name, node: int, version: int, tree, *,
             pop: bool = False):
        slot = self.slots.get((name, node))
        if slot is not None and slot[0] == version:
            if slot[2]:
                # integrity gate: poisoned bits are never served
                del self.slots[(name, node)]
                self.stats["corrupt_detected"] += 1
            else:
                if pop:
                    del self.slots[(name, node)]
                self.stats["prefetch_hits"] += 1
                self._event("handoff:prefetch_hit", name, node, version)
                return slot[1]
        dev = self.devices[node]
        local = self._on_device(tree, dev)
        self.stats["pulls_local" if local else "pulls_cross"] += 1
        self._event("handoff:pull_local" if local else "handoff:pull_cross",
                    name, node, version)
        return jax.device_put(tree, dev)

    def drop_node_slots(self, node: int):
        """Forget parked copies destined for a dead node's device."""
        for key in [k for k in self.slots if k[1] == node]:
            del self.slots[key]


class PFFExecutor:
    """Runs one PFF schedule for real on ``num_nodes`` devices.

    ``run()`` re-initializes params from ``cfg.seed`` every call, so
    calling it twice and timing the second run measures a warm cache
    (all per-device executables compiled) — what the benchmark does.
    """

    def __init__(self, cfg, task: data_lib.ImageTask, schedule: str,
                 num_nodes: int, *, devices=None, overlap: bool = True,
                 resilience: Optional[faults_lib.ResilienceConfig] = None):
        if schedule not in pff_dag.SCHEDULES:
            raise ValueError(f"unknown schedule {schedule!r}; expected "
                             f"one of {pff_dag.SCHEDULES}")
        if schedule == "sequential" and num_nodes != 1:
            raise ValueError("sequential means num_nodes=1")
        self.cfg = cfg
        self.task = task
        self.schedule = schedule
        self.num_nodes = num_nodes
        self.overlap = overlap
        self.devices = (list(devices)[:num_nodes] if devices is not None
                        else mesh_lib.pff_node_devices(num_nodes))
        self._devices_init = list(self.devices)   # undo dead-node remaps
        self.n_layers = len(cfg.layer_sizes) - 1
        self.C = max(cfg.epochs // cfg.splits, 1)
        self.impl = ff_mlp.kernel_impl(cfg)
        self.good = strategies.goodness.get(cfg.goodness_fn)
        self.neg = strategies.negatives.get(cfg.neg_mode)
        self.cls = strategies.classifier.get(cfg.classifier)
        self.has_head = self.cls.trains_head
        self.has_neg = self.good.uses_negatives and self.neg.regenerates
        self.resilience = resilience
        if resilience is not None and resilience.membership is not None:
            if schedule != "federated":
                raise ValueError(
                    "elastic membership is a Federated-PFF mode; got "
                    f"schedule={schedule!r}")
            if self.has_neg and self.neg.needs_scores:
                raise ValueError(
                    f"elastic federated membership supports key-only "
                    f"negative strategies; {cfg.neg_mode!r} needs "
                    f"full-model scores")
        self._tracer = obs_trace.NOOP
        self._block = False
        self._const_dirty = False
        self._setup_constants()

    # ---- per-device constants (replicated once, before any timing) -------
    def _setup_constants(self):
        cfg, task = self.cfg, self.task
        key = jax.random.PRNGKey(cfg.seed)
        self.key = key
        self.kneg = jax.random.fold_in(key, 999)
        shards = None
        if self.schedule == "federated":
            # same shard construction as the sequential federated
            # trainer: chapter c uses shard c % N — which IS node
            # c % N's own shard, so training data never crosses a node
            # boundary.
            shards = pff.federated_shards(cfg, task, self.num_nodes)
        self._const: Dict[int, dict] = {}
        for node, dev in enumerate(self.devices):
            x_d = jax.device_put(task.x_train, dev)
            y_d = jax.device_put(task.y_train, dev)
            c = {"x": x_d, "y": y_d,
                 "idx": (jax.device_put(shards[node], dev)
                         if shards is not None else None)}
            if self.good.uses_negatives:
                c["xp0"] = ff_mlp._norm(ff.overlay_label(
                    x_d, y_d, cfg.num_classes))
                c["xn0_init"] = ff_mlp._norm(self.neg.fn(
                    self.kneg, cfg, None, x_d, y_d, None))
            else:
                c["xk0"] = ff_mlp._norm(ff.overlay_neutral(
                    x_d, cfg.num_classes))
            if self.has_head:
                c["x_neutral"] = ff.overlay_neutral(x_d, cfg.num_classes)
            self._const[node] = c
        jax.block_until_ready([v for c in self._const.values()
                               for v in c.values() if v is not None])

    # ---- helpers ---------------------------------------------------------
    def _lrs(self, chapter):
        cfg, C = self.cfg, self.C
        lrs = jnp.asarray([
            optim.cooldown_lr(cfg.lr_ff, chapter * C + e, cfg.epochs,
                              cfg.cooldown_after) for e in range(C)],
            jnp.float32)
        return lrs, lrs * (cfg.lr_softmax / cfg.lr_ff)

    def _pull(self, tree, node):
        """Async hand-off of a param/opt pytree onto ``node``'s device."""
        return jax.device_put(tree, self.devices[node])

    def _layer_params(self, k, node):
        """Layer k's current params resident on ``node`` — prefetched by
        the producing train task when the DAG says this node consumes
        them, on-demand ``device_put`` otherwise."""
        return self._handoff.take(("params", k), node, self._ver[k],
                                  self._states[k][0])

    def _prefetch_state(self, k, chapter, state):
        """Publish train(k, chapter)'s output toward its DAG consumers
        while the producing node is still crunching (double-buffering)."""
        nxt, param_nodes = pff_dag.handoff_targets(
            self.schedule, self.num_nodes, n_layers=self.n_layers,
            splits=self.cfg.splits, layer=k, chapter=chapter,
            has_head=self.has_head,
            has_neg=self.has_neg and self.neg.needs_scores)
        if nxt is not None:
            self._handoff.prefetch(("state", k), nxt, chapter, state)
        for node in param_nodes:
            self._handoff.prefetch(("params", k), node, chapter, state[0])

    def _handoff_fwd(self, k, lp, acts):
        """Layer k forward + Hinton length-norm of every stream — the
        inter-layer hand-off. ``ff_mlp.fwd_norm`` is the exact call the
        sequential trainer makes (bit-exactness depends on it); the
        norm divide runs in the ``ff_dense`` kernel epilogue."""
        with obs_trace.annotate("task:handoff", layer=k,
                                rows=sum(a.shape[0] for a in acts)):
            return tuple(ff_mlp.fwd_norm(lp, a, impl=self.impl)
                         for a in acts)

    def _xn0_for(self, chapter, node):
        """The (full-size, normalized) negatives the sequential trainer
        would use for this chapter, resident on ``node``."""
        const = self._const[node]
        if not self.has_neg or chapter == 0:
            return const["xn0_init"]
        if not self.neg.needs_scores:
            # key-only — each node regenerates its own copy locally
            # (the paper's parallel per-node UpdateXNEG), bit-identical
            # to the sequential trainer's stream by PRNG determinism.
            return ff_mlp._norm(self.neg.fn(
                jax.random.fold_in(self.kneg, chapter - 1), self.cfg,
                None, const["x"], const["y"], None))
        # score-needing (AdaptiveNEG): published by chapter-(c-1)'s
        # neg_gen task (and prefetched to this node while chapter c-1
        # was still computing, when overlap is on)
        src_chapter, xn0 = self._neg
        assert src_chapter == chapter - 1, (src_chapter, chapter)
        return self._handoff.take(("neg",), node, src_chapter, xn0)

    def _chapter_inputs(self, chapter, node):
        """(acts, extras) exactly as the sequential trainer builds them:
        activations flow layer-to-layer, extras (labels) do not."""
        const = self._const[node]
        idx = const["idx"]
        if self.good.uses_negatives:
            xn0 = self._xn0_for(chapter, node)
            return ((const["xp0"] if idx is None else const["xp0"][idx],
                     xn0 if idx is None else xn0[idx]), ())
        return ((const["xk0"] if idx is None else const["xk0"][idx],),
                (const["y"] if idx is None else const["y"][idx],))

    def _task_span(self, kind, layer, chapter, node, **attrs):
        """The ``task:<kind>`` span one DAG task body runs in: the
        analyzer's critical path is built from these spans, and
        ``ExecResult.records`` / ``node_busy`` are derived from them
        after the run."""
        return self._tracer.span("task:" + kind, kind=kind, layer=layer,
                                 chapter=chapter, node=node, **attrs)

    def _finish_task(self, span, out):
        """End one DAG task inside its span: block (timeline mode only
        — real device time at the cost of overlap) and name the device
        its output landed on."""
        if self._block:
            with obs_trace.annotate("task:wait"):
                jax.block_until_ready(out)
        if self._tracer.enabled:
            span["device"] = _device_id(out)

    def _rtime(self, name, dt):
        """Resilience seconds: one mechanism feeds both ``_rstats``
        (surfaced via ``FitResult.resilience``) and the tracer's
        counters — the scattered ad-hoc timers folded onto the trace."""
        self._rstats[name] += dt
        self._tracer.counter(name, dt)

    # ---- resilience: fault consult, retry/backoff, death, checkpoints ----
    @property
    def _fault_plan(self):
        return (self.resilience.fault_plan
                if self.resilience is not None else None)

    def _resilient(self, kind, layer, chapter, node, body):
        """Run one task body under the resilience policy.

        Injected crashes fire at task ENTRY — before the hand-off take
        and therefore before any buffer donation or state mutation — so
        a retry re-dispatches the IDENTICAL task and the weight stream
        stays bit-exact. Only ``faults.InjectedFault`` is caught; real
        errors still propagate. On budget exhaustion the node is
        declared dead and the loop continues: the crash check skips dead
        nodes, so the body then runs on the reassigned device
        (all_layers / single_layer) — federated instead aborts the
        chapter via ``_ShardDropped`` (the caller rolls it back).
        """
        rc, plan = self.resilience, self._fault_plan
        if plan is not None and node not in self._dead:
            d = plan.delay_s(kind, layer, chapter, node)
            if d > 0:
                time.sleep(d)
        attempt = 0
        while True:
            try:
                if (plan is not None and node not in self._dead
                        and plan.should_crash(kind, layer, chapter, node)):
                    raise faults_lib.InjectedFault(
                        f"injected crash: {kind}(layer={layer}, "
                        f"chapter={chapter}) on node {node}")
                return body()
            except faults_lib.InjectedFault:
                t0 = time.perf_counter()
                if attempt < rc.max_retries:
                    time.sleep(rc.backoff_base_s
                               * rc.backoff_factor ** attempt)
                    attempt += 1
                    self._rstats["retries"] += 1
                    if self._tracer.enabled:
                        self._tracer.event(
                            "resilience:retry", kind=kind, layer=layer,
                            chapter=chapter, node=node, attempt=attempt)
                    self._rtime("recovery_time_s",
                                time.perf_counter() - t0)
                    continue
                self._declare_dead(node)
                self._rtime("recovery_time_s", time.perf_counter() - t0)
                if self.schedule == "federated":
                    raise _ShardDropped(node) from None

    def _declare_dead(self, node):
        """Retry budget exhausted: graceful degradation.

        all_layers / single_layer: remap the LOGICAL node to a surviving
        device (``self.devices`` is mutated in place — the hand-off
        shares the list) and re-place its replicated constants; the
        DAG's node assignments are untouched, so the same tasks run the
        same math on the new device — still bit-exact. What this models
        is scheduling-level task reassignment (state travels through
        the same hand-off/pull path as before). federated: no remap —
        the dead node's shard simply stops contributing.
        """
        self._dead.add(node)
        self._rstats["dead_nodes"].append(node)
        if self.schedule == "federated":
            self._rstats["shards_dropped"] += 1
            return
        live = [n for n in range(self.num_nodes) if n not in self._dead]
        if not live:
            raise RuntimeError("resilience: every node is dead")
        new_dev = self.devices[live[0]]
        self.devices[node] = new_dev
        self._const[node] = jax.device_put(self._const[node], new_dev)
        self._handoff.drop_node_slots(node)
        self._const_dirty = True
        self._rstats["reassignments"] += 1

    def _maybe_kill(self, chapter, phase):
        plan = self._fault_plan
        if plan is not None and plan.kill_now(chapter, phase):
            # a HARD kill — no cleanup, no atexit — so the kill-then-
            # resume tests exercise real crash recovery. Sync first so
            # the pre-kill checkpoint (phase="post") is really on disk.
            jax.block_until_ready([s[0] for s in self._states])
            print(f"[pff_exec] injected kill at chapter {chapter} "
                  f"({phase})", flush=True)
            os._exit(faults_lib.KILL_EXIT)

    # ---- chapter-granular checkpoint / resume ----------------------------
    def _ckpt_has_neg(self):
        """Published negatives are part of the recovery line only for
        score-needing strategies — key-only ones regenerate from the
        PRNG, so persisting them would be dead weight."""
        return self.has_neg and self.neg.needs_scores

    def _ckpt_template(self):
        """Restore template derived purely from the config — a resumed
        process can rebuild it without reading the manifest first."""
        cfg = self.cfg
        params = ff_mlp.init(jax.random.PRNGKey(cfg.seed), cfg)
        opt = ff_mlp.opt_init(params)
        template = {"states": [self.good.get_state(params, opt, k)
                               for k in range(self.n_layers)],
                    "head": (params["head"], opt["head"])}
        if self._ckpt_has_neg():
            x = jnp.asarray(self.task.x_train)
            template["neg"] = jax.ShapeDtypeStruct(x.shape, jnp.float32)
        return template

    def _write_checkpoint(self, chapter):
        rc = self.resilience
        if rc is None or rc.checkpoint_dir is None:
            return
        last = chapter == self.cfg.splits - 1
        if (chapter + 1) % rc.checkpoint_every != 0 and not last:
            return
        cfg = self.cfg
        t0 = time.perf_counter()
        tree = {"states": list(self._states), "head": self._head}
        if self._ckpt_has_neg():
            tree["neg"] = self._neg[1]
        meta = {"chapter": chapter, "schedule": self.schedule,
                "num_nodes": self.num_nodes, "splits": cfg.splits,
                "seed": cfg.seed, "goodness_fn": cfg.goodness_fn,
                "neg_mode": cfg.neg_mode, "classifier": cfg.classifier,
                "layer_sizes": list(cfg.layer_sizes),
                "neg_chapter": self._neg[0],
                "ver": [int(v) for v in self._ver],
                "head_ver": int(self._head_ver)}
        # checkpoint.save syncs leaves to host — that device->host drain
        # is the per-chapter overhead BENCH_pff_faults.json measures
        checkpoint_lib.save(checkpoint_path(rc.checkpoint_dir, chapter),
                            tree, step=chapter, meta=meta,
                            tracer=self._tracer)
        kept = sorted(glob.glob(os.path.join(rc.checkpoint_dir,
                                             "pff_chapter_*.npz")))
        for old in kept[:-rc.keep_last] if rc.keep_last > 0 else []:
            os.remove(old)
        self._rstats["checkpoints_written"] += 1
        self._rtime("checkpoint_time_s", time.perf_counter() - t0)

    def _restore(self, resume_from):
        """Load a chapter manifest and return its completed chapter."""
        path = resume_from
        if os.path.isdir(path):
            path = latest_checkpoint(path)
            if path is None:
                raise FileNotFoundError(
                    f"no pff_chapter_*.npz manifest in {resume_from!r}")
        cfg = self.cfg
        tree, _, meta = checkpoint_lib.restore(
            path, self._ckpt_template(), strict=True, with_meta=True,
            tracer=self._tracer)
        if meta is None:
            raise ValueError(f"{path!r} carries no manifest meta — not a "
                             f"PFF chapter checkpoint")
        want = {"schedule": self.schedule, "num_nodes": self.num_nodes,
                "splits": cfg.splits, "seed": cfg.seed,
                "goodness_fn": cfg.goodness_fn, "neg_mode": cfg.neg_mode,
                "classifier": cfg.classifier,
                "layer_sizes": list(cfg.layer_sizes)}
        for k, v in want.items():
            if meta.get(k) != v:
                raise ValueError(
                    f"checkpoint {path!r} was written by a different run: "
                    f"{k}={meta.get(k)!r} != {v!r}")
        self._states = list(tree["states"])
        self._head = tree["head"]
        if self._ckpt_has_neg():
            self._neg = (int(meta["neg_chapter"]), tree["neg"])
        self._ver = [int(v) for v in meta["ver"]]
        self._head_ver = int(meta["head_ver"])
        return int(meta["chapter"])

    # ---- per-task bodies (each mirrors the sequential trainer) -----------
    def _train_task(self, k, chapter, node, acts, extras, lrs, kc):
        if self.resilience is None:
            return self._train_task_body(k, chapter, node, acts, extras,
                                         lrs, kc)
        out = self._resilient(
            "train", k, chapter, node,
            lambda: self._train_task_body(k, chapter, node, acts, extras,
                                          lrs, kc))
        if k == 0:
            # "mid-chapter" kill point: the chapter's first train task
            # has completed but the chapter has not — resume must replay
            # the partially-executed chapter from the previous manifest
            self._maybe_kill(chapter, "mid")
        return out

    def _head_task(self, chapter, node, idx, lrs_head, kc):
        if self.resilience is None:
            return self._head_task_body(chapter, node, idx, lrs_head, kc)
        return self._resilient(
            "head", self.n_layers, chapter, node,
            lambda: self._head_task_body(chapter, node, idx, lrs_head,
                                         kc))

    def _neg_task(self, chapter, node):
        if self.resilience is None:
            return self._neg_task_body(chapter, node)
        return self._resilient(
            "neg_gen", -1, chapter, node,
            lambda: self._neg_task_body(chapter, node))

    def _train_task_body(self, k, chapter, node, acts, extras, lrs, kc):
        """One chapter-train task via the goodness strategy. For
        Performance-Optimized goodness this call carries the layer's
        local_head task fused in (see module docstring); it records as
        ONE train task — exactly like the sequential trainer's timing.
        The incoming state was prefetched onto ``node`` while the
        previous chapter computed (popped: the jit donates its buffers);
        the outgoing state is immediately published toward its DAG
        consumers."""
        steps = ff_mlp._num_batches(acts[0].shape[0],
                                    self.cfg.batch_size) * self.C
        with self._task_span("train", k, chapter, node,
                             steps=steps) as span:
            if self.resilience is not None:
                # the driver computed acts/extras before this (possibly
                # retried) dispatch — if the node was reassigned to a
                # surviving device mid-retry they still live on the
                # dead one, so re-place them (same-device no-op
                # otherwise)
                acts = jax.device_put(acts, self.devices[node])
                extras = jax.device_put(extras, self.devices[node])
            state = self._handoff.take(("state", k), node, self._ver[k],
                                       self._states[k], pop=True)
            state = self.good.train_chapter(
                state, acts, extras, lrs, jax.random.fold_in(kc, k),
                cfg=self.cfg, epochs=self.C)
            self._states[k] = state
            self._ver[k] = chapter
            self._prefetch_state(k, chapter, state)
            if self._publish is not None:
                # push the freshly-trained layer onto the serving bus
                # the moment its chapter-train task completes — FF's
                # layer locality is what makes the mid-run per-layer
                # hot-swap sound (no global backward pass to invalidate
                # it). The bus copies before parking; the donated
                # buffers stay ours.
                self._publish.publish_layer(k, chapter,
                                            self.good.export([state]))
            self._finish_task(span, state[0])
        return state[0]

    def _head_task_body(self, chapter, node, idx, lrs_head, kc):
        const = self._const[node]
        with self._task_span("head", self.n_layers, chapter,
                             node) as span:
            xn_all = (const["x_neutral"] if idx is None
                      else const["x_neutral"][idx])
            # pull every layer onto the head node (no-op when already
            # there, e.g. all_layers; prefetched hand-off for
            # single_layer)
            feats = ff_mlp.softmax_feats(
                [self._layer_params(k, node)
                 for k in range(self.n_layers)], xn_all, impl=self.impl)
            head, op = self._handoff.take(("head",), node, self._head_ver,
                                          self._head, pop=True)
            head, op = ff_mlp.train_head_chapter(
                head, op, feats,
                const["y"] if idx is None else const["y"][idx],
                lrs_head, jax.random.fold_in(kc, 77),
                batch=self.cfg.batch_size, epochs=self.C)
            self._head = (head, op)
            self._head_ver = chapter
            if self._publish is not None:
                self._publish.publish_head(chapter, head)
            if chapter + 1 < self.cfg.splits:
                nxt = pff_dag.head_node_of(self.schedule, self.num_nodes,
                                           n_layers=self.n_layers,
                                           chapter=chapter + 1)
                if nxt != node:
                    self._handoff.prefetch(("head",), nxt, chapter,
                                           (head, op))
            self._finish_task(span, head["w"])

    def _neg_task_body(self, chapter, node):
        """Score-needing (AdaptiveNEG) regeneration from the full
        chapter-c model, published for the next chapter
        ("UpdateXNEG(publish=True)" — the DAG's strict_neg gating,
        matching the sequential trainer)."""
        const = self._const[node]
        with self._task_span("neg_gen", -1, chapter, node) as span:
            params = {"layers": [self._layer_params(k, node)
                                 for k in range(self.n_layers)]}
            with self._tracer.span("neg:score", chapter=chapter,
                                   rows=const["x"].shape[0]):
                scores = pff._class_scores_chunked(params, const["x"],
                                                   self.cfg)
            xn0 = ff_mlp._norm(self.neg.fn(
                jax.random.fold_in(self.kneg, chapter), self.cfg, params,
                const["x"], const["y"], scores))
            self._neg = (chapter, xn0)
            # publish toward every node that trains chapter c+1 while
            # the current chapter's tail (head task etc.) is still in
            # flight
            if chapter + 1 < self.cfg.splits:
                for nxt in pff_dag.chapter_train_nodes(
                        self.schedule, self.num_nodes, self.n_layers,
                        chapter=chapter + 1):
                    if nxt != node:
                        self._handoff.prefetch(("neg",), nxt, chapter,
                                               xn0)
            self._finish_task(span, xn0)

    # ---- schedule drivers ------------------------------------------------
    def _run_chapter_owned(self, chapter):
        """all_layers / federated / sequential: one node runs the whole
        chapter, computing its own forward features as it trains."""
        node = pff_dag.node_of(self.schedule, self.num_nodes, layer=0,
                               chapter=chapter)
        idx = self._const[node]["idx"]
        with self._tracer.span("chapter:inputs", chapter=chapter):
            lrs, lrs_head = self._lrs(chapter)
            kc = jax.random.fold_in(self.key, chapter)
            acts, extras = self._chapter_inputs(chapter, node)
        for k in range(self.n_layers):
            lp = self._train_task(k, chapter, node, acts, extras, lrs,
                                  kc)
            if k + 1 < self.n_layers:
                if self.resilience is not None:
                    # a mid-chapter reassignment leaves this loop's acts
                    # on the dead node's device while lp lands on the
                    # surviving one — re-place (same-device no-op)
                    acts = jax.device_put(acts, self.devices[node])
                acts = self._handoff_fwd(k, lp, acts)
        if self.has_head:
            self._head_task(chapter, node, idx, lrs_head, kc)
        if self.has_neg and self.neg.needs_scores:
            self._neg_task(chapter, node)

    def _run_chapter_single_layer(self, chapter):
        """single_layer: node k owns layer k and re-runs the forward
        pass of layers < k over the train set (Algorithm 1 lines 3-5) —
        the load imbalance the paper observes. Weight hand-off: node k
        pulls layers 0..k-1's chapter-c weights as they appear."""
        with self._tracer.span("chapter:inputs", chapter=chapter):
            lrs, lrs_head = self._lrs(chapter)
            kc = jax.random.fold_in(self.key, chapter)
        for k in range(self.n_layers):
            node = pff_dag.node_of(self.schedule, self.num_nodes,
                                   layer=k, chapter=chapter)
            with self._tracer.span("chapter:inputs", chapter=chapter):
                acts, extras = self._chapter_inputs(chapter, node)
            for j in range(k):       # Algorithm-1 forward recompute
                acts = self._handoff_fwd(j, self._layer_params(j, node),
                                         acts)
            self._train_task(k, chapter, node, acts, extras, lrs, kc)
        if self.has_head:
            node = pff_dag.head_node_of(self.schedule, self.num_nodes,
                                        n_layers=self.n_layers,
                                        chapter=chapter)
            self._head_task(chapter, node, None, lrs_head, kc)
        if self.has_neg and self.neg.needs_scores:
            # the LAST node holds the full model freshest: it generates
            # and publishes for everyone (the paper's serialization).
            self._neg_task(chapter,
                           pff_dag.neg_node_of(self.schedule,
                                               self.num_nodes,
                                               chapter=chapter))

    # ---- elastic federated rounds (resilience.membership) ----------------
    def _run_round_elastic(self, r):
        """One elastic Federated-PFF round: every live node trains a
        COPY of the round-start model on its own shard (concurrently —
        the dispatches are async and land on distinct devices), then the
        aggregator replaces the global model with the live results
        averaged by live shard sizes. The per-node math and the
        aggregation walk nodes in sorted order and go through
        ``pff.elastic_node_round`` / ``pff.weighted_average_trees`` —
        the EXACT calls of the sequential reference
        ``pff.run_elastic_federated`` — so the multi-device round is
        bit-identical to the single-device one."""
        rc = self.resilience
        live = [n for n in pff._check_membership(rc.membership(r),
                                                 self.num_nodes, r)
                if n not in self._dead]
        if not live:
            self._rstats["chapters_skipped"] += 1
            self._rstats["elastic_rounds"].append(
                {"round": r, "live": [], "weights": []})
            return
        lrs, lrs_head = self._lrs(r)
        kr = jax.random.fold_in(self.key, r)
        # place per-node copies FIRST: the chapter trainers donate their
        # buffers, and a same-device device_put aliases — without the
        # copies node B's round would consume node A's donated input
        placed = {}
        for node in live:
            dev = self.devices[node]
            placed[node] = (
                [jax.tree_util.tree_map(jnp.copy,
                                        jax.device_put(s, dev))
                 for s in self._states],
                jax.tree_util.tree_map(jnp.copy,
                                       jax.device_put(self._head, dev)))
        per_node = {}
        first_done = False
        for node in live:
            const = self._const[node]
            idx = const["idx"]
            acts, extras = self._chapter_inputs(r, node)
            st0, head0 = placed[node]

            def body(node=node, const=const, idx=idx, acts=acts,
                     extras=extras, st0=st0, head0=head0):
                with self._task_span("round", -1, r, node) as span:
                    out = pff.elastic_node_round(
                        self.good, self.cfg, st0, head0, acts, extras,
                        lrs, lrs_head, jax.random.fold_in(kr, node),
                        epochs=self.C, impl=self.impl,
                        y=const["y"][idx] if self.has_head else None,
                        x_neutral=(const["x_neutral"][idx]
                                   if self.has_head else None),
                        train_head=self.has_head)
                    self._finish_task(span, out[0][0][0])
                return out

            try:
                per_node[node] = self._resilient("round", -1, r, node,
                                                 body)
            except _ShardDropped:
                continue
            if not first_done:
                first_done = True
                self._maybe_kill(r, "mid")
        ok = [n for n in live if n in per_node]
        if not ok:
            self._rstats["chapters_skipped"] += 1
            self._rstats["elastic_rounds"].append(
                {"round": r, "live": [], "weights": []})
            return
        total = float(sum(int(self._const[n]["idx"].shape[0])
                          for n in ok))
        w = [int(self._const[n]["idx"].shape[0]) / total for n in ok]
        dev0 = self.devices[0]
        self._states = [pff.weighted_average_trees(
            [jax.device_put(per_node[n][0][k], dev0) for n in ok], w)
            for k in range(self.n_layers)]
        self._ver = [r] * self.n_layers
        if self.has_head:
            self._head = pff.weighted_average_trees(
                [jax.device_put(per_node[n][1], dev0) for n in ok], w)
            self._head_ver = r
        self._publish_snapshot(r)
        self._rstats["elastic_rounds"].append(
            {"round": r, "live": ok, "weights": w})

    # ---- entry point -----------------------------------------------------
    def _fresh_rstats(self):
        return {"retries": 0, "reassignments": 0, "dead_nodes": [],
                "checkpoints_written": 0, "checkpoint_time_s": 0.0,
                "restore_time_s": 0.0, "resumed_from_chapter": None,
                "recovery_time_s": 0.0, "faults_injected": {},
                "shards_dropped": 0, "chapters_skipped": 0,
                "elastic_rounds": None}

    def _publish_snapshot(self, version: int):
        """Publish the CURRENT full model (every layer + head) at one
        version — the initial pre-training snapshot, a restored
        recovery line, and the elastic federated aggregate (whose
        layers all advance together)."""
        if self._publish is None:
            return
        for k, state in enumerate(self._states):
            self._publish.publish_layer(k, version,
                                        self.good.export([state]))
        if self.has_head:
            self._publish.publish_head(version, self._head[0])

    def run(self, *, profile: bool = False,
            resume_from: Optional[str] = None,
            publish=None, trace=None) -> ExecResult:
        """Executes the schedule once. ``profile=True`` blocks after
        every task to collect per-task ``TaskRecord``s (destroys the
        overlap, so use a separate non-profiled run for makespan).

        trace: an ``obs.trace.Tracer`` (or True for a fresh one) —
        records one ``task:<kind>`` span per DAG task plus hand-off /
        retry / checkpoint events and a closing ``run`` span, all on
        the tracer's clock domain (shared with the serve loop when
        ``train_while_serve`` passes one tracer to both).
        ``ExecResult.records`` / ``node_busy`` are DERIVED from the
        task spans whenever they carry real device time (``profile``,
        or a tracer with ``block_tasks`` — the default), so every
        timeline-traced run doubles as a profile run; with
        ``block_tasks=False`` spans measure dispatch only and records
        stay None. Use a FRESH tracer per run — the analyzer treats
        all task spans in a trace as one run.

        resume_from: a chapter manifest written by a previous run (or
        its directory — the newest manifest is used); training replays
        the DAG from the first chapter after it, bit-exactly (the
        restore cost rides the timed window, like initial placement).

        publish: a ``repro.serve.WeightBus`` (anything with
        ``publish_layer``/``publish_head``) — every chapter-train task
        pushes its freshly-trained layer the moment it completes, plus
        an initial snapshot before chapter 0 (or the restored chapter),
        so serving replicas hot-swap per layer mid-run. Publication is
        read-only with copy-on-publish: the weight stream stays
        bit-exact, publish or not.
        """
        cfg = self.cfg
        rc = self.resilience
        plan = self._fault_plan
        if plan is not None:
            plan.reset()
        tracer = obs_trace.as_tracer(trace)
        if profile and not tracer.enabled:
            tracer = obs_trace.Tracer()     # profile rides the tracer now
        self._tracer = tracer
        # timeline mode: block per task so span durations are device
        # time (profile's historical semantics — destroys overlap)
        self._block = profile or (tracer.enabled and tracer.block_tasks)
        timeline = tracer.enabled and self._block
        span0 = tracer.span_count()
        # undo a previous run's dead-node remapping (benchmarks reuse
        # the executor for warm-cache timing)
        self.devices[:] = self._devices_init
        with tracer.span("fit:init"):
            if self._const_dirty:
                self._setup_constants()
                self._const_dirty = False
            params = ff_mlp.init(jax.random.PRNGKey(cfg.seed), cfg)
            opt = ff_mlp.opt_init(params)
        self._dead: set = set()
        self._rstats = self._fresh_rstats()
        elastic = rc is not None and rc.membership is not None
        if elastic:
            self._rstats["elastic_rounds"] = []
        self._neg: Tuple[int, object] = (-1, None)
        self._ver = [-1] * self.n_layers       # chapter of last train(k)
        self._head_ver = -1
        self._publish = publish
        self._handoff = _Handoff(
            self.devices, self.overlap,
            fault_cb=plan.handoff_action if plan is not None else None,
            tracer=tracer)

        t_start = time.perf_counter()
        t_trace0 = tracer.now()
        # initial placement rides the timed window: it is part of the
        # schedule's real cost (the simulator's t=0 is the same state).
        self._states = [self.good.get_state(params, opt, k)
                        for k in range(self.n_layers)]
        self._head = (params["head"], opt["head"])
        start_chapter = 0
        if resume_from is not None:
            t0 = time.perf_counter()
            done = self._restore(resume_from)
            start_chapter = done + 1
            # sanity: the resume point must be a closed cut of the DAG
            pff_dag.replay_frontier(
                self.n_layers, cfg.splits, start_chapter,
                has_head=self.has_head, has_neg=self._ckpt_has_neg(),
                strict_neg=self._ckpt_has_neg())
            self._rstats["resumed_from_chapter"] = done
            self._rtime("restore_time_s", time.perf_counter() - t0)
        # serving replicas get a full pre-training (or restored-line)
        # snapshot before the first chapter task dispatches
        self._publish_snapshot(min([self._head_ver] + self._ver
                                   if self.has_head else self._ver))
        for chapter in range(start_chapter, cfg.splits):
            with tracer.span("chapter", chapter=chapter):
                if elastic:
                    self._run_round_elastic(chapter)
                elif self.schedule == "single_layer":
                    self._run_chapter_single_layer(chapter)
                elif (self.schedule == "federated" and self._dead
                      and pff_dag.node_of(self.schedule, self.num_nodes,
                                          layer=0, chapter=chapter)
                      in self._dead):
                    # the owning node died in an earlier chapter — its shard
                    # no longer contributes (the model rests this round)
                    self._rstats["chapters_skipped"] += 1
                elif self.schedule == "federated" and plan is not None:
                    # a mid-chapter death must not leave a half-trained
                    # chapter: snapshot the recovery line (copies — the
                    # chapter trainers donate the live buffers) and roll
                    # back on _ShardDropped
                    snap = jax.tree_util.tree_map(
                        jnp.copy, (list(self._states), self._head))
                    snap_meta = (list(self._ver), self._head_ver, self._neg)
                    try:
                        self._run_chapter_owned(chapter)
                    except _ShardDropped:
                        self._states, self._head = list(snap[0]), snap[1]
                        self._ver, self._head_ver, self._neg = (
                            list(snap_meta[0]), snap_meta[1], snap_meta[2])
                        self._rstats["chapters_skipped"] += 1
                else:
                    self._run_chapter_owned(chapter)
                self._write_checkpoint(chapter)
                if rc is not None:
                    self._maybe_kill(chapter, "post")
        outs = [s[0] for s in self._states] + [self._head[0]]
        if self._neg[1] is not None:
            outs.append(self._neg[1])
        jax.block_until_ready(outs)
        makespan = time.perf_counter() - t_start
        if tracer.enabled:
            # the closing run span carries the DAG shape so
            # obs.analyze can rebuild the exact pff_dag dependency
            # structure from the trace alone
            strict = self.has_neg and self.neg.needs_scores
            tracer.add_span(
                "run", t_trace0, schedule=self.schedule,
                num_nodes=self.num_nodes, splits=cfg.splits,
                n_layers=self.n_layers, has_head=self.has_head,
                has_neg=strict, strict_neg=strict,
                start_chapter=start_chapter, overlap=self.overlap,
                blocked=self._block, makespan_s=makespan)

        with tracer.span("fit:eval", rows=len(self.task.x_test)):
            final = self._pull({**self.good.export(self._states),
                                "head": self._head[0]}, 0)
            acc = ff_mlp.accuracy(final, self.task.x_test, self.task.y_test,
                                  cfg.num_classes, self.good.eval_mode(cfg),
                                  impl=self.impl)
        records = node_busy = None
        if timeline:
            records, node_busy = _records_from_spans(tracer, span0,
                                                     self.num_nodes)
        res_stats = None
        if rc is not None or resume_from is not None:
            res_stats = dict(self._rstats)
            res_stats["faults_injected"] = (dict(plan.fired)
                                            if plan is not None else {})
        self._block = False
        return ExecResult(final, self.schedule, self.num_nodes, makespan,
                          acc, records, node_busy,
                          dict(self._handoff.stats), res_stats,
                          tracer if tracer.enabled else None)


def run_pff_exec(cfg, task, schedule, num_nodes, *, devices=None,
                 profile=False) -> ExecResult:
    """Deprecated: use ``repro.api.fit(cfg, task, backend="executor",
    schedule=..., num_nodes=...)``."""
    import warnings

    warnings.warn("pff_exec.run_pff_exec is deprecated; use repro.api."
                  "fit(cfg, task, backend=\"executor\", schedule=..., "
                  "num_nodes=...)", DeprecationWarning, stacklevel=2)
    from repro import api
    return api.fit(cfg, task, backend="executor", schedule=schedule,
                   num_nodes=num_nodes, devices=devices,
                   profile=profile).raw


def params_bit_equal(a, b, *, with_head=False, with_local_heads=False):
    """True iff two FF-MLP params pytrees carry BIT-IDENTICAL layer
    (and optionally head / §4.4 local-head) weights — the executor's
    correctness oracle, shared by the selftest, the benchmark gate, and
    the example."""
    def leaves_equal(pa, pb):
        return all(bool(jnp.array_equal(pa[name], pb[name]))
                   for name in ("w", "b"))
    if len(a["layers"]) != len(b["layers"]):
        return False
    ok = all(leaves_equal(pa, pb)
             for pa, pb in zip(a["layers"], b["layers"]))
    if with_head:
        ok = ok and leaves_equal(a["head"], b["head"])
    if with_local_heads:
        ok = (ok and len(a["local_heads"]) == len(b["local_heads"])
              and all(leaves_equal(pa, pb) for pa, pb in
                      zip(a["local_heads"], b["local_heads"])))
    return ok


class LMExecutor:
    """Runs the LM chapter schedule (``core.pff_lm``) for real on
    ``num_nodes`` devices — the transformer sibling of ``PFFExecutor``,
    sharing its DAG (``pff_dag``), its ``_Handoff`` transfer slots, its
    tracer conventions, and its oracle discipline.

    Bit-exactness: every task replays the EXACT jitted calls of the
    sequential reference ``pff_lm.train_chapters`` — the same
    ``make_block_step``/``make_head_step`` programs, the same
    ``chapter_batches`` stream (regenerated locally per node: the
    ``data.Source`` purity contract means training data never crosses
    the hand-off), and the same global step counters. Blocks are
    addressed by global index over every group of the stack
    (``pff_lm.block_layout``). The programs take the block trees they
    read and the task's own state, so a task passes them what the
    ``_Handoff`` slots deliver: the Algorithm-1 frozen prefix params,
    the task's own block state, the head params. The executor's
    programs donate nothing: a slot's tree may be the same buffers the
    producer holds.

    Hand-off traffic per train(k, c): the block's full (params, m, v)
    state streams to the node that trains it in chapter c+1
    (``("state", k)``), and its params-only copy fans out to the
    Algorithm-1 forward-recompute / head consumers within chapter c
    (``("params", k)``) — both driven by ``pff_dag.handoff_targets``.
    The head task additionally publishes its full state toward the
    next chapter's head node (``("head",)``) and — tied embeddings
    only — its params toward every chapter-(c+1) train node
    (``("headp",)``): that is the DAG's ``head_feedback`` edge (the
    embed table every block task reads is the post-head one).
    """

    def __init__(self, cfg, source, schedule: str, num_nodes: int, *,
                 chapters: int, steps_per_chapter: int, batch: int = 8,
                 lr: float = 1e-3, head_lr: Optional[float] = None,
                 seed: int = 0, devices=None, overlap: bool = True):
        if schedule not in ("sequential", "single_layer", "all_layers"):
            raise ValueError(
                f"LM chapter executor supports sequential / single_layer"
                f" / all_layers; got {schedule!r} (federated LM shards "
                f"are ROADMAP work)")
        if schedule == "sequential" and num_nodes != 1:
            raise ValueError("sequential means num_nodes=1")
        self.cfg = cfg
        self.source = source
        self.schedule = schedule
        self.num_nodes = num_nodes
        self.chapters = chapters
        self.steps_per_chapter = steps_per_chapter
        self.overlap = overlap
        self.seed = seed
        self.devices = (list(devices)[:num_nodes] if devices is not None
                        else mesh_lib.pff_node_devices(num_nodes))
        self.n_layers = len(pff_lm.block_layout(cfg))
        self.tied = bool(cfg.tie_embeddings)
        self._head_names = pff_lm.head_param_names(cfg)
        self._step = pff_lm.make_block_step(cfg, lr=lr, seed=seed,
                                            donate=False)
        self._head_step = pff_lm.make_head_step(
            cfg, head_lr=lr if head_lr is None else head_lr, donate=False)
        self._data = pff_lm.chapter_batches(source, batch=batch,
                                            steps=steps_per_chapter)
        self._tracer = obs_trace.NOOP
        self._block = False

    def _finish_task(self, node, kind, layer, chapter, t0, out):
        if self._block:
            jax.block_until_ready(out)
        tr = self._tracer
        if tr.enabled:
            tr.add_span("task:" + kind, t0, kind=kind, layer=layer,
                        chapter=chapter, node=node, device=_device_id(out))

    def _blocks_on(self, node, chapter, n):
        """The chapter-``chapter`` params of blocks < n on ``node``."""
        out = []
        for j in range(n):
            # Algorithm-1 frozen prefix: block j at chapter `chapter`
            assert self._ver[j] == chapter, (j, self._ver[j], chapter)
            out.append(self._handoff.take(("params", j), node, chapter,
                                          self._blk[j][0]))
        return tuple(out)

    def _embed_on(self, node):
        """The input table a block task on ``node`` reads: the untied
        (never trained) table, or — tied — the post-head one that
        head(chapter-1) produced (the head_feedback edge)."""
        if not self.tied:
            return self._embed[node]
        return self._handoff.take(("headp",), node, self._head_ver,
                                  self._head[0])["embed"]

    def _train_task(self, k, chapter, node):
        """One per-block chapter task: take the frozen prefix and the
        block's state on the node, replay ``steps_per_chapter``
        sequential block steps with the sequential trainer's global
        step numbers, publish toward the DAG consumers."""
        t0 = self._tracer.now()
        dev = self.devices[node]
        prefix = self._blocks_on(node, chapter, k)
        up, um, uv = self._handoff.take(("state", k), node, self._ver[k],
                                        self._blk[k])
        embed = self._embed_on(node)
        base = (chapter * self.n_layers + k) * self.steps_per_chapter
        last = None
        for s, batch in enumerate(self._data(chapter, k)):
            up, um, uv, last, _ = self._step(
                embed, prefix, up, um, uv, jax.device_put(batch, dev), k,
                base + s + 1)
        self._blk[k] = (up, um, uv)
        self._ver[k] = chapter
        nxt, param_nodes = pff_dag.handoff_targets(
            self.schedule, self.num_nodes, n_layers=self.n_layers,
            splits=self.chapters, layer=k, chapter=chapter,
            has_head=True, has_neg=False)
        if nxt is not None:
            self._handoff.prefetch(("state", k), nxt, chapter,
                                   self._blk[k])
        for pn in param_nodes:
            self._handoff.prefetch(("params", k), pn, chapter,
                                   self._blk[k][0])
        self._finish_task(node, "train", k, chapter, t0, last)

    def _head_task(self, chapter, node):
        """The per-chapter softmax-head task: frozen forward through
        every chapter-c block, CE on the head subset (``pff_lm.
        make_head_step``), head state published toward chapter c+1."""
        t0 = self._tracer.now()
        dev = self.devices[node]
        blocks_p = self._blocks_on(node, chapter, self.n_layers)
        hp, hm, hv = self._handoff.take(("head",), node, self._head_ver,
                                        self._head)
        embed = None if self.tied else self._embed[node]
        base = chapter * self.steps_per_chapter
        last = None
        for s, batch in enumerate(self._data(chapter, self.n_layers)):
            hp, hm, hv, last = self._head_step(
                embed, blocks_p, hp, hm, hv, jax.device_put(batch, dev),
                base + s + 1)
        self._head = (hp, hm, hv)
        self._head_ver = chapter
        if chapter + 1 < self.chapters:
            nh = pff_dag.head_node_of(self.schedule, self.num_nodes,
                                      n_layers=self.n_layers,
                                      chapter=chapter + 1)
            if nh != node:
                self._handoff.prefetch(("head",), nh, chapter,
                                       self._head)
            if self.tied:
                for tn in pff_dag.chapter_train_nodes(
                        self.schedule, self.num_nodes, self.n_layers,
                        chapter=chapter + 1):
                    if tn != node:
                        self._handoff.prefetch(("headp",), tn, chapter,
                                               self._head[0])
        self._finish_task(node, "head", self.n_layers, chapter, t0, last)

    def run(self, *, profile: bool = False, trace=None) -> ExecResult:
        """Executes the LM chapter schedule once. Same tracer/profile
        semantics as ``PFFExecutor.run`` (``records``/``node_busy``
        derive from the ``task:*`` spans when they carry blocked
        durations); ``test_acc`` is None — LM quality is eval CE,
        computed by the facade (``api.fit`` → ``FitResult.eval_ce``)
        so the sequential and executor paths are scored identically."""
        cfg = self.cfg
        tracer = obs_trace.as_tracer(trace)
        if profile and not tracer.enabled:
            tracer = obs_trace.Tracer()
        self._tracer = tracer
        self._block = profile or (tracer.enabled and tracer.block_tasks)
        timeline = tracer.enabled and self._block
        span0 = tracer.span_count()
        bp, rest = pff_lm.init_state(cfg, self.seed)
        # canonical state partition: per-block unit slices + head subset
        self._blk = [(u, *(optim.adam_init(u)["m"] for _ in range(2)))
                     for u in bp]
        hp = {n: rest.pop(n) for n in self._head_names}
        self._head = (hp, *(optim.adam_init(hp)["m"] for _ in range(2)))
        self._ver = [-1] * self.n_layers
        self._head_ver = -1
        self._handoff = _Handoff(self.devices, self.overlap,
                                 tracer=tracer)
        t_start = time.perf_counter()
        t_trace0 = tracer.now()
        # initial placement rides the timed window (like PFFExecutor):
        # the untied input table on every node
        if not self.tied:
            self._embed = {node: jax.device_put(rest["embed"], dev)
                           for node, dev in enumerate(self.devices)}
        for c in range(self.chapters):
            for k in range(self.n_layers):
                self._train_task(k, c, pff_dag.node_of(
                    self.schedule, self.num_nodes, layer=k, chapter=c))
            self._head_task(c, pff_dag.head_node_of(
                self.schedule, self.num_nodes, n_layers=self.n_layers,
                chapter=c))
        outs = [s[0] for s in self._blk] + [self._head[0]]
        jax.block_until_ready(outs)
        makespan = time.perf_counter() - t_start
        if tracer.enabled:
            tracer.add_span(
                "run", t_trace0, schedule=self.schedule,
                num_nodes=self.num_nodes, splits=self.chapters,
                n_layers=self.n_layers, has_head=True, has_neg=False,
                strict_neg=False, head_feedback=self.tied,
                start_chapter=0, overlap=self.overlap,
                blocked=self._block, makespan_s=makespan)
        # reassemble the canonical full params pytree on node 0 —
        # exactly the tree the sequential trainer returns
        dev0 = self.devices[0]
        final = pff_lm.join_params(
            cfg, [jax.device_put(b[0], dev0) for b in self._blk],
            jax.device_put({**rest, **self._head[0]}, dev0))
        records = node_busy = None
        if timeline:
            records, node_busy = _records_from_spans(tracer, span0,
                                                     self.num_nodes)
        self._block = False
        return ExecResult(final, self.schedule, self.num_nodes, makespan,
                          None, records, node_busy,
                          dict(self._handoff.stats), None,
                          tracer if tracer.enabled else None)


# ---------------------------------------------------------------------------
# Self-test: weight-stream bit-equality vs the sequential trainer.
# Run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=4
# (tests/test_pff_exec.py does; `make pff-exec-smoke` exercises the same
# path through benchmarks/pff_exec.py).
# ---------------------------------------------------------------------------

def _case_setup(splits, n_train, neg_mode, classifier, goodness_fn):
    """The (cfg, task) every selftest case trains — shared by the
    bit-exactness matrix and the resilience cases so a fault-injected
    run is comparable against the same reference."""
    from repro.configs.ff_mlp import FFMLPConfig

    task = data_lib.mnist_like(n_train=n_train, n_test=200)
    # kernel_impl pinned to "ref": this matrix promises BIT-exactness,
    # and a populated tuning table may legitimately steer impl="auto"
    # onto a Pallas block shape whose float summation order differs.
    # The tuned path is gated on the 1e-4 oracle error instead (see
    # kernels.autotune.TABLE_META) — pinning keeps this gate green with
    # tuning on or off.
    cfg = FFMLPConfig(layer_sizes=(784, 128, 128), epochs=splits * 2,
                      splits=splits, neg_mode=neg_mode,
                      classifier=classifier, goodness_fn=goodness_fn,
                      batch_size=64, kernel_impl="ref", seed=0)
    return cfg, task


def _check_case(schedule, nodes, splits, n_train, neg_mode, classifier,
                goodness_fn="sumsq", *, check_sim_bound=False,
                check_overlap_ab=False):
    """Trains one config both ways — THROUGH THE FACADE (``api.fit``) —
    and returns a list of failure strings (empty = the executor
    reproduced the sequential trainer's weight stream bit-exactly).

    check_overlap_ab: additionally runs the executor with the
    double-buffered hand-off DISABLED and requires the overlap-on and
    overlap-off weight streams to be bit-identical to each other (the
    prefetched copies must be the same bits as the on-demand pulls)."""
    from repro import api

    cfg, task = _case_setup(splits, n_train, neg_mode, classifier,
                            goodness_fn)
    if schedule == "federated":
        ref = api.fit(cfg, task, backend="federated", num_nodes=nodes)
    else:
        ref = api.fit(cfg, task, backend="sequential")
    res = api.fit(cfg, task, backend="executor", schedule=schedule,
                  num_nodes=nodes)

    failures = []
    perf_opt = goodness_fn == "perf_opt"
    if check_overlap_ab:
        off = api.fit(cfg, task, backend="executor", schedule=schedule,
                      num_nodes=nodes, overlap=False)
        stats_on, stats_off = res.raw.handoff, off.raw.handoff
        if not params_bit_equal(off.params, res.params,
                                with_head=classifier == "softmax",
                                with_local_heads=perf_opt):
            failures.append(f"{schedule}: overlap-on vs overlap-off "
                            "weight streams diverged")
        if stats_off["prefetch_issued"] != 0:
            failures.append(f"{schedule}: overlap=False still issued "
                            f"{stats_off['prefetch_issued']} prefetches")
        if nodes > 1 and stats_on["prefetch_hits"] == 0:
            failures.append(f"{schedule}: overlap=True never hit a "
                            f"prefetched slot ({stats_on})")
        print(f"  overlap A/B {schedule}: on={stats_on} off={stats_off}")
    if not params_bit_equal(ref.params, res.params,
                            with_head=classifier == "softmax",
                            with_local_heads=perf_opt):
        # diagnose which leaves diverged and by how much
        named = [(f"layer {k}", lp_ref, lp_ex) for k, (lp_ref, lp_ex) in
                 enumerate(zip(ref.params["layers"], res.params["layers"]))]
        if classifier == "softmax":
            named.append(("head", ref.params["head"], res.params["head"]))
        if perf_opt:
            named += [(f"local_head {k}", h_ref, h_ex)
                      for k, (h_ref, h_ex) in
                      enumerate(zip(ref.params["local_heads"],
                                    res.params["local_heads"]))]
        for label, pa, pb in named:
            for name in ("w", "b"):
                if not bool(jnp.array_equal(pa[name], pb[name])):
                    err = float(jnp.abs(pa[name] - pb[name]).max())
                    failures.append(f"{schedule}: {label} {name} diverged,"
                                    f" max|diff|={err:.3e}")
    sim_note = ""
    if check_sim_bound:
        # Sanity bound, deliberately loose (shared-core container, cold
        # executor caches): a real run can never beat the simulator's
        # perfect-overlap replay of the same median task times by 4x.
        sim = pff.simulate_schedule(ref.records, schedule, nodes)
        sim_note = f" sim={sim.makespan:.2f}s"
        if res.makespan < 0.25 * sim.makespan:
            failures.append(
                f"{schedule}: measured makespan {res.makespan:.3f}s "
                f"implausibly beats the simulator's perfect-overlap "
                f"prediction {sim.makespan:.3f}s by more than 4x")
    print(f"devices={len(jax.devices())} schedule={schedule} "
          f"nodes={nodes} neg={neg_mode} cls={classifier} "
          f"goodness={goodness_fn}: "
          f"exec acc={res.test_acc:.4f} seq acc={ref.test_acc:.4f} "
          f"makespan={res.makespan:.2f}s{sim_note} -> "
          + ("FAIL" if failures else "bit-exact"))
    return failures


# (schedule, nodes, splits, n_train, neg_mode, classifier[, goodness_fn])
# n_train=520: 520 % 64 != 0 — the tail-batch path is always exercised;
# federated shards of 130 hit a different (also non-divisible) tail.
# The perf_opt rows check the §4.4 path (fused per-layer local-head
# task) end to end, including the single_layer forward recompute.
# The _AB_CASES rows double as the double-buffering A/B gate: row 1
# (all_layers adaptive softmax) routes published negatives, the softmax
# head and full layer states through the next-chapter prefetch; row 3
# (single_layer random) covers the params-only forward-recompute
# fan-out; row 6 (single_layer adaptive softmax) covers the
# single_layer head-node and published-negatives fan-out paths, which
# rows 1/3 never create slots for.
_MATRIX = (
    ("all_layers", 4, 4, 520, "random", "goodness"),
    ("all_layers", 4, 3, 520, "adaptive", "softmax"),
    ("federated", 4, 4, 520, "random", "goodness"),
    ("single_layer", 2, 3, 520, "random", "goodness"),
    ("all_layers", 4, 3, 520, "random", "goodness", "perf_opt"),
    ("single_layer", 2, 3, 520, "random", "goodness", "perf_opt"),
    ("single_layer", 2, 3, 520, "adaptive", "softmax"),
)
# rows that additionally run the overlap-on vs overlap-off comparison
_AB_CASES = (1, 3, 6)


def _lm_case_setup(n_blocks, tied, arch="qwen2-0.5b", *, seq_len=16):
    """The (cfg, source) every LM selftest case trains: a tiny
    qwen2-0.5b-shaped stack over the real-text BPE source — the same
    construction ``benchmarks/lm_exec.py`` and ``tests/test_pff_lm.py``
    use — or a tiny deepseek-v2-lite stack of two groups (one dense MLA
    block, then MLA + MoE blocks holding 2 of the 4 experts)."""
    from repro.configs import get_config

    cfg = get_config(arch).reduced()
    if arch == "deepseek-v2-lite":
        cfg = dataclasses.replace(
            cfg, num_layers=n_blocks,
            groups=((("mla_dense",), 1), (("mla_moe",), n_blocks - 1)),
            moe=dataclasses.replace(cfg.moe, held=2, shard=1))
    else:
        cfg = dataclasses.replace(cfg, num_layers=n_blocks,
                                  groups=((("attn",), n_blocks),))
    if not tied:
        cfg = dataclasses.replace(cfg, tie_embeddings=False)
    source = data_lib.text_source(vocab=cfg.vocab, seq_len=seq_len,
                                  seed=0)
    return cfg, source


def _lm_check_case(schedule, nodes, n_blocks, chapters, steps, tied,
                   arch="qwen2-0.5b", *, check_overlap_ab=False):
    """Trains one LM config both ways — through ``api.fit`` — and
    returns failure strings (empty = the executor reproduced the
    sequential ``train_chapters`` weight stream bit-exactly)."""
    from repro import api

    cfg, source = _lm_case_setup(n_blocks, tied, arch)
    kw = dict(chapters=chapters, steps_per_chapter=steps, batch=4,
              lr=1e-3)
    ref = api.fit(cfg, source, backend="sequential", **kw)
    res = api.fit(cfg, source, backend="executor", schedule=schedule,
                  num_nodes=nodes, **kw)
    failures = []
    if not pff_lm.lm_params_bit_equal(ref.params, res.params):
        failures.append(f"lm {schedule}: executor weight stream "
                        f"diverged from sequential train_chapters "
                        f"(tied={tied})")
    if check_overlap_ab:
        off = api.fit(cfg, source, backend="executor", schedule=schedule,
                      num_nodes=nodes, overlap=False, **kw)
        stats_on, stats_off = res.raw.handoff, off.raw.handoff
        if not pff_lm.lm_params_bit_equal(off.params, res.params):
            failures.append(f"lm {schedule}: overlap-on vs overlap-off "
                            "weight streams diverged")
        if stats_off["prefetch_issued"] != 0:
            failures.append(f"lm {schedule}: overlap=False still issued "
                            f"{stats_off['prefetch_issued']} prefetches")
        if nodes > 1 and stats_on["prefetch_hits"] == 0:
            failures.append(f"lm {schedule}: overlap=True never hit a "
                            f"prefetched slot ({stats_on})")
        print(f"  lm overlap A/B {schedule}: on={stats_on} "
              f"off={stats_off}")
    print(f"devices={len(jax.devices())} lm {arch} schedule={schedule} "
          f"nodes={nodes} blocks={n_blocks} tied={tied}: "
          f"exec ce={res.eval_ce:.4f} seq ce={ref.eval_ce:.4f} "
          f"makespan={res.makespan:.2f}s -> "
          + ("FAIL" if failures else "bit-exact"))
    return failures


# (schedule, nodes, n_blocks, chapters, steps_per_chapter, tied[, arch])
# Row 1/2: the acceptance-criteria pair — both paper schedules, N=4
# faked devices, tied embeddings (the head_feedback edge: every block
# task must see the post-head embed table), with the overlap A/B gate.
# Row 3: untied head (lm_head path) + nodes not dividing the block
# count, so the single_layer round-robin wraps.
# Row 4: a stack of two groups (deepseek-v2-lite: a dense MLA block,
# then MLA + held-expert MoE blocks), blocks addressed across groups.
_LM_MATRIX = (
    ("all_layers", 4, 4, 3, 2, True),
    ("single_layer", 4, 4, 3, 2, True),
    ("single_layer", 2, 3, 2, 2, False),
    ("all_layers", 2, 3, 2, 1, False, "deepseek-v2-lite"),
)
_LM_AB_CASES = (0, 1)


def _resilience_case(args):
    """One resilience run from the CLI: inject ``--fault-plan``, write
    chapter manifests into ``--checkpoint-dir``, resume from
    ``--resume-from`` — and gate the surviving weight stream against the
    fault-free reference with ``params_bit_equal`` wherever the policy
    promises bit-exactness (everywhere except federated shard drops,
    which degrade gracefully instead). A ``kill_*`` plan exits the
    process with ``faults.KILL_EXIT`` before any comparison — the
    caller re-invokes with ``--resume-from`` (what
    ``benchmarks/pff_faults.py`` and the subprocess tests do)."""
    from repro import api

    cfg, task = _case_setup(args.splits, args.n_train, args.neg_mode,
                            args.classifier, args.goodness_fn)
    plan = None
    if args.fault_plan:
        plan = faults_lib.named_plan(
            args.fault_plan, splits=cfg.splits,
            n_layers=len(cfg.layer_sizes) - 1, num_nodes=args.nodes)
    rc = faults_lib.ResilienceConfig(checkpoint_dir=args.checkpoint_dir,
                                     fault_plan=plan)
    res = api.fit(cfg, task, backend="executor", schedule=args.schedule,
                  num_nodes=args.nodes, resilience=rc,
                  resume_from=args.resume_from)
    stats = res.raw.resilience
    print(f"resilience {args.schedule} nodes={args.nodes} "
          f"plan={args.fault_plan or '-'} "
          f"resume={'yes' if args.resume_from else 'no'}: "
          f"acc={res.test_acc:.4f} retries={stats['retries']} "
          f"reassignments={stats['reassignments']} "
          f"dead={stats['dead_nodes']} "
          f"faults={stats['faults_injected']}")
    degraded = stats["shards_dropped"] or stats["chapters_skipped"]
    if degraded:
        print("  federated shard-drop degradation: bit-exactness gate "
              "skipped (weighted rounds lost a shard by design)")
        return []
    if args.schedule == "federated":
        ref = api.fit(cfg, task, backend="federated",
                      num_nodes=args.nodes)
    else:
        ref = api.fit(cfg, task, backend="sequential")
    if not params_bit_equal(ref.params, res.params,
                            with_head=args.classifier == "softmax",
                            with_local_heads=args.goodness_fn
                            == "perf_opt"):
        return [f"{args.schedule}: resilient run diverged from the "
                f"fault-free reference (plan={args.fault_plan})"]
    print("  bit-exact vs fault-free reference")
    return []


def _selftest(argv=None):
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--matrix", action="store_true",
                   help="run the full schedule/neg/classifier matrix "
                        "in one process (what tests/test_pff_exec.py "
                        "invokes)")
    p.add_argument("--lm-matrix", action="store_true",
                   help="run the LM chapter-schedule bit-exactness "
                        "matrix (executor vs pff_lm.train_chapters on "
                        "the real-text BPE source; what "
                        "tests/test_pff_lm.py invokes)")
    p.add_argument("--nodes", type=int, default=4)
    p.add_argument("--schedule", default="all_layers",
                   choices=list(pff_dag.SCHEDULES))
    p.add_argument("--splits", type=int, default=4)
    p.add_argument("--n-train", type=int, default=1000,
                   help="deliberately NOT divisible by the batch size, "
                        "so the tail-batch path is exercised too")
    p.add_argument("--neg-mode", default="random",
                   choices=list(strategies.negatives.names()))
    p.add_argument("--classifier", default="goodness",
                   choices=list(strategies.classifier.names()))
    p.add_argument("--goodness-fn", default="sumsq",
                   choices=list(strategies.goodness.names()))
    p.add_argument("--fault-plan", default=None,
                   choices=sorted(faults_lib.NAMED_PLANS),
                   help="inject a named deterministic fault plan "
                        "(repro.core.faults.NAMED_PLANS) and gate the "
                        "surviving weight stream")
    p.add_argument("--checkpoint-dir", default=None,
                   help="write chapter-granular manifests here (one "
                        "atomic .npz per completed chapter)")
    p.add_argument("--resume-from", default=None,
                   help="chapter manifest (or its directory: newest "
                        "wins) to resume from — replays the DAG from "
                        "the next chapter bit-exactly")
    args = p.parse_args(argv)

    failures = []
    if args.fault_plan or args.checkpoint_dir or args.resume_from:
        failures = _resilience_case(args)
    elif args.matrix:
        for i, case in enumerate(_MATRIX):
            failures += _check_case(*case, check_sim_bound=i == 0,
                                    check_overlap_ab=i in _AB_CASES)
    elif args.lm_matrix:
        for i, case in enumerate(_LM_MATRIX):
            failures += _lm_check_case(
                *case, check_overlap_ab=i in _LM_AB_CASES)
        if not failures:
            print("lm selftest OK: executor chapter schedule bit-exact "
                  "vs train_chapters on the BPE text source")
    else:
        failures = _check_case(args.schedule, args.nodes, args.splits,
                               args.n_train, args.neg_mode,
                               args.classifier, args.goodness_fn,
                               check_sim_bound=True,
                               check_overlap_ab=True)
    if failures:
        print("SELFTEST FAILED:\n  " + "\n  ".join(failures))
        return 1
    print("selftest OK: executor weight stream bit-exact vs the "
          "sequential trainer")
    return 0


if __name__ == "__main__":
    raise SystemExit(_selftest())
