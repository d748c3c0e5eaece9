"""The paper's chapter schedule applied to transformer stacks.

``core.train.make_ff_train_step`` trains every block each step ("joint
FF" — all local losses in one fused pass, the TPU-native formulation).
This module implements the paper's ACTUAL schedule instead: chapters of
per-BLOCK training (chapter c trains block k for a fixed step budget on
the outputs of blocks < k), producing the same TaskRecord stream the
PFF simulator consumes — so the paper's Single-Layer / All-Layers
wall-clock analysis applies to the assigned architectures directly.

This is the bridge between the paper's MLP experiments and the
production archs: FF locality means the chapter schedule and the joint
step optimize the same per-block objectives; the schedule only changes
WHEN each block's updates happen (and therefore what its inputs look
like). The benchmark compares both on eval CE.
"""
from __future__ import annotations

import functools
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.core import ff
from repro.core import train as train_lib
from repro.core.pff import TaskRecord
from repro.models import blocks, common, transformer
from repro.models.mlp import NO_DIST
from repro.obs import trace as obs_trace


def block_layout(cfg):
    """[(group, unit, pattern)] of every chapter block in stack order:
    block k of the chapter schedule is unit ``unit`` of group ``group``
    (one pattern instance), so a stack of several groups (a leading
    dense layer, then the MoE layers) is addressed by global index."""
    return [(gi, u, pattern) for gi, (pattern, repeat)
            in enumerate(cfg.groups) for u in range(repeat)]


def split_params(cfg, tree):
    """(blocks, rest) of a params-shaped tree (the params, or Adam's m
    or v): the per-block unit trees in ``block_layout`` order, and the
    entries outside the groups."""
    blocks = [jax.tree.map(lambda a, u=u: a[u], tree["groups"][gi])
              for gi, u, _ in block_layout(cfg)]
    return blocks, {k: v for k, v in tree.items() if k != "groups"}


def join_params(cfg, blocks, rest):
    """The inverse of ``split_params``: the model's params pytree."""
    groups, i = [], 0
    for _, repeat in cfg.groups:
        groups.append(jax.tree.map(lambda *a: jnp.stack(a),
                                   *blocks[i:i + repeat]))
        i += repeat
    return {**rest, "groups": tuple(groups)}


@functools.lru_cache(maxsize=8)
def _init_program(cfg):
    return jax.jit(lambda key: split_params(cfg, transformer.init(key, cfg)))


def init_state(cfg, seed):
    """(blocks, rest) of ``transformer.init`` at ``seed``, split on the
    device in one program, so the stacked tree is never held beside its
    split."""
    return _init_program(cfg)(jax.random.PRNGKey(seed))


def _unit_forward(cfg, pattern, unit_p, x):
    """x through one frozen chapter block (a pattern instance)."""
    for kind, bp in zip(pattern, unit_p):
        x, _ = blocks.block_apply(bp, cfg, kind, x,
                                  {"causal": True, "dist": NO_DIST})
    return x


def _forward_program(cfg, name):
    """A jitted frozen forward through one chapter block, one program
    per block pattern, shared by every block of that pattern: a step
    runs its prefix as a chain of these rather than compiling one
    forward per prefix length."""
    fn = lambda unit_p, x, pattern: _unit_forward(cfg, pattern, unit_p, x)
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, static_argnames=("pattern",))


@functools.lru_cache(maxsize=8)
def make_block_step(cfg, *, lr=1e-3, seed=0, theta=None, donate=True):
    """Returns ``step(embed, prefix, unit_p, unit_m, unit_v, batch,
    block_idx, step_no)`` -> ``(unit_p, unit_m, unit_v, loss, counts)``:
    one FF step of block ``block_idx`` alone (the paper's per-node task)
    on the outputs of the frozen ``prefix`` (the trees of blocks <
    block_idx). Three jitted programs, all named ``lm_block_step*`` in
    a trace: the inputs (the embedded [positives; corrupted negatives]
    of the step), the frozen forward through each prefix block, and the
    block's own loss, gradient and Adam update. Only the block's own
    state is an output of the last, and with ``donate`` its buffers
    are donated to it, so the stacked state is held once. ``counts``
    (MoE layers, held experts) are the step's assignments to the held
    experts of each dropless MoE layer of the block (0 rows for a block
    without one). One set of programs per (cfg, lr, seed, theta,
    donate), kept: every job of a configuration runs the programs the
    first one compiled."""
    layout = block_layout(cfg)
    theta = theta if theta is not None else cfg.ff.theta
    aux_w = cfg.moe.router_aux_weight if cfg.moe is not None else 0.0

    @jax.jit
    def lm_block_step_inputs(embed, batch, step_no):
        tokens = batch["tokens"][:, :-1]
        B = tokens.shape[0]
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step_no)
        neg = ff.corrupt_tokens(key, tokens, cfg.vocab)
        x = jnp.take(embed, jnp.concatenate([tokens, neg], axis=0), axis=0)
        is_pos = jnp.concatenate(
            [jnp.ones((B,)), jnp.zeros((B,))]).astype(jnp.float32)
        return x, is_pos

    forward = _forward_program(cfg, "lm_block_step_forward")

    def lm_block_step(x, is_pos, unit_p, unit_m, unit_v, step_no, pattern):
        ctx = {"causal": True, "dist": NO_DIST}

        def loss_fn(up):
            stats = []
            h = x
            total = jnp.zeros(())
            for kind, bp in zip(pattern, up):
                h_sg = jax.lax.stop_gradient(h)
                y, moe_aux = blocks.block_apply(
                    bp, cfg, kind, h_sg, {**ctx, "moe_stats": stats})
                g = ff.mean_goodness(y - h_sg)
                total = total + ff.ff_loss_masked(g, is_pos, theta) \
                    + aux_w * moe_aux
                h = y
            counts = (jnp.stack(stats) if stats
                      else jnp.zeros((0, 0), jnp.int32))
            return total, counts

        (loss, counts), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(unit_p)
        new_p, st = optim.adam_update(
            unit_p, grads, {"m": unit_m, "v": unit_v}, lr=lr, step=step_no)
        return new_p, st["m"], st["v"], loss, counts

    train = jax.jit(lm_block_step, static_argnames=("pattern",),
                    donate_argnames=(("unit_p", "unit_m", "unit_v")
                                     if donate else ()))

    def step(embed, prefix, unit_p, unit_m, unit_v, batch, block_idx,
             step_no):
        assert len(prefix) == block_idx < len(layout), (block_idx,)
        x, is_pos = lm_block_step_inputs(embed, batch, step_no)
        for j, up in enumerate(prefix):
            x = forward(up, x, pattern=layout[j][2])
        return train(x, is_pos, unit_p, unit_m, unit_v, step_no,
                     pattern=layout[block_idx][2])

    return step


def head_param_names(cfg):
    """The per-chapter head task's parameter subset: ``final_norm``
    plus the softmax weights — the tied embedding table (which then
    doubles as the paper's softmax layer, exactly like the joint step
    in ``core/train.py``) or the untied ``lm_head``."""
    return ("final_norm", "embed" if cfg.tie_embeddings else "lm_head")


@functools.lru_cache(maxsize=8)
def make_head_step(cfg, *, head_lr=1e-3, donate=True):
    """Returns ``head_step(embed, blocks, hp, hm, hv, batch, step_no)``
    -> ``(hp, hm, hv, loss)`` — the per-chapter softmax-head task (DAG
    ``Task("head", n_blocks, c)``): a frozen forward through ALL blocks
    (``blocks``, every block's tree), then local CE on the head subset
    ``hp`` only (``head_param_names``; its Adam moments ``hm``, ``hv``,
    donated with it under ``donate``). ``embed`` is the input table of
    an untied model (None where tied: the table is ``hp["embed"]``).
    Mirrors the joint step's head treatment: features are
    stop-gradded, so a tied table receives the CE grad only through the
    unembed. Jitted programs named ``lm_head_step*``: the embedding,
    the frozen forward per block pattern, and the head's CE and Adam."""
    layout = block_layout(cfg)

    @jax.jit
    def lm_head_step_inputs(table, batch):
        return jnp.take(table, batch["tokens"][:, :-1], axis=0)

    forward = _forward_program(cfg, "lm_head_step_forward")

    def lm_head_step(x, hp, hm, hv, batch, step_no):
        labels = batch["tokens"][:, 1:]

        def head_loss(hp):
            h = common.rms_norm(x, hp["final_norm"], cfg.norm_eps)
            w = hp["embed"] if cfg.tie_embeddings else hp["lm_head"].T
            ones = jnp.ones(labels.shape, jnp.float32)
            total = train_lib._ce_chunked(h, w, labels, ones,
                                          softcap=cfg.logit_softcap)
            return total / labels.size

        loss, grads = jax.value_and_grad(head_loss)(hp)
        new_hp, st = optim.adam_update(hp, grads, {"m": hm, "v": hv},
                                       lr=head_lr, step=step_no)
        return new_hp, st["m"], st["v"], loss

    train = jax.jit(lm_head_step,
                    donate_argnames=("hp", "hm", "hv") if donate else ())

    def head_step(embed, blocks_p, hp, hm, hv, batch, step_no):
        x = lm_head_step_inputs(hp["embed"] if cfg.tie_embeddings
                                else embed, batch)
        for j, up in enumerate(blocks_p):
            x = forward(up, x, pattern=layout[j][2])
        return train(x, hp, hm, hv, batch, step_no)

    return head_step


def chapter_batches(source, *, batch, steps):
    """The canonical (chapter, task)-addressed batch stream over a
    ``data.TextSource``-style source: a pure function of its arguments
    (the ``data.Source`` contract), so the sequential trainer and EVERY
    executor node regenerate identical batches locally — training data
    never crosses the hand-off. The head task is addressed as
    ``block = n_blocks`` (its DAG layer index)."""
    def data_iter(chapter, block):
        blk = source.blocks("train", batch * steps,
                            seed=chapter * 1009 + block)
        for s in range(steps):
            yield {"tokens": jnp.asarray(blk[s * batch:(s + 1) * batch])}
    return data_iter


def load_attrs(counts):
    """Span attrs of a MoE block task from its steps' ``counts``:
    ``routed``, the assignments to held experts, and
    ``load_max_over_mean`` over the held experts."""
    per_expert = np.asarray(jnp.stack(counts)).sum(axis=(0, 1))
    return {"routed": int(per_expert.sum()),
            "load_max_over_mean": float(per_expert.max()
                                        / max(per_expert.mean(), 1e-9))}


def train_chapters(cfg, data_iter_fn, *, chapters, steps_per_chapter,
                   lr=1e-3, head_lr=None, seed=0, tracer=obs_trace.NOOP):
    """Runs the chapter schedule; returns (params, records, losses,
    head_losses).

    data_iter_fn(chapter, block) -> iterable of batches for that task;
    the per-chapter head task draws ``data_iter_fn(c, n_blocks)``.
    Blocks are addressed by global index (``block_layout``), across
    every group of the stack. The LM head (final_norm +
    lm_head/embed-as-softmax) trains at the end of each chapter, like
    the paper's softmax layer, at ``head_lr`` (default: ``lr``); its
    ``TaskRecord("head", n_blocks, c)`` rides the same record stream
    the simulator consumes. ``losses`` holds one device array per block
    task, its steps' FF losses; ``head_losses`` one per head task, its
    steps' CE. Neither is read back here: a task waits for its state
    alone, for its record.

    Spans (``tracer``): ``fit:init``; per task ``task:train`` (layer,
    chapter, steps, kind; on a traced MoE block also ``routed`` and
    ``load_max_over_mean``, ``load_attrs``) with ``task:wait`` inside,
    and ``task:head``.
    """
    layout = block_layout(cfg)
    nb = len(layout)
    with tracer.span("fit:init"):
        bp, rest = init_state(cfg, seed)
        heads = head_param_names(cfg)
        hp = {k: rest.pop(k) for k in heads}
        bm, bv = ([optim.adam_init(u)["m"] for u in bp] for _ in range(2))
        hm, hv = (optim.adam_init(hp)["m"] for _ in range(2))
        embed = None if cfg.tie_embeddings else rest["embed"]
        jax.block_until_ready((bp, bm, bv, hm, hv))
    step = make_block_step(cfg, lr=lr, seed=seed)
    head_step = make_head_step(
        cfg, head_lr=lr if head_lr is None else head_lr)
    records: List[TaskRecord] = []
    losses, head_losses = [], []
    n = 0
    n_head = 0
    for c in range(chapters):
        for k, (_, _, pattern) in enumerate(layout):
            with tracer.span("task:train", layer=k, chapter=c,
                             steps=steps_per_chapter,
                             kind="+".join(pattern)) as attrs:
                t0 = time.perf_counter()
                step_losses, counts = [], []
                for batch in data_iter_fn(c, k):
                    n += 1
                    bp[k], bm[k], bv[k], loss, cnt = step(
                        hp["embed"] if embed is None else embed,
                        tuple(bp[:k]), bp[k], bm[k], bv[k], batch, k, n)
                    step_losses.append(loss)
                    counts.append(cnt)
                with obs_trace.annotate("task:wait"):
                    jax.block_until_ready(bp[k])
                losses.append(jnp.stack(step_losses))
                if tracer.enabled and counts[0].size:
                    attrs.update(load_attrs(counts))
                records.append(TaskRecord("train", k, c,
                                          time.perf_counter() - t0))
        with tracer.span("task:head", chapter=c):
            t0 = time.perf_counter()
            step_losses = []
            for batch in data_iter_fn(c, nb):
                n_head += 1
                hp, hm, hv, loss = head_step(embed, tuple(bp), hp, hm, hv,
                                             batch, n_head)
                step_losses.append(loss)
            jax.block_until_ready(hp)
            head_losses.append(jnp.stack(step_losses))
            records.append(TaskRecord("head", nb, c,
                                      time.perf_counter() - t0))
    del bm, bv, hm, hv
    return join_params(cfg, bp, {**rest, **hp}), records, losses, head_losses


def lm_params_bit_equal(a, b) -> bool:
    """True iff two transformer params pytrees are BIT-identical on
    every leaf — the LM executor's correctness oracle (the transformer
    analog of ``pff_exec.params_bit_equal``)."""
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    return (jax.tree.structure(a) == jax.tree.structure(b)
            and all(bool(jnp.array_equal(x, y))
                    for x, y in zip(fa, fb)))
