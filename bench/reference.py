"""Plain ``jax.numpy`` reference of the FF-MLP training job.

It imports nothing of the program and follows the published recipe
(arXiv:2404.08573 §5.1 and §4.4, Hinton's Forward-Forward) with the
program's documented choices: the label overlay on the first C pixels,
length normalisation between layers, goodness as the mean of squared
activities against theta, Adam with the paper's cooldown, one key per
(chapter, layer, mini-epoch) folded from the seed, negatives redrawn
after every chapter (RandomNEG: a uniform wrong label; AdaptiveNEG: a
wrong label drawn from z-scored class goodness), and the goodness
classifier over layers 2..L. It draws the same random numbers as the
program from the same seed, so the two follow one trajectory up to
rounding.

Every matrix product, forward and backward, goes through ``pdot`` at
one precision: ``"highest"`` (full float32, what the configuration
states) or ``"bf16_3x"`` (three bfloat16 passes, XLA's ``high``: the
control, one step below).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

NORM_EPS = 1e-8
F32 = jnp.float32
BF16 = jnp.bfloat16


def _dot(a, b, precision):
    if precision == "highest":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=F32)
    if precision == "bf16_3x":
        def split(u):
            # hi: u rounded to the nearest bfloat16 (ties to even) in
            # integer arithmetic, so no float32 -> bfloat16 -> float32
            # round trip is left for the compiler to fold away (on the
            # TPU that folding left lo = 0: one bfloat16 pass)
            bits = jax.lax.bitcast_convert_type(u, jnp.uint32)
            bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & 1)
            hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                              F32)
            return hi.astype(BF16), (u - hi).astype(BF16)

        (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)

        def one(u, v):
            return jnp.dot(u, v, preferred_element_type=F32)
        return one(a_hi, b_hi) + (one(a_hi, b_lo) + one(a_lo, b_hi))
    raise ValueError(f"unknown precision {precision!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def pdot(a, b, precision):
    """a @ b with every product, forward and backward, at ``precision``."""
    return _dot(a, b, precision)


def _pdot_fwd(a, b, precision):
    return _dot(a, b, precision), (a, b)


def _pdot_bwd(precision, res, g):
    a, b = res
    return _dot(g, b.T, precision), _dot(a.T, g, precision)


pdot.defvjp(_pdot_fwd, _pdot_bwd)


# ---------------------------------------------------------------------------
# The network
# ---------------------------------------------------------------------------

def init(seed, model):
    """Layer weights from the seed."""
    sizes = model["layer_sizes"]
    ks = jax.random.split(jax.random.PRNGKey(seed), len(sizes))
    return {"layers": [
        {"w": jax.random.normal(ks[i], (sizes[i], sizes[i + 1]), F32)
         * sizes[i] ** -0.5, "b": jnp.zeros((sizes[i + 1],), F32)}
        for i in range(len(sizes) - 1)]}


def length_norm(x):
    return x / (jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)) + NORM_EPS)


def layer(lp, x, precision):
    """relu(x W + b) and its sum of squares."""
    y = jax.nn.relu(pdot(x, lp["w"], precision) + lp["b"])
    return y, jnp.sum(y * y, axis=-1)


def layer_norm_out(lp, x, precision):
    """One layer and the length normalisation of its output."""
    y, g = layer(lp, x, precision)
    return y / (jnp.sqrt(g)[:, None] + NORM_EPS), g


def overlay(x, labels, num_classes):
    lab = jax.nn.one_hot(labels, num_classes, dtype=x.dtype)
    return jnp.concatenate([lab, x[:, num_classes:]], axis=1)


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

def adam(p, g, m, v, lr, step, b1=0.9, b2=0.999, eps=1e-8):
    t = jnp.asarray(step, F32)
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    u = (m / (1.0 - b1 ** t)) / (jnp.sqrt(v / (1.0 - b2 ** t)) + eps)
    return p - lr * u, m, v


def cooldown_lrs(model, chapter):
    """Per-mini-epoch learning rates of one chapter (paper §5.1)."""
    C = max(model["epochs"] // model["splits"], 1)
    e = jnp.asarray([chapter * C + i for i in range(C)], F32)
    frac = e / max(model["epochs"], 1)
    scale = jnp.clip((1.0 - frac) / max(1.0 - model["cooldown_after"], 1e-9),
                     0.0, 1.0)
    return model["lr_ff"] * scale


def _batches(key, n, batch, epochs):
    """(epochs * n_batches, batch) sample indices: a fresh permutation
    per mini-epoch, wrapped to whole batches."""
    nb = -(-n // batch)
    out = []
    for ei in range(epochs):
        perm = jax.random.permutation(jax.random.fold_in(key, ei), n)
        if nb * batch > n:
            perm = jnp.tile(perm, -(-nb * batch // n))[:nb * batch]
        out.append(perm.reshape(nb, batch))
    return jnp.concatenate(out), nb


def ff_loss(lp, xb, theta, precision, fault=None):
    """Softplus FF loss of a stacked [pos; neg] batch, goodness as the
    mean of squared activities."""
    y, g = layer(lp, xb, precision)
    g = g / y.shape[-1]
    half = xb.shape[0] // 2
    gp, gn = g[:half], g[half:]
    if fault == "half_batch":
        gp, gn = gp[:half // 2], gn[:half // 2]
    return (jnp.mean(jax.nn.softplus(theta - gp))
            + jnp.mean(jax.nn.softplus(gn - theta)))


@functools.partial(jax.jit, static_argnames=("batch", "epochs", "theta",
                                             "precision", "fault"))
def train_chapter(lp, m, v, xp, xn, lrs, key, *, batch, epochs, theta,
                  precision, fault=None):
    """One layer's chapter task: Adam over every batch of every
    mini-epoch."""
    idx, nb = _batches(key, xp.shape[0], batch, epochs)

    def body(carry, i):
        lp, m, v, step = carry
        rows = idx[i]
        xb = jnp.concatenate([xp[rows], xn[rows]])
        g = jax.grad(ff_loss)(lp, xb, theta, precision, fault)
        step = step + 1
        new = jax.tree.map(lambda p, g, m, v: adam(p, g, m, v, lrs[i // nb],
                                                   step), lp, g, m, v)
        pick = lambda j: jax.tree.map(lambda _, t: t[j], lp, new)
        return (pick(0), pick(1), pick(2), step), None

    (lp, m, v, _), _ = jax.lax.scan(body, (lp, m, v, jnp.int32(0)),
                                    jnp.arange(idx.shape[0]))
    return lp, m, v


@functools.partial(jax.jit, static_argnames=("precision", "norm"))
def forward_norm(lp, x, precision, norm=True):
    """The hand-off to the next layer: one layer, length-normalised
    (``norm=False``: the fault of a hand-off that leaves it out)."""
    return layer_norm_out(lp, x, precision)[0] if norm else \
        layer(lp, x, precision)[0]


# ---------------------------------------------------------------------------
# Scoring and evaluation
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_classes", "precision"))
def goodness_scores(layers, x, num_classes, precision):
    """(B, C) goodness of layers 2..L summed, one label overlay each."""
    B = x.shape[0]
    xs = jnp.tile(x, (num_classes, 1))
    labels = jnp.repeat(jnp.arange(num_classes), B)
    h = length_norm(overlay(xs, labels, num_classes))
    total = jnp.zeros((num_classes * B,), F32)
    for i, lp in enumerate(layers):
        yn, g = layer_norm_out(lp, h, precision)
        if i >= 1 or len(layers) == 1:
            total = total + g / yn.shape[-1]
        h = yn
    return total.reshape(num_classes, B).T


def class_scores(layers, x, num_classes, precision, chunk=2000):
    """Goodness scores in blocks of rows."""
    return jnp.concatenate([
        goodness_scores(layers, x[i:i + chunk], num_classes, precision)
        for i in range(0, x.shape[0], chunk)])


def adaptive_wrong_labels(scores, y, key):
    """AdaptiveNEG: a wrong label drawn in proportion to its z-scored
    goodness over the wrong labels."""
    B, C = scores.shape
    true = jax.nn.one_hot(y, C, dtype=bool)
    masked = jnp.where(true, -jnp.inf, scores)
    mu = jnp.sum(jnp.where(true, 0.0, scores), axis=1, keepdims=True) / (C - 1)
    var = jnp.sum(jnp.where(true, 0.0, jnp.square(scores - mu)), axis=1,
                  keepdims=True) / (C - 1)
    z = jnp.where(jnp.isfinite(masked), (masked - mu) / (jnp.sqrt(var) + 1e-6),
                  -jnp.inf)
    return jax.random.categorical(key, z, axis=1).astype(y.dtype)


def random_wrong_labels(key, y, num_classes):
    """RandomNEG: a uniform wrong label."""
    return (y + jax.random.randint(key, y.shape, 1, num_classes)) % num_classes


# ---------------------------------------------------------------------------
# One job, cut to its first chapters
# ---------------------------------------------------------------------------

def _leaves(layers):
    """{"layers/0/w": array, ...} of a list of layer states."""
    return {f"layers/{k}/{n}": t for k, s in enumerate(layers)
            for n, t in s[0].items()}


@dataclasses.dataclass
class Job:
    init: dict            # leaves as initialised from the seed
    chapter0: dict        # leaves after chapter 0
    final: dict           # leaves after the last chapter
    pred: object          # test-set class predictions


def run_job(model, seed, x, y, x_test, chapters, *, precision="highest",
            fault=None, exchange_nodes=0, nudge=0.0, handoff_precision=None):
    """Train ``model`` from ``seed`` for ``chapters`` chapters on the
    train set (x, y), as the sequential schedule orders the tasks.

    Returns a ``Job``; leaves are keyed like "layers/0/w".

    ``fault`` plants one fault in this reference, for reading what the
    comparison sees when the program has it: "half_batch" (half of
    every batch left out, the mean taken over the rest) or
    "handoff_unnormed" (the hand-off between layers without its length
    normalisation). ``handoff_precision`` computes the hand-off alone
    at another precision. With
    ``exchange_nodes`` = N > 1, the hand-off between N nodes that take
    chapters in turn is left out: each node trains on from its own last
    state of each layer, never the one the previous chapter handed on.
    ``nudge`` multiplies every initial weight by (1 + nudge * N(0, 1)),
    to read how far round-off of that size grows.
    """
    L = len(model["layer_sizes"]) - 1
    C, B = model["num_classes"], model["batch_size"]
    epochs = max(model["epochs"] // model["splits"], 1)
    key = jax.random.PRNGKey(seed)
    kneg = jax.random.fold_in(key, 999)
    params = init(seed, model)
    if nudge:
        k = jax.random.PRNGKey(7)
        params = jax.tree.map(
            lambda t: t * (1 + nudge * jax.random.normal(k, t.shape)), params)
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
    first = [(lp, zeros(lp), zeros(lp)) for lp in params["layers"]]
    xp0 = length_norm(overlay(x, y, C))
    xn0 = length_norm(overlay(x, random_wrong_labels(kneg, y, C), C))
    states = first
    own = {}        # node -> its own last states (exchange fault only)
    for chapter in range(chapters):
        lrs = cooldown_lrs(model, chapter)
        kc = jax.random.fold_in(key, chapter)
        node = chapter % exchange_nodes if exchange_nodes > 1 else 0
        if exchange_nodes > 1:
            states = own.get(node, first)
        acts = (xp0, xn0)
        new_states = []
        for k in range(L):
            st = train_chapter(
                *states[k], *acts, lrs, jax.random.fold_in(kc, k), batch=B,
                epochs=epochs, theta=model["theta"], precision=precision,
                fault=fault if fault == "half_batch" else None)
            new_states.append(st)
            if k + 1 < L:
                acts = tuple(forward_norm(
                    st[0], a, handoff_precision or precision,
                    norm=fault != "handoff_unnormed") for a in acts)
        states = own[node] = new_states
        if chapter == 0:
            chapter0 = _leaves(states)
        kc_neg = jax.random.fold_in(kneg, chapter)
        if model["neg_mode"] == "adaptive":
            scores = class_scores([s[0] for s in states], x, C, precision)
            xn0 = length_norm(overlay(x, adaptive_wrong_labels(
                scores, y, kc_neg), C))
        elif model["neg_mode"] == "random":
            xn0 = length_norm(overlay(x, random_wrong_labels(kc_neg, y, C),
                                      C))
    pred = jnp.argmax(class_scores([s[0] for s in states], x_test, C,
                                   precision), axis=1)
    return Job(_leaves(first), chapter0, _leaves(states), pred)
