"""Plain ``jax.numpy`` reference of DeepSeek-V2's blocks trained by
chapter Forward-Forward: chapters of per-block FF tasks, each followed
by the softmax-head task, in float32 with every product at the precision the
caller names (``"highest"``: full float32).

It follows the published equations (arXiv:2405.04434 §2.1 and the
released ``modeling_deepseek.py``):

- MLA without a query low-rank: q = x W_q (H heads of nope + rope
  dims); [c_kv, k_pe] = x W_kv_a; c_kv <- RMSNorm(c_kv); [k_nope, v] =
  c_kv W_kv_b; k_pe one rope head shared by every head; softmax scale
  (nope + rope) ** -0.5 times YaRN's mscale(factor, mscale_all_dim)
  squared; YaRN frequencies from factor, original length, beta_fast
  and beta_slow, and cos, sin times mscale(factor, mscale) /
  mscale(factor, mscale_all_dim).
- the dense SwiGLU silu(x W_g) * (x W_i) W_o;
- the MoE: softmax router over every routed expert, greedy top-k, the
  gates renormalised only where ``norm_topk``, times
  ``routed_scaling_factor`` 1; this chip's held experts' part of the
  routed sum, every assignment computed (a held expert runs over every
  token, weighted by its gate or 0); plus the shared experts as one
  SwiGLU;
- each block pre-norm residual; its FF loss the program's: goodness
  the mean square of the block's residual update (y - h) per token,
  softplus(theta - g) on positives and softplus(g - theta) on
  negatives, averaged, plus the router aux weight times the aux loss;
  Adam on the block alone, its moments kept from chapter to chapter,
  at the step number the chapter schedule gives (one counter over
  every block task of the job);
- the head task: RMSNorm, logits over the vocabulary, mean CE over the
  positive sequences, Adam on the final norm and the head (its own
  moments and step counter, kept across chapters).

Departures, each a choice of the program this reference is compared
with:

- rope pairs dimension i with i + rope/2 (split halves); the published
  code pairs neighbours. The two differ by a fixed permutation of the
  rope columns of W_q and W_kv_a, so on random weights they are the
  same model;
- an RMSNorm scales by (1 + w) with w initialised to 0; the published
  one by w initialised to 1;
- the aux loss is the batch-wise switch loss (number of experts times
  the dot of the mean router probability and the top-1 assignment
  share); DeepSeek-V2's is sequence-wise over its top-k.

Negatives come from the program's seeded generator
(``repro.core.ff.corrupt_tokens``): that is data. The parameter trees
are the program's (a block: ``norm1``, ``attn`` {wq, wkv_a, kv_norm,
wkv_b, wo}, ``norm2``, ``mlp`` {wi, wg, wo} or {router, wi, wg, wo,
shared}); nothing else of the program is imported.

Planted faults (keywords of ``chapters``; none is the published
mathematics): ``half_batch`` trains every task on half of each batch;
``capacity_factor`` drops assignments past that multiple
of the mean load per expert, in token order; ``norm_topk`` forced on;
``yarn_mscale=False`` leaves YaRN's factor out of the softmax scale;
``all_experts`` computes every routed assignment, the absent experts'
with the weights of the held expert of the same index modulo the held
count.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from repro.core import ff

F32 = jnp.float32


def hparams(cfg):
    """The numbers the reference reads, from a ``ModelConfig``."""
    m, moe, y = cfg.mla, cfg.moe, cfg.rope_scaling
    return {
        "n_heads": cfg.n_heads, "vocab": cfg.vocab, "eps": cfg.norm_eps,
        "theta_ff": cfg.ff.theta, "rope_theta": cfg.rope_theta,
        "kv_lora_rank": m.kv_lora_rank, "nope": m.qk_nope_head_dim,
        "rope": m.qk_rope_head_dim, "v": m.v_head_dim,
        "yarn": None if y is None else {
            "factor": y.factor, "original": y.original_max_position_embeddings,
            "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
            "mscale": y.mscale, "mscale_all_dim": y.mscale_all_dim},
        "num_experts": moe.num_experts, "top_k": moe.top_k,
        "held": moe.held or moe.num_experts, "shard": moe.shard,
        "norm_topk": moe.norm_topk, "aux_weight": moe.router_aux_weight,
    }


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope(x, pos, hp):
    """x: (S, ..., dims) at positions pos (S,)."""
    dim, base, y = x.shape[-1], hp["rope_theta"], hp["yarn"]
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    inv, scale = extra, 1.0
    if y is not None:
        def corr(rot):
            return dim * math.log(y["original"] / (rot * 2 * math.pi)) \
                / (2 * math.log(base))
        lo = max(math.floor(corr(y["beta_fast"])), 0)
        hi = min(math.ceil(corr(y["beta_slow"])), dim - 1)
        hi = hi + 0.001 if lo == hi else hi
        keep = 1.0 - jnp.clip((jnp.arange(dim // 2, dtype=F32) - lo)
                              / (hi - lo), 0.0, 1.0)
        inv = extra / y["factor"] * (1.0 - keep) + extra * keep
        scale = (_mscale(y["factor"], y["mscale"])
                 / _mscale(y["factor"], y["mscale_all_dim"]))
    ang = pos.astype(F32)[:, None] * inv                     # (S, dim/2)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def softmax_scale(hp, yarn_mscale=True):
    s = (hp["nope"] + hp["rope"]) ** -0.5
    y = hp["yarn"]
    if y is not None and y["mscale_all_dim"] and yarn_mscale:
        s *= _mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return s


def mla(p, x, hp, yarn_mscale=True):
    """One sequence x: (S, d) through causal MLA."""
    S = x.shape[0]
    pos = jnp.arange(S)
    r, nope = hp["kv_lora_rank"], hp["nope"]
    q = jnp.einsum("sd,dhk->shk", x, p["wq"])
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, hp)], -1)
    kv = x @ p["wkv_a"]
    c = rms_norm(kv[:, :r], p["kv_norm"], hp["eps"])
    k_pe = rope(kv[:, r:], pos, hp)                          # (S, rope)
    kvb = jnp.einsum("sc,chk->shk", c, p["wkv_b"])
    k = jnp.concatenate(
        [kvb[..., :nope],
         jnp.broadcast_to(k_pe[:, None], (S, hp["n_heads"], hp["rope"]))],
        -1)
    v = kvb[..., nope:]
    s = jnp.einsum("qhk,shk->hqs", q, k) * softmax_scale(hp, yarn_mscale)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    o = jnp.einsum("hqs,shv->qhv", jax.nn.softmax(s, -1), v)
    return jnp.einsum("qhv,hvd->qd", o, p["wo"])


def swiglu(p, x):
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]


def moe(p, x, hp, *, capacity_factor=None, norm_topk=None,
        all_experts=False):
    """x: (T, d) -> (held experts' part of the routed sum + shared, aux)."""
    T = x.shape[0]
    E, k, held = hp["num_experts"], hp["top_k"], hp["held"]
    probs = jax.nn.softmax(x @ p["router"], -1)
    gate, eids = jax.lax.top_k(probs, k)
    if hp["norm_topk"] if norm_topk is None else norm_topk:
        gate = gate / gate.sum(-1, keepdims=True)
    if capacity_factor is not None:
        flat = eids.reshape(-1)
        onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)
        rank = jnp.sum((jnp.cumsum(onehot, 0) - 1) * onehot, -1)
        cap = max(8, -(-int(T * k / E * capacity_factor) // 8) * 8)
        gate = jnp.where((rank < cap).reshape(T, k), gate, 0.0)
    local = eids - hp["shard"] * held
    if all_experts:
        local = eids % held
    y = swiglu(p["shared"], x)
    for e in range(held):
        g = jnp.sum(jnp.where(local == e, gate, 0.0), -1)
        y = y + g[:, None] * swiglu(
            {n: p[n][e] for n in ("wi", "wg", "wo")}, x)
    share = jnp.mean(jax.nn.one_hot(eids[:, 0], E, dtype=F32), 0)
    return y, E * jnp.sum(jnp.mean(probs, 0) * share)


def _attend(p, h, hp, faults):
    """h: (B, S, d) -> (h + attention, the MLP's normed input (B*S, d))."""
    B, S, d = h.shape
    attn = jax.checkpoint(lambda x: mla(p["attn"], x, hp,
                                        faults.get("yarn_mscale", True)))
    h = h + jax.lax.map(attn, rms_norm(h, p["norm1"], hp["eps"]))
    return h, rms_norm(h, p["norm2"], hp["eps"]).reshape(B * S, d)


def block(p, h, hp, faults):
    """h: (B, S, d) -> (block output, aux loss)."""
    h, x = _attend(p, h, hp, faults)
    if "router" in p["mlp"]:
        y, aux = moe(p["mlp"], x, hp, **{
            n: faults[n] for n in ("capacity_factor", "norm_topk",
                                   "all_experts") if n in faults})
    else:
        y, aux = swiglu(p["mlp"], x), jnp.zeros((), F32)
    return h + y.reshape(h.shape), aux


def routes(p, h, hp):
    """The top-k experts (B*S, k) a MoE block's router picks for h."""
    x = _attend(p, h, hp, {})[1]
    return jax.lax.top_k(x @ p["mlp"]["router"], hp["top_k"])[1]


def unit_forward(unit, h, hp, faults):
    """h through a chapter block: a tuple of block trees, in order."""
    for p in unit:
        h = block(p, h, hp, faults)[0]
    return h


def unit_loss(unit, h, is_pos, hp, faults):
    """The FF loss of a chapter block, summed over its blocks, each on
    its input held fixed."""
    total = 0.0
    for p in unit:
        h = jax.lax.stop_gradient(h)
        y, aux = block(p, h, hp, faults)
        g = jnp.mean(jnp.square(y - h), -1)
        per = jnp.where(is_pos[:, None] > 0.5,
                        jax.nn.softplus(hp["theta_ff"] - g),
                        jax.nn.softplus(g - hp["theta_ff"]))
        total = total + jnp.mean(per) + (
            hp["aux_weight"] * aux if "router" in p["mlp"] else 0.0)
        h = y
    return total


def adam(p, g, m, v, lr, step, b1=0.9, b2=0.999, eps=1e-8):
    t = jnp.asarray(step, F32)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    p = jax.tree.map(lambda p, m, v: p - lr * (m / (1 - b1 ** t)) / (
        jnp.sqrt(v / (1 - b2 ** t)) + eps), p, m, v)
    return p, m, v


def head_loss(hp_, x, labels, eps):
    """Mean CE of the head over positive sequences x: (B, S, d)."""
    logits = rms_norm(x, hp_["final_norm"], eps) @ hp_["lm_head"]
    lp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1))


def chapters(hp, blocks, head, embed, batches, head_batches, *, lr,
             head_lr, seed, precision="highest", feed=None, on_block=None,
             on_head=None, half_batch=False, **faults):
    """The chapter schedule: in chapter c block k trains for its steps
    on the outputs of blocks < k, then the head on every block.
    ``blocks``: the initial trees of the chapter blocks, each a tuple of
    block trees; ``head``: {final_norm, lm_head}; ``batches[c][k]``,
    ``head_batches[c]``: the token arrays (B, S + 1) of each step. A
    block keeps its Adam moments from chapter to chapter, and so does
    the head; one step counter runs over every block task of the job,
    another over the head's.

    ``feed``, where given: ``feed[c]`` holds every block's trees after
    chapter c as a run under test has them, and the blocks < k of
    chapter c are those rather than the reference's own. Each task is
    then followed on the inputs that run gave it, so round-off that
    flips a routing choice upstream does not reach the blocks after it.
    ``on_block(c, k, p)`` and ``on_head(c, head)`` see each task's
    result. ``half_batch`` (a planted fault) leaves out the second half
    of every batch.

    Returns (the FF losses of each block over its steps in every
    chapter, the head's CE per step, ``route_diff``): with ``feed``,
    ``route_diff[k]`` is, for each MoE block k > 0, the largest share
    over chapters of its first step's top-k assignments that differ
    between its inputs through ``feed``'s blocks < k and through the
    reference's own."""
    fwd, step, inputs, head_step, route_diff = _programs(json.dumps(
        [hp, faults, lr, head_lr, precision], sort_keys=True))
    key = jax.random.PRNGKey(seed)
    cut = (lambda t: t[:t.shape[0] // 2]) if half_batch else (lambda t: t)
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
    nb = len(blocks)
    state = [(p, zeros(p), zeros(p)) for p in blocks]
    head_state = (head, zeros(head), zeros(head))
    losses, head_out, routed = [[] for _ in range(nb)], [], {}
    n = n_head = 0
    with jax.default_matmul_precision(precision):
        for c in range(len(batches)):
            given = None if feed is None else jax.device_put(feed[c])
            for k in range(nb):
                prefix = [s[0] for s in state[:k]] if given is None \
                    else given[:k]
                p, m, v = state[k]
                for i, tokens in enumerate(batches[c][k]):
                    n += 1
                    x, is_pos = inputs(embed, cut(tokens), key, n)
                    own = x
                    for j in range(k):
                        x = fwd(prefix[j], x)
                    if i == 0 and given is not None and k \
                            and "router" in p[0]["mlp"]:
                        for j in range(k):
                            own = fwd(state[j][0], own)
                        routed[k] = max(routed.get(k, 0.0),
                                        float(route_diff(p[0], x, own)))
                    del own
                    p, m, v, loss = step(p, m, v, x, is_pos, n)
                    losses[k].append(float(loss))
                state[k] = (p, *jax.device_get((m, v)))   # off the device
                if on_block is not None:
                    on_block(c, k, p)
            prefix = [s[0] for s in state] if given is None else given
            hp_, hm, hv = head_state
            for tokens in head_batches[c]:
                n_head += 1
                tokens = cut(tokens)
                x = embed[tokens[:, :-1]]
                for j in range(nb):
                    x = fwd(prefix[j], x)
                hp_, hm, hv, loss = head_step(hp_, hm, hv, x, tokens[:, 1:],
                                              n_head)
                head_out.append(float(loss))
            head_state = (hp_, hm, hv)
            if on_head is not None:
                on_head(c, hp_)
            del given, prefix
    return losses, head_out, routed


@functools.lru_cache(maxsize=16)
def _programs(key):
    """The jitted pieces of a chapter, one set per (hparams, faults,
    learning rates, precision), kept across calls."""
    hp, faults, lr, head_lr, _ = json.loads(key)
    fwd = jax.jit(lambda p, h: unit_forward(p, h, hp, faults))

    @jax.jit
    def step(p, m, v, x, is_pos, n):
        loss, g = jax.value_and_grad(unit_loss)(p, x, is_pos, hp, faults)
        return (*adam(p, g, m, v, lr, n), loss)

    @jax.jit
    def inputs(embed, tokens, key, n):
        tok = tokens[:, :-1]
        neg = ff.corrupt_tokens(jax.random.fold_in(key, n), tok,
                                hp["vocab"])
        x = embed[jnp.concatenate([tok, neg])]
        is_pos = jnp.concatenate([jnp.ones(tok.shape[:1]),
                                  jnp.zeros(tok.shape[:1])])
        return x, is_pos

    @jax.jit
    def head_step(h, m, v, x, labels, n):
        loss, g = jax.value_and_grad(head_loss)(h, x, labels, hp["eps"])
        return (*adam(h, g, m, v, head_lr, n), loss)

    @jax.jit
    def route_diff(p, a, b):
        ea, eb = routes(p, a, hp), routes(p, b, hp)
        return 1.0 - jnp.mean(jnp.any(ea[:, :, None] == eb[:, None, :], -1))

    return fwd, step, inputs, head_step, route_diff
