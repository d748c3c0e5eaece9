"""FF training for the assigned (transformer-family) architectures, plus
the backpropagation baseline.

The FF transformer step is the paper's technique made TPU-native:

  * positive batch = real token sequences; negative batch = corrupted
    sequences (``repro.core.ff``), concatenated on the BATCH axis so both
    FF passes share every matmul (MXU-friendly — the paper runs them as
    two separate passes on CPU nodes).
  * each block's loss is layer-local: ``stop_gradient`` on the block
    input, goodness of the block's residual update (pos high / neg low).
    No gradient ever crosses a block boundary — this is what deletes the
    backward dependency chain the paper's pipeline exploits.
  * the per-block grad AND its Adam update run INSIDE the ``lax.scan``
    over stacked layers. Peak live state is one block's activations +
    grads, independent of depth — no remat needed (the backprop baseline
    needs ``jax.checkpoint``). This is the beyond-paper memory win.
  * the LM head is the paper's softmax classifier: trained with a local
    CE loss that does not propagate into FF blocks (stop-grad features).
  * the embedding is trained with its own local goodness loss (the FF
    "layer 1"); when embeddings are tied, the head CE also reaches the
    table through the unembed — we keep the FF-faithful separation by
    stop-gradding the table in the unembed.

Goodness modes (cfg.ff.goodness):
  "sumsq"    — paper Eq. 1 on the block's residual update (needs neg data)
  "perf_opt" — paper §4.4 Performance-Optimized: local classifier loss
               (CE to next token via the stop-gradded embedding table as
               classifier) — no negative data.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import optim
from repro.core import ff
from repro.models import blocks, common, transformer
from repro.models.mlp import NO_DIST

AUX_WEIGHT = 0.01      # router load-balance weight (local per block)


# ---------------------------------------------------------------------------
# Local losses
# ---------------------------------------------------------------------------

def _block_ff_loss(delta, is_pos, theta):
    """delta: (B2, S, d) the block's residual update."""
    g = ff.mean_goodness(delta)                       # (B2, S)
    return ff.ff_loss_masked(g, is_pos, theta), g


CE_CHUNK = 512     # sequence chunk for vocab-logit computation


def _ce_chunked(h, w_unembed, labels, mask, softcap=0.0):
    """Cross-entropy without materializing (B, S, V) logits.

    Scans over sequence chunks; the chunk body is rematerialized so the
    backward pass never holds more than one chunk's logits either.
    h: (B, S, d); w_unembed: (V, d); labels/mask: (B, S).
    Returns summed CE and summed mask weight.
    """
    B, S, d = h.shape
    c = min(CE_CHUNK, S)
    if S % c:
        pad = c - S % c
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
        S += pad
    nc = S // c

    @jax.checkpoint
    def body(carry, inp):
        hc, lc, mc = inp                    # (B, c, d), (B, c), (B, c)
        logits = jnp.einsum("bsd,vd->bsv", hc.astype(jnp.float32),
                            w_unembed.astype(jnp.float32))
        logits = common.softcap(logits, softcap)
        lp = jax.nn.log_softmax(logits)
        ce = -jnp.take_along_axis(lp, lc[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(ce * mc), None

    r = lambda a: a.reshape(B, nc, c, *a.shape[2:]).transpose(
        1, 0, *range(2, a.ndim + 1))
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (r(h), r(labels), r(mask)))
    return total


def _local_ce(h, embed_sg, labels, mask):
    """Local classifier loss via the (stop-gradded) embedding table."""
    z = common.rms_normalize(h)
    total = _ce_chunked(z, embed_sg, labels, mask)
    return total / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# FF train step
# ---------------------------------------------------------------------------

def make_ff_train_step(cfg, *, dist=NO_DIST, lr=1e-3, seed=0):
    """Returns step_fn(params, opt_state, batch, step) ->
    (params, opt_state, metrics).

    batch: {"tokens": (B, S+1) int32, optional "aux": (B, T, d)}.
    opt_state: optim.adam_init(params).
    """
    perf_opt = cfg.ff.goodness == "perf_opt"
    theta = cfg.ff.theta

    def step_fn(params, opt_state, batch, step):
        tokens = batch["tokens"]
        B, S1 = tokens.shape
        S = S1 - 1
        pos_tok, labels = tokens[:, :-1], tokens[:, 1:]
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        metrics = {}

        if perf_opt:
            x_tok = pos_tok
            is_pos = jnp.ones((B,), jnp.float32)
            lab_all = labels
        else:
            if cfg.ff.neg_mode == "adaptive":
                # self-generated negatives from the current model (no-grad
                # extra forward — the AdaptiveNEG cost the paper reports)
                logits0, _ = transformer.forward(
                    jax.lax.stop_gradient(params), cfg, pos_tok,
                    aux=batch.get("aux"), dist=dist, remat=False)
                neg_tok = ff.adaptive_corrupt_tokens(
                    key, pos_tok, jax.lax.stop_gradient(logits0))
            else:
                nkey = (jax.random.PRNGKey(seed + 1)
                        if cfg.ff.neg_mode == "fixed" else key)
                neg_tok = ff.corrupt_tokens(nkey, pos_tok, cfg.vocab)
            x_tok = jnp.concatenate([pos_tok, neg_tok], axis=0)
            is_pos = jnp.concatenate(
                [jnp.ones((B,)), jnp.zeros((B,))]).astype(jnp.float32)
            lab_all = jnp.concatenate([labels, labels], axis=0)

        aux_in = batch.get("aux")
        if aux_in is not None and x_tok.shape[0] != aux_in.shape[0]:
            aux_in = jnp.concatenate([aux_in, aux_in], axis=0)

        embed_sg = jax.lax.stop_gradient(params["embed"])
        ce_mask = (is_pos[:, None] * jnp.ones((1, S))).astype(jnp.float32)

        # ---- embedding: FF layer 1 (local loss) -------------------------
        def embed_loss(embed):
            h = jnp.take(embed, x_tok, axis=0)
            if perf_opt:
                loss = _local_ce(h, embed_sg, lab_all, ce_mask)
            else:
                g = ff.mean_goodness(common.rms_normalize(h))
                loss = ff.ff_loss_masked(g, is_pos, theta)
            return loss, h

        # grad now, update later (tied archs add the head-CE grad below —
        # the table doubles as the paper's softmax layer)
        (emb_l, x), emb_g = jax.value_and_grad(
            embed_loss, has_aux=True)(params["embed"])
        metrics["loss_embed"] = emb_l

        # ---- encoder (enc-dec archs): FF over stub frame embeddings -----
        cross_src = aux_in
        new_groups = []
        new_m_groups = []
        new_v_groups = []
        ff_losses = []
        g_pos_sum = jnp.zeros(())
        g_neg_sum = jnp.zeros(())

        infos = transformer.group_infos(cfg)

        def make_scan(pattern, ctx):
            def body(carry, leaf):
                x_in = dist.constrain_batch(carry)
                unit_p, unit_m, unit_v = leaf

                def loss_fn(up):
                    h = jax.lax.stop_gradient(x_in)
                    total = jnp.zeros(())
                    gp = jnp.zeros(())
                    gn = jnp.zeros(())
                    for kind, bp in zip(pattern, up):
                        h_sg = jax.lax.stop_gradient(h)
                        y, moe_aux = blocks.block_apply(
                            bp, cfg, kind, h_sg, ctx)
                        if perf_opt:
                            loss = _local_ce(y, embed_sg, lab_all, ce_mask)
                        else:
                            loss, g = _block_ff_loss(y - h_sg, is_pos,
                                                     theta)
                            npos = jnp.maximum(is_pos.sum(), 1.0)
                            gp += (g.mean(1) * is_pos).sum() / npos
                            gn += (g.mean(1) * (1 - is_pos)).sum() / \
                                jnp.maximum((1 - is_pos).sum(), 1.0)
                        total = total + loss + AUX_WEIGHT * moe_aux
                        h = y
                    return total, (h, gp / len(pattern), gn / len(pattern))

                (loss, (y, gp, gn)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(unit_p)
                new_p, st = optim.adam_update(
                    unit_p, grads, {"m": unit_m, "v": unit_v},
                    lr=lr, step=step)
                y = dist.constrain_batch(jax.lax.stop_gradient(y))
                return y, (new_p, st["m"], st["v"], loss, gp, gn)
            return body

        # encoder first (if any), over aux embeddings
        if cfg.enc_dec:
            xe = aux_in
            for gi, pattern, repeat, is_enc in infos:
                if not is_enc:
                    continue
                ctx = {"causal": False, "dist": dist}
                body = make_scan(pattern, ctx)
                xe, ys = jax.lax.scan(
                    body, xe, (params["groups"][gi],
                               opt_state["m"]["groups"][gi],
                               opt_state["v"]["groups"][gi]))
                new_groups.append(ys[0])
                new_m_groups.append(ys[1])
                new_v_groups.append(ys[2])
                ff_losses.append(ys[3].sum())
                g_pos_sum += ys[4].sum()
                g_neg_sum += ys[5].sum()
            cross_src = common.rms_norm(xe, params["enc_norm"],
                                        cfg.norm_eps)

        # decoder / main stack
        ctx = {"causal": True, "aux": cross_src, "dist": dist}
        for gi, pattern, repeat, is_enc in infos:
            if is_enc:
                continue
            body = make_scan(pattern, ctx)
            x, ys = jax.lax.scan(
                body, x, (params["groups"][gi],
                          opt_state["m"]["groups"][gi],
                          opt_state["v"]["groups"][gi]))
            new_groups.append(ys[0])
            new_m_groups.append(ys[1])
            new_v_groups.append(ys[2])
            ff_losses.append(ys[3].sum())
            g_pos_sum += ys[4].sum()
            g_neg_sum += ys[5].sum()

        # ---- head: the paper's softmax layer (local CE) ------------------
        head_keys = ["final_norm"] + (
            [] if cfg.tie_embeddings else ["lm_head"])
        if cfg.enc_dec:
            head_keys.append("enc_norm")

        # CE is evaluated on the positive half only (negatives carry no
        # next-token signal); sequence-chunked so (B, S, V) logits never
        # materialize. For tied embeddings the table IS the softmax layer
        # (paper §3: trained with local CE), so it receives this grad too.
        x_pos_h = x if perf_opt else x[:B]

        def head_loss(hp):
            h = common.rms_norm(jax.lax.stop_gradient(x_pos_h),
                                hp["final_norm"], cfg.norm_eps)
            w = hp["embed"] if cfg.tie_embeddings else hp["lm_head"].T
            ones = jnp.ones(labels.shape, jnp.float32)
            total = _ce_chunked(h, w, labels, ones,
                                softcap=cfg.logit_softcap)
            return total / labels.size

        hp = {k: params[k] for k in head_keys}
        if cfg.tie_embeddings:
            hp["embed"] = params["embed"]
        ce_l, head_g = jax.value_and_grad(head_loss)(hp)

        # embedding: FF(layer-1) grad + (tied) softmax-layer CE grad
        emb_g_total = emb_g
        if cfg.tie_embeddings:
            emb_g_total = jax.tree.map(jnp.add, emb_g,
                                       head_g.pop("embed"))
            hp.pop("embed")
        new_embed, emb_opt = optim.adam_update(
            params["embed"], emb_g_total,
            {"m": opt_state["m"]["embed"], "v": opt_state["v"]["embed"]},
            lr=lr, step=step)
        new_hp, head_opt = optim.adam_update(
            hp, {k: head_g[k] for k in hp},
            {"m": {k: opt_state["m"][k] for k in hp},
             "v": {k: opt_state["v"][k] for k in hp}},
            lr=lr, step=step)

        # ---- reassemble -----------------------------------------------------
        new_params = dict(params)
        new_params["embed"] = new_embed
        new_params["groups"] = tuple(new_groups)
        for k in new_hp:
            new_params[k] = new_hp[k]
        new_m = dict(opt_state["m"])
        new_v = dict(opt_state["v"])
        new_m["embed"], new_v["embed"] = emb_opt["m"], emb_opt["v"]
        new_m["groups"] = tuple(new_m_groups)
        new_v["groups"] = tuple(new_v_groups)
        for k in new_hp:
            new_m[k] = head_opt["m"][k]
            new_v[k] = head_opt["v"][k]

        n_units = sum(r for _, _, r, _ in infos)
        metrics.update(
            loss_ff=sum(ff_losses) / max(len(ff_losses), 1),
            loss_ce=ce_l,
            goodness_pos=g_pos_sum / n_units,
            goodness_neg=g_neg_sum / n_units,
        )
        return new_params, {"m": new_m, "v": new_v}, metrics

    return step_fn


# ---------------------------------------------------------------------------
# Backprop baseline (the paper's comparison target)
# ---------------------------------------------------------------------------

def make_bp_train_step(cfg, *, dist=NO_DIST, lr=1e-3):
    """Standard end-to-end cross-entropy training step (with remat)."""

    def loss_fn(params, tokens, aux):
        inp, labels = tokens[:, :-1], tokens[:, 1:]
        logits, aux_l = transformer.forward(params, cfg, inp, aux=aux,
                                            dist=dist, remat=True)
        lp = jax.nn.log_softmax(logits)
        ce = -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(ce) + AUX_WEIGHT * aux_l

    def step_fn(params, opt_state, batch, step):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, batch["tokens"], batch.get("aux"))
        new_p, new_s = optim.adam_update(params, grads, opt_state,
                                         lr=lr, step=step)
        return new_p, new_s, {"loss_ce": loss}

    return step_fn


# ---------------------------------------------------------------------------
# Eval
# ---------------------------------------------------------------------------

EVAL_ROWS = 2      # sequences a chunk of eval_ce carries through the model


@functools.partial(jax.jit, static_argnames=("cfg",))
def eval_ce(params, cfg, tokens, aux=None):
    """Mean next-token CE over ``tokens`` (B, S+1), EVAL_ROWS sequences
    at a time through the model (B a multiple of it, or B alone), and
    the logits a sequence chunk at a time (``_ce_chunked``): neither the
    whole batch's activations nor its (B, S, V) logits are held."""
    B = tokens.shape[0]
    rows = EVAL_ROWS if B % EVAL_ROWS == 0 else B

    def chunk(args):
        tok, ax = args
        inp, labels = tok[:, :-1], tok[:, 1:]
        x, _ = transformer.hidden(params, cfg, inp, aux=ax, remat=False)
        h = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
        w = params["embed"] if cfg.tie_embeddings else params["lm_head"].T
        if cfg.padded_vocab != cfg.vocab:
            w = w[:cfg.vocab]
        return _ce_chunked(h, w, labels, jnp.ones(labels.shape, jnp.float32),
                           softcap=cfg.logit_softcap)

    split = lambda a: None if a is None else a.reshape(
        (B // rows, rows) + a.shape[1:])
    total = jnp.sum(jax.lax.map(chunk, (split(tokens), split(aux))))
    return total / (B * (tokens.shape[1] - 1))
