"""Top-level model assembly: embedding -> grouped blocks -> norm -> head.

A model is a stack of *groups*; each group is ``(pattern, repeat)`` and its
parameters are stacked on a leading ``repeat`` axis, applied with
``lax.scan`` so the HLO is O(#patterns), not O(#layers).

Three entry points:
  ``forward``     — full-sequence (train / prefill) -> logits
  ``prefill``     — full-sequence forward that also fills decode caches
  ``serve_step``  — one-token decode against caches

Encoder-decoder (``cfg.enc_dec``): the leading groups that fall inside
``cfg.enc_layers`` form the (bidirectional) encoder over the stub audio
embeddings; the rest form the decoder, cross-attending to encoder output.
VLM (``cfg.vision_tokens``): cross_attn blocks attend to the stub patch
embeddings passed as ``aux``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import blocks, common
from repro.models.mlp import NO_DIST


# ---------------------------------------------------------------------------
# Group bookkeeping
# ---------------------------------------------------------------------------

def group_infos(cfg):
    """Yields (index, pattern, repeat, is_encoder) for each group."""
    seen = 0
    out = []
    for gi, (pattern, repeat) in enumerate(cfg.groups):
        n = len(pattern) * repeat
        is_enc = bool(cfg.enc_dec) and seen + n <= cfg.enc_layers
        out.append((gi, pattern, repeat, is_enc))
        seen += n
    return out


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init(key, cfg):
    dtype = common.dtype_of(cfg)
    ks = jax.random.split(key, 3 + len(cfg.groups))
    params = {
        "embed": common.dense_init(ks[0], (cfg.padded_vocab, cfg.d_model),
                                   dtype, fan_in=cfg.d_model),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    groups = []
    for gi, (pattern, repeat) in enumerate(cfg.groups):
        def unit(k, pattern=pattern):
            kk = jax.random.split(k, len(pattern))
            return tuple(blocks.block_init(kk[i], cfg, kind)
                         for i, kind in enumerate(pattern))
        groups.append(common.stack_init(ks[3 + gi], repeat, unit))
    params["groups"] = tuple(groups)
    if cfg.enc_dec:
        params["enc_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
    if not cfg.tie_embeddings:
        params["lm_head"] = common.dense_init(
            ks[1], (cfg.d_model, cfg.padded_vocab), dtype)
    return params


def param_count(params):
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------

def unit_apply(unit_params, cfg, pattern, x, ctx):
    aux_total = jnp.zeros((), jnp.float32)
    for kind, bp in zip(pattern, unit_params):
        x, a = blocks.block_apply(bp, cfg, kind, x, ctx)
        aux_total += a
    return x, aux_total


def scan_group(gp, cfg, pattern, x, ctx, remat=False):
    dist = ctx.get("dist", NO_DIST)

    def body(carry, unit_p):
        carry = dist.constrain_batch(carry)
        y, aux = unit_apply(unit_p, cfg, pattern, carry, ctx)
        return dist.constrain_batch(y), aux

    if remat:
        body = jax.checkpoint(body)
    x, auxs = jax.lax.scan(body, x, gp)
    return x, jnp.sum(auxs)


def encode(params, cfg, aux_embed, dist=NO_DIST, remat=False):
    """Run encoder groups bidirectionally over stub frame embeddings."""
    x = aux_embed
    ctx = {"causal": False, "dist": dist}
    aux_loss = jnp.zeros((), jnp.float32)
    for gi, pattern, repeat, is_enc in group_infos(cfg):
        if not is_enc:
            continue
        x, a = scan_group(params["groups"][gi], cfg, pattern, x, ctx, remat)
        aux_loss += a
    return common.rms_norm(x, params["enc_norm"], cfg.norm_eps), aux_loss


def embed_tokens(params, cfg, tokens):
    return jnp.take(params["embed"], tokens, axis=0)


def unembed(params, cfg, x):
    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x, params["embed"])
    else:
        logits = x @ params["lm_head"]
    logits = common.softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    if cfg.padded_vocab != cfg.vocab:       # mask pad-row logits
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab
        logits = jnp.where(pad_mask, -1e30, logits)
    return logits


def hidden(params, cfg, tokens, *, aux=None, dist=NO_DIST, remat=None):
    """tokens: (B, S) int32; aux: (B, T, d) stub embeddings (audio/vlm).

    Returns (the last block's output (B, S, d), aux_loss scalar).
    """
    remat = cfg.remat if remat is None else remat
    aux_loss = jnp.zeros((), jnp.float32)
    cross_src = aux
    if cfg.enc_dec:
        cross_src, aux_loss = encode(params, cfg, aux, dist, remat)
    x = embed_tokens(params, cfg, tokens)
    ctx = {"causal": True, "aux": cross_src, "dist": dist}
    for gi, pattern, repeat, is_enc in group_infos(cfg):
        if is_enc:
            continue
        x, a = scan_group(params["groups"][gi], cfg, pattern, x, ctx, remat)
        aux_loss += a
    return x, aux_loss


def forward(params, cfg, tokens, *, aux=None, dist=NO_DIST, remat=None):
    """tokens: (B, S) int32; aux: (B, T, d) stub embeddings (audio/vlm).

    Returns (logits (B, S, V) f32, aux_loss scalar).
    """
    x, aux_loss = hidden(params, cfg, tokens, aux=aux, dist=dist,
                         remat=remat)
    return unembed(params, cfg, x), aux_loss


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------

def init_caches(cfg, batch, max_len, dtype=None):
    """Stacked (repeat-leading) caches for every decoder group."""
    dtype = dtype or common.dtype_of(cfg)
    caches = []
    for gi, pattern, repeat, is_enc in group_infos(cfg):
        if is_enc:
            caches.append(None)
            continue
        unit = tuple(
            jax.eval_shape(
                lambda kind=kind: blocks.block_cache_init(
                    cfg, kind, batch, max_len, dtype))
            for kind in pattern)
        caches.append(jax.tree.map(
            lambda s: jnp.full((repeat,) + s.shape,
                               -1 if s.dtype == jnp.int32 else 0, s.dtype),
            unit))
    return tuple(caches)


def cache_specs(cfg, batch, max_len, dtype=None):
    """ShapeDtypeStruct pytree of init_caches, for dry-run lowering."""
    dtype = dtype or common.dtype_of(cfg)
    return jax.eval_shape(lambda: init_caches(cfg, batch, max_len, dtype))


# ---------------------------------------------------------------------------
# Prefill (fills caches) and decode
# ---------------------------------------------------------------------------

def prefill(params, cfg, tokens, *, aux=None, dist=NO_DIST, max_len=None,
            last_only=False):
    """Full forward that also returns filled decode caches.

    Returns (logits, caches) — logits over all S positions, or only the
    final position with ``last_only`` (what real serving needs: at 32k
    context the full (B, S, V) logits are ~TBs; the last row is MBs).
    ``max_len`` sizes the KV caches (defaults to S; pass S + generation
    budget for real serving).
    """
    B, S = tokens.shape
    max_len = max_len or S
    aux_loss = jnp.zeros((), jnp.float32)
    cross_src = aux
    if cfg.enc_dec:
        cross_src, aux_loss = encode(params, cfg, aux, dist, remat=False)
    x = embed_tokens(params, cfg, tokens)
    ctx = {"causal": True, "aux": cross_src, "dist": dist,
           "max_len": max_len}
    caches = []
    for gi, pattern, repeat, is_enc in group_infos(cfg):
        if is_enc:
            caches.append(None)
            continue

        def body(carry, unit_p, pattern=pattern):
            h = carry
            ucaches = []
            for kind, bp in zip(pattern, unit_p):
                h, c = blocks.block_prefill(bp, cfg, kind, h, ctx)
                ucaches.append(c)
            return h, tuple(ucaches)

        x, gcache = jax.lax.scan(body, x, params["groups"][gi])
        caches.append(gcache)
    if last_only:
        x = x[:, -1]
    return unembed(params, cfg, x), tuple(caches)


def serve_step(params, cfg, caches, tokens, pos, *, dist=NO_DIST):
    """One decode step. tokens: (B,) int32; pos: scalar int32 (position of
    the new token). Returns (logits (B, V), new_caches)."""
    x = embed_tokens(params, cfg, tokens)
    ctx = {"dist": dist}
    new_caches = []
    for gi, pattern, repeat, is_enc in group_infos(cfg):
        if is_enc:
            new_caches.append(None)
            continue

        def body(carry, pc, pattern=pattern):
            h = carry
            unit_p, unit_c = pc
            ucaches = []
            for kind, bp, c in zip(pattern, unit_p, unit_c):
                h, c2 = blocks.block_decode(bp, cfg, kind, c, h, pos, ctx)
                ucaches.append(c2)
            return h, tuple(ucaches)

        x, gcache = jax.lax.scan(
            body, x, (params["groups"][gi], caches[gi]))
        new_caches.append(gcache)
    return unembed(params, cfg, x), tuple(new_caches)
