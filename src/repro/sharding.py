"""Logical-to-mesh sharding rules.

Single-pod production mesh: ``(data=16, model=16)``.
Multi-pod: ``(pod=2, data=16, model=16)`` — baseline shards batch on
``(pod, data)`` (pure DP over pods) and parameters exactly as single-pod
(replicated over ``pod``). The beyond-paper PFF mode instead uses ``pod``
as the pipeline-stage axis (see ``repro.core.pff_pod``).

Parameter rules are name-based over the pytree path, with a divisibility
guard: any named mesh axis that does not divide the corresponding dim is
dropped (-> replicated) so every assigned architecture lowers (e.g. KV=4
heads cannot shard over model=16; h2o-danube head_dim=120 cannot shard
over 16).

Conventions (leading ``R`` = stacked scan axis, never sharded):
  embed (V, d)            -> (model, data)        vocab-parallel + FSDP
  lm_head (d, V)          -> (data, model)
  attn wq (R, d, H, hd)   -> (-, data, model, -)  head-parallel + FSDP
  attn wk/wv (R, d,KV,hd) -> (-, data, model|-, model if KV undiv)
  attn wo (R, H, hd, d)   -> (-, model, -, data)
  mlp wi/wg (R, d, ff)    -> (-, data, model)
  mlp wo (R, ff, d)       -> (-, model, data)
  moe wi/wg (R, E, d, ff) -> (-, model, data, -)  expert-parallel + ZeRO
  moe wo (R, E, ff, d)    -> (-, model, -, data)
  ssm/rglru projections   -> (-, data, model) ; out_proj (-, model, data)
  norms / scalars         -> replicated
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def shard_map(body, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with ``check_vma`` spelled ``check``: one
    wrapper for every call site."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def _fit(spec, shape, mesh):
    """Drop axis names that don't divide the dim; None-pad to rank."""
    names = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, name in zip(shape, names):
        if name is None:
            out.append(None)
            continue
        size = 1
        for n in (name if isinstance(name, tuple) else (name,)):
            size *= mesh.shape[n]
        out.append(name if dim % size == 0 else None)
    return P(*out)


# desired spec by (parent-key or leaf-name); checked most-specific-first
_RULES = [
    # (predicate on path names, spec builder)  — R axis is always first for
    # group params; non-group params (embed/lm_head) have no R axis.
    ("embed",      lambda: P("model", "data")),
    ("lm_head",    lambda: P("data", "model")),
    ("final_norm", lambda: P(None)),
    ("enc_norm",   lambda: P(None)),
]

_GROUP_LEAF = {
    "wq":       (None, "data", "model", None),
    "wk":       (None, "data", "model", None),
    "wv":       (None, "data", "model", None),
    "wo":       None,   # context-dependent: attn (R,H,hd,d) vs mlp (R,ff,d)
    "wi":       None,   # mlp (R,d,ff) vs moe (R,E,d,ff)
    "wg":       None,
    "bq":       (None, "model", None),
    "bk":       (None, "model", None),
    "bv":       (None, "model", None),
    "q_norm":   (None, None),
    "k_norm":   (None, None),
    "gate":     (None,),
    "router":   (None, None, None),
    "in_proj":  (None, "data", "model"),
    "conv_w":   (None, None, "model"),
    "A_log":    (None, None),
    "D":        (None, None),
    "dt_bias":  (None, None),
    "norm":     (None, "model"),
    "out_proj": (None, "model", "data"),
    "x_branch": (None, "data", "model"),
    "gate_branch": (None, "data", "model"),
    "w_a":      (None, "data", "model"),
    "w_x":      (None, "data", "model"),
    "lambda":   (None, "model"),
    "norm1":    (None, None),
    "norm2":    (None, None),
    "norm_x":   (None, None),
}


def _leaf_spec(path_names, shape):
    name = path_names[-1]
    if name in ("wi", "wg"):
        if len(shape) == 4:                       # moe (R, E, d, ff)
            return (None, "model", "data", None)
        return (None, "data", "model")            # dense (R, d, ff)
    if name == "wo":
        if len(shape) == 4:
            if "attn" in path_names or "xattn" in path_names:
                return (None, "model", None, "data")   # attn (R,H,hd,d)
            return (None, "model", None, "data")       # moe (R,E,ff,d)
        return (None, "model", "data")                 # mlp (R, ff, d)
    if name in _GROUP_LEAF and _GROUP_LEAF[name] is not None:
        return _GROUP_LEAF[name]
    return tuple(None for _ in shape)


def param_specs(params, mesh):
    """PartitionSpec pytree matching ``params`` (works for opt m/v too)."""
    def spec_for(path, leaf):
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        for key, builder in _RULES:
            if names and names[0] == key:
                return _fit(builder(), leaf.shape, mesh)
        return _fit(P(*_leaf_spec(names, leaf.shape)), leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def shardings(tree_specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_axes(mesh):
    """Mesh axes that shard the batch dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def data_spec(mesh, rank, batch_dim=0):
    """Spec for a batch-dim-sharded array of given rank."""
    ba = batch_axes(mesh)
    dims = [None] * rank
    dims[batch_dim] = ba if len(ba) > 1 else ba[0]
    return P(*dims)


def cache_specs_tree(caches, mesh, *, seq_axis_model=False):
    """Shardings for decode caches.

    KV caches (R, B, S, KV, hd): batch -> data axes; when
    ``seq_axis_model`` shard S on 'model' (used for batch=1 long-context,
    where batch cannot use the data axis).
    SSM/RG-LRU states (R, B, ...): batch -> data, trailing dims on model
    where divisible.
    """
    ba = batch_axes(mesh)
    b_name = ba if len(ba) > 1 else ba[0]

    def spec_for(leaf):
        shape = leaf.shape
        if len(shape) >= 3:
            want = [None, b_name]
            if len(shape) == 5:                      # (R, B, S, KV, hd)
                want += ["model" if seq_axis_model else None, "model"
                         if not seq_axis_model else None, None]
            elif len(shape) == 4:                    # (R, B, H, ...) state
                want += ["model", None]
            else:
                want += [None] * (len(shape) - 2)
            return _fit(P(*want[:len(shape)]), shape, mesh)
        return P(*([None] * len(shape)))

    return jax.tree.map(spec_for, caches)
