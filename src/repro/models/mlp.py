"""Dense (gated) MLP and Mixture-of-Experts.

MoE uses MegaBlocks-style sort-based dispatch with a fixed per-shard
capacity. Under distribution it runs inside ``shard_map``:

  tokens sharded on the batch ('data') axis, experts sharded on the
  'model' axis (expert parallelism), expert weights additionally sharded
  on 'data' (ZeRO-3) and all-gathered per layer. Dispatch:
  local sort -> all_to_all over 'model' -> per-expert matmul ->
  all_to_all back -> weighted combine.

For decode-sized token counts a dense-local-experts path is used (every
device runs its local experts over all tokens, psum over 'model'): this
matches real decode behaviour — memory-bound on expert weights — and
avoids degenerate capacities.

A configuration without a capacity (``MoEConfig.capacity_factor`` None)
drops no token: the router scores every expert, and the layer computes
the part of the routed sum that the experts it holds give
(``MoEConfig.held``, ``shard``) for every assignment to them, sorted by
expert and multiplied group by group (``_moe_held``). It runs on one
chip, without the exchange expert parallelism adds between chips.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro.models import common


# ---------------------------------------------------------------------------
# Distribution context threaded through the model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Dist:
    """Names of mesh axes; None disables explicit collectives (smoke/CPU)."""
    mesh: object = None
    batch_axes: Tuple[str, ...] = ()     # axes sharding the batch/token dim
    model_axis: Optional[str] = None     # tensor/expert-parallel axis
    fsdp_axis: Optional[str] = None      # axis sharding expert d_model (ZeRO)

    @property
    def enabled(self):
        return self.mesh is not None and self.model_axis is not None

    def model_size(self):
        return self.mesh.shape[self.model_axis] if self.enabled else 1

    def constrain_batch(self, x):
        """Pin an activation's leading (batch) dim to the data axes —
        GSPMD sometimes loses batch sharding through scan bodies +
        value_and_grad; this keeps every layer batch-parallel."""
        if not self.enabled or x is None:
            return x
        P = jax.sharding.PartitionSpec
        ba = self.batch_axes
        if not ba:
            return x
        spec = P(ba if len(ba) > 1 else ba[0],
                 *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec))


NO_DIST = Dist()


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------

def dense_mlp_init(key, d_model, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": common.dense_init(k1, (d_model, d_ff), dtype),
        "wg": common.dense_init(k2, (d_model, d_ff), dtype),
        "wo": common.dense_init(k3, (d_ff, d_model), dtype),
    }


def dense_mlp_apply(p, x, act_name="silu"):
    act = common.activation(act_name)
    h = act(x @ p["wg"]) * (x @ p["wi"])
    return h @ p["wo"]


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def moe_init(key, moe, d_model, dtype):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    E = moe.held_experts
    p = {
        "router": common.dense_init(k1, (d_model, moe.num_experts),
                                    jnp.float32),
        "wi": common.dense_init(k2, (E, d_model, moe.expert_ff),
                                dtype, fan_in=d_model),
        "wg": common.dense_init(k3, (E, d_model, moe.expert_ff),
                                dtype, fan_in=d_model),
        "wo": common.dense_init(k4, (E, moe.expert_ff, d_model),
                                dtype, fan_in=moe.expert_ff),
    }
    if moe.num_shared:
        p["shared"] = dense_mlp_init(
            k5, d_model, moe.num_shared * moe.shared_ff, dtype)
    return p


def _capacity(tokens, top_k, num_experts, cf):
    c = int(tokens * top_k / num_experts * cf)
    return max(8, -(-c // 8) * 8)        # round up to multiple of 8


def _router(p, x, moe):
    logits = x.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eids = jax.lax.top_k(probs, moe.top_k)           # (T, k)
    if moe.norm_topk:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    # switch-style load-balance loss (computed locally, pmean'd by caller)
    me = probs.mean(axis=0)                                # (E,)
    one_hot = jax.nn.one_hot(eids[:, 0], moe.num_experts, dtype=jnp.float32)
    ce = one_hot.mean(axis=0)
    aux = moe.num_experts * jnp.sum(me * ce)
    return gate, eids, aux


def _sorted_dispatch(x, eids, num_experts, capacity):
    """x: (T, d), eids: (T, k) -> buf (E, C, d), plus combine metadata."""
    T, d = x.shape
    k = eids.shape[1]
    flat_e = eids.reshape(-1)                              # (Tk,)
    sort_idx = jnp.argsort(flat_e)                         # stable
    sorted_e = flat_e[sort_idx]
    counts = jnp.bincount(flat_e, length=num_experts)
    offsets = jnp.concatenate([jnp.zeros(1, counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(T * k, dtype=jnp.int32) - offsets[sorted_e]
    valid = pos_in_e < capacity
    dest = jnp.where(valid, sorted_e * capacity + pos_in_e,
                     num_experts * capacity)               # overflow -> drop
    buf = jnp.zeros((num_experts * capacity + 1, d), x.dtype)
    buf = buf.at[dest].set(x[sort_idx // k], mode="drop")
    return buf[:-1].reshape(num_experts, capacity, d), (sort_idx, dest, valid)


def _combine(buf_out, meta, T, k, gate):
    sort_idx, dest, valid = meta
    d = buf_out.shape[-1]
    flat = buf_out.reshape(-1, d)
    rows = jnp.where(valid, dest, 0)[..., None]
    y_sorted = jnp.take_along_axis(
        flat, jnp.broadcast_to(rows, (T * k, d)), axis=0)
    y_sorted = jnp.where(valid[:, None], y_sorted, 0)
    inv = jnp.argsort(sort_idx)
    y_tk = y_sorted[inv].reshape(T, k, d)
    return jnp.einsum("tkd,tk->td", y_tk.astype(jnp.float32),
                      gate).astype(buf_out.dtype)


def _expert_ffn(wi, wg, wo, tokens, act_name):
    act = common.activation(act_name)
    h = act(jnp.einsum("ecd,edf->ecf", tokens, wg))
    h = h * jnp.einsum("ecd,edf->ecf", tokens, wi)
    return jnp.einsum("ecf,efd->ecd", h, wo)


def _moe_held(p, x, moe, act_name):
    """Every assignment to a held expert, none dropped. x: (T, d).
    Returns (y, aux, counts): counts (held,) are the assignments to each
    held expert."""
    T, d = x.shape
    k, held = moe.top_k, moe.held_experts
    gate, eids, aux = _router(p, x, moe)
    local = eids.reshape(-1) - moe.shard * held
    group = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(group)                             # stable
    counts = jnp.bincount(group, length=held + 1)[:held]
    # the first sum(counts) sorted rows are the held experts' groups;
    # the rows after them belong to no group and stay zero both ways
    rows = jnp.arange(T * k) < jnp.sum(counts)
    tok = order // k
    xs = jnp.where(rows[:, None], x[tok], 0)

    def grouped(a, w):
        return jax.lax.ragged_dot(a, w, counts)

    act = common.activation(act_name)
    h = act(grouped(xs, p["wg"])) * grouped(xs, p["wi"])
    out = jnp.where(rows[:, None], grouped(h, p["wo"]), 0)
    g = jnp.where(rows, gate.reshape(-1)[order], 0)
    y = jnp.zeros((T, d), jnp.float32).at[tok].add(
        out.astype(jnp.float32) * g[:, None])
    return y.astype(x.dtype), aux, counts


def _moe_local(p, x, moe, act_name, dist: Dist):
    """Body that runs per-shard (or globally when dist is disabled).

    x: (T, d) local tokens; p['wi'] etc are LOCAL shards when dist.enabled:
    (E_local, d_local, ff). Gathers weights over the fsdp axis, dispatches
    tokens over the model axis with all_to_all.
    """
    T, d = x.shape
    gate, eids, aux = _router(p, x, moe)
    n_model = dist.model_size()
    wi, wg, wo = p["wi"], p["wg"], p["wo"]
    if dist.enabled and dist.fsdp_axis is not None:
        wi = jax.lax.all_gather(wi, dist.fsdp_axis, axis=1, tiled=True)
        wg = jax.lax.all_gather(wg, dist.fsdp_axis, axis=1, tiled=True)
        wo = jax.lax.all_gather(wo, dist.fsdp_axis, axis=2, tiled=True)

    decode_sized = T <= 64 * moe.top_k
    if decode_sized:
        # dense-local-experts: (T, E_l) gates for the local expert slice
        e_l = wi.shape[0]
        shard_id = (jax.lax.axis_index(dist.model_axis)
                    if dist.enabled else 0)
        gates_full = jnp.zeros((T, moe.num_experts), jnp.float32)
        gates_full = jax.vmap(
            lambda g, e, row: row.at[e].set(g))(gate, eids, gates_full)
        local_slice = jax.lax.dynamic_slice(
            gates_full, (0, shard_id * e_l), (T, e_l))
        h = _expert_ffn(wi, wg, wo, jnp.broadcast_to(x, (e_l, T, d))
                        .transpose(0, 1, 2), act_name)       # (E_l, T, d)
        y = jnp.einsum("etd,te->td", h.astype(jnp.float32), local_slice)
        if dist.enabled:
            y = jax.lax.psum(y, dist.model_axis)
        y = y.astype(x.dtype)
    else:
        cap = _capacity(T, moe.top_k, moe.num_experts, moe.capacity_factor)
        buf, meta = _sorted_dispatch(x, eids, moe.num_experts, cap)
        if dist.enabled:
            e_l = moe.num_experts // n_model
            buf = buf.reshape(n_model, e_l, cap, d)
            buf = jax.lax.all_to_all(buf, dist.model_axis, split_axis=0,
                                     concat_axis=0, tiled=False)
            # (n_model, e_l, cap, d) axis0 = source shard
            tokens = buf.transpose(1, 0, 2, 3).reshape(e_l, n_model * cap, d)
            out = _expert_ffn(wi, wg, wo, tokens, act_name)
            out = out.reshape(e_l, n_model, cap, d).transpose(1, 0, 2, 3)
            out = jax.lax.all_to_all(out, dist.model_axis, split_axis=0,
                                     concat_axis=0, tiled=False)
            buf_out = out.reshape(moe.num_experts, cap, d)
        else:
            buf_out = _expert_ffn(wi, wg, wo, buf, act_name)
        y = _combine(buf_out, meta, T, moe.top_k, gate)

    if "shared" in p:
        # shared experts run tensor-parallel: ff sharded on model axis
        ys = dense_mlp_apply(p["shared"], x, act_name)
        if dist.enabled:
            ys = jax.lax.psum(ys, dist.model_axis)
        y = y + ys
    if dist.enabled and dist.batch_axes:
        aux = jax.lax.pmean(aux, dist.batch_axes)
    return y, aux


def moe_apply(p, x, moe, act_name, dist: Dist = NO_DIST, stats=None):
    """x: (B, S, d) global (pjit-land). Returns (y, aux_loss). Without a
    capacity, ``stats`` (a list, if given) receives the held experts'
    assignment counts."""
    B, S, d = x.shape
    if moe.capacity_factor is None:
        if dist.enabled:
            raise NotImplementedError(
                "the dropless held-expert MoE runs on one chip, without "
                "the exchange between chips")
        y, aux, counts = _moe_held(p, x.reshape(-1, d), moe, act_name)
        if "shared" in p:
            y = y + dense_mlp_apply(p["shared"], x.reshape(-1, d), act_name)
        if stats is not None:
            stats.append(counts)
        return y.reshape(x.shape), aux
    if moe.held:
        raise NotImplementedError("a layer holding part of the experts "
                                  "routes without a capacity")

    def body(p_, x_):
        xt = x_.reshape(-1, d)
        y, aux = _moe_local(p_, xt, moe, act_name, dist)
        return y.reshape(x_.shape), aux

    if not dist.enabled:
        return body(p, x)

    P = jax.sharding.PartitionSpec
    ba = dist.batch_axes
    ma, fa = dist.model_axis, dist.fsdp_axis
    in_x = P(ba if ba else None, None, None)
    specs = {
        "router": P(None, None),
        "wi": P(ma, fa, None),
        "wg": P(ma, fa, None),
        "wo": P(ma, None, fa),
    }
    if "shared" in p:
        specs["shared"] = {"wi": P(None, ma), "wg": P(None, ma),
                           "wo": P(ma, None)}
    fn = sharding.shard_map(
        body, mesh=dist.mesh, in_specs=(specs, in_x),
        out_specs=(in_x, P()))
    return fn(p, x)
