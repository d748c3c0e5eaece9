"""PFF over the pod axis — the paper's pipeline, TPU-native (beyond-paper).

The paper pipelines FF layer-training across socket-connected CPU nodes.
On a multi-pod TPU system the same idea maps onto the mesh: the ``pod``
axis becomes the PIPELINE-STAGE axis. Each pod owns a contiguous block
range; within a pod the usual (data, model) sharding applies.

Because FF deletes the backward pass, the inter-pod traffic is ONE
forward activation tensor per microbatch, sent via collective_permute —
no gradient return traffic, no bubble-filling schedule needed. This is
Figure 2 of the paper realized in ICI collectives:

  pod 0: block range [0, L/2)   trains on microbatch t
  pod 1: block range [L/2, L)   trains on microbatch t-1 (activations
                                 received from pod 0 last step)

Implementation: ``shard_map`` over the pod axis. Every pod executes the
same program on its own stacked slice of the layer parameters; a
carried "inflight activation" buffer plays the role of the pipeline
register. After S steps the pipeline is full and every pod trains every
step — utilization (S - P + 1)/S, exactly the paper's chapter pipeline.

The per-pod inner step reuses ``repro.core.train``'s scan body (local
FF losses + inline Adam), so numerics per block are identical to the
single-pod path; only WHERE a block trains changes — the paper's claim.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import optim, sharding
from repro.core import ff
from repro.models import blocks, common
from repro.models.mlp import NO_DIST


def make_pff_pod_step(cfg, mesh, *, lr=1e-3, seed=0, theta=None):
    """Returns step_fn(stage_params, stage_opt, batch, step) for a mesh
    with axes ("stage", "data", "model").

    stage_params: the SINGLE group's stacked params (R, ...) where R is
    divisible by the stage count; stage s owns rows [s*R/P, (s+1)*R/P).
    batch: {"tokens": (B, S+1)} — every stage needs the tokens only for
    the embedding stage; activations flow between stages.

    Restriction (documented): cfg must be single-group (uniform pattern),
    which covers 8/10 assigned archs; the hybrid/enc-dec archs use the
    single-pod FF step.
    """
    assert len(cfg.groups) == 1, "pod-pipeline needs a uniform stack"
    pattern, repeat = cfg.groups[0]
    stages = mesh.shape["stage"]
    assert repeat % stages == 0, (repeat, stages)
    theta = theta if theta is not None else cfg.ff.theta

    def local_ff_update(x, unit_p, unit_m, unit_v, is_pos, step):
        """One block-unit FF update (same math as core.train)."""
        ctx = {"causal": True, "dist": NO_DIST}

        def loss_fn(up):
            h = jax.lax.stop_gradient(x)
            total = jnp.zeros(())
            for kind, bp in zip(pattern, up):
                h_sg = jax.lax.stop_gradient(h)
                y, moe_aux = blocks.block_apply(bp, cfg, kind, h_sg, ctx)
                g = ff.mean_goodness(y - h_sg)
                total = total + ff.ff_loss_masked(g, is_pos, theta) \
                    + 0.01 * moe_aux
                h = y
            return total, h

        (loss, y), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(unit_p)
        # Data-parallel correctness: each data shard sees a DIFFERENT
        # slice of the stacked [pos; neg] batch (the first shards are
        # all-positive, the last all-negative), so the shard-local
        # gradients MUST be averaged over the data axis before the
        # update — the out_specs claim params replicated over "data",
        # and without this pmean the replicas silently diverge (and the
        # unchecked-replication assembly turns that into NaNs on
        # multi-axis meshes).
        grads = jax.lax.pmean(grads, "data")
        loss = jax.lax.pmean(loss, "data")
        new_p, st = optim.adam_update(unit_p, grads,
                                      {"m": unit_m, "v": unit_v},
                                      lr=lr, step=step)
        return jax.lax.stop_gradient(y), new_p, st, loss

    def stage_step(gp, gm, gv, x_in, is_pos, step):
        """Run this pod's block range over the incoming activations."""
        def body(carry, leaf):
            up, um, uv = leaf
            y, new_p, st, loss = local_ff_update(
                carry, up, um, uv, is_pos, step)
            return y, (new_p, st["m"], st["v"], loss)

        x_out, ys = jax.lax.scan(body, x_in, (gp, gm, gv))
        return x_out, ys[0], ys[1], ys[2], ys[3].sum()

    def pod_program(gp, gm, gv, x_in, inflight, is_pos, step):
        """shard_map body over the stage axis. inflight: the pipeline
        activation register, stage-local slice (1, 2B_local, S, d) of
        the global (stages, 2B, S, d) array — the explicit leading
        stage axis is what makes its out_specs sound (each stage's
        register genuinely differs, so it must be SHARDED over "stage",
        not falsely claimed replicated)."""
        sid = jax.lax.axis_index("stage")
        # stage 0 consumes the fresh embedding; others consume inflight
        x = jnp.where(sid == 0, x_in, inflight[0])
        y, new_gp, new_gm, new_gv, loss = stage_step(
            gp, gm, gv, x, is_pos, step)
        # forward the produced activations to the next stage (the FF
        # pipeline register) — pure forward traffic, no backward edge.
        perm = [(s, int((s + 1) % stages)) for s in range(stages)]
        new_inflight = jax.lax.ppermute(y, "stage", perm)[None]
        # total pipeline loss: the scalar leaves the shard_map with
        # out_specs P(), i.e. claimed replicated over EVERY mesh axis —
        # without this psum the claim is false over "stage" (each stage
        # had its own stage-local sum), which is exactly the kind of
        # unsound spec that miscompiles under jit (NaN weights on
        # multi-axis meshes) and that check_rep/check_vma rejects.
        loss = jax.lax.psum(loss, "stage")
        return new_gp, new_gm, new_gv, new_inflight, loss

    gspec = P("stage")          # stacked layer axis sharded over stages

    # check=True: every out_specs replication claim is sound (grads/loss
    # pmean'd over "data", loss psum'd over "stage"), so let the checker
    # prove it instead of trusting us.
    pipeline = sharding.shard_map(
        pod_program, mesh=mesh,
        in_specs=(gspec, gspec, gspec, P("data"),
                  P("stage", "data"), P("data"), P()),
        out_specs=(gspec, gspec, gspec, P("stage", "data"), P()),
        check=True)

    def prep(embed, tokens, step):
        """Negative corruption + embedding lookup (the per-step glue)."""
        tokens = tokens[:, :-1]
        B = tokens.shape[0]
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        neg = ff.corrupt_tokens(key, tokens, cfg.vocab)
        x_tok = jnp.concatenate([tokens, neg], axis=0)
        is_pos = jnp.concatenate(
            [jnp.ones((B,)), jnp.zeros((B,))]).astype(jnp.float32)
        return jnp.take(embed, x_tok, axis=0), is_pos

    @jax.jit
    def step_fn(params, opt_state, batch, inflight, step):
        """params: {"embed": ..., "groups": (stacked,)}; inflight is the
        pipeline register pytree returned by the previous call. One XLA
        program: the glue and the manually-sharded pipeline fuse."""
        step = jnp.asarray(step, jnp.int32)
        x, is_pos = prep(params["embed"], batch["tokens"], step)
        gp = params["groups"][0]
        gm = opt_state["m"]["groups"][0]
        gv = opt_state["v"]["groups"][0]
        new_gp, new_gm, new_gv, new_inflight, loss = pipeline(
            gp, gm, gv, x, inflight, is_pos, step)
        new_params = dict(params)
        new_params["groups"] = (new_gp,)
        new_m = dict(opt_state["m"]); new_m["groups"] = (new_gm,)
        new_v = dict(opt_state["v"]); new_v["groups"] = (new_gv,)
        return new_params, {"m": new_m, "v": new_v}, new_inflight, {
            "loss_ff": loss}

    return step_fn


def init_inflight(cfg, batch, seq, stages=1):
    """Zero pipeline register: (stages, 2*batch, seq, d_model) — one
    activation slot per pipeline stage (sharded over the stage axis)."""
    return jnp.zeros((stages, 2 * batch, seq, cfg.d_model),
                     common.dtype_of(cfg))
