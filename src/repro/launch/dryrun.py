"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination against ShapeDtypeStruct inputs, and extract the roofline
terms from the compiled artifact.

The os.environ lines below MUST run before any other import (jax locks
the device count on first init).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
      --shape train_4k [--multi-pod] [--out experiments/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro import sharding
from repro.configs import INPUT_SHAPES, get_config, list_configs
from repro.core import train as train_lib
from repro.launch import hlo_analysis
from repro.launch import specs as specs_lib
from repro.launch.mesh import make_production_mesh
from repro.models import transformer
from repro.models.mlp import Dist

# Per-chip peaks of a TPU v5e ("TPU v5 lite"), the one device these
# roofline terms describe
V5E_PEAK_FLOPS = 197e12      # bf16
V5E_HBM_BW = 819e9           # bytes/s
V5E_ICI_BW = 50e9            # bytes/s/link

# ---------------------------------------------------------------------------
# Lower + compile one combination
# ---------------------------------------------------------------------------

def make_dist(cfg, mesh, *, batch_fold_model=False):
    """Axis assignment. ``batch_fold_model`` is the §Perf optimization
    for dense archs whose head count does not divide the model axis
    (qwen2: 14 heads vs 16) — tensor parallelism degenerates to 16x
    replication of attention there, so we fold the model axis into the
    batch axes instead (pure DP for activations; weights stay sharded =
    ZeRO-style). Off by default: baselines are recorded without it."""
    ba = sharding.batch_axes(mesh)
    if batch_fold_model:
        ba = ba + ("model",)
    return Dist(mesh=mesh, batch_axes=ba,
                model_axis="model",
                fsdp_axis="data" if cfg.moe is not None else None)


def lower_combo(arch: str, shape_name: str, *, multi_pod=False,
                step_kind=None, lr=1e-3, opts=()):
    """Returns (lowered, meta). step_kind defaults from the shape kind:
    train -> FF train step; prefill -> prefill; decode -> serve_step.
    opts: iterable of optimization names (see §Perf), e.g.
    ("batch_fold_model",)."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if not specs_lib.combo_is_applicable(cfg, shape_name):
        raise ValueError(f"{arch} x {shape_name}: inapplicable "
                         "(full attention at 500k)")
    mesh = make_production_mesh(multi_pod=multi_pod)
    dist = make_dist(cfg, mesh,
                     batch_fold_model="batch_fold_model" in opts)
    from repro.models import attention as attention_mod
    attention_mod.DEFAULT_CAUSAL_SKIP = "causal_skip" in opts
    attention_mod.PV_BF16 = "pv_bf16" in opts
    kind = step_kind or shape.kind

    p_sds, o_sds = specs_lib.param_specs_abstract(
        cfg, mesh, with_opt=(kind == "train"))

    if kind == "train":
        step_fn = train_lib.make_ff_train_step(cfg, dist=dist, lr=lr)
        batch = specs_lib.train_input_specs(cfg, shape, mesh,
                                            batch_axes=dist.batch_axes)
        step = jax.ShapeDtypeStruct(
            (), jnp.int32,
            sharding=jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()))
        with mesh:
            lowered = jax.jit(step_fn).lower(p_sds, o_sds, batch, step)
    elif kind == "prefill":
        def fn(params, batch):
            return transformer.prefill(
                params, cfg, batch["tokens"], aux=batch.get("aux"),
                dist=dist, last_only=True)
        batch = specs_lib.prefill_input_specs(cfg, shape, mesh)
        with mesh:
            lowered = jax.jit(fn).lower(p_sds, batch)
    elif kind == "decode":
        def fn(params, caches, tokens, pos):
            return transformer.serve_step(params, cfg, caches, tokens,
                                          pos, dist=dist)
        caches, tokens, pos = specs_lib.decode_input_specs(cfg, shape, mesh)
        with mesh:
            lowered = jax.jit(fn).lower(p_sds, caches, tokens, pos)
    else:
        raise ValueError(kind)

    meta = {"arch": arch, "shape": shape_name, "kind": kind,
            "multi_pod": multi_pod, "mesh": dict(mesh.shape),
            "chips": mesh.size}
    return lowered, meta


def model_flops(cfg, shape, kind):
    """Reference FLOPs: 6*N_active*D (train) / 2*N_active*D (inference)
    plus the attention term 12*B*S^2*(H*hd) per attention layer (times 3
    for train fwd+bwd, halved for causality). This is the 'useful work'
    yardstick for HLO_FLOPs / MODEL_FLOPS."""
    import math
    p_sds = jax.eval_shape(lambda k: transformer.init(k, cfg),
                           jax.random.PRNGKey(0))
    total = sum(math.prod(s.shape) for s in jax.tree.leaves(p_sds))
    active = total
    if cfg.moe is not None:
        m = cfg.moe
        expert_p = 3 * cfg.d_model * m.expert_ff * m.num_experts \
            * cfg.num_layers
        active_expert = expert_p * m.top_k / m.num_experts
        active = total - expert_p + active_expert

    # attention layers and their effective context
    n_attn = 0
    ctx = shape.seq_len
    for pattern, repeat in cfg.groups:
        for kind_b in pattern:
            if kind_b in ("attn", "xdec"):
                n_attn += repeat
            elif kind_b == "local_attn":
                n_attn += repeat * min(
                    (cfg.rglru.window if cfg.rglru else cfg.window or ctx),
                    ctx) / ctx
    if cfg.window:
        ctx = min(cfg.window, ctx)
    hhd = cfg.n_heads * cfg.resolved_head_dim

    B, S = shape.global_batch, shape.seq_len
    if kind == "train":
        # pos+neg concat doubles tokens; FF ~ 3x fwd (fwd + 1-block bwd)
        tokens = 2 * B * S
        attn = 3 * 2 * 2 * tokens * (ctx / 2) * hhd * n_attn / 1
        return 6 * active * tokens + attn
    if kind == "prefill":
        tokens = B * S
        attn = 2 * 2 * tokens * (ctx / 2) * hhd * n_attn
        return 2 * active * tokens + attn
    # decode: 1 token/seq against a ctx-deep cache; enc-dec archs run
    # only the decoder blocks
    if cfg.enc_dec:
        embed_p = cfg.vocab * cfg.d_model * (1 if cfg.tie_embeddings
                                             else 2)
        frac = cfg.num_layers / (cfg.num_layers + cfg.enc_layers)
        active = embed_p + (active - embed_p) * frac
    attn = 2 * 2 * B * ctx * hhd * n_attn
    return 2 * active * B + attn


def analyze_combo(arch, shape_name, *, multi_pod=False, compile_=True,
                  step_kind=None, opts=()):
    t0 = time.time()
    lowered, meta = lower_combo(arch, shape_name, multi_pod=multi_pod,
                                step_kind=step_kind, opts=opts)
    meta["lower_s"] = round(time.time() - t0, 1)
    if opts:
        meta["opts"] = list(opts)
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    chips = meta["chips"]

    if compile_:
        t1 = time.time()
        compiled = lowered.compile()
        meta["compile_s"] = round(time.time() - t1, 1)
        ca = compiled.cost_analysis() or {}
        ma = compiled.memory_analysis()
        hlo = compiled.as_text()
    else:
        ca, ma = {}, None
        hlo = lowered.as_text()

    # trip-count-aware static analysis of the per-device SPMD program
    an = hlo_analysis.analyze(hlo)
    per_dev_flops = an["flops"]
    per_dev_bytes = an["bytes"]
    per_dev_coll = an["collective_bytes"]

    mem = {}
    if ma is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)

    mflops = model_flops(cfg, shape, meta["kind"])

    res = dict(meta)
    res.update(
        hlo_flops_total=per_dev_flops * chips,
        hlo_bytes_total=per_dev_bytes * chips,
        collective_bytes_per_dev=per_dev_coll,
        collective_by_type=an["collective_by_type"],
        collective_counts=an["collective_counts"],
        memory=mem,
        xla_cost_analysis={k: float(v) for k, v in ca.items()
                           if k in ("flops", "bytes accessed")},
        model_flops=mflops,
        compute_term_s=per_dev_flops / V5E_PEAK_FLOPS,
        memory_term_s=per_dev_bytes / V5E_HBM_BW,
        collective_term_s=per_dev_coll / V5E_ICI_BW,
        flops_utilization=(mflops / (per_dev_flops * chips)
                           if per_dev_flops else 0.0),
    )
    terms = {"compute": res["compute_term_s"],
             "memory": res["memory_term_s"],
             "collective": res["collective_term_s"]}
    res["bottleneck"] = max(terms, key=terms.get)
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    combos = []
    if args.all:
        for arch in list_configs():
            cfg = get_config(arch)
            for shape in INPUT_SHAPES:
                if specs_lib.combo_is_applicable(cfg, shape):
                    combos.append((arch, shape, args.multi_pod))
    else:
        combos = [(args.arch, args.shape, args.multi_pod)]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch, shape, mp in combos:
        tag = f"{arch}__{shape}__{'pod2' if mp else 'pod1'}"
        try:
            res = analyze_combo(arch, shape, multi_pod=mp,
                                compile_=not args.no_compile)
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(res, f, indent=1)
            print(f"OK   {tag}: bottleneck={res['bottleneck']} "
                  f"compute={res['compute_term_s']:.4f}s "
                  f"memory={res['memory_term_s']:.4f}s "
                  f"collective={res['collective_term_s']:.4f}s "
                  f"(lower {res['lower_s']}s compile "
                  f"{res.get('compile_s', 0)}s)")
        except Exception as e:  # noqa: BLE001 — report and continue
            failures.append((tag, repr(e)[:200]))
            print(f"FAIL {tag}: {repr(e)[:200]}")
    if failures:
        raise SystemExit(f"{len(failures)} combos failed: "
                         + ", ".join(t for t, _ in failures))
    print("all combos lowered + compiled")


if __name__ == "__main__":
    main()
