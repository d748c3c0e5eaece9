"""The LM chapter family: a DeepSeek-V2 stack (multi-head latent
attention, a leading dense layer, then shared + routed experts, of
which this chip holds a share) trained by chapter Forward-Forward
through ``repro.api.fit`` (``core.pff_lm``: per-block FF tasks, then
the softmax-head task, every chapter), on seeded Zipf tokens, and
compared with the plain reference ``bench/ff_lm_reference.py``.

The configuration file holds the published ``config.json`` values
under their own keys, with the cut ones (``reduced``) at what runs,
plus ``router_experts`` (what the router scores), ``expert_shard``,
``aux_loss_alpha``, ``theta``, ``lr`` and ``head_lr``. The traffic file
gives the backend, chips, chapters, steps per chapter, batch (positive
sequences; as many corrupted negatives join them in a block step), the
sequence length and the Zipf exponent of the token law.

A sample is one token of a positive training sequence through every
block task and the head of one chapter. The check job is the window's
job (and, for the state its first chapter leaves, the same job cut to
that chapter). The reference follows it from the same initial weights
(``pff_lm.init_state``; ``tests/test_deepseek_v2.py`` holds that state
to the published layout) and the same tokens, drawn from the source
here, and is fed the program's blocks: in each chapter block k trains
on the outputs of the blocks < k as the program left them, with its own
state carried from chapter to chapter, and the head on all of them.
So each task is compared on the inputs the program gave it, and a
routing choice that round-off flips upstream does not reach the blocks
after it. The numbers (the cell's limits file names those compared):

- ``loss_gap_b<k>``: the largest relative gap, over block k's steps in
  every chapter, between the FF loss the program reports and the
  reference's;
- ``unit_diff_b<k>``: after each chapter, per leaf of the block (each
  held expert's matrices a leaf of their own), the median over the
  units (columns of the leaf's last axis) the reference moves (over a
  thousandth of the median unit's change from the initial weights) of
  |program's change - reference's| over |reference's change|; the
  worst leaf and chapter. A state returned unchanged reads 1;
- ``head_ce_gap``, ``unit_diff_head``: the same of the head task (its
  CE per step; ``final_norm`` and ``lm_head``);
- ``route_diff_b<k>`` (read, not compared): for MoE block k > 0, the
  share of its first step's top-k assignments that differ between its
  inputs through the program's blocks < k and through the reference's
  own, the largest over chapters: what routing flips would have
  carried downstream had the reference followed its own blocks.
"""
from __future__ import annotations

import dataclasses
import os

import numpy as np

BLOCKS = 5
NAMES = (tuple(f"loss_gap_b{k}" for k in range(BLOCKS))
         + tuple(f"unit_diff_b{k}" for k in range(BLOCKS))
         + ("head_ce_gap", "unit_diff_head")
         + tuple(f"route_diff_b{k}" for k in range(1, BLOCKS)))
HEAD = ("final_norm", "lm_head")     # the head task's parameters
SOUND = ("nudged",)     # variants that are no fault: the look at round-off
NOUGHT = 1e-3
EVAL_ROWS = 16          # api.fit's held-out draw of sequences


class ZipfTokens:
    """A ``data.Source`` of token blocks: ``blocks(split, n, seed)`` is
    (n, seq + 1) int32 ids drawn i.i.d. from P(i) ~ (i + 1) ** -s over
    the vocabulary, a pure function of (the run's seed, split, n,
    seed)."""

    def __init__(self, vocab, seq_len, s, seed):
        p = np.arange(1, vocab + 1, dtype=np.float64) ** -s
        self.cdf = np.cumsum(p / p.sum())
        self.seq_len, self.seed = seq_len, int(seed)

    def blocks(self, split, n, seed=0):
        rng = np.random.default_rng(
            [self.seed, sum(map(ord, split)), int(seed)])
        u = rng.random((n, self.seq_len + 1))
        ids = np.searchsorted(self.cdf, u, side="right")
        return np.minimum(ids, len(self.cdf) - 1).astype(np.int32)


MODEL_KEYS = frozenset((
    "attention_bias", "first_k_dense_replace", "hidden_act", "hidden_size",
    "intermediate_size", "kv_lora_rank", "max_position_embeddings",
    "model_type", "moe_intermediate_size", "moe_layer_freq", "n_group",
    "n_routed_experts", "n_shared_experts", "norm_topk_prob",
    "num_attention_heads", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "q_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "rms_norm_eps", "rope_scaling", "rope_theta",
    "routed_scaling_factor", "scoring_func", "seq_aux",
    "tie_word_embeddings", "topk_group", "topk_method", "v_head_dim",
    "vocab_size", "router_experts", "expert_shard", "aux_loss_alpha",
    "theta", "lr", "head_lr", "deployment"))
TRAFFIC_KEYS = frozenset(("backend", "num_nodes", "chips", "chapters",
                          "steps_per_chapter", "batch", "seq", "zipf"))
# what the program implements of the published configuration
IMPLEMENTED = {"attention_bias": False, "hidden_act": "silu",
               "moe_layer_freq": 1, "n_group": 1, "q_lora_rank": None,
               "routed_scaling_factor": 1, "scoring_func": "softmax",
               "topk_group": 1, "topk_method": "greedy"}


def validate(model, traffic):
    """Refuse a configuration or traffic mix with keys this family does
    not know (another family's cell), or published settings the
    program does not implement."""
    for what, got, known in (("configuration", model, MODEL_KEYS),
                             ("traffic", traffic, TRAFFIC_KEYS)):
        unknown = sorted(set(got) - known)
        if unknown:
            raise ValueError(f"the ff_lm family does not know the {what} "
                             f"keys {unknown}")
    wrong = {k: model[k] for k, v in IMPLEMENTED.items() if model[k] != v}
    if wrong or model["rope_scaling"]["type"] != "yarn":
        raise ValueError(f"the program does not implement {wrong or 'rope'}")


def model_config(model, seed=0):
    """The ``repro`` ModelConfig the configuration file describes."""
    from repro.configs import (FFConfig, MLAConfig, ModelConfig, MoEConfig,
                               YaRNConfig)
    y = model["rope_scaling"]
    dense = model["first_k_dense_replace"]
    return ModelConfig(
        name="deepseek-v2-lite-chip", arch_type="moe",
        num_layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"],
        n_kv=model["num_key_value_heads"],
        d_ff=model["intermediate_size"], vocab=model["vocab_size"],
        groups=((("mla_dense",), dense),
                (("mla_moe",), model["num_hidden_layers"] - dense)),
        rope_theta=float(model["rope_theta"]),
        norm_eps=model["rms_norm_eps"],
        tie_embeddings=model["tie_word_embeddings"],
        mla=MLAConfig(kv_lora_rank=model["kv_lora_rank"],
                      qk_nope_head_dim=model["qk_nope_head_dim"],
                      qk_rope_head_dim=model["qk_rope_head_dim"],
                      v_head_dim=model["v_head_dim"]),
        rope_scaling=YaRNConfig(
            factor=float(y["factor"]),
            original_max_position_embeddings=y[
                "original_max_position_embeddings"],
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=y["mscale"], mscale_all_dim=y["mscale_all_dim"]),
        moe=MoEConfig(num_experts=model["router_experts"],
                      top_k=model["num_experts_per_tok"],
                      expert_ff=model["moe_intermediate_size"],
                      num_shared=model["n_shared_experts"],
                      shared_ff=model["moe_intermediate_size"],
                      capacity_factor=None,
                      router_aux_weight=model["aux_loss_alpha"],
                      held=model["n_routed_experts"],
                      shard=model["expert_shard"],
                      norm_topk=model["norm_topk_prob"]),
        ff=FFConfig(theta=model["theta"]),
        dtype="float32", remat=False, seed=seed)


@dataclasses.dataclass
class Built:
    args: tuple           # (cfg, source) of the timed ``api.fit`` call
    kwargs: dict          # its keywords: backend and the job's shape


def build(cell, seed, devices) -> Built:
    model, traffic = cell.model, cell.traffic
    validate(model, traffic)
    cfg = model_config(model, seed)
    source = ZipfTokens(cfg.vocab, traffic["seq"], traffic["zipf"], seed)
    kwargs = {"backend": traffic["backend"], "chapters": traffic["chapters"],
              "steps_per_chapter": traffic["steps_per_chapter"],
              "batch": traffic["batch"], "seq": traffic["seq"],
              "lr": model["lr"], "head_lr": model["head_lr"]}
    return Built((cfg, source), kwargs)


def _host(tree):
    import jax
    return jax.tree.map(np.asarray, tree)


def _tokens(built, chapter, task):
    """The token arrays (batch, seq + 1) of each step of one task (block
    k, or the head as task ``BLOCKS``), drawn from the cell's token
    source by (chapter, task) as the LM chapter path addresses its
    data."""
    source, kw = built.args[1], built.kwargs
    B, steps = kw["batch"], kw["steps_per_chapter"]
    blk = source.blocks("train", B * steps, seed=chapter * 1009 + task)
    return [blk[s * B:(s + 1) * B] for s in range(steps)]


def check_job(built: Built):
    """The window's job through ``api.fit`` and the window's compiled
    programs, and before it the same job cut to each shorter number of
    chapters, for the state each chapter leaves (the job is
    deterministic: its first chapters are the cut job's). Returns
    {"losses": each block's FF losses over its steps in every chapter,
    "head": the head's CE per step, "blocks": per chapter, each block's
    trees on the host, "heads": per chapter, the head task's
    parameters on the host}."""
    from repro import api
    from repro.core import pff_lm

    cfg, source = built.args
    C, steps = built.kwargs["chapters"], built.kwargs["steps_per_chapter"]
    out = {"blocks": [], "heads": []}
    for c in range(1, C + 1):
        res = api.fit(cfg, source, **{**built.kwargs, "chapters": c})
        blocks, rest = pff_lm.split_params(cfg, res.params)
        out["blocks"].append([_host(b) for b in blocks])
        out["heads"].append(_host({n: rest[n] for n in HEAD}))
        del blocks, rest
        if c < C:
            del res
    losses = [l for _, l in res.history]
    nb = len(out["blocks"][0])
    out["losses"] = [[l for c in range(C) for l in losses[
        (c * nb + k) * steps:(c * nb + k + 1) * steps]] for k in range(nb)]
    out["head"] = [l for _, l in res.head_history]
    del res
    return out


def variants(cell):
    """Keywords of the reference's ``chapters`` for each calibration
    variant: ``control`` one precision step below the configuration's
    (every product at the default, one bfloat16 pass); the planted
    faults ``half_batch`` (half of every batch left out),
    ``capacity_drop`` (capacity 1.25, assignments past it dropped),
    ``renorm_topk`` (the top-6 gates renormalised), ``no_yarn_mscale``
    (YaRN's factor left out of the softmax scale), ``all_experts`` (all
    64 experts' assignments computed, not the 8 held); and ``nudged``,
    no fault: every initial weight times 1 + 1e-7."""
    return {"control": {"precision": "default"},
            "half_batch": {"half_batch": True},
            "capacity_drop": {"capacity_factor": 1.25},
            "renorm_topk": {"norm_topk": True},
            "no_yarn_mscale": {"yarn_mscale": False},
            "all_experts": {"all_experts": True},
            "nudged": {"nudge": 1e-7}}


def _reference():
    from bench import run
    return run._module(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "ff_lm_reference.py"),
        "bench_ff_lm_reference")


def _initial(cfg):
    """(blocks, rest) of the job's initial state, on the device."""
    from repro.core import pff_lm
    return pff_lm.init_state(cfg, cfg.seed)


def _run(built, ref, feed=None, nudge=0.0, **kw):
    """The reference's job from its initial weights (times 1 +
    ``nudge``), fed the blocks ``feed`` gives per chapter (see the
    reference's ``chapters``). Returns what ``check_job`` returns, and
    ``route_diff``."""
    import jax

    cfg = built.args[0]
    C = built.kwargs["chapters"]
    blocks, rest = _initial(cfg)
    if nudge:
        blocks, rest = jax.tree.map(lambda a: a * (1 + nudge),
                                    (blocks, rest))
    nb = len(blocks)
    out = {"blocks": [[None] * nb for _ in range(C)], "heads": [None] * C}

    def keep(c, k, p):
        out["blocks"][c][k] = _host(p)

    def keep_head(c, head):
        out["heads"][c] = _host(head)

    out["losses"], out["head"], out["route_diff"] = ref.chapters(
        ref.hparams(cfg), blocks, {n: rest[n] for n in HEAD}, rest["embed"],
        [[_tokens(built, c, k) for k in range(nb)] for c in range(C)],
        [_tokens(built, c, nb) for c in range(C)], lr=built.kwargs["lr"],
        head_lr=built.kwargs["head_lr"], seed=cfg.seed, feed=feed,
        on_block=keep, on_head=keep_head, **kw)
    return out


def _units(tree):
    """{leaf: (rows, units)} of a tree's leaves, a unit being a column
    of the leaf's last axis; each held expert's matrices are a leaf of
    their own."""
    import jax
    out = {}
    for path, a in jax.tree_util.tree_leaves_with_path(tree):
        keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        name, a = jax.tree_util.keystr(path), np.asarray(a)
        if a.ndim == 3 and keys[-2:-1] == ["mlp"] \
                and keys[-1] in ("wi", "wg", "wo"):
            out.update({f"{name}[{e}]": a[e] for e in range(a.shape[0])})
        else:
            out[name] = a.reshape(-1, a.shape[-1])
    return out


def unit_diff(init, cand, ref):
    """The worst leaf's median, over the units the reference moves, of
    |cand's change - ref's| / |ref's change|."""
    worst = 0.0
    i, c, r = _units(init), _units(cand), _units(ref)
    for name in i:
        dr = np.linalg.norm(r[name] - i[name], axis=0)
        moved = dr > NOUGHT * np.median(dr)
        if moved.any():     # a held expert no token chose is not moved
            worst = max(worst, float(np.median(
                np.linalg.norm(c[name] - r[name], axis=0)[moved]
                / dr[moved])))
    return worst


def _gap(a, b):
    return float(max(abs(x - y) / abs(y) for x, y in zip(a, b)))


def readings(init, ref_run, cand_run):
    """The compared numbers of one run against the reference's (the
    output of ``check_job`` or ``_run``): per block over its steps and
    per chapter, and the head's."""
    init_blocks, init_head = init
    out = {}
    for k in range(len(init_blocks)):
        out[f"loss_gap_b{k}"] = _gap(cand_run["losses"][k],
                                     ref_run["losses"][k])
        out[f"unit_diff_b{k}"] = max(
            unit_diff(init_blocks[k], cb[k], rb[k])
            for cb, rb in zip(cand_run["blocks"], ref_run["blocks"]))
    out["head_ce_gap"] = _gap(cand_run["head"], ref_run["head"])
    out["unit_diff_head"] = max(
        unit_diff(init_head, ch, rh)
        for ch, rh in zip(cand_run["heads"], ref_run["heads"]))
    for k, v in ref_run.get("route_diff", {}).items():
        out[f"route_diff_b{k}"] = v
    return out


def calibration_readings(cell, seed, built: Built, prog, names=()):
    """The compared numbers of the program's check job (``prog``, unless
    None) and of each variant in ``names`` put in its place, each
    against the reference fed that run's blocks: {"program" or
    variant: {number: value}}."""
    cfg = built.args[0]
    ref = _reference()
    blocks, rest = _initial(cfg)
    init = ([_host(b) for b in blocks], _host({n: rest[n] for n in HEAD}))
    del blocks, rest
    out = {}
    if prog is not None:
        out["program"] = readings(init, _run(built, ref, feed=prog["blocks"]),
                                  prog)
    kws = variants(cell)
    for name in names:
        cand = _run(built, ref, **kws[name])
        out[name] = readings(init, _run(built, ref, feed=cand["blocks"]),
                             cand)
        del cand
    return out


# ---------------------------------------------------------------------------
# Counts
# ---------------------------------------------------------------------------

def _block_flops(model, kind, T, n_seq, S):
    """Forward matrix-product FLOPs of one block over T = n_seq * S
    tokens: projections, attention at its causal half, and the MLP (the
    routed experts at their mean load on the held share)."""
    d, H = model["hidden_size"], model["num_attention_heads"]
    r, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    rope, v = model["qk_rope_head_dim"], model["v_head_dim"]
    proj = d * H * (nope + rope) + d * (r + rope) + r * H * (nope + v) \
        + H * v * d
    attn = n_seq * H * S * S / 2 * ((nope + rope) + v)
    f = 2.0 * T * proj + 2.0 * attn
    if kind == "mla_dense":
        return f + 2.0 * T * 3 * d * model["intermediate_size"]
    ff = model["moe_intermediate_size"]
    E = model["router_experts"]
    routed = T * model["num_experts_per_tok"] * model["n_routed_experts"] / E
    return (f + 2.0 * T * d * E
            + 2.0 * T * 3 * d * ff * model["n_shared_experts"]
            + 2.0 * routed * 3 * d * ff)


def _kinds(model):
    dense = model["first_k_dense_replace"]
    return (["mla_dense"] * dense
            + ["mla_moe"] * (model["num_hidden_layers"] - dense))


def job_model_flops(model: dict, traffic: dict) -> float:
    """Model FLOPs of one job: every block step's forward through the
    frozen prefix and the trained block, and the trained block's
    backward (twice its forward); every head step's forward through
    all blocks, its logits and their backward (the head's and the
    final norm's gradients); the final evaluation's forward. Attention
    at its causal half; Adam, norms and elementwise work excluded."""
    B, S, C = traffic["batch"], traffic["seq"], traffic["chapters"]
    steps = traffic["steps_per_chapter"]
    kinds = _kinds(model)
    d, V = model["hidden_size"], model["vocab_size"]
    step = lambda n_seq: [_block_flops(model, k, n_seq * S, n_seq, S)
                          for k in kinds]
    train, pos = step(2 * B), step(B)
    blocks = sum(sum(train[:k]) + 3 * train[k] for k in range(len(kinds)))
    head = sum(pos) + 3 * 2.0 * B * S * d * V
    evaluation = sum(step(EVAL_ROWS)) + 2.0 * EVAL_ROWS * S * d * V
    return C * steps * (blocks + head) + evaluation


def samples_per_job(model: dict, traffic: dict) -> int:
    return (traffic["chapters"] * traffic["steps_per_chapter"]
            * traffic["batch"] * traffic["seq"])


def layer_steps_per_job(model: dict, traffic: dict) -> int:
    """Block steps of one job (every chapter, every block)."""
    return (traffic["chapters"] * traffic["steps_per_chapter"]
            * model["num_hidden_layers"])


def job_kernel_calls(model: dict, traffic: dict) -> list:
    """No Pallas kernel runs on this path."""
    return []
