"""Joint-FF vs the paper's chapter schedule on a reduced transformer.

The joint step (core/train.py) updates every block each batch; the
chapter schedule (core/pff_lm.py) trains one block at a time on the
frozen outputs of the blocks below — the paper's task granularity,
which is what pipelines across nodes. Both optimize the same per-block
local objectives; this benchmark compares eval CE at an equal update
budget and reports the PFF schedule times for the chapter variant.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp

from repro import data as data_lib, optim
from repro.configs import get_config
from repro.core import pff, pff_lm, train as train_lib
from repro.models import transformer

NODES = 4


def run(arch="qwen2-0.5b", blocks=4, chapters=4, steps_per_chapter=8,
        batch=8, seq=64, lr=3e-3, out_dir="experiments"):
    cfg = get_config(arch).reduced()
    cfg = dataclasses.replace(cfg, num_layers=blocks,
                              groups=((("attn",), blocks),))
    key = jax.random.PRNGKey(0)
    eval_tokens = jnp.asarray(next(iter(
        data_lib.lm_batches(cfg.vocab, 16, seq, 1, seed=321))))
    total_updates = chapters * blocks * steps_per_chapter

    # ---- joint FF (every block each step) -------------------------------
    params = transformer.init(key, cfg)
    opt = optim.adam_init(params)
    step_fn = jax.jit(train_lib.make_ff_train_step(cfg, lr=lr))
    joint_steps = total_updates // blocks     # same per-block update count
    for i, tokens in enumerate(data_lib.lm_batches(
            cfg.vocab, batch, seq, joint_steps, seed=0)):
        params, opt, _ = step_fn(params, opt,
                                 {"tokens": jnp.asarray(tokens)}, i + 1)
    ce_joint = float(train_lib.eval_ce(params, cfg, eval_tokens))

    # ---- chapter schedule ------------------------------------------------
    def data_iter(chapter, block):
        return ({"tokens": jnp.asarray(t)} for t in data_lib.lm_batches(
            cfg.vocab, batch, seq, steps_per_chapter,
            seed=chapter * 1009 + block))

    params_c, records, _, _ = pff_lm.train_chapters(
        cfg, data_iter, chapters=chapters,
        steps_per_chapter=steps_per_chapter, lr=lr)
    ce_chap = float(train_lib.eval_ce(params_c, cfg, eval_tokens))

    sims = {}
    for sched in ("sequential", "single_layer", "all_layers"):
        s = pff.simulate_schedule(records, sched,
                                  1 if sched == "sequential" else NODES)
        sims[sched] = {"time_s": round(s.makespan, 2),
                       "speedup": round(s.speedup, 2)}

    res = {"arch": arch, "blocks": blocks,
           "per_block_updates": chapters * steps_per_chapter,
           "ce_joint": round(ce_joint, 3),
           "ce_chapters": round(ce_chap, 3),
           "schedules": sims}
    print(f"  joint-FF eval CE {ce_joint:.3f} | chapter-FF eval CE "
          f"{ce_chap:.3f} (equal per-block updates)")
    print(f"  chapter-FF PFF times: " + "  ".join(
        f"{k}={v['time_s']}s (x{v['speedup']})" for k, v in sims.items()))
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "lm_schedules.json"), "w") as f:
        json.dump(res, f, indent=1)
    return res


if __name__ == "__main__":
    run()
