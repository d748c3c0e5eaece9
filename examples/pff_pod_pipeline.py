"""PFF on real parallel devices, two ways, on 8 faked host devices:

  1. the paper's own schedule for real: ``repro.core.pff_exec`` runs
     All-Layers PFF with one device per paper "node", prints measured
     makespan next to the simulator's prediction, and verifies the
     distributed weight stream is BIT-IDENTICAL to sequential training;
  2. beyond-paper: the PFF pipeline mapped onto a (stage, data, model)
     device mesh — each stage owns a contiguous block range and
     activations flow forward via collective_permute; FF means NOTHING
     flows backward.

  PYTHONPATH=src python examples/pff_pod_pipeline.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import api, data, optim
from repro.configs import get_config
from repro.configs.ff_mlp import FFMLPConfig
from repro.core import pff_exec, pff_pod
from repro.models import transformer

# --- 1. the paper's All-Layers schedule, executed for real ----------------
NODES = 4
mlp_cfg = FFMLPConfig(layer_sizes=(784, 256, 256), epochs=8, splits=8,
                      neg_mode="random", classifier="goodness",
                      batch_size=64, seed=0)
mlp_task = data.mnist_like(n_train=1024, n_test=200)
print(f"All-Layers PFF on {NODES} of {len(jax.devices())} host devices:")
seq = api.fit(mlp_cfg, mlp_task)                   # canonical + timings
res = api.fit(mlp_cfg, mlp_task, backend="executor",
              schedule="all_layers", num_nodes=NODES)
sim = api.simulate(seq, "all_layers", NODES)
same = pff_exec.params_bit_equal(seq.params, res.params)
print(f"  measured makespan {res.makespan:.2f}s | simulator predicts "
      f"{sim.makespan:.2f}s (speedup {sim.speedup:.2f}x)")
print(f"  distributed weight stream bit-identical to sequential: {same}")

# --- 2. beyond-paper: pipeline stages over a TPU-style mesh ---------------
# (api.fit(cfg, backend="pod", num_nodes=S) runs this on a (S, 1, 1)
# mesh; build the mesh by hand, as here, for data/model parallelism too)
cfg = get_config("tinyllama-1.1b").reduced()
cfg = dataclasses.replace(cfg, num_layers=4, groups=((("attn",), 4),))
mesh = jax.make_mesh((2, 2, 2), ("stage", "data", "model"))
print(f"mesh: {dict(mesh.shape)} — 2 pipeline stages x 2 data x 2 model")

key = jax.random.PRNGKey(0)
params = transformer.init(key, cfg)
opt = optim.adam_init(params)
B, S = 8, 64
inflight = pff_pod.init_inflight(cfg, B, S, stages=2)
step_fn = pff_pod.make_pff_pod_step(cfg, mesh, lr=1e-3)

t0 = time.time()
with mesh:
    for i, tokens in enumerate(data.lm_batches(cfg.vocab, B, S, 40)):
        params, opt, inflight, m = step_fn(
            params, opt, {"tokens": jnp.asarray(tokens)}, inflight, i + 1)
        if (i + 1) % 10 == 0:
            print(f"step {i+1:3d}: pipeline FF loss "
                  f"{float(m['loss_ff']):.4f} ({time.time()-t0:.0f}s)")
print("pipeline ran with zero backward traffic between stages.")
