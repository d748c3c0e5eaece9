"""The FF-MLP family: the paper's fully connected Forward-Forward
network (``repro.configs.ff_mlp.FFMLPConfig``, every field of the
configuration file) trained by ``repro.api.fit`` on the benchmark's
seeded MNIST stand-in (``bench.data``), and compared with the plain
reference ``bench.reference`` by ``bench.check``.

The traffic file gives the backend (``sequential`` or ``executor``),
the schedule, ``num_nodes``, ``chips`` and the data sizes. A sample is
one train row through every layer for one mini-epoch.
"""
from __future__ import annotations

import dataclasses

from bench import check, flops

NAMES = check.NAMES
SOUND = ("nudged",)       # variants that are no fault: the look at round-off


@dataclasses.dataclass
class Built:
    args: tuple           # (cfg, task) of the timed ``api.fit`` call
    kwargs: dict          # its keywords: backend, schedule, nodes, devices
    chapters: int         # chapters of the check job
    arrays: tuple         # (x, y, x_test, y_test): what the reference trains on


def build(cell, seed, devices) -> Built:
    """The data on the device from the seed, the configuration and the
    keywords of the cell's ``api.fit`` call."""
    import jax
    from repro import data as data_lib
    from repro.configs.ff_mlp import FFMLPConfig

    from bench import data

    model, traffic = cell.model, cell.traffic
    nodes = traffic["num_nodes"]
    arrays = data.mnist_like(data.seed_key(seed), n_train=traffic["n_train"],
                             n_test=traffic["n_test"])
    jax.block_until_ready(arrays)
    task = data_lib.ImageTask(*arrays, model["num_classes"],
                              arrays[0].shape[1])
    cfg = FFMLPConfig(**{**model, "layer_sizes": tuple(model["layer_sizes"])},
                      seed=seed)
    kwargs = {"backend": traffic["backend"]}
    if traffic["backend"] == "executor":
        kwargs.update(schedule=traffic["schedule"], num_nodes=nodes,
                      devices=devices[:nodes])
    return Built((cfg, task), kwargs, max(nodes, 2), arrays)


def check_model(model, chapters):
    """The configuration of the check job: the same chapters and
    mini-epochs per chapter, cut to ``chapters`` chapters."""
    per_chapter = max(model["epochs"] // model["splits"], 1)
    return {**model, "splits": chapters, "epochs": chapters * per_chapter}


def check_job(built: Built):
    """The check job: the window's job through ``api.fit``, cut to its
    first ``built.chapters`` chapters. The goodness strategy's chapter
    trainer is wrapped for this job only, to keep a copy of each
    layer's state after chapter 0. Returns (leaves after chapter 0,
    trained leaves, test accuracy)."""
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.core import strategies

    cfg, task = built.args
    good = strategies.goodness.get(cfg.goodness_fn)
    n_layers = len(cfg.layer_sizes) - 1
    first = []

    def keep_chapter0(state, *args, **kw):
        out = good.train_chapter(state, *args, **kw)
        if len(first) < n_layers:
            first.append(jax.tree.map(jnp.copy, good.export([out])))
        return out

    strategies.register_goodness(
        cfg.goodness_fn, dataclasses.replace(good,
                                             train_chapter=keep_chapter0),
        overwrite=True)
    try:
        cut = check_model({"epochs": cfg.epochs, "splits": cfg.splits},
                          built.chapters)
        res = api.fit(dataclasses.replace(cfg, **cut), task, **built.kwargs)
    finally:
        strategies.register_goodness(cfg.goodness_fn, good, overwrite=True)
    ch0 = {g: [f[g][0] for f in first] for g in first[0]}
    return check.leaves_of(ch0), check.leaves_of(res.params), res.test_acc


def variants(cell):
    """Keywords of ``bench.reference.run_job`` for each calibration
    variant (``bench.calibrate`` says what each is)."""
    out = {"control": {"precision": "bf16_3x"},
           "half_batch": {"fault": "half_batch"},
           "handoff_unnormed": {"fault": "handoff_unnormed"},
           "nudged": {"nudge": 1e-7},
           "handoff_bf16": {"handoff_precision": "bf16_3x"}}
    if cell.traffic["num_nodes"] > 1:
        out["no_exchange"] = {"exchange_nodes": cell.traffic["num_nodes"]}
    return out


def calibration_readings(cell, seed, built: Built, prog, names=()):
    """The reference over the check job's chapters, then the compared
    numbers of the program's check job (``prog``, unless None) and of
    each variant in ``names`` put in its place: {"program" or variant:
    {number: value, ..., "accuracy": {...}}}."""
    import jax.numpy as jnp

    from bench import reference

    model = check_model(cell.model, built.chapters)
    x, y, x_test, y_test = built.arrays

    def job(**kw):
        j = reference.run_job(model, seed, x, y, x_test, built.chapters, **kw)
        return j, float(jnp.mean(j.pred == y_test))

    ref, ref_acc = job()
    out = {}
    if prog is not None:
        out["program"] = check.readings(ref, *prog, ref_acc)
    kws = variants(cell)
    for name in names:
        v, acc = job(**kws[name])
        out[name] = check.readings(ref, v.chapter0, v.final, acc, ref_acc)
    return out


samples_per_job = flops.train_samples_per_job
job_kernel_calls = flops.job_kernel_calls
job_model_flops = flops.job_model_flops
layer_steps_per_job = flops.layer_steps_per_job
