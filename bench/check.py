"""The comparison that decides ``correct``.

Set-up drives the program's job, through the window's own entry and
compiled programs, over its first chapters from the seed; the plain
reference (``bench.reference``) follows the same chapters.

The job runs whole chapters (hundreds of steps a layer), and FF
training at the paper's learning rate amplifies round-off within one:
Adam turns a gradient that round-off alone decides into a step of the
full learning rate, so a few hidden units swing far whatever the
precision. Every number is therefore read per layer and per hidden unit
(the unit's column of weights), and each layer's numbers are taken
over the units the reference moves (a change over a thousandth of the
median unit's): the few units that swing do not move them.

Candidates, for layer ``k`` after its first chapter task (``first_task``,
layer k > 0 trains on the hand-off forward of layers 0..k-1) and after
the check job's last chapter (``final``; on AdaptiveNEG it trains on
negatives regenerated by scoring through every layer):

- ``<when>_unit_diff_l<k>``: |program's change - reference's| over
  |reference's change|;
- ``<when>_unit_q10_l<k>``: the same per-unit ratio, its 10th
  percentile over the units instead of the median: the units that the
  chapter's round-off moved least, which a lower precision in every
  product still moves;
- ``test_acc_gap``: the gap between the test accuracy the program's
  evaluation reports and the reference's.

A state returned unchanged reads exactly 1 on each per-unit number. A
cell compares the numbers its limits file (``bench/limits/<cell>.json``)
names (``decide``, for every family); the calibration prints them all.
"""
from __future__ import annotations

import jax.numpy as jnp

NOUGHT = 1e-3
LAYERS = 4
WHEN = ("first_task", "final")
KINDS = ("unit_diff", "unit_q10")
NAMES = tuple(f"{when}_{kind}_l{k}" for when in WHEN for kind in KINDS
              for k in range(LAYERS)) + ("test_acc_gap",)


def leaves_of(params):
    """{"layers/0/w": array, ...} of a params dict's FF layers (the
    untrained softmax head is not a leaf of this job)."""
    return {f"layers/{k}/{n}": t for k, d in enumerate(params["layers"])
            for n, t in d.items()}


def per_unit(init, prog, ref):
    """{kind: number} of one layer's weights: the per-unit ratio's
    median and 10th percentile over the units the reference moves."""
    dr = jnp.linalg.norm(ref - init, axis=0)
    moved = dr > NOUGHT * jnp.median(dr)
    ratio = jnp.linalg.norm(prog - ref, axis=0)[moved] / dr[moved]
    return {"unit_diff": float(jnp.median(ratio)),
            "unit_q10": float(jnp.quantile(ratio, 0.1))}


def readings(ref, prog0, prog, prog_acc, ref_acc):
    """The numbers for the program's leaves after each layer's first
    chapter task (``prog0``) and after the check job (``prog``) against
    the reference's ``Job``."""
    out = {}
    for when, p, r in (("first_task", prog0, ref.chapter0),
                       ("final", prog, ref.final)):
        for k in range(LAYERS):
            w = f"layers/{k}/w"
            if w in r:
                for kind, v in per_unit(ref.init[w], p[w], r[w]).items():
                    out[f"{when}_{kind}_l{k}"] = v
    out["test_acc_gap"] = abs(prog_acc - ref_acc)
    out["accuracy"] = {"program": prog_acc, "reference": ref_acc}
    return out


def decide(values, limits):
    """(correct, {name: {"value", "limit"}}) for every number the cell's
    limits file names (all its keys but ``set_from``). A number the
    family did not read has the value None and makes the run not
    correct."""
    checks = {n: {"value": values.get(n), "limit": lim}
              for n, lim in limits.items() if n != "set_from"}
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values()), checks
