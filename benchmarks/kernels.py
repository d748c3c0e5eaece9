"""Kernel validation sweep: every Pallas kernel vs its oracle across a
shape grid, max-abs-error reported. (Wall-time is meaningless in
interpret mode on CPU — correctness is the deliverable here; the TPU
perf story lives in the roofline analysis.)

``run_tune`` is the autotuner sweep behind ``make tune-smoke`` /
``--only=tune``: it runs the measure-many pick-fastest pass, reports
each winner as %-of-roofline (the load-insensitive framing), writes
``BENCH_kernel_tune.json``, and gates the table plumbing end-to-end —
table written, re-lookup a pure memo hit, ``impl="auto"`` resolving
through the winners, a poisoned entry degrading to defaults with a
warning instead of crashing.
"""
from __future__ import annotations

import json
import os
import warnings

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.ff_dense import ff_dense
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba2_ssd import mamba2_ssd


def run():
    """Prints the sweep and returns the worst max-abs error across every
    kernel/shape, so run.py can fail loudly on a regression."""
    worst = 0.0
    key = jax.random.PRNGKey(0)
    print("ff_dense:")
    for M, K, N in [(64, 784, 2000), (128, 3072, 400), (256, 256, 256)]:
        x = jax.random.normal(key, (M, K))
        w = jax.random.normal(key, (K, N)) * K ** -0.5
        b = jnp.zeros((N,))
        y, g = ff_dense(x, w, b)
        yr, gr = ref.ff_dense_ref(x, w, b)
        err = max(float(jnp.abs(y - yr).max()),
                  float(jnp.abs(g - gr).max() / (float(gr.max()) + 1e-9)))
        worst = max(worst, err)
        print(f"  ({M},{K},{N}): max_err={err:.2e}")

    print("flash_attention:")
    for B, S, H, KV, hd, causal, win in [(2, 256, 8, 2, 64, True, None),
                                         (1, 256, 4, 1, 128, True, 128),
                                         (2, 128, 4, 4, 64, False, None)]:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, S, H, hd))
        k = jax.random.normal(ks[1], (B, S, KV, hd))
        v = jax.random.normal(ks[2], (B, S, KV, hd))
        o = flash_attention(q, k, v, causal=causal, window=win,
                            bq=64, bk=64)
        orf = ref.flash_attention_ref(q, k, v, causal=causal, window=win)
        err = float(jnp.abs(o - orf).max())
        worst = max(worst, err)
        print(f"  B{B} S{S} H{H}/{KV} hd{hd} causal={causal} win={win}: "
              f"max_err={err:.2e}")

    print("mamba2_ssd:")
    for B, S, H, hd, N, chunk in [(2, 256, 8, 32, 64, 64),
                                  (1, 512, 4, 64, 128, 128)]:
        ks = jax.random.split(key, 4)
        xbar = jax.random.normal(ks[0], (B, S, H, hd))
        dA = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        b = jax.random.normal(ks[2], (B, S, N))
        c = jax.random.normal(ks[3], (B, S, N))
        y, hT = mamba2_ssd(xbar, dA, b, c, chunk=chunk)
        yr, hTr = ref.mamba2_ssd_ref(xbar, dA, b, c)
        # scale-normalized (same convention as the ff_dense goodness
        # entry): the long-scan outputs are O(10), where float32
        # reassociation alone moves the raw max-abs past 1e-4
        err = max(float(jnp.abs(y - yr).max() /
                        (float(jnp.abs(yr).max()) + 1e-9)),
                  float(jnp.abs(hT - hTr).max() /
                        (float(jnp.abs(hTr).max()) + 1e-9)))
        worst = max(worst, err)
        print(f"  B{B} S{S} H{H} hd{hd} N{N} L{chunk}: max_err={err:.2e}")
    return worst


# ---------------------------------------------------------------------------
# Autotuner sweep (``--only=tune`` / ``make tune-smoke``)
# ---------------------------------------------------------------------------

# two shape buckets with different M so the candidate grids differ and
# the selected blocks can too; quick keeps interpret-mode wall time low
_TUNE_SHAPES_QUICK = [(32, 128, 256), (128, 512, 512)]
_TUNE_SHAPES_FULL = [(32, 128, 256), (64, 784, 2000), (128, 512, 512),
                     (100, 333, 257)]

_OUT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         os.pardir, "BENCH_kernel_tune.json")


def _check_memo(autotune, rows, platform, failures):
    """After a save, lookups must read the file once then run from the
    in-memory memo — the 'pure memo hit' gate."""
    autotune.invalidate_cache()
    tuned = [r for r in rows if r["winner"] is not None]
    loads0 = autotune.STATS["loads"]
    for r in tuned:
        e = autotune.lookup("ff_dense", r["M"], r["K"], r["N"],
                            jnp.float32, platform, norm=r["norm"])
        if e is None:
            failures.append(f"tune: lookup miss for tuned bucket "
                            f"{r['key']}")
    loads_after_first = autotune.STATS["loads"]
    hits0 = autotune.STATS["memo_hits"]
    for r in tuned:                      # the re-run: zero file reads
        autotune.lookup("ff_dense", r["M"], r["K"], r["N"],
                        jnp.float32, platform, norm=r["norm"])
    if loads_after_first - loads0 != 1:
        failures.append(f"tune: first lookup pass read the table "
                        f"{loads_after_first - loads0} times (want 1)")
    if autotune.STATS["loads"] != loads_after_first:
        failures.append("tune: re-lookup re-read the table instead of "
                        "hitting the memo")
    if autotune.STATS["memo_hits"] - hits0 < len(tuned):
        failures.append("tune: re-lookup pass was not a pure memo hit")


def _check_poisoned(autotune, ops_mod, rows, platform, failures):
    """Corrupt one persisted winner, point the process at the poisoned
    copy, and require warn-and-default rather than a crash."""
    tuned = [r for r in rows if r["winner"] is not None]
    if not tuned:
        return
    r = tuned[0]
    src = autotune.TuneTable.open()
    poisoned_path = src.path + ".poisoned"
    bad = autotune.TuneTable(poisoned_path)
    bad.entries = {k: dict(v) for k, v in src.entries.items()}
    bad.entries[r["key"]]["bm"] = "not-an-int"
    bad.save()
    prev = os.environ.get("REPRO_TUNE_TABLE")
    os.environ["REPRO_TUNE_TABLE"] = poisoned_path
    autotune.invalidate_cache()
    try:
        with warnings.catch_warnings(record=True) as wlog:
            warnings.simplefilter("always")
            entry = autotune.lookup("ff_dense", r["M"], r["K"], r["N"],
                                    jnp.float32, platform,
                                    norm=r["norm"])
            key = jax.random.PRNGKey(3)
            x = jax.random.normal(key, (r["M"], r["K"]))
            w = jax.random.normal(key, (r["K"], r["N"])) * r["K"] ** -0.5
            b = jnp.zeros((r["N"],))
            y, g = ops_mod.ff_dense(x, w, b, norm=r["norm"])
        if entry is not None:
            failures.append("tune: poisoned entry was not rejected by "
                            "lookup validation")
        if not any("poisoned" in str(m.message) for m in wlog):
            failures.append("tune: poisoned entry produced no warning")
        if not (bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(g).all())):
            failures.append("tune: fallback path after poisoned entry "
                            "produced non-finite output")
        else:
            print(f"  poisoned-entry fallback: lookup rejected, "
                  f"{len(wlog)} warning(s), defaults ran clean")
    finally:
        if prev is None:
            os.environ.pop("REPRO_TUNE_TABLE", None)
        else:
            os.environ["REPRO_TUNE_TABLE"] = prev
        autotune.invalidate_cache()
        os.remove(poisoned_path)


def run_tune(quick=True, out_path=None):
    """The tuning sweep + its smoke gates; returns {"failures": [...]}
    for run.py and writes BENCH_kernel_tune.json."""
    from benchmarks import roofline
    from repro.kernels import autotune, ops as ops_mod

    failures = []
    platform = jax.default_backend()
    shapes = _TUNE_SHAPES_QUICK if quick else _TUNE_SHAPES_FULL
    print(f"tuning table: {autotune.table_path()}")
    rows = autotune.tune_ff_dense(
        shapes, norms=(False, True),
        max_candidates=3 if quick else None, seed=0)

    # gate: the table landed on disk
    path = autotune.table_path()
    if not os.path.exists(path):
        failures.append(f"tune: table not written to {path}")

    # gate: every tuned bucket's winner honors the 1e-4 oracle budget
    blocks_seen = set()
    for r in rows:
        w = r["winner"]
        if w is None:
            failures.append(f"tune: no candidate passed the gate for "
                            f"{r['key']}")
            continue
        if w["err"] > autotune.ERR_GATE or w["grad_err"] > autotune.ERR_GATE:
            failures.append(
                f"tune: persisted winner for {r['key']} breaches the "
                f"gate (err={w['err']:.2e} grad_err={w['grad_err']:.2e})")
        if "bm" in w:
            blocks_seen.add((w["bm"], w["bn"]))
        roof = roofline.ff_dense_roofline(
            r["M"], r["K"], r["N"], device_kind=jax.devices()[0].device_kind)
        r["roofline"] = {
            "roof_s": roof["roof_s"], "bound": roof["bound"],
            "winner_pct_of_roof": roofline.pct_of_roofline(
                w["time_s"], roof["roof_s"]),
            "pallas_pct_of_roof": roofline.pct_of_roofline(
                w.get("pallas_time_s", 0.0), roof["roof_s"]),
        }
        blk = f" bm={w['bm']} bn={w['bn']}" if "bm" in w else ""
        print(f"  {r['key']}: winner={w['impl']}{blk} "
              f"{r['roofline']['winner_pct_of_roof']:.3g}% of "
              f"{roof['bound']}-bound roof "
              f"({roof['roof_s'] * 1e6:.1f}us analytic)")

    # gate: tuned blocks actually vary across shape buckets
    if len([r for r in rows if r["winner"]]) >= 2 and len(blocks_seen) < 2:
        failures.append(f"tune: every shape bucket selected the same "
                        f"blocks {blocks_seen} — sweep is degenerate")

    _check_memo(autotune, rows, platform, failures)

    # gate: impl="auto" end-to-end through registry + table
    M, K, N = shapes[0]
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (M, K))
    w = jax.random.normal(key, (K, N)) * K ** -0.5
    b = jnp.zeros((N,))
    ya, ga = ops_mod.ff_dense(x, w, b, impl="auto")
    yr, gr = ref.ff_dense_ref(x, w, b)
    auto_err = max(
        float(jnp.abs(ya - yr).max() / (jnp.abs(yr).max() + 1e-9)),
        float(jnp.abs(ga - gr).max() / (jnp.abs(gr).max() + 1e-9)))
    if auto_err > autotune.ERR_GATE:
        failures.append(f"tune: impl='auto' through the tuned table "
                        f"err {auto_err:.2e} > {autotune.ERR_GATE:.0e}")
    else:
        print(f"  impl='auto' vs oracle after tuning: {auto_err:.1e}")

    _check_poisoned(autotune, ops_mod, rows, platform, failures)

    out_path = out_path or _OUT_PATH
    with open(out_path, "w") as f:
        json.dump({"platform": platform,
                   "interpret": platform != "tpu",
                   "table_path": path,
                   "err_gate": autotune.ERR_GATE,
                   "distinct_blocks": sorted(blocks_seen),
                   "stats": dict(autotune.STATS),
                   "rows": rows,
                   "failures": failures}, f, indent=2)
        f.write("\n")
    print(f"  wrote {os.path.normpath(out_path)} ({len(rows)} buckets, "
          f"{len(blocks_seen)} distinct block shapes)")
    return {"failures": failures, "rows": rows}
