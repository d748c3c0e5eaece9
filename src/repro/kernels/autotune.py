"""Measure-many, pick-fastest kernel autotuner with a persisted tuning
table — nebullvm's compiler-framework idea applied to the FF hot loop.

For each ``(M, K, N, dtype, platform, norm)`` shape bucket the tuner
benchmarks every registered ``ff_dense`` impl — the tunable ones
(Pallas) across a grid of candidate block shapes ``(bm, bn)``, the rest
(the jnp oracle) as a single candidate — through ONE forward + fused
backward step (a jitted ``value_and_grad``, so the custom_vjp backward
kernel is part of what is timed), and:

  * rejects any candidate whose scale-normalized VALUE or GRAD error vs
    the ``ref`` oracle exceeds ``ERR_GATE`` (the same 1e-4 budget
    ``benchmarks/run.py`` enforces) — a fast-but-wrong impl never wins;
  * filters candidate block shapes through the VMEM row-residency
    invariant documented in ``ff_dense.py`` (``vmem_block_bytes`` <=
    ``VMEM_BUDGET_BYTES``): norm=True keeps the whole (bm, N) y row
    block resident across the inner j sweep (j-constant index map), so
    a shape that cannot fit is never even measured;
  * persists the winner in a JSON tuning table keyed like a compile
    cache (stable sorted keys, atomic replace), with in-memory
    memoization and an env-var path override ``REPRO_TUNE_TABLE``.

``ops.ff_dense(impl="auto")`` consults the table at TRACE time (shapes
are static under jit, so the lookup costs nothing at runtime): a hit
resolves to the measured-fastest impl with its tuned block shapes, a
miss falls back to the registry's platform default. Entries record both
the overall winner impl AND the best Pallas block shapes, so a caller
forcing ``impl="pallas"`` on a platform where the oracle won still gets
tuned blocks. A poisoned table (corrupt JSON, non-int blocks, shapes
breaking the residency budget, unregistered impl) degrades gracefully:
warn once and fall back to defaults, never crash.

Bit-exactness note (also recorded in the table meta): winners are gated
on the 1e-4 oracle error, NOT bit-exactness — a tuned block shape may
legitimately change float summation order on the Pallas path. The
pff-exec sequential-vs-executor weight-stream matrix therefore pins
``kernel_impl="ref"`` (see ``core.pff_exec._case_setup``) and stays
bit-exact with tuning on or off; this table only steers ``"auto"``.

The candidate axes are ``(bm, bn)`` today; ``bk`` joins the sweep once
the forward kernel tiles its inner K sweep (it currently streams K
whole — ``bk`` only parameterizes the fused backward, where it rides
along at its default).

Timing is injectable (``timer=``) so tests can pin a seeded fake timer
and assert a deterministic winner; the default wall-clock timer takes
the best of ``repeats`` blocked calls after a compile warmup.
"""
from __future__ import annotations

import json
import os
import time
import warnings

import jax
import jax.numpy as jnp

from repro.kernels import registry as registry_lib
from repro.kernels.ff_dense import VMEM_BUDGET_BYTES, vmem_block_bytes
from repro.obs import trace as obs_trace

# Same correctness budget as benchmarks.run.ERR_BUDGET (not imported:
# src/ must not depend on the benchmarks package).
ERR_GATE = 1e-4

DEFAULT_TABLE_PATH = os.path.join(
    os.path.expanduser("~"), ".cache", "repro", "tune_table.json")

TABLE_META = {
    "format": "repro-kernel-tune-v1",
    "err_gate": ERR_GATE,
    "note": (
        "Winners are gated on scale-normalized value AND fused-grad "
        "error vs the ref oracle (<= err_gate), not on bit-exactness: "
        "a tuned Pallas block shape may legitimately change float "
        "summation order. The pff-exec sequential-vs-executor "
        "bit-exactness matrix pins kernel_impl='ref' and is therefore "
        "immune to this table; only impl='auto' (and the block shapes "
        "of a forced impl='pallas') read it."),
}

# candidate axes; the generator clamps/filters per shape
_BM_CANDIDATES = (8, 16, 32, 64, 128, 256)
_BN_CANDIDATES = (128, 256, 512)


def table_path():
    """Resolved table location: ``REPRO_TUNE_TABLE`` env override, else
    the per-user cache default."""
    return os.environ.get("REPRO_TUNE_TABLE") or DEFAULT_TABLE_PATH


def key_for(op, M, K, N, dtype, platform, norm):
    """Compile-cache-style table key for one shape bucket."""
    dtype = jnp.dtype(dtype).name
    return (f"{op}|M={M}|K={K}|N={N}|dtype={dtype}"
            f"|platform={platform}|norm={int(bool(norm))}")


def candidate_blocks(M, K, N, *, norm=False, budget=VMEM_BUDGET_BYTES):
    """The legal (bm, bn) grid for one shape: clamped to the operand
    (the kernel would clamp anyway — clamping here dedupes), lane-
    aligned (bn a 128-multiple unless it IS N), and within the VMEM
    row-residency budget (see ``ff_dense.vmem_block_bytes``)."""
    bms = sorted({min(bm, M) for bm in _BM_CANDIDATES})
    bns = sorted({min(bn, N) for bn in _BN_CANDIDATES})
    out = []
    for bm in bms:
        for bn in bns:
            if bn % 128 and bn != N:
                continue                      # misaligned lane dim
            if vmem_block_bytes(K, N, bm, bn, norm=norm) > budget:
                continue                      # breaks row residency
            out.append((bm, bn))
    return out


# ---------------------------------------------------------------------------
# Tuning table: JSON persistence + in-memory memoization
# ---------------------------------------------------------------------------

class TuneTable:
    """The persisted winners, keyed by ``key_for``.

    Entry schema: {"impl": str, "time_s": float, "err": float,
    "grad_err": float} plus — whenever any Pallas candidate passed the
    gates — {"bm": int, "bn": int, "pallas_time_s": float} for the
    fastest passing Pallas block shape (``bk`` reserved for the future
    inner-sweep tiling).
    """

    def __init__(self, path=None):
        self.path = path or table_path()
        self.meta = dict(TABLE_META)
        self.entries = {}

    @classmethod
    def open(cls, path=None):
        return cls(path).load()

    def load(self):
        if not os.path.exists(self.path):
            return self
        try:
            with open(self.path) as f:
                raw = json.load(f)
            entries = raw["entries"]
            if not isinstance(entries, dict):
                raise ValueError("'entries' is not an object")
        except (OSError, json.JSONDecodeError, KeyError, ValueError,
                TypeError) as e:
            warnings.warn(
                f"poisoned kernel tuning table at {self.path} ({e}); "
                f"ignoring it and falling back to default block shapes")
            return self
        self.entries = entries
        self.meta = raw.get("meta", self.meta)
        return self

    def save(self):
        """Atomic write with byte-stable key ordering (sort_keys), so a
        re-tune that changes nothing leaves the file bit-identical."""
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"meta": self.meta, "entries": self.entries}, f,
                      indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, self.path)
        invalidate_cache(self.path)
        return self.path

    def get(self, key):
        return self.entries.get(key)

    def put(self, key, entry):
        self.entries[key] = entry

    def __len__(self):
        return len(self.entries)


# path -> TuneTable; ops.ff_dense consults this at trace time, so one
# process reads the file at most once per path (STATS proves the memo
# in `make tune-smoke` and the tests).
_CACHE = {}
STATS = {"loads": 0, "memo_hits": 0}


def cached_table():
    path = table_path()
    if path in _CACHE:
        STATS["memo_hits"] += 1
        return _CACHE[path]
    STATS["loads"] += 1
    t = TuneTable.open(path)
    _CACHE[path] = t
    return t


def invalidate_cache(path=None):
    """Drop the in-memory table memo (one path, or all)."""
    if path is None:
        _CACHE.clear()
    else:
        _CACHE.pop(path, None)


def _validated(entry, key, op, K, N, norm):
    """None (with a warning) unless the entry is shaped like a winner
    and its blocks honor the residency budget — the poisoned-table
    fallback path."""
    try:
        impl = entry["impl"]
        if not isinstance(impl, str):
            raise ValueError("impl is not a string")
        if impl not in registry_lib.registry(op):
            raise ValueError(f"impl {impl!r} is not registered")
        if "bm" in entry or "bn" in entry:
            bm, bn = entry["bm"], entry["bn"]
            if not (isinstance(bm, int) and bm > 0
                    and isinstance(bn, int) and bn > 0):
                raise ValueError(f"bad block shape ({bm!r}, {bn!r})")
            if vmem_block_bytes(K, N, bm, bn, norm=norm) \
                    > VMEM_BUDGET_BYTES:
                raise ValueError(
                    f"blocks ({bm}, {bn}) break the VMEM row-residency "
                    f"budget for K={K} N={N} norm={norm}")
        elif impl == "pallas":
            raise ValueError("pallas winner without block shapes")
    except (KeyError, ValueError, TypeError) as e:
        warnings.warn(f"poisoned tuning-table entry {key!r} ({e}); "
                      f"falling back to default block shapes")
        return None
    return entry


def lookup(op, M, K, N, dtype, platform, *, norm=False):
    """Trace-time table consultation for ``ops``: the validated winning
    entry for this shape bucket, or None (use registry defaults)."""
    t = cached_table()
    key = key_for(op, M, K, N, dtype, platform, norm)
    entry = t.get(key)
    if entry is None:
        return None
    return _validated(entry, key, op, K, N, norm)


def entry_blocks(entry):
    """An entry's tuned ``(bm, bn, bk)`` tuple, or None if it has no
    Pallas block shapes (e.g. only the oracle passed the gates)."""
    if "bm" not in entry:
        return None
    return (entry["bm"], entry["bn"], entry.get("bk"))


# ---------------------------------------------------------------------------
# The sweep
# ---------------------------------------------------------------------------

def _tune_data(key, M, K, N, dtype):
    kx, kw, ky, kg = jax.random.split(key, 4)
    x = jax.random.normal(kx, (M, K), dtype)
    w = (jax.random.normal(kw, (K, N)) * K ** -0.5).astype(dtype)
    b = jnp.full((N,), 0.1, dtype)
    # cotangents exercising BOTH outputs (y through cy, raw goodness
    # through cg) so the fused backward's dg path is gated too
    cy = jax.random.normal(ky, (M, N), jnp.float32) * 0.01
    cg = jax.random.normal(kg, (M,), jnp.float32) * 0.01
    return x, w, b, cy, cg


def _make_loss(fn, norm, interpret, blocks):
    def loss(w, x, b, cy, cg):
        y, g = fn(x, w, b, norm=norm, interpret=interpret, blocks=blocks)
        return jnp.vdot(y.astype(jnp.float32), cy) + jnp.vdot(g, cg)
    return loss


def _scale_err(a, b):
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))


def _candidate_errors(impl_name, blocks, data, oracle, *, norm,
                      interpret):
    """(value_err, grad_err) of one candidate vs the ref oracle —
    scale-normalized, same convention as ``benchmarks/kernels.py``."""
    fn = registry_lib.ff_dense.get(impl_name).fn
    x, w, b, cy, cg = data
    y, g = fn(x, w, b, norm=norm, interpret=interpret, blocks=blocks)
    dw = jax.grad(_make_loss(fn, norm, interpret, blocks))(w, x, b, cy,
                                                           cg)
    y_r, g_r, dw_r = oracle
    err = max(_scale_err(y, y_r), _scale_err(g, g_r))
    grad_err = _scale_err(dw, dw_r)
    return err, grad_err


def _wall_timer(thunk, label, repeats=2):
    """Best-of-``repeats`` wall clock after one compile/warmup call."""
    del label
    jax.block_until_ready(thunk())
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(thunk())
        best = min(best, time.perf_counter() - t0)
    return best


def tune_ff_dense(shapes, *, norms=(False, True), dtype=jnp.float32,
                  table=None, timer=None, err_gate=ERR_GATE, seed=0,
                  max_candidates=None, save=True, verbose=True,
                  tracer=obs_trace.NOOP):
    """Sweep ``shapes`` (iterable of (M, K, N)), persist winners.

    Returns a list of per-bucket row dicts (winner, best blocks, ref
    baseline, rejected candidates) — what ``benchmarks/kernels.py``
    turns into BENCH_kernel_tune.json. ``timer(thunk, label) -> s`` is
    injectable; ``max_candidates`` bounds the Pallas grid per bucket
    (smoke mode). ``save=True`` writes the table and drops the memo so
    subsequent ``lookup``s see the new winners. ``tracer=`` (an
    ``obs.trace`` tracer) records one ``tune:candidate`` span per
    measured candidate.
    """
    platform = jax.default_backend()
    interpret = platform != "tpu"
    if table is None:
        table = TuneTable.open()
    if timer is None:
        timer = _wall_timer
    rows = []
    root = jax.random.PRNGKey(seed)
    for si, (M, K, N) in enumerate(shapes):
        for norm in norms:
            key = key_for("ff_dense", M, K, N, dtype, platform, norm)
            data = _tune_data(
                jax.random.fold_in(root, 2 * si + int(norm)),
                M, K, N, dtype)
            x, w, b, cy, cg = data
            ref_fn = registry_lib.ff_dense.get("ref").fn
            y_r, g_r = ref_fn(x, w, b, norm=norm, interpret=interpret,
                              blocks=None)
            dw_r = jax.grad(_make_loss(ref_fn, norm, interpret, None))(
                w, x, b, cy, cg)
            oracle = (y_r, g_r, dw_r)

            cands = []
            for name in registry_lib.ff_dense.names():
                if name in registry_lib.ff_dense.tunable_names():
                    grid = candidate_blocks(M, K, N, norm=norm)
                    if max_candidates and len(grid) > max_candidates:
                        # smoke mode: keep an evenly-spaced spread that
                        # always includes the largest blocks (fewest
                        # grid steps — the usual winners), so the
                        # truncated sweep still explores the range
                        step = len(grid) / max_candidates
                        grid = [grid[len(grid) - 1 - int(i * step)]
                                for i in range(max_candidates)][::-1]
                    cands += [(name, (bm, bn, None)) for bm, bn in grid]
                else:
                    cands.append((name, None))

            measured, rejected = [], []
            for name, blocks in cands:
                label = f"{key}|{name}" + (
                    f"|bm={blocks[0]}|bn={blocks[1]}" if blocks else "")
                try:
                    err, grad_err = _candidate_errors(
                        name, blocks, data, oracle, norm=norm,
                        interpret=interpret)
                except Exception as e:  # an impl that cannot even run
                    rejected.append({"impl": name, "blocks": blocks,
                                     "reason": f"raised {e!r}"})
                    continue
                if err > err_gate or grad_err > err_gate:
                    rejected.append({
                        "impl": name, "blocks": blocks,
                        "reason": (f"oracle error breach: err={err:.2e} "
                                   f"grad_err={grad_err:.2e} > "
                                   f"{err_gate:.0e}")})
                    continue
                step = jax.jit(jax.value_and_grad(
                    _make_loss(registry_lib.ff_dense.get(name).fn,
                               norm, interpret, blocks)))
                t0_m = tracer.now()
                t = timer(lambda: step(w, x, b, cy, cg), label)
                if tracer.enabled:
                    tracer.add_span(
                        "tune:candidate", t0_m, key=key, impl=name,
                        bm=blocks[0] if blocks else None,
                        bn=blocks[1] if blocks else None,
                        time_s=float(t))
                measured.append({"impl": name, "blocks": blocks,
                                 "time_s": float(t), "err": err,
                                 "grad_err": grad_err})
            if not measured:
                warnings.warn(f"no candidate passed the {err_gate:.0e} "
                              f"oracle gate for {key}; bucket left "
                              f"untuned")
                rows.append({"key": key, "M": M, "K": K, "N": N,
                             "norm": norm, "winner": None,
                             "rejected": rejected})
                continue

            best = min(measured, key=lambda m: m["time_s"])
            entry = {"impl": best["impl"], "time_s": best["time_s"],
                     "err": best["err"], "grad_err": best["grad_err"]}
            pallas = [m for m in measured if m["blocks"] is not None]
            if pallas:
                bp = min(pallas, key=lambda m: m["time_s"])
                entry["bm"], entry["bn"] = bp["blocks"][0], bp["blocks"][1]
                entry["pallas_time_s"] = bp["time_s"]
            ref_m = [m for m in measured if m["impl"] == "ref"]
            if ref_m:
                entry["ref_time_s"] = ref_m[0]["time_s"]
            table.put(key, entry)
            rows.append({"key": key, "M": M, "K": K, "N": N,
                         "norm": norm, "winner": dict(entry),
                         "n_candidates": len(cands),
                         "n_rejected": len(rejected),
                         "rejected": rejected})
            if verbose:
                blk = (f" bm={entry['bm']} bn={entry['bn']}"
                       if "bm" in entry else "")
                print(f"  {key}: winner={entry['impl']}{blk} "
                      f"t={entry['time_s']:.4g}s "
                      f"err={entry['err']:.1e} "
                      f"grad_err={entry['grad_err']:.1e} "
                      f"({len(measured)} passed, {len(rejected)} "
                      f"rejected)")
    if save:
        path = table.save()
        if verbose:
            print(f"  tuning table: {len(table)} entries -> {path}")
    return rows
