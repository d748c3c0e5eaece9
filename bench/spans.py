"""The device's idle time split by the program span the host was in.

The FF-MLP job opens a ``repro.obs`` span at every layer boundary, and
every such span is also a profiler annotation on the host's main
thread, on the device trace's own clock. ``idle_by_span`` attributes
each interval in which no program ran on a device to the innermost
program span open on the host at that time; ``shares`` sums those
seconds by layer, as the share of the traced window:

    idle_job_share       fit:* (fit:init, fit:eval, the backend's span)
    idle_driver_share    chapter, chapter:inputs, task:neg_gen, neg:score
    idle_trainer_share   task:train, task:wait, task:handoff, task:head,
                         task:round

Together with the idle time outside every program span (key ``""``)
they add up to ``idle_share``. A trace with none of a layer's spans
(a program without them) reads ``None`` for that layer.

``program_spans`` reads the spans from every line of the host plane:
the main thread's line is named after the process, ``python3`` when
the benchmark's command starts it, and ``tracefile.load`` reads only
lines named ``python`` or ``main/<tid>``.

    python3 -m bench.spans --workload <name> --seed <n>

runs one cell's job untraced and then under the profiler, on the chips,
and prints the split, the span counts and what the spans cost.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import glob
import json
import os
import shutil
import statistics
import tempfile
import time
from typing import Dict, List, Optional, Sequence, Tuple

from bench import run, tracefile

LAYERS = {
    "idle_driver_share": ("chapter", "chapter:inputs", "task:neg_gen",
                          "neg:score"),
    "idle_trainer_share": ("task:train", "task:wait", "task:handoff",
                           "task:head", "task:round"),
}


def layer_of(name: str) -> Optional[str]:
    """The share a program span's idle time counts under; None for a
    host event that is not a program span (JAX's own, ``bench:``)."""
    if name.startswith("fit:"):
        return "idle_job_share"
    for layer, names in LAYERS.items():
        if name in names:
            return layer
    return None


def program_spans(log_dir: str) -> List[tracefile.Event]:
    """The program spans of the newest ``.xplane.pb`` under
    ``log_dir``, from any line of the host plane."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return [tracefile.Event(e.name, e.start_ns, e.duration_ns)
            for plane in ProfileData.from_file(paths[-1]).planes
            if plane.name == "/host:CPU" for line in plane.lines
            for e in line.events if layer_of(e.name) is not None]


def _innermost(spans: Sequence[tracefile.Event], lo: float, hi: float):
    """(start, end, name) pieces covering [lo, hi], each labelled with
    the innermost (shortest) program span open over it, "" where none
    is."""
    cuts = sorted({lo, hi} | {t for e in spans for t in (e.start_ns,
                                                          e.end_ns)
                              if lo < t < hi})
    out = []
    for s, t in zip(cuts, cuts[1:]):
        open_ = [e for e in spans if e.start_ns <= s and t <= e.end_ns]
        out.append((s, t, min(open_, key=lambda e: e.dur_ns).name
                    if open_ else ""))
    return out


def idle_by_span(trace: tracefile.Trace, window: Tuple[float, float]
                 ) -> Dict[str, float]:
    """Device idle seconds inside ``window`` (ns) by the innermost
    program span open on the host, averaged over the trace's devices
    (the normalisation of ``idle_share``). Every program span name the
    window holds is a key, with 0.0 if the device never idled under
    it; idle time outside every program span is under ``""``."""
    lo, hi = window
    spans = [e for e in trace.host if layer_of(e.name) is not None
             and e.start_ns < hi and e.end_ns > lo]
    pieces = _innermost(spans, lo, hi)
    out = dict.fromkeys({e.name for e in spans}, 0.0)
    for ev in trace.programs.values():
        i = 0
        for s, t in tracefile.gaps_ns(ev, lo, hi):
            while pieces[i][1] <= s:
                i += 1
            j = i
            while j < len(pieces) and pieces[j][0] < t:
                a, b, name = pieces[j]
                out[name] = out.get(name, 0.0) + (
                    min(b, t) - max(a, s)) / 1e9 / len(trace.programs)
                j += 1
    return out


def shares(by_span: Dict[str, float], window_s: float
           ) -> Dict[str, Optional[float]]:
    """100 × each layer's idle seconds over the window, None for a layer
    none of whose spans ``by_span`` holds, and the remainder outside
    every program span under ``""``."""
    out: Dict[str, Optional[float]] = dict.fromkeys(
        ("idle_job_share", *LAYERS), None)
    for name, seconds in by_span.items():
        key = layer_of(name) or ""
        out[key] = (out.get(key) or 0.0) + 100.0 * seconds / window_s
    return out


def _noop_span_us(iters=20000) -> float:
    """Cost of one program span with no profiler session running."""
    from repro.obs import trace as obs_trace
    t0 = time.perf_counter()
    for _ in range(iters):
        with obs_trace.NOOP.span("task:train", layer=1, chapter=2,
                                 steps=94):
            pass
    return 1e6 * (time.perf_counter() - t0) / iters


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--jobs", type=int, default=5,
                    help="untraced jobs timed before the traced one")
    args = ap.parse_args(argv)
    cell = run.find_cell(args.workload)
    run.environment()
    devices = run.tpu_devices(cell.traffic["chips"])
    import jax.profiler
    from repro import api

    _, task, cfg, fit_kw, _ = run.build(cell, args.seed, devices)
    api.fit(cfg, task, **fit_kw)          # compiles every program
    untraced = []
    for _ in range(args.jobs):
        t0 = time.perf_counter()
        api.fit(cfg, task, **fit_kw)
        untraced.append(time.perf_counter() - t0)
    log_dir = tempfile.mkdtemp(prefix="bench_spans_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench:job"):
            t0 = time.perf_counter()
            api.fit(cfg, task, **fit_kw)
            traced = time.perf_counter() - t0
        jax.profiler.stop_trace()
        tr = tracefile.load(log_dir)
        prog = program_spans(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    window = tr.marks["bench:job"]
    reduced = tracefile.reduce(tr, window)
    by_span = idle_by_span(dataclasses.replace(tr, host=prog), window)
    counts = collections.Counter(e.name for e in prog
                                 if window[0] <= e.start_ns < window[1])
    gaps = sorted(((t - s, s) for ev in tr.programs.values()
                   for s, t in tracefile.gaps_ns(ev, *window)),
                  reverse=True)[:10]
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "untraced_job_s": untraced, "traced_job_s": traced,
        "traced_over_untraced": traced / statistics.median(untraced),
        "window_s": reduced.window_s,
        "idle_share": 100.0 * (1.0 - reduced.mean_busy_s
                               / reduced.window_s),
        "shares": shares(by_span, reduced.window_s),
        "idle_by_span_s": by_span,
        "span_counts": dict(counts),
        "noop_span_us": _noop_span_us(),
        "idle_gaps": reduced.idle_gaps,
        "idle_gaps_by_span": [(tracefile.host_label(prog, s + dt / 2),
                               dt / 1e9) for dt, s in gaps],
    }), flush=True)


if __name__ == "__main__":
    main()
