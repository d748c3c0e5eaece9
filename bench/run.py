"""Run one benchmark cell once on the chips of this machine.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: the
workload names its configuration (``bench/configs/<config>.json``) and
its traffic mix (``bench/traffic/<traffic>.json``); the comparison's
limits are ``bench/limits/<workload>.json`` and each per-layer metric
is read by ``bench/metrics/<metric>.py``. The configuration's
``"family"`` (``ff_mlp`` where it names none) selects
``bench/families/<family>.py``, which holds all that depends on what
is trained:

- ``build(cell, seed, devices)``: the inputs and weights from the seed,
  as an object whose ``args`` and ``kwargs`` are the timed
  ``repro.api.fit`` call;
- ``check_job(built)``: set-up's check job through the same entry and
  compiled programs, whose result the comparison reads;
- ``calibration_readings(cell, seed, built, prog, names=())``: the
  plain reference and the compared numbers of the program's check job
  (``prog``) under ``"program"``, every one of them a name in
  ``NAMES``, and of each calibration variant in ``names``;
- ``samples_per_job``, ``job_kernel_calls``, ``job_model_flops`` and
  ``layer_steps_per_job`` of (model, traffic): the counts the window's
  rate and the per-layer readers use (a sample is the family's own
  unit);
- ``variants`` and ``SOUND`` for ``bench.calibrate``.

A configuration of another family is one more file under
``bench/families/`` beside its configuration, traffic and limits files.

Set-up (``setup_s``): imports, the family's inputs made from the seed,
the persistent compile cache, and the check job, which compiles every
program of the window on every device. With ``--trace 0`` the window
then runs whole jobs (``api.fit(*built.args, **built.kwargs)``) back to
back while less than ``--seconds`` have passed, and reports
``train_samples_per_s``: samples per job times jobs, over the first
job's start to the last job's end. With ``--trace 1`` one whole job
runs under the profiler and the per-layer metrics are read from its
trace. After the window, and once the program's state is freed, the
family's readings are compared with the limits (``bench.check.decide``).

The last line of standard output is one JSON object; the compared
numbers and their limits are also the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(msg):
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Finding a cell's files by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    limits: dict          # bench/limits/<workload>.json
    end_to_end: list      # BENCHMARK.json metrics this cell reports
    per_layer: list
    root: str = ROOT      # the checkout the files were found in

    @property
    def family(self) -> str:
        return self.config.get("family", "ff_mlp")

    @property
    def model(self) -> dict:
        """The configuration file's fields of the model as run."""
        return {k: v for k, v in self.config.items()
                if k not in ("family", "source", "reduced", "assumed",
                             "precision", "dataset")}


def _json(path):
    with open(path) as f:
        return json.load(f)


def _applies(metric, name):
    return "workloads" not in metric or name in metric["workloads"]


def find_cell(name, root=ROOT) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(by_name)}")
    wl = by_name[name]
    cfg = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    d = os.path.join(root, "bench")
    return Cell(
        workload=wl, config=_json(os.path.join(root, cfg["file"])),
        traffic=_json(os.path.join(d, "traffic", wl["traffic"] + ".json")),
        limits=_json(os.path.join(d, "limits", name + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod       # where dataclasses look up its names
    spec.loader.exec_module(mod)
    return mod


def family(cell: Cell):
    """The module ``bench/families/<family>.py`` of the cell's
    configuration."""
    return _module(os.path.join(cell.root, "bench", "families",
                                cell.family + ".py"),
                   "bench_family_" + cell.family)


def metric_reader(name, root=ROOT):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    return _module(os.path.join(root, "bench", "metrics", name + ".py"),
                   "bench_metric_" + name.replace(".", "_")).read


# ---------------------------------------------------------------------------
# What the readers see
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    reduced: object       # tracefile.Reduced of the traced job
    model: dict
    traffic: dict
    chips: int
    peak: object          # peaks.Peak
    job_calls: list       # the family's kernel calls of one job
    job_flops: float      # model FLOPs of one job
    layer_steps: int      # layer steps of one job


def samples_per_s(samples_per_job, jobs, seconds):
    """The window's rate: every sample of every whole job over the
    window's length."""
    return samples_per_job * jobs / seconds


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def environment():
    """Compile cache inside the checkout (every program, however quick
    to compile), no tuning table, and full f32 products in every XLA dot
    (the configuration's precision)."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["REPRO_TUNE_TABLE"] = os.path.join(CACHE_DIR, "untuned",
                                                  "none.json")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # no size limit, so no eviction pass: that pass reads every entry's
    # access-time file and fails when one is missing
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_default_matmul_precision", "highest")
    from repro.launch import compile_cache
    return compile_cache.use_compile_cache()


def tpu_devices(chips):
    """The first ``chips`` TPU devices, or exit non-zero."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.exit(f"bench.run: needs {chips} TPU chip(s); JAX found "
                 f"{len(devs)} {devs[0].platform} device(s) "
                 f"({devs[0].device_kind}). No result.")
    return devs[:chips]


def _gc_timer(pauses):
    """A ``gc.callbacks`` entry that appends each collection's seconds
    to ``pauses``."""
    start = []

    def timer(phase, info):
        if phase == "start":
            start[:] = [time.perf_counter()]
        elif start:
            pauses.append(time.perf_counter() - start[0])
    return timer


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             meter, *, t_start=T_START, peak=None):
    """Set-up, window or traced job, then the comparison. Returns the
    result object."""
    from repro import api

    from bench import check, tracefile

    fam = family(cell)
    model, traffic = cell.model, cell.traffic
    t0 = time.perf_counter()
    built = fam.build(cell, seed, devices)
    log(f"setup: {cell.family} inputs {time.perf_counter() - t0:.3f}s")
    c0 = meter.snapshot()
    t0 = time.perf_counter()
    prog = fam.check_job(built)
    c1 = meter.snapshot()
    log(f"setup: check job {time.perf_counter() - t0:.3f}s, {c1[1] - c0[1]} "
        f"compiles in {c1[0] - c0[0]:.3f}s, {c1[2] - c0[2]} persistent-cache"
        f" hits")
    # a run that compiled has just written its programs to the cache:
    # flush them now, not while the window runs
    os.sync()
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s:.3f}s in all; {c1[1]} compiles in {c1[0]:.3f}s, "
        f"{c1[2]} persistent-cache hits since start")

    result = {"correct": None, "attempted": 0, "failed": 0, "metrics": {},
              "device": {}}
    if not trace:
        c0 = meter.snapshot()
        pauses = []                 # the collector's passes in the window
        gc.callbacks.append(_gc_timer(pauses))
        ends = [time.perf_counter()]
        try:
            while True:
                res = api.fit(*built.args, **built.kwargs)
                ends.append(time.perf_counter())
                if ends[-1] - ends[0] >= seconds:
                    break
        finally:
            gc.callbacks.pop()
        del res
        c1 = meter.snapshot()
        jobs, t0, t1 = len(ends) - 1, ends[0], ends[-1]
        log(f"window: {jobs} jobs in {t1 - t0:.3f}s, {c1[1] - c0[1]} "
            f"compiles in the window, {len(pauses)} garbage collections in "
            f"{sum(pauses):.3f}s (longest {max(pauses, default=0):.3f}s)")
        log("window: job seconds " + " ".join(
            f"{b - a:.4f}" for a, b in zip(ends, ends[1:])))
        result["attempted"] = jobs
        values = {"train_samples_per_s": samples_per_s(
                      fam.samples_per_job(model, traffic), jobs, t1 - t0),
                  "setup_s": setup_s}
    else:
        import jax.profiler
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench:job"):
                t0 = time.perf_counter()
                res = api.fit(*built.args, **built.kwargs)
                t1 = time.perf_counter()
            jax.profiler.stop_trace()
            del res
            t2 = time.perf_counter()
            tr = tracefile.load(log_dir)
            lo, hi = tr.marks["bench:job"]
            evs = [e for ev in tr.programs.values() for e in ev] or [
                tracefile.Event("", lo, hi - lo)]
            log(f"trace: {len(evs)} program and "
                f"{sum(map(len, tr.ops.values()))} op events on "
                f"{len(tr.programs)} devices, {len(tr.host)} host events, "
                f"from {min(e.start_ns for e in evs) - lo:.0f} ns after the "
                f"job's start to {max(e.end_ns for e in evs) - hi:.0f} ns "
                f"after its end")
            reduced = tracefile.reduce(tr, (lo, hi))
            log(f"trace: job {t1 - t0:.3f}s, trace written and read in "
                f"{time.perf_counter() - t2:.3f}s, window "
                f"{reduced.window_s:.3f}s")
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        result["attempted"] = 1
        ctx = Context(reduced=reduced, model=model, traffic=traffic,
                      chips=len(devices), peak=peak,
                      job_calls=fam.job_kernel_calls(model, traffic),
                      job_flops=fam.job_model_flops(model, traffic),
                      layer_steps=fam.layer_steps_per_job(model, traffic))
        values = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"], cell.root)(ctx)
            if v is None:
                log(f"trace: {m['name']}: nothing to read")
            else:
                values[m["name"]] = v
        result["device"].update(busy_s=reduced.mean_busy_s,
                                window_s=reduced.window_s)
        result["breakdown"] = {"device_ops": reduced.top_ops,
                               "idle_gaps": reduced.idle_gaps}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in values.items() if k in units}
    stats = [d.memory_stats() or {} for d in devices]
    result["device"] = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                 for s in stats),
        **result["device"]}

    # the comparison, once the program's state is freed
    gc.collect()
    t0 = time.perf_counter()
    got = fam.calibration_readings(cell, seed, built, prog)["program"]
    correct, checks = check.decide(got, cell.limits)
    log(f"check: reference in {time.perf_counter() - t0:.3f}s")
    for name, v in got.items():
        if name not in checks:
            log(f"check: {name} {v!r} (not compared)")
    result["correct"] = correct
    if not correct:
        result["failed"] = result["attempted"]
    result["checks"] = checks
    for name, c in checks.items():
        if c["value"] is None:
            log(f"check: {name}: the {cell.family} family read no such "
                f"number")
    for name, c in checks.items():
        log(f"{name} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = find_cell(args.workload)
    cache = environment()
    devices = tpu_devices(cell.traffic["chips"])
    from bench import compiles, peaks
    peak = peaks.peak_for(devices[0].device_kind)
    meter = compiles.CompileMeter()
    log(f"device: {devices[0].platform} {devices[0].device_kind!r} "
        f"x{len(devices)}; compile cache {cache}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, meter, peak=peak)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
