"""Observability subsystem tests: tracer core (nesting, thread safety,
the zero-overhead no-op), exporter registry + Chrome/JSONL schemas, the
critical-path analyzer on a hand-built DAG trace, and the executor /
api integration (records-from-spans bit-compat, resilience timers
folded onto counters, the ``--trace`` CLI flag).

Like tests/test_pff_exec.py, the real multi-device invariant check
(critical path <= measured makespan <= serial bound on an N=4 run)
happens in ONE subprocess — ``python -m repro.obs.analyze`` — because
conftest keeps the in-process runner on a single CPU device. The
in-process executor tests hand the same device to N logical nodes.
"""
import collections
import json
import os
import subprocess
import sys
import threading

import pytest

from repro.obs import analyze as analyze_lib
from repro.obs import export as export_lib
from repro.obs import trace as trace_lib

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     os.pardir)
_SRC = os.path.join(_ROOT, "src")


def _profiled(log_dir):
    """A profiler session with the benchmark's options (no Python
    tracer)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(log_dir), profiler_options=opts)


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------

def test_span_context_manager_nests_and_orders():
    tr = trace_lib.Tracer()
    with tr.span("outer", a=1):
        with tr.span("inner"):
            pass
    spans = tr.snapshot()
    # inner closes first (spans append at close time)
    assert [s.name for s in spans] == ["inner", "outer"]
    outer = spans[1]
    assert outer.attrs == {"a": 1}
    assert outer.t0 <= spans[0].t0 and outer.t1 >= spans[0].t1
    assert outer.duration >= 0


def test_manual_spans_events_counters():
    tr = trace_lib.Tracer(meta={"who": "test"})
    t0 = tr.now()
    sp = tr.add_span("task:train", t0, kind="train", layer=0, chapter=1)
    assert sp.t1 >= sp.t0 and sp.thread == threading.current_thread().name
    tr.event("handoff:prefetch_hit", node=2)
    tr.counter("recovery_time_s", 0.25)
    tr.counter("recovery_time_s", 0.5)
    d = tr.to_dict()
    assert d["meta"] == {"who": "test"}
    assert d["spans"][0]["attrs"]["layer"] == 0
    assert d["events"][0]["name"] == "handoff:prefetch_hit"
    assert d["counters"] == {"recovery_time_s": pytest.approx(0.75)}


def test_snapshot_start_returns_only_new_spans():
    tr = trace_lib.Tracer()
    tr.add_span("a", 0.0, 1.0)
    mark = tr.span_count()
    tr.add_span("b", 1.0, 2.0)
    assert [s.name for s in tr.snapshot(start=mark)] == ["b"]
    assert [s.name for s in tr.snapshot()] == ["a", "b"]


def test_thread_safety_hammer():
    tr = trace_lib.Tracer()
    n_threads, n_iter = 8, 200

    def work(i):
        for j in range(n_iter):
            with tr.span(f"w{i}", j=j):
                pass
            tr.event(f"e{i}")
            tr.counter("total", 1.0)

    threads = [threading.Thread(target=work, args=(i,), name=f"t{i}")
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tr.span_count() == n_threads * n_iter
    assert len(tr.events) == n_threads * n_iter
    assert tr.counters["total"] == pytest.approx(n_threads * n_iter)
    # every record landed with its recording thread's name
    assert {s.thread for s in tr.snapshot()} == {f"t{i}"
                                                 for i in range(n_threads)}


def test_noop_is_inert_and_allocation_free(tmp_path):
    from bench import spans
    noop = trace_lib.NOOP
    assert not noop.enabled
    with noop.span("x", a=1) as got:
        assert got == {"a": 1}
    # a NOOP span records nothing, but it is a profiler annotation
    with _profiled(tmp_path):
        with noop.span("task:train", layer=0):
            pass
    assert [e.name for e in spans.program_spans(str(tmp_path))] \
        == ["task:train"]
    assert noop.add_span("x", 0.0) is None
    assert noop.event("x") is None
    assert noop.counter("x", 1.0) is None
    assert noop.now() == 0.0 and noop.span_count() == 0
    assert noop.snapshot() == []
    assert noop.to_dict() == {"meta": {}, "spans": [], "events": [],
                              "counters": {}}


def test_span_yields_attrs_that_the_block_may_extend():
    tr = trace_lib.Tracer()
    with tr.span("task:train", layer=0) as attrs:
        attrs["device"] = 3
    assert tr.snapshot()[0].attrs == {"layer": 0, "device": 3}


def test_obs_imports_jax_profiler_on_the_first_span_only():
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; from repro.obs import trace; "
         "assert 'jax' not in sys.modules; "
         "trace.Tracer(); trace.NOOP.event('e'); "
         "assert 'jax' not in sys.modules; "
         "trace.NOOP.span('s').__enter__(); "
         "assert 'jax.profiler' in sys.modules"],
        capture_output=True, text=True,
        env={**os.environ,
             "PYTHONPATH": _SRC + os.pathsep
             + os.environ.get("PYTHONPATH", "")},
        timeout=120)
    assert r.returncode == 0, r.stderr


def test_as_tracer_normalization():
    assert trace_lib.as_tracer(None) is trace_lib.NOOP
    assert trace_lib.as_tracer(False) is trace_lib.NOOP
    fresh = trace_lib.as_tracer(True)
    assert isinstance(fresh, trace_lib.Tracer) and fresh.block_tasks
    tr = trace_lib.Tracer(block_tasks=False)
    assert trace_lib.as_tracer(tr) is tr


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def _sample_tracer():
    tr = trace_lib.Tracer(meta={"run": "sample"})
    tr.add_span("task:train", 0.001, 0.002, kind="train", layer=0,
                chapter=0, node=1)
    tr.add_span("run", 0.0, 0.01, schedule="all_layers")
    tr.event("handoff:prefetch_hit", node=1)
    tr.counter("checkpoint_time_s", 0.003)
    return tr


def test_chrome_export_schema(tmp_path):
    path = str(tmp_path / "trace.json")
    export_lib.export(_sample_tracer(), path, format="chrome")
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    task = next(e for e in xs if e["name"] == "task:train")
    # µs on the chrome clock, pid = node, int tid
    assert task["ts"] == pytest.approx(1000.0)
    assert task["dur"] == pytest.approx(1000.0)
    assert task["pid"] == 1 and isinstance(task["tid"], int)
    assert task["args"]["layer"] == 0
    run = next(e for e in xs if e["name"] == "run")
    assert run["pid"] == 0                     # no node attr -> pid 0
    inst = [e for e in evs if e["ph"] == "i"]
    assert inst and inst[0]["name"] == "handoff:prefetch_hit"
    assert inst[0]["s"] == "t"
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in evs)
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["counters"]["checkpoint_time_s"] \
        == pytest.approx(0.003)


def test_jsonl_roundtrip_is_lossless(tmp_path):
    tr = _sample_tracer()
    path = str(tmp_path / "trace.jsonl")
    export_lib.export(tr, path, format="jsonl")
    reloaded = export_lib.load_jsonl(path)
    want = tr.to_dict()
    assert reloaded["meta"] == want["meta"]
    assert reloaded["counters"] == want["counters"]
    assert reloaded["spans"] == want["spans"]
    assert reloaded["events"] == want["events"]


def test_exporter_registry_surface(tmp_path):
    assert "chrome" in export_lib.names()
    assert "jsonl" in export_lib.names()
    with pytest.raises(KeyError, match="unknown trace exporter"):
        export_lib.export(_sample_tracer(), str(tmp_path / "x"),
                          format="nope")
    seen = {}
    export_lib.register_exporter(
        "test_fmt", lambda trace, path: seen.update(path=path,
                                                    n=len(trace["spans"])))
    try:
        with pytest.raises(ValueError, match="already registered"):
            export_lib.register_exporter("test_fmt", lambda t, p: None)
        export_lib.export(_sample_tracer(), str(tmp_path / "y"),
                          format="test_fmt")
        assert seen["n"] == 2
    finally:
        export_lib.EXPORTERS.unregister("test_fmt")
    assert "test_fmt" not in export_lib.names()


# ---------------------------------------------------------------------------
# Analyzer on a hand-built trace (known critical path)
# ---------------------------------------------------------------------------

def _synthetic_trace():
    """2 layers x 2 chapters on 2 nodes. Durations make the heavy chain
    train(0,0) -> train(1,0) -> train(1,1) = 1.0 + 2.0 + 0.7 = 3.7s the
    critical path (the alternative through train(0,1) is 2.2s)."""
    def span(name, t0, t1, **attrs):
        return {"name": name, "t0": t0, "t1": t1, "thread": "main",
                "attrs": attrs}

    spans = [
        span("task:train", 0.0, 1.0, kind="train", layer=0, chapter=0,
             node=0),
        span("task:train", 1.0, 3.0, kind="train", layer=1, chapter=0,
             node=1),
        span("task:train", 1.0, 1.5, kind="train", layer=0, chapter=1,
             node=0),
        span("task:train", 3.0, 3.7, kind="train", layer=1, chapter=1,
             node=1),
        span("run", 0.0, 5.0, schedule="all_layers", num_nodes=2,
             splits=2, n_layers=2, has_head=False, has_neg=False,
             strict_neg=False),
    ]
    events = [
        # a prefetch hit inside train(1,0)'s window: cost off the path
        {"name": "handoff:prefetch_hit", "t": 2.0, "thread": "main",
         "attrs": {"node": 1}},
        # a synchronous cross-node pull inside train(1,1) — which IS on
        # the critical path
        {"name": "handoff:pull_cross", "t": 3.2, "thread": "main",
         "attrs": {"node": 1}},
        # and one inside the off-path train(0,1)
        {"name": "handoff:pull_cross", "t": 1.2, "thread": "main",
         "attrs": {"node": 0}},
    ]
    return {"meta": {}, "spans": spans, "events": events,
            "counters": {"recovery_time_s": 0.1}}


def test_analyze_synthetic_dag():
    a = analyze_lib.analyze(_synthetic_trace())
    assert a.schedule == "all_layers" and a.num_nodes == 2
    assert a.makespan == pytest.approx(5.0)
    assert a.critical_path == [("train", 0, 0), ("train", 1, 0),
                               ("train", 1, 1)]
    assert a.critical_path_s == pytest.approx(3.7)
    assert a.sum_task_s == pytest.approx(4.2)
    assert a.node_busy == {0: pytest.approx(1.5), 1: pytest.approx(2.7)}
    assert a.node_idle[0] == pytest.approx(3.5)
    assert a.handoff["prefetch_hits"] == 1
    assert a.handoff["off_critical_path"] == 1
    assert a.handoff["pulls_cross"] == 2
    # only the pull inside the on-path task counts against the makespan
    assert a.handoff["on_critical_path"] == 1
    assert a.decomposition["critical_path_s"] == pytest.approx(3.7)
    assert a.decomposition["parallel_slack_s"] == pytest.approx(0.5)
    assert a.counters == {"recovery_time_s": pytest.approx(0.1)}


def test_analyze_measured_makespan_and_invariants():
    a = analyze_lib.analyze(_synthetic_trace(), measured_makespan=4.0)
    assert a.decomposition["measured_makespan_s"] == pytest.approx(4.0)
    assert a.decomposition["makespan_gap_s"] == pytest.approx(0.3)
    assert analyze_lib.check_invariants(a, 4.0) == []
    # cp > makespan trips the lower bound
    fails = analyze_lib.check_invariants(a, 3.0)
    assert len(fails) == 1 and "critical path" in fails[0]
    # makespan > serial bound trips the upper bound...
    fails = analyze_lib.check_invariants(a, 4.5)
    assert len(fails) == 1 and "serial bound" in fails[0]
    # ...unless a measured serial run raises it (shared-core hosts)
    assert analyze_lib.check_invariants(a, 4.5,
                                        serial_makespan=4.6) == []


def test_analyze_rejects_traces_without_executor_run():
    with pytest.raises(ValueError, match="no 'run' span"):
        analyze_lib.analyze({"spans": [], "events": []})
    tr = trace_lib.Tracer()
    tr.add_span("run", 0.0, 1.0, schedule="all_layers", num_nodes=1,
                splits=1, n_layers=1)
    with pytest.raises(ValueError, match="no task"):
        analyze_lib.analyze(tr)


def test_obs_package_is_jax_free():
    """Traces must be analyzable offline where jax is absent — the
    trace/export/analyze import graph may not pull jax in."""
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; import repro.obs, repro.obs.export, "
         "repro.obs.analyze; sys.exit(1 if 'jax' in sys.modules else 0)"],
        capture_output=True, text=True,
        env={**os.environ,
             "PYTHONPATH": _SRC + os.pathsep
             + os.environ.get("PYTHONPATH", "")},
        timeout=120)
    assert r.returncode == 0, r.stderr


# ---------------------------------------------------------------------------
# Executor integration: records are a view of the task spans
# ---------------------------------------------------------------------------

def _cfg(splits=3, sizes=(784, 32, 32), **kw):
    from repro.configs.ff_mlp import FFMLPConfig
    base = dict(layer_sizes=sizes, epochs=splits * 2, splits=splits,
                neg_mode="random", classifier="goodness",
                goodness_fn="sumsq", batch_size=64, seed=0)
    base.update(kw)
    return FFMLPConfig(**base)


@pytest.fixture(scope="module")
def task():
    from repro import data as data_lib
    return data_lib.mnist_like(n_train=260, n_test=100)


def _exec_fit(cfg, task, nodes=3, schedule="all_layers", **kw):
    import jax
    from repro import api
    d0 = jax.devices()[0]
    return api.fit(cfg, task, backend="executor", schedule=schedule,
                   num_nodes=nodes, devices=[d0] * nodes, **kw)


def test_traced_records_are_the_task_spans(task):
    from repro import api
    from repro.core import pff

    cfg = _cfg()
    tr = trace_lib.Tracer()
    res = _exec_fit(cfg, task, trace=tr)
    assert res.trace is tr
    assert res.records is not None and res.profile is not None
    # re-derive records from the spans: must be the identical view
    derived = [pff.TaskRecord(s.attrs["kind"], s.attrs["layer"],
                              s.attrs["chapter"], s.duration)
               for s in tr.snapshot() if s.name.startswith("task:")]
    assert derived == res.records
    busy = [0.0] * 3
    for s in tr.snapshot():
        if s.name.startswith("task:"):
            busy[s.attrs["node"]] += s.duration
    assert busy == pytest.approx(res.profile["node_busy"])
    # and they drive the simulator identically
    sim_a = api.simulate(res, "single_layer", 3)
    sim_b = api.simulate(derived, "single_layer", 3)
    assert sim_a.makespan == sim_b.makespan
    assert sim_a.speedup == sim_b.speedup


def test_profile_flag_still_yields_records(task):
    res = _exec_fit(_cfg(), task, profile=True)
    assert res.records and res.profile and len(res.profile["node_busy"]) == 3


def test_nonblocking_tracer_keeps_overlap_and_drops_records(task):
    tr = trace_lib.Tracer(block_tasks=False)
    res = _exec_fit(_cfg(), task, trace=tr)
    assert res.trace is tr and res.records is None
    assert any(s.name.startswith("task:") for s in tr.snapshot())


def test_task_spans_name_the_device_that_holds_the_output(task):
    import jax
    tr = trace_lib.Tracer(block_tasks=False)
    _exec_fit(_cfg(), task, trace=tr)
    devs = {s.attrs["device"] for s in tr.snapshot()
            if s.name.startswith("task:")}
    # every node was handed device 0, so every task's output lives there
    assert devs == {jax.devices()[0].id}


def test_tracing_does_not_change_the_weight_stream(task):
    from repro.core import pff_exec
    cfg = _cfg()
    ref = _exec_fit(cfg, task)
    res = _exec_fit(cfg, task, trace=True)
    assert pff_exec.params_bit_equal(ref.params, res.params)


def test_fit_sequential_and_simulate_traced(task):
    from repro import api
    res = api.fit(_cfg(), task, backend="sequential", trace=True)
    assert any(s.name == "fit:sequential" for s in res.trace.snapshot())
    res = api.fit(_cfg(), task, backend="simulate", schedule="all_layers",
                  num_nodes=3, trace=True)
    assert any(s.name == "fit:simulate" for s in res.trace.snapshot())
    assert res.trace.snapshot()[-1].attrs["num_nodes"] == 3


# ---------------------------------------------------------------------------
# Resilience timers fold onto tracer counters (and surface on FitResult)
# ---------------------------------------------------------------------------

def test_resilience_timers_surface_on_fit_and_counters(task, tmp_path):
    from repro.core import faults

    cfg = _cfg()
    plan = faults.FaultPlan([faults.Fault("crash", task="train", layer=0,
                                          chapter=1, times=1)])
    rc = faults.ResilienceConfig(checkpoint_dir=str(tmp_path),
                                 fault_plan=plan, backoff_base_s=0.001)
    tr = trace_lib.Tracer()
    res = _exec_fit(cfg, task, resilience=rc, trace=tr)
    st = res.resilience
    assert st["retries"] == 1
    assert st["recovery_time_s"] > 0.0
    assert st["checkpoint_time_s"] > 0.0
    # the SAME accumulations land on the tracer's counters
    assert tr.counters["recovery_time_s"] == \
        pytest.approx(st["recovery_time_s"])
    assert tr.counters["checkpoint_time_s"] == \
        pytest.approx(st["checkpoint_time_s"])
    names = [e.name for e in tr.events]
    assert "resilience:retry" in names
    saves = [s for s in tr.snapshot() if s.name == "checkpoint:save"]
    assert len(saves) == cfg.splits
    assert all(s.attrs["bytes"] > 0 for s in saves)

    # kill-then-resume's other half: restore cost on a resumed run
    tr2 = trace_lib.Tracer()
    res2 = _exec_fit(cfg, task, resume_from=str(tmp_path), trace=tr2)
    st2 = res2.resilience
    assert st2["resumed_from_chapter"] is not None
    assert st2["restore_time_s"] > 0.0
    assert tr2.counters["restore_time_s"] == \
        pytest.approx(st2["restore_time_s"])
    assert any(s.name == "checkpoint:restore" for s in tr2.snapshot())


# ---------------------------------------------------------------------------
# Multi-device invariants + CLI (subprocess)
# ---------------------------------------------------------------------------

def _sub_env():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_analyze_selftest_invariants_n4_subprocess():
    r = subprocess.run(
        [sys.executable, "-m", "repro.obs.analyze"],
        capture_output=True, text=True, env=_sub_env(), timeout=540)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "obs.analyze selftest" in r.stdout


def test_train_cli_trace_flag(tmp_path):
    out = tmp_path / "cli_trace.jsonl"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--paper-mlp",
         "--backend", "sequential", "--epochs", "2", "--splits", "2",
         "--layers", "1", "--hidden", "16", "--n-train", "128",
         "--n-test", "64", "--trace", str(out),
         "--trace-format", "jsonl"],
        capture_output=True, text=True, env=_sub_env(), timeout=540)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert out.exists()
    trace = export_lib.load_jsonl(str(out))
    assert any(s["name"] == "fit:sequential" for s in trace["spans"])
    # unknown format is rejected at argparse level (registry choices)
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--paper-mlp",
         "--trace", str(out), "--trace-format", "bogus"],
        capture_output=True, text=True, env=_sub_env(), timeout=120)
    assert r.returncode == 2 and "invalid choice" in r.stderr


# ---------------------------------------------------------------------------
# Program spans in a profiler trace, on the device's clock
# ---------------------------------------------------------------------------

ADAPTIVE, RANDOM = "mnist_adaptive.seq_1chip", "mnist_random.seq_1chip"


def _tiny_job(name):
    """(cfg, task, model, traffic) of a benchmark cell at ``bench.tiny``
    size: 784-64x4, 4 chapters of 10 mini-epochs, 256 train rows."""
    from bench.tiny import tiny
    from repro import data as data_lib
    from repro.configs.ff_mlp import FFMLPConfig
    cell = tiny(name)
    m = cell.model
    cfg = FFMLPConfig(**{**m, "layer_sizes": tuple(m["layer_sizes"])},
                      seed=2 ** 31 + 7)
    task = data_lib.mnist_like(n_train=cell.traffic["n_train"],
                               n_test=cell.traffic["n_test"])
    return cfg, task, m, cell.traffic


def _span_tree(prog):
    """Count of (program span, innermost program span around it)."""
    tree = collections.Counter()
    for e in prog:
        around = [p for p in prog if p is not e and p.start_ns <= e.start_ns
                  and e.end_ns <= p.end_ns]
        tree[e.name, min(around, key=lambda p: p.dur_ns).name
             if around else None] += 1
    return tree


def _expected_tree(fit, L, S, *, scores, handoff_in, waits):
    tree = collections.Counter({
        (fit, None): 1, ("fit:eval", fit): 1, ("chapter", fit): S,
        ("chapter:inputs", "chapter"): S, ("task:train", "chapter"): L * S,
        ("task:handoff", handoff_in): (L - 1) * S})
    tree["fit:init", fit] = 1 if fit == "fit:sequential" else 2
    if waits:
        tree["task:wait", "task:train"] = L * S
    if fit == "fit:sequential" or scores:
        tree["task:neg_gen", "chapter"] = S
        if waits:
            tree["task:wait", "task:neg_gen"] = S
    if scores:
        tree["neg:score", "task:neg_gen"] = S
    return tree


@pytest.mark.parametrize("name", [ADAPTIVE, RANDOM])
def test_sequential_spans_in_a_profiler_trace(name, tmp_path):
    from bench import spans
    from repro import api
    cfg, task, _, _ = _tiny_job(name)
    api.fit(cfg, task)                          # compile outside the trace
    with _profiled(tmp_path):
        api.fit(cfg, task)
    got = _span_tree(spans.program_spans(str(tmp_path)))
    assert got == _expected_tree(
        "fit:sequential", L=4, S=4, scores=name == ADAPTIVE,
        handoff_in="task:train", waits=True)


_EXEC_SPANS = """
import json, sys, tempfile
import jax
from bench import spans
from repro import api, obs
sys.path.insert(0, "tests")
from test_obs import _profiled, _tiny_job
out = {}
for name, trace in (("%s", obs.Tracer()), ("%s", None)):
    cfg, task, _, _ = _tiny_job(name)
    kw = dict(backend="executor", schedule="all_layers", num_nodes=4,
              devices=jax.devices()[:4])
    api.fit(cfg, task, **kw)
    d = tempfile.mkdtemp()
    with _profiled(d):
        api.fit(cfg, task, trace=trace, **kw)
    out[name] = [(e.name, e.start_ns, e.dur_ns)
                 for e in spans.program_spans(d)]
print(json.dumps(out))
""" % (ADAPTIVE, RANDOM)


def test_executor_spans_in_a_profiler_trace_n4_subprocess():
    from bench import tracefile
    r = subprocess.run([sys.executable, "-c", _EXEC_SPANS], cwd=_ROOT,
                       capture_output=True, text=True, env=_sub_env(),
                       timeout=540)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    runs = json.loads(r.stdout.strip().splitlines()[-1])
    for name, blocking in ((ADAPTIVE, True), (RANDOM, False)):
        got = _span_tree([tracefile.Event(*e) for e in runs[name]])
        assert got == _expected_tree(
            "fit:executor", L=4, S=4, scores=name == ADAPTIVE,
            handoff_in="chapter", waits=blocking), name


@pytest.mark.parametrize("backend", ["sequential", "executor"])
def test_span_attrs_count_the_jobs_work(backend):
    """The steps of the task:train spans are the layer steps the
    benchmark counts, the rows of neg:score its AdaptiveNEG scoring
    rows."""
    import jax
    from bench import flops
    from repro import api
    cfg, task, model, traffic = _tiny_job(ADAPTIVE)
    kw = {}
    if backend == "executor":
        kw = dict(schedule="all_layers", num_nodes=4,
                  devices=[jax.devices()[0]] * 4)
    tr = trace_lib.Tracer(block_tasks=False)
    api.fit(cfg, task, backend=backend, trace=tr, **kw)
    got = collections.Counter()
    for s in tr.snapshot():
        got[s.name] += s.attrs.get("steps", s.attrs.get("rows", 0))
    assert got["task:train"] == flops.layer_steps_per_job(model, traffic)
    assert got["neg:score"] == sum(
        c.M // model["num_classes"] * c.count
        for c in flops.job_kernel_calls(model, traffic)
        if c.what == "AdaptiveNEG scoring"
        and c.K == model["layer_sizes"][0])


def test_profiler_session_leaves_the_weight_stream_bit_identical(tmp_path):
    from repro import api
    from repro.core import pff_exec
    cfg, task, _, _ = _tiny_job(ADAPTIVE)
    plain = api.fit(cfg, task)
    with _profiled(tmp_path):
        profiled = api.fit(cfg, task)
    assert pff_exec.params_bit_equal(plain.params, profiled.params)
