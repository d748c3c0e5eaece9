"""Hand counts of the benchmark's yardstick: peaks, required operations
and bytes per ``ff_dense`` call, model FLOPs per job, and the trace
reduction."""
import json
import os
import subprocess
import sys

import pytest

from bench import flops, peaks, tracefile
from bench.tracefile import Event

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def test_peak_table_has_the_v5e_row_only_and_refuses_others():
    p = peaks.peak_for("TPU v5 lite")
    assert (p.flops_per_s, p.hbm_bytes_per_s) == (197e12, 819e9)
    assert list(peaks.PEAKS) == ["TPU v5 lite"]
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak_for("cpu")


@pytest.mark.parametrize("M,K,N", [(128, 784, 2000), (128, 2000, 2000),
                                   (64, 2000, 2000), (60000, 784, 2000)])
def test_ff_dense_forward_counts(M, K, N):
    c = flops.ff_dense_fwd(M, K, N)
    assert c.flops == 2 * M * K * N
    # x, w, b read; y and g written; 4 bytes each
    assert c.bytes == 4 * (M * K + K * N + N) + 4 * (M * N + M)


@pytest.mark.parametrize("M,K,N", [(128, 784, 2000), (64, 2000, 2000)])
def test_ff_dense_backward_counts_dw_and_db_only(M, K, N):
    c = flops.ff_dense_bwd(M, K, N)
    # dW = x^T dy only: no dx = dy w^T product (another 2MKN) ...
    assert c.flops == 2 * M * K * N
    # ... and no read of w, which only dx needs
    reads = M * K + M * N + M * N + M          # x, y, dL/dy, dL/dg
    writes = K * N + N                         # dW, db
    assert c.bytes == 4 * (reads + writes)


def test_least_time_is_the_larger_bound():
    v5e = peaks.peak_for("TPU v5 lite")
    step = flops.ff_dense_fwd(128, 2000, 2000)
    # 16 MB of weights at 819 GB/s outweigh 1.02 GFLOP at 197 TFLOP/s
    assert step.least_seconds(v5e) == pytest.approx(step.bytes / 819e9)
    big = flops.ff_dense_fwd(20000, 2000, 2000)
    assert big.least_seconds(v5e) == pytest.approx(big.flops / 197e12)


def test_random_job_flops_have_no_scoring():
    a = _load("configs", "ff_mlp_mnist_adaptive")
    r = _load("configs", "ff_mlp_mnist_random")
    t = _load("traffic", "seq_1chip")
    widths = 784 * 2000 + 3 * 2000 * 2000
    assert flops.job_model_flops(a, t) - flops.job_model_flops(r, t) \
        == 10 * 2 * 60000 * widths


def test_adaptive_job_flops_by_hand():
    m = _load("configs", "ff_mlp_mnist_adaptive")
    t = _load("traffic", "seq_1chip")
    steps = 94 * 10                             # ceil(6000/64) x 10 chapters
    widths = 784 * 2000 + 3 * 2000 * 2000
    train = 2 * (2 * 128 * widths) * steps      # forward + weight gradient
    handoff = 10 * 2 * 2 * 6000 * (784 * 2000 + 2 * 2000 * 2000)
    scoring = 10 * 2 * 60000 * widths           # 6k rows x 10 labels
    evaluation = 2 * (10000 + 20000) * widths   # test + 2000 train rows
    assert flops.job_model_flops(m, t) == train + handoff + scoring + evaluation
    assert flops.train_samples_per_job(m, t) == 60000
    assert flops.layer_steps_per_job(m, t) == 4 * steps


def test_four_chip_job_counts_no_train_accuracy():
    # the executor evaluates the test set only
    r = _load("configs", "ff_mlp_mnist_random")
    one = flops.job_kernel_calls(r, _load("traffic", "seq_1chip"))
    four = flops.job_kernel_calls(r, _load("traffic", "all_layers_4chip"))
    rows = lambda calls: sum(c.M * c.count for c in calls
                             if c.what == "evaluation" and c.K == 784)
    assert rows(one) - rows(four) == 2000 * 10
    assert sum(c.count for c in four if c.kernel == "ff_dense_bwd") \
        == 4 * 94 * 10


def _ev(name, start, dur):
    return Event(name, float(start), float(dur))


def test_trace_reduction_on_hand_built_events():
    fwd = ("%ff_dense.10 = (f32[128,2048]{1,0}, f32[128,1]{1,0}) "
           "custom-call(f32[128,2000]{1,0} %x, f32[2000,2048]{1,0} %w)")
    bwd = ("%transpose_jvp_jit_ff_dense_bwd___.12 = (f32[128,2048]{1,0}, "
           "f32[2048,2048]{1,0}, f32[2048]{0}) custom-call(f32[128,2048] %x)")
    loop = "%while.14 = (s32[], f32[2000]{0}) while((s32[], f32[2000]) %t)"
    add = "%add.3 = f32[2000]{0} add(f32[2000]{0} %a, f32[2000]{0} %b)"
    trace = tracefile.Trace(
        programs={0: [_ev("jit_train_layer_chapter(123)", 0, 600),
                      # overlaps the first program: busy is a union
                      _ev("jit_ff_dense(9)", 500, 200),
                      # a 300 ns gap before this one
                      _ev("jit_goodness_class_scores(7)", 1000, 400)]},
        ops={0: [_ev(loop, 0, 600), _ev(fwd, 10, 100), _ev(bwd, 120, 200),
                 _ev(fwd, 330, 100), _ev(add, 1000, 50)]},
        host=[_ev("PjitFunction(goodness_class_scores)", 650, 400),
              _ev("np.asarray(jax.Array)", 700, 200)])
    r = tracefile.reduce(trace, (0, 1500))
    assert r.window_s == pytest.approx(1500e-9)
    assert r.busy_s[0] == pytest.approx(1100e-9)
    assert r.by_program["train_layer_chapter"] == (pytest.approx(600e-9), 1)
    assert r.by_program["goodness_class_scores"][1] == 1
    assert r.by_kernel["ff_dense"] == (pytest.approx(200e-9), 2)
    assert r.by_kernel["ff_dense_bwd"] == (pytest.approx(200e-9), 1)
    # the while loop spans the ops inside it and is not an op of its own
    assert all(not name.startswith("while") for name, _ in r.top_ops)
    # gaps: [700, 1000) and [1400, 1500); the longer is labelled by the
    # innermost host span open at its middle
    assert r.idle_gaps[0] == ("device 0 idle, host: np.asarray(jax.Array)",
                              pytest.approx(300e-9))
    assert r.idle_gaps[1][1] == pytest.approx(100e-9)
    assert r.mean_busy_s == pytest.approx(1100e-9)


@pytest.mark.parametrize("name,expected", [
    ("%ff_dense.8 = (f32[20096,2048], f32[20096,1]) custom-call(f32[1])",
     ("ff_dense.8", "custom-call")),
    ("%pad.29 = f32[20096,2000]{1,0:T(8,128)} pad(f32[20000,2000] %s)",
     ("pad.29", "pad")),
    ("jit_ff_dense(11623669655191331439)", ("jit_ff_dense(11623669655191331439)", "")),
])
def test_op_parts(name, expected):
    assert tracefile.op_parts(name) == expected


_PROFILED = """
import json, sys
import jax, jax.numpy as jnp
from bench import tracefile
jax.profiler.start_trace(sys.argv[1])
with jax.profiler.TraceAnnotation("bench:job"):
    with jax.profiler.TraceAnnotation("task:wait"):
        jnp.ones(8).block_until_ready()
jax.profiler.stop_trace()
tr = tracefile.load(sys.argv[1])
print(json.dumps({"host": [e.name for e in tr.host], "marks": list(tr.marks)}))
"""


def test_load_reads_the_main_thread_named_after_the_process(tmp_path):
    # the profiler names the main thread's line after the process: a
    # command started as python3, as the benchmark's is, gives "python3"
    exe = tmp_path / "python3"
    exe.symlink_to(sys.executable)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(p for p in sys.path if p))
    r = subprocess.run([str(exe), "-c", _PROFILED, str(tmp_path / "trace")],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["marks"] == ["bench:job"]
    assert "task:wait" in got["host"]
