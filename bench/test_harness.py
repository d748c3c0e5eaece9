"""The harness on the CPU: cells found by name, a configuration of
another family taken by new files alone, the window's rate, the refusal
without a TPU, and whole runs at a tiny size, sound and with the timed
path broken underneath (``correct`` must come out false)."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import check, compiles, peaks, run
from bench.tiny import FOUR_NODE, tiny

ROOT = run.ROOT
CELLS = [w["name"] for w in run._json(
    os.path.join(ROOT, "BENCHMARK.json"))["workloads"]]


@pytest.fixture(autouse=True)
def _untuned(tmp_path, monkeypatch):
    from repro.kernels import autotune
    monkeypatch.setenv("REPRO_TUNE_TABLE", str(tmp_path / "none.json"))
    autotune.invalidate_cache()
    yield
    autotune.invalidate_cache()


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_are_found_by_name(name):
    cell = run.find_cell(name)
    fam = run.family(cell)
    assert cell.traffic["chips"] == cell.workload["chips"]
    assert set(cell.limits) - {"set_from"} <= set(fam.NAMES)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                      "train_samples_per_s"}
    for m in cell.per_layer:
        assert callable(run.metric_reader(m["name"]))
    if cell.family == "ff_mlp":
        assert set(cell.model["layer_sizes"]) == {784, 2000}


def test_per_layer_workloads_key_limits_a_metric_to_its_cells():
    names = lambda c: {m["name"] for m in run.find_cell(c).per_layer}
    assert "neg_gen_share" in names("mnist_adaptive.seq_1chip")
    assert "neg_gen_share" not in names("mnist_random.seq_1chip")
    assert "mfu" in names("mnist_random.seq_1chip")


# ---------------------------------------------------------------------------
# A configuration of another family, by new files alone
# ---------------------------------------------------------------------------

_STUB_FAMILY = '''"""A family of the harness's tests: the smallest job ``repro.api.fit``
takes, one chapter of one mini-epoch on the program's host-side MNIST
stand-in, with one reading of its own, ``error_rate``: one minus the
check job's test accuracy."""
import dataclasses

NAMES = ("error_rate",)
SOUND = ()
BATCH = 64


@dataclasses.dataclass
class Built:
    args: tuple
    kwargs: dict


def build(cell, seed, devices):
    from repro import data as data_lib
    from repro.configs.ff_mlp import FFMLPConfig

    rows = cell.traffic["rows"]
    task = data_lib.mnist_like(n_train=rows, n_test=rows, seed=seed)
    cfg = FFMLPConfig(layer_sizes=tuple(cell.model["layer_sizes"]),
                      epochs=1, splits=1, batch_size=BATCH,
                      neg_mode="random", seed=seed)
    return Built((cfg, task), {"backend": "sequential"})


def check_job(built):
    from repro import api
    return api.fit(*built.args, **built.kwargs).test_acc


def variants(cell):
    return {}


def calibration_readings(cell, seed, built, prog, names=()):
    return {} if prog is None else {"program": {"error_rate": 1.0 - prog}}


def samples_per_job(model, traffic):
    return traffic["rows"]


def job_kernel_calls(model, traffic):
    return []


def layer_steps_per_job(model, traffic):
    return -(-traffic["rows"] // BATCH) * (len(model["layer_sizes"]) - 1)


def job_model_flops(model, traffic):
    sizes = model["layer_sizes"]
    return sum(2 * 2.0 * (2 * traffic["rows"]) * k * n
               for k, n in zip(sizes, sizes[1:]))
'''


def _stub_root(root, limits):
    """A checkout holding the benchmark's ``BENCHMARK.json`` with one
    more configuration and cell, whose configuration names the family
    ``stub``, and only the new files that cell needs: its
    configuration, traffic and limits, and ``bench/families/stub.py``."""
    bench = run._json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"].append({
        "name": "stub_mlp", "source": "https://arxiv.org/abs/2404.08573",
        "file": "bench/configs/stub_mlp.json", "reduced": [],
        "why": "a family of the harness's tests"})
    bench["workloads"].append({
        "name": "stub_mlp.one", "config": "stub_mlp", "traffic": "rows",
        "chips": 1, "why": "one tiny job a window"})
    files = {"BENCHMARK.json": bench,
             "bench/configs/stub_mlp.json": {"family": "stub",
                                             "source": "a tiny FF layer",
                                             "layer_sizes": [784, 16]},
             "bench/traffic/rows.json": {"chips": 1, "rows": 128},
             "bench/limits/stub_mlp.one.json": limits}
    for rel, obj in files.items():
        os.makedirs(os.path.dirname(os.path.join(root, rel)), exist_ok=True)
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)
    os.makedirs(os.path.join(root, "bench", "families"))
    with open(os.path.join(root, "bench", "families", "stub.py"), "w") as f:
        f.write(_STUB_FAMILY)
    return run.find_cell("stub_mlp.one", root=str(root))


def _go_stub(cell):
    return run.run_cell(cell, 2 ** 31 + 11, 0.1, False, jax.devices()[:1],
                        compiles.CompileMeter(), t_start=0.0,
                        peak=peaks.peak_for("TPU v5 lite"))


def test_another_family_runs_by_new_files_alone(tmp_path):
    cell = _stub_root(tmp_path, {"error_rate": 1.0})
    assert cell.family == "stub" and "family" not in cell.model
    fam = run.family(cell)
    assert fam.NAMES == ("error_rate",)
    assert fam.samples_per_job(cell.model, cell.traffic) == 128
    # the FF-MLP cells' own per-layer metrics do not hold it
    assert {m["name"] for m in cell.per_layer} == {"mfu", "idle_share"}
    res = _go_stub(cell)
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == {"error_rate"}
    assert set(res["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_a_limit_on_a_number_the_family_does_not_read_fails(tmp_path,
                                                           capsys):
    assert check.decide({"a": 0.5}, {"a": 1.0, "set_from": "x"}) == (
        True, {"a": {"value": 0.5, "limit": 1.0}})
    assert check.decide({"a": 0.5}, {"a": 1.0, "b": 1.0})[0] is False
    res = _go_stub(_stub_root(tmp_path, {"error_rate": 1.0,
                                         "final_unit_diff_l0": 1.0}))
    assert res["correct"] is False
    assert res["checks"]["final_unit_diff_l0"]["value"] is None
    assert res["failed"] == res["attempted"] > 0
    assert "final_unit_diff_l0: the stub family read no such number" \
        in capsys.readouterr().err


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        run.find_cell("no_such.cell")


def test_window_rate_counts_every_sample_of_every_job():
    # 30 jobs of 6,000 samples x 10 mini-epochs in 45 s
    assert run.samples_per_s(60000, 30, 45.0) == pytest.approx(40000.0)


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "mnist_random.seq_1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs 1 TPU chip" in p.stderr


# ---------------------------------------------------------------------------
# Whole runs at a tiny size
# ---------------------------------------------------------------------------

def go(cell, seed=2 ** 31 + 3):
    devices = jax.devices()[:1] * cell.traffic["num_nodes"]
    return run.run_cell(cell, seed, 0.1, False, devices,
                        compiles.CompileMeter(), t_start=0.0,
                        peak=peaks.peak_for("TPU v5 lite"))


@pytest.mark.parametrize("name", CELLS + [FOUR_NODE])
def test_tiny_run_is_correct(name):
    res = go(tiny(name))
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"train_samples_per_s", "setup_s"}
    json.dumps(res)


@pytest.fixture
def patched():
    """Undo every program patch of a test, and the jit caches that
    hold what was traced under it."""
    from repro import api  # noqa: F401  (registers every strategy)
    from repro.core import strategies
    saved = {r: dict(r._entries) for r in (strategies.goodness,
                                          strategies.classifier)}
    undo = []
    yield undo
    for obj, attr, val in undo:
        setattr(obj, attr, val)
    for r, entries in saved.items():
        r._entries.clear()
        r._entries.update(entries)
    jax.clear_caches()


def _patch(undo, obj, attr, val):
    undo.append((obj, attr, getattr(obj, attr)))
    setattr(obj, attr, val)


def fault_unchanged_state(undo, cfg_goodness):
    from repro.core import strategies
    good = strategies.goodness.get(cfg_goodness)
    strategies.register_goodness(cfg_goodness, dataclasses.replace(
        good, train_chapter=lambda state, *a, **kw: state), overwrite=True)


def fault_half_batch(undo, cfg_goodness):
    from repro.core import ff_mlp
    name = "_perf_opt_loss" if cfg_goodness == "perf_opt" else \
        "_ff_layer_loss"
    orig = getattr(ff_mlp, name)
    if cfg_goodness == "perf_opt":
        def half(lp_head, xb, yb, impl="auto"):
            h = xb.shape[0] // 2
            return orig(lp_head, xb[:h], yb[:h], impl)
    else:
        def half(lp, xb, theta, peer_w, impl="auto"):
            h, q = xb.shape[0] // 2, xb.shape[0] // 4
            return orig(lp, jnp.concatenate([xb[:q], xb[h:h + q]]), theta,
                        peer_w, impl)
    _patch(undo, ff_mlp, name, half)
    jax.clear_caches()                    # retrace the chapter trainers


def fault_answer_altered(undo, cfg_goodness):
    """The test accuracy is altered where the evaluation produces it:
    off by one half."""
    from repro.core import ff_mlp
    orig = ff_mlp.accuracy
    _patch(undo, ff_mlp, "accuracy",
           lambda *a, **kw: (orig(*a, **kw) + 0.5) % 1.0)


def fault_no_exchange(undo, cfg_goodness):
    """Each node trains on from its own last state of a layer, never
    the state the previous chapter's node handed on."""
    from repro.core import pff_exec
    orig = pff_exec.PFFExecutor._train_task_body

    def body(self, k, chapter, node, *a):
        own = self.__dict__.setdefault("_own_states", {})
        if chapter == 0 and k == 0:
            own.clear()
            self.__dict__["_first_states"] = [
                jax.tree.map(jnp.copy, s) for s in self._states]
        start = own.get((node, k), self.__dict__["_first_states"][k])
        self._states[k] = jax.tree.map(jnp.copy, start)
        self._ver[k] = -2                 # no prefetched copy is taken
        out = orig(self, k, chapter, node, *a)
        own[(node, k)] = jax.tree.map(jnp.copy, self._states[k])
        return out
    _patch(undo, pff_exec.PFFExecutor, "_train_task_body", body)


def fault_handoff_unnormed(undo, cfg_goodness):
    """The hand-off between layers (the ``ff_dense`` norm epilogue of
    ``fwd_norm``) returns the activations without their length
    normalisation."""
    from repro.core import ff_mlp
    from repro.kernels import ops
    _patch(undo, ff_mlp, "fwd_norm", lambda lp, x, impl="auto": ops.ff_dense(
        x, lp["w"], lp["b"], impl=impl, norm=False)[0])


FAULTS = {"unchanged_state": fault_unchanged_state,
          "half_batch": fault_half_batch,
          "answer_altered": fault_answer_altered,
          "handoff_unnormed": fault_handoff_unnormed,
          "no_exchange": fault_no_exchange}


@pytest.mark.parametrize("name,fault", [
    (c, f) for c in CELLS + [FOUR_NODE] for f in FAULTS
    if f != "no_exchange" or tiny(c).traffic["num_nodes"] > 1])
def test_broken_timed_path_is_not_correct(name, fault, patched):
    cell = tiny(name)
    FAULTS[fault](patched, cell.model["goodness_fn"])
    res = go(cell)
    assert res["correct"] is False, res["checks"]
    assert res["failed"] == res["attempted"] > 0
