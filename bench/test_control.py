"""The control and the planted faults, at a size the CPU can hold: each
put in the program's place must read far above the program's own
readings (``bench.calibrate`` reads the same on the chip at the cells'
sizes)."""
import jax
import pytest

from bench import calibrate
from bench.tiny import tiny


@pytest.fixture(autouse=True)
def _untuned(tmp_path, monkeypatch):
    from repro.kernels import autotune
    monkeypatch.setenv("REPRO_TUNE_TABLE", str(tmp_path / "none.json"))
    autotune.invalidate_cache()
    yield
    autotune.invalidate_cache()


@pytest.mark.parametrize("name", ["mnist_adaptive.seq_1chip",
                                  "mnist_random.all_layers_4chip"])
def test_control_and_faults_read_above_the_program(name):
    cell = tiny(name)
    devices = jax.devices()[:1] * cell.traffic["num_nodes"]
    got = calibrate.readings(cell, 2 ** 31 + 5, devices, controls=True)
    sound = got["program"]
    # one precision step below the configuration's, in every product
    # (at this size Adam's normalisation keeps the median unit within a
    # few times round-off; the chip's readings at the cells' sizes set
    # the limits)
    assert got["control"]["first_task_unit_diff_l0"] \
        > 1.5 * sound["first_task_unit_diff_l0"]
    # half of every batch left out
    assert got["half_batch"]["first_task_unit_diff_l0"] \
        > 1e3 * sound["first_task_unit_diff_l0"]
    # the hand-off without its length normalisation: every layer after
    # the first trains on other inputs
    for k in (1, 2):
        assert got["handoff_unnormed"][f"first_task_unit_diff_l{k}"] \
            > 1e3 * sound[f"first_task_unit_diff_l{k}"]
    if cell.traffic["num_nodes"] > 1:
        # chapter 0 starts from the seed on every node: the missing
        # hand-off shows from chapter 1 on
        assert got["no_exchange"]["final_unit_diff_l0"] \
            > 1e3 * sound["final_unit_diff_l0"]
    else:
        assert "no_exchange" not in got
