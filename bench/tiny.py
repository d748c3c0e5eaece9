"""Cells cut to a size the CPU runs in seconds, for the tests under
``bench/``.

Besides the cells of ``BENCHMARK.json``, ``tiny`` knows the RandomNEG
job on the four-node traffic mix (``mnist_random.all_layers_4chip``), a
cell that is not measured yet, with the one-chip cell's limits: the
harness and its comparison keep the executor's path, its hand-off
between nodes included, under test.
"""
from __future__ import annotations

import dataclasses
import os

from bench import run

FOUR_NODE = "mnist_random.all_layers_4chip"


def _four_node():
    cell = run.find_cell("mnist_random.seq_1chip")
    return dataclasses.replace(
        cell, workload={**cell.workload, "name": FOUR_NODE,
                        "traffic": "all_layers_4chip", "chips": 4},
        traffic=run._json(os.path.join(run.BENCH_DIR, "traffic",
                                       "all_layers_4chip.json")))


def tiny(name):
    """The cell ``name`` with 784-64-64-64-64 layers, 4 chapters of 10
    mini-epochs, 256 train and 128 test samples."""
    cell = _four_node() if name == FOUR_NODE else run.find_cell(name)
    return dataclasses.replace(
        cell, config={**cell.config, "layer_sizes": [784, 64, 64, 64, 64],
                      "epochs": 40, "splits": 4},
        traffic={**cell.traffic, "n_train": 256, "n_test": 128})
